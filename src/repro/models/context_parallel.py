"""Ring attention — sequence(context)-parallel exact attention.

The prefill_32k cells shard the batch only; at long context the S×S score
working set per device grows quadratically.  Ring attention shards the
*sequence* over the tp axis and rotates KV blocks around the ring with one
``ppermute`` per step, merging partial results with the online-softmax
rule — the same rotate-halo-and-accumulate structure as the paper's
lattice rounds (a KV block is a halo that visits every shard instead of
only its neighbour).

Exactness: identical math to flash attention — per-step partial
(m, l, acc) merged across ring steps; validated against the naive
materialised-scores oracle on virtual devices
(tests/test_context_parallel.py).

Layout (inside shard_map over ``axis_name``):
    q, k, v: (B, S_local, KVH[, G], hd) — the global sequence is the
    concatenation over shards; causal masking uses global positions.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

from ..compat import shard_map

__all__ = ["ring_attention_local", "make_ring_attention"]

_NEG = -1e30


def ring_attention_local(q, k, v, axis_name: str, *, causal: bool = True,
                         window: Optional[int] = None):
    """Per-shard body (call inside shard_map).

    q: (B, Sl, KVH, G, hd); k, v: (B, Sl, KVH, hd).  Returns (B, Sl, KVH,
    G, hd) — exact global attention over the ring.
    """
    W = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    B, Sl, KVH, G, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    pos_q = (me * Sl + jnp.arange(Sl))[:, None]          # (Sl, 1)
    perm = [(i, (i - 1) % W) for i in range(W)]          # kv moves left

    def step(j, carry):
        m, l, acc, kj, vj = carry
        src = (me + j) % W                               # kv block origin
        pos_k = (src * Sl + jnp.arange(Sl))[None, :]     # (1, Sl)
        s = jnp.einsum("bqkgh,bskh->bkgqs", q, kj,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((Sl, Sl), bool)
        if causal:
            mask &= pos_q >= pos_k
        if window is not None:
            mask &= pos_q - pos_k < window
        s = jnp.where(mask[None, None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        kj = jax.lax.ppermute(kj, axis_name, perm)
        vj = jax.lax.ppermute(vj, axis_name, perm)
        return m_new, l_new, acc_new, kj, vj

    m0 = jnp.full((B, KVH, G, Sl), _NEG, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sl), jnp.float32)
    a0 = jnp.zeros((B, Sl, KVH, G, hd), jnp.float32)
    m, l, acc, _, _ = jax.lax.fori_loop(0, W, step, (m0, l0, a0, k, v))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(v.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "model", *,
                        causal: bool = True, window: Optional[int] = None):
    """Host-level wrapper: q (B, S, KVH, G, hd), k/v (B, S, KVH, hd) with S
    sharded over ``axis_name``; returns the same global result as
    single-device attention."""
    body = partial(ring_attention_local, axis_name=axis_name, causal=causal,
                   window=window)
    seq_spec = PS(None, axis_name)
    return shard_map(
        body, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False)

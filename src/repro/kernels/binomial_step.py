"""Pallas TPU kernel: blocked binomial backward induction (no-TC lattice).

This is the paper's appendix workload (classic American option pricing,
Tables III / Fig. 11) as a TPU kernel, and the VMEM realisation of the
paper's §4 block scheme:

  * the node axis is tiled into blocks of ``block`` lanes;
  * each kernel invocation advances a block ``levels`` levels (the paper's
    L) entirely in VMEM — the inter-level dependency v[i] <- f(v[i],
    v[i+1]) never leaves the core;
  * the dependency window (paper's region B / our halo) is satisfied by
    mapping the *same* HBM array through two BlockSpecs — the block and
    its right neighbour — so each invocation sees 2*block lanes and can
    take up to ``levels <= block`` steps before the stale tail reaches
    its owned lanes;
  * grid = (padded_nodes / block,) — blocks are independent within a
    round (the paper's region-A property), rounds iterate on the host via
    ``lax.fori_loop`` in ops.py.

TPU layout: the node vector is viewed as ``(nblk, 1, block)`` so every
block is one lane row whose last two dims equal the array's (the Mosaic
block rule), the halo joins the block at lane offset ``block`` (a
multiple of 128 when compiled), and the one-node shift is a lane
rotation (``pltpu.roll``).  The per-round scalars live in SMEM as a
``(1, n)`` row, which also keeps the block rule under ``vmap``.

Numerics follow the caller's dtype: float64 in interpret mode matches
the sequential oracle digit for digit (the paper reports its computed
price 13.906 in doubles); compiled Mosaic has no float64, so the
compiled kernel runs float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.platform import resolve_interpret

__all__ = ["lattice_round", "lattice_round_param", "DEFAULT_BLOCK",
           "PARAM_SCALARS"]

DEFAULT_BLOCK = 256

# scalar-vector layout of the payoff-parameterised kernel:
#   [lvl0, p_up, inv_r, s0, sig_sqrt_dt, alpha, zeta, w1, w2, k1, k2]
# intrinsic(s) = max(alpha*k1 + w1*(s-k1)^+ + w2*(s-k2)^+ + zeta*s, 0)
# (put: alpha=1, zeta=-1; call: alpha=-1, zeta=+1; bull spread: w1=1, w2=-1)
PARAM_SCALARS = 11


def _block_inputs(cur_ref, nxt_ref, block: int):
    """(buf, idx): this block + its right-neighbour halo as one
    ``(1, 2*block)`` lane row, and the global column index of each lane."""
    i = pl.program_id(0)
    buf = jnp.concatenate([cur_ref[...], nxt_ref[...]], axis=1)
    idx = (i * block + jax.lax.broadcasted_iota(jnp.int32, (1, 2 * block), 1)
           ).astype(buf.dtype)
    return buf, idx


def _backward_steps(buf, lvl0, p_up, inv_r, payoff, levels: int):
    """``levels`` backward induction steps on one lane buffer."""
    shift = jnp.int32(buf.shape[1] - 1)     # int32 also under the x64 flag
    for j in range(levels):                                    # static unroll
        lvl = lvl0 - (j + 1)
        up = pltpu.roll(buf, shift, 1)                         # buf[i + 1]
        cont = (p_up * up + (1.0 - p_up) * buf) * inv_r
        new = jnp.maximum(payoff(lvl), cont)
        # final (short) round: levels below 0 are no-ops
        buf = jnp.where(lvl >= 0, new, buf)
    return buf


def _round_kernel(lvl_ref, cur_ref, nxt_ref, out_ref, *, levels: int,
                  block: int, kind: str):
    """Advance one block of nodes ``levels`` levels toward the root.

    lvl_ref: SMEM scalars [[lvl0, p_up, inv_r, strike, s0, sig_sqrt_dt]];
    cur_ref/nxt_ref: this block and its right neighbour (same array);
    out_ref: updated block.
    """
    lvl0, p_up, inv_r, strike, s0, sig = (lvl_ref[0, j] for j in range(6))
    buf, idx = _block_inputs(cur_ref, nxt_ref, block)

    def payoff(lvl):
        s = s0 * jnp.exp((2.0 * idx - lvl) * sig)
        pay = strike - s if kind == "put" else s - strike
        return jnp.maximum(pay, jnp.zeros_like(pay))

    buf = _backward_steps(buf, lvl0, p_up, inv_r, payoff, levels)
    out_ref[...] = buf[:, :block]


def _round_kernel_param(sc_ref, cur_ref, nxt_ref, out_ref, *, levels: int,
                        block: int):
    """Payoff-parameterised variant of :func:`_round_kernel`.

    The payoff family is data, not code: the intrinsic is the branchless
    4-parameter form documented at ``PARAM_SCALARS``, so one compiled
    kernel serves puts, calls and cash-settled spreads — the scenario-grid
    engine batches mixed payoffs through it with a single ``vmap``.
    """
    lvl0, p_up, inv_r, s0, sig = (sc_ref[0, j] for j in range(5))
    alpha, zeta, w1, w2, k1, k2 = (sc_ref[0, 5 + j] for j in range(6))
    buf, idx = _block_inputs(cur_ref, nxt_ref, block)

    def payoff(lvl):
        s = s0 * jnp.exp((2.0 * idx - lvl) * sig)
        pay = (alpha * k1 + w1 * jnp.maximum(s - k1, 0.0)
               + w2 * jnp.maximum(s - k2, 0.0) + zeta * s)
        return jnp.maximum(pay, jnp.zeros_like(pay))

    buf = _backward_steps(buf, lvl0, p_up, inv_r, payoff, levels)
    out_ref[...] = buf[:, :block]


def _round_call(kernel, v, scalars, levels: int, block: int,
                interpret: bool):
    """Shared pallas_call scaffolding: per-block grid, double BlockSpec
    (own block + right-neighbour halo over the same HBM array, clamped at
    the boundary where lanes are beyond the live tree)."""
    P = v.shape[0]
    nblk = P // block
    if P % block or levels > block or (not interpret and block % 128):
        raise ValueError(
            f"need nodes ({P}) a multiple of block ({block}), levels "
            f"({levels}) <= block, and a compiled block a multiple of 128")
    zero = lambda: jnp.int32(0)      # index maps stay int32 under x64

    def row(f):
        return pl.BlockSpec((None, 1, block), lambda i: (f(i), zero(), zero()))

    out = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, scalars.shape[0]), lambda i: (zero(), zero()),
                         memory_space=pltpu.SMEM),
            row(lambda i: i),
            row(lambda i: jnp.minimum(i + 1, nblk - 1)),
        ],
        out_specs=row(lambda i: i),
        out_shape=jax.ShapeDtypeStruct((nblk, 1, block), v.dtype),
        interpret=interpret,
    )(scalars.reshape(1, -1), v.reshape(nblk, 1, block),
      v.reshape(nblk, 1, block))
    return out.reshape(P)


def lattice_round_param(v, scalars, *, levels: int,
                        block: int = DEFAULT_BLOCK,
                        interpret: bool | None = None):
    """One round of ``levels`` steps with the payoff passed as data.

    v: (P,) node values, P a multiple of ``block``; scalars: (11,) array
    with the ``PARAM_SCALARS`` layout (dtype of v).  ``interpret=None``
    resolves from the platform policy (``core/platform.py``).
    """
    kernel = functools.partial(_round_kernel_param, levels=levels,
                               block=block)
    return _round_call(kernel, v, scalars, levels, block,
                       resolve_interpret(interpret))


def lattice_round(v, scalars, *, levels: int, block: int = DEFAULT_BLOCK,
                  kind: str = "put", interpret: bool | None = None):
    """One round of ``levels`` backward steps over all node blocks.

    v: (P,) node values, P a multiple of ``block``;  scalars: (6,) array
    [lvl0, p_up, inv_r, strike, s0, sig_sqrt_dt] (dtype of v).
    ``interpret=None`` resolves from the platform policy.
    """
    kernel = functools.partial(_round_kernel, levels=levels, block=block,
                               kind=kind)
    return _round_call(kernel, v, scalars, levels, block,
                       resolve_interpret(interpret))

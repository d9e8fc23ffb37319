"""Pallas kernel: blocked Roux–Zastawniak PWL rounds (transaction costs).

This is the paper's *headline* workload — American option pricing under
proportional transaction costs (§3) — run through the §4 block/region
scheme as a Pallas kernel, the TC sibling of ``binomial_step.py``:

  * the node axis is tiled into blocks of ``block`` lanes; each lane
    carries one fixed-capacity SoA PWL record (``core/pwl.py``:
    ``xs, ys: (lanes, K)``, ``sl, sr: (lanes,)``, ``m: (lanes,)``);
  * one kernel invocation advances a block ``levels`` (the paper's L)
    levels toward the root entirely in VMEM — per level the full §3
    recursion ``w = max(z_up, z); v = cone(w / r); z = max/min(u, v)``
    (``core/rz.py::rz_level_step_lanes``), data-parallel over lanes;
  * the dependency window (paper's region B) is satisfied by mapping the
    *same* HBM arrays through two BlockSpecs — the block and its right
    neighbour — so each invocation sees ``2*block`` lanes and can take up
    to ``levels <= block`` steps before the stale tail reaches its owned
    lanes;
  * blocks are independent within a round (region-A property); rounds
    iterate on the host (``core/rz.py::rz_backward_pallas``) following the
    static schedule of ``core/partition.py::kernel_round_plan``, which
    also re-balances the lane extent as the tree narrows (§4.2's thread
    shedding).  A single-block round (``nblk == 1``) skips the halo
    operands entirely: the whole live level is the block.

Capacity overflow reporting is identical to the jnp path: the kernel's
second output is the per-block maximum of the raw (pre-truncation) knot
counts over *owned, live* lanes; the engine carries the running max and
the caller raises ``OverflowError`` if it exceeded K.  Halo lanes are
excluded — their values go stale within a round, and their owning block
reports the authoritative count.

The PWL level step is **sort-free** (``core/pwl.py``'s merge-path
envelope algebra: binary-search rank computation + gathers — no
``sort``/``argsort`` primitives, jaxpr-asserted by
``tests/test_pwl_merge.py``), and its per-lane dynamic gathers and
int32 knot-count bookkeeping are *declared* in the kernel's lowering
contract (``kernels/contracts.py``).  It still has no compiled Mosaic
lowering: compiling for a v5e recurses without end in
``convert_element_type`` under x64, refuses the merge-path gathers
("Only 2D gather is supported") with x64 off, and rejects the rank-1
``pieces`` block.  So the kernel runs in interpret mode only, and the
engine (``core/rz.py::rz_backward_pallas``) raises
``NotImplementedError`` when ``interpret`` resolves to False
(``docs/KNOWN_ISSUES.md``); ``tests/test_tpu_compile.py`` keeps the
2-D gather as a strict xfail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import pwl as P
from ..core.payoff import param_payoff
from ..core.platform import resolve_interpret
from ..core.rz import rz_level_step_lanes

__all__ = ["rz_round", "RZ_SCALARS"]

# scalar-vector layout of the round kernel:
#   [lvl0, s0, sig_sqrt_dt, r, k, alpha, zeta, w1, w2, k1, k2]
# lvl0 is the base level B (levels B-1 .. B-levels are computed); the
# payoff tail is the 4-parameter family of core/payoff.py::param_payoff.
RZ_SCALARS = 11


def _rz_round_kernel(sc_ref, *refs, levels: int, block: int,
                     sellers: tuple, halo: bool):
    """Advance one block of PWL lanes ``levels`` levels toward the root.

    The leading axis of every PWL component is the *side* axis (seller /
    buyer), walked fused in one pass: ``rz_level_step_lanes`` takes the
    per-side flags as a traced ``(S, 1)`` array, so max/min envelopes and
    the expense sign are per-lane selects, not separate kernels.  Lanes
    of different sides never mix — the level recursion couples lane l to
    l+1 within its own side row only.
    """
    ncomp = 5                                   # xs, ys, sl, sr, m
    lvl0, s0, sig, r, k = (sc_ref[j] for j in range(5))
    pay = param_payoff(*(sc_ref[5 + j] for j in range(6)))
    params = dict(s0=s0, k=k, sig_sqrt_dt=sig, r=r)

    if halo:
        cur, nxt = refs[:ncomp], refs[ncomp:2 * ncomp]
        z = P.PWL(*(jnp.concatenate([c[...], n[...]], axis=1)
                    for c, n in zip(cur, nxt)))
        outs = refs[2 * ncomp:]
    else:
        z = P.PWL(*(c[...] for c in refs[:ncomp]))
        outs = refs[ncomp:]
    dtype = z.xs.dtype
    capacity = z.capacity
    lanes = z.sl.shape[-1]
    idx0 = pl.program_id(0) * block
    owned = jax.lax.broadcasted_iota(jnp.int32, (lanes,), 0) < block
    # (S, 1) per-side seller flags, broadcast against the lane axis.
    # Built from an iota, not jnp.asarray(sellers): pallas kernels may
    # not capture array constants (scalar literals fold fine).
    S = z.sl.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    side = jnp.zeros((S, 1), bool)
    for j, s_j in enumerate(sellers):
        if s_j:
            side = side | (row == j)

    def body(j, carry):
        z, pieces = carry
        lvl = lvl0 - (j + 1).astype(dtype)
        z, pc = rz_level_step_lanes(z, lvl, params, capacity=capacity,
                                    seller=side, payoff=pay, dtype=dtype,
                                    idx_offset=idx0)
        pieces = jnp.maximum(pieces, jnp.max(jnp.where(owned, pc, 0)))
        return z, pieces

    # int32 loop bounds keep the carried counter int32 (python ints would
    # canonicalise to int64 under x64 — a compiled-path contract violation)
    z, pieces = jax.lax.fori_loop(jnp.int32(0), jnp.int32(levels), body,
                                  (z, jnp.zeros((), jnp.int32)))
    for ref, arr in zip(outs[:ncomp], z):
        ref[...] = arr[:, :block]
    outs[ncomp][...] = pieces[None]


def rz_round(z: P.PWL, scalars, *, levels: int, block: int,
             sellers: tuple = (True, False),
             interpret: bool | None = None):
    """One round of ``levels`` fused TC level-steps over all node blocks.

    z: PWL with a leading side axis of ``len(sellers)`` rows (the engine
    walks ``(seller, buyer)``; the white-box tests use a single side) and
    a node axis of P lanes, P a multiple of ``block``; scalars:
    (RZ_SCALARS,) array (dtype of z.xs).  Multi-block rounds require
    ``levels <= block`` (halo staleness bound).  Returns ``(z_new,
    pieces)`` with ``pieces`` the scalar int32 max raw knot count over
    owned live lanes of every side — the overflow signal the engines
    carry.

    ``interpret=None`` resolves from the platform policy
    (``core/platform.py``: interpret on CPU, compiled on GPU/TPU).
    """
    interpret = resolve_interpret(interpret)
    S, lanes = z.sl.shape
    # loud ValueErrors, not asserts: these are user-reachable contracts and
    # a violation misprices silently (a short scalars vector clamp-indexes
    # inside the kernel; levels > block lets halo staleness reach owned
    # lanes) — they must survive python -O
    if S != len(sellers):
        raise ValueError(f"side axis {S} != len(sellers) {len(sellers)}")
    if lanes % block != 0:
        raise ValueError(f"lanes {lanes} not a multiple of block {block}")
    if scalars.shape != (RZ_SCALARS,):
        raise ValueError(f"scalars must have shape ({RZ_SCALARS},), "
                         f"got {scalars.shape}")
    nblk = lanes // block
    halo = nblk > 1
    if halo and levels > block:
        raise ValueError(f"multi-block round needs levels <= block "
                         f"(halo staleness bound), got levels={levels} "
                         f"> block={block}")
    K = z.capacity
    dtype = z.xs.dtype

    cur_specs = [
        pl.BlockSpec((S, block, K), lambda i: (0, i, 0)),    # xs
        pl.BlockSpec((S, block, K), lambda i: (0, i, 0)),    # ys
        pl.BlockSpec((S, block), lambda i: (0, i)),          # sl
        pl.BlockSpec((S, block), lambda i: (0, i)),          # sr
        pl.BlockSpec((S, block), lambda i: (0, i)),          # m
    ]
    nxt = lambda i: jnp.minimum(i + 1, nblk - 1)             # clamped halo
    nxt_specs = [
        pl.BlockSpec((S, block, K), lambda i: (0, nxt(i), 0)),
        pl.BlockSpec((S, block, K), lambda i: (0, nxt(i), 0)),
        pl.BlockSpec((S, block), lambda i: (0, nxt(i))),
        pl.BlockSpec((S, block), lambda i: (0, nxt(i))),
        pl.BlockSpec((S, block), lambda i: (0, nxt(i))),
    ]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + cur_specs
    operands = [scalars, *z]
    if halo:
        in_specs += nxt_specs
        operands += list(z)

    kernel = functools.partial(_rz_round_kernel, levels=levels, block=block,
                               sellers=tuple(bool(s) for s in sellers),
                               halo=halo)
    out = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=[*cur_specs, pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=[
            jax.ShapeDtypeStruct((S, lanes, K), dtype),      # xs
            jax.ShapeDtypeStruct((S, lanes, K), dtype),      # ys
            jax.ShapeDtypeStruct((S, lanes), dtype),         # sl
            jax.ShapeDtypeStruct((S, lanes), dtype),         # sr
            jax.ShapeDtypeStruct((S, lanes), jnp.int32),     # m
            jax.ShapeDtypeStruct((nblk,), jnp.int32),        # pieces/block
        ],
        interpret=interpret,
    )(*operands)
    return P.PWL(*out[:5]), jnp.max(out[5])

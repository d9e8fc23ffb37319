"""Per-kernel Mosaic/Triton lowering-compatibility contracts.

``core/platform.py`` decides *where* a Pallas kernel runs compiled; this
registry declares *what each kernel promises* a compiled lowering so the
promise can be asserted statically on CPU, long before a GPU/TPU lane
ever lowers it:

  * **no sort primitives** — Mosaic has no sort lowering; the merge-path
    PWL engine (PR 5) exists precisely to keep ``sort``/``argsort`` out
    of the trace;
  * **dtype policy** — a kernel traced at float32 must stay
    ``{float32, int32, bool}``: a stray float64 (weak-typed Python
    scalars) or int64 (x64-canonicalised ``arange``/``cumsum``/loop
    counters) would either fail to lower or silently double register
    pressure on hardware with no native 64-bit lanes;
  * **declared dynamic gathers** — data-dependent ``gather`` /
    ``dynamic_slice`` patterns (the PWL binary search, halo indexing)
    are legal but must be declared per kernel, so a new undeclared one
    is a reviewable event, not an accident.

``tests/test_lowering_contract.py`` (marker ``lowering``) asserts every
contract statically on every platform and re-runs the kernels
``interpret=False`` against the interpret oracle where the platform has
a compiled lowering (:func:`repro.core.platform.supports_compiled_pallas`).

The registry is *closed over the repo*: the conformance suite scans the
source tree for pallas-call sites and asserts every module containing
one is covered here, so a new kernel without a declared contract fails
CI.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp

__all__ = [
    "FORBIDDEN_PRIMITIVES", "ALLOWED_INT_DTYPES", "GATHER_PRIMITIVES",
    "LoweringContract", "CONTRACTS", "trace_kernel", "jaxpr_summary",
    "check_static_contract", "run_kernel",
]

# Primitives with no Mosaic lowering (and no place in a lattice kernel).
FORBIDDEN_PRIMITIVES = frozenset(
    {"sort", "sort_key_val", "argsort", "top_k", "approx_top_k"})

# Bookkeeping dtypes a compiled lowering accepts alongside the value
# dtype.  int64 is deliberately absent: x64 canonicalisation leaks it.
ALLOWED_INT_DTYPES = frozenset({"bool", "int32", "uint32"})

# Data-dependent addressing primitives a kernel must declare to use.
GATHER_PRIMITIVES = frozenset(
    {"gather", "dynamic_slice", "dynamic_update_slice"})


@dataclasses.dataclass(frozen=True)
class LoweringContract:
    """What one Pallas kernel promises a compiled (non-interpret) lowering.

    ``build(dtype, interpret)`` returns ``(fn, args)`` with ``fn(*args)``
    a jit-traceable closed call of the kernel at that dtype — small
    shapes, fixed values, usable both for :func:`jax.make_jaxpr` (static
    checks) and execution (interpret-vs-compiled differencing).
    """
    name: str
    module: str                       # repo module owning the pallas_call
    build: Callable[..., Tuple[Callable, tuple]]
    dtypes: Tuple[str, ...] = ("float64", "float32")
    dynamic_gather: bool = False      # declared data-dependent addressing
    tol: Tuple[Tuple[str, float], ...] = (("float64", 1e-12),
                                          ("float32", 1e-5))

    def tolerance(self, dtype) -> float:
        return dict(self.tol)[str(jnp.dtype(dtype))]


# --------------------------------------------------------------------- #
# example-trace builders (tiny fixed workloads, one per kernel)
# --------------------------------------------------------------------- #
def _build_rz_round(dtype, interpret=None):
    from ..core import pwl as P
    from ..core.payoff import american_put
    from .rz_step import RZ_SCALARS, rz_round
    lanes, capacity, levels, block = 8, 8, 2, 8
    slope = jnp.tile(jnp.asarray([-1.0, -0.5], dtype)[:, None], (1, lanes))
    val0 = jnp.full((2, lanes), 100.0, dtype)
    z = P.make_affine(slope, val0, capacity, dtype)
    # [lvl0, s0, sig_sqrt_dt, r, k, *payoff params] — a live put workload
    scalars = jnp.asarray([6.0, 100.0, 0.05, 1.001, 0.01,
                           *american_put(100.0).params], dtype)
    assert scalars.shape == (RZ_SCALARS,)
    fn = lambda z, s: rz_round(z, s, levels=levels, block=block,
                               interpret=interpret)
    return fn, (z, scalars)


def _build_lattice_round(dtype, interpret=None):
    from .binomial_step import lattice_round
    v = jnp.linspace(0.0, 10.0, 16).astype(dtype)
    # [lvl0, p_up, inv_r, strike, s0, sig_sqrt_dt]
    scalars = jnp.asarray([8.0, 0.5, 0.999, 100.0, 100.0, 0.05], dtype)
    fn = lambda v, s: lattice_round(v, s, levels=4, block=8,
                                    interpret=interpret)
    return fn, (v, scalars)


def _build_lattice_round_param(dtype, interpret=None):
    from .binomial_step import PARAM_SCALARS, lattice_round_param
    v = jnp.linspace(0.0, 10.0, 16).astype(dtype)
    scalars = jnp.zeros((PARAM_SCALARS,), dtype)
    scalars = scalars.at[0].set(8.0).at[1].set(0.5).at[2].set(0.999)
    fn = lambda v, s: lattice_round_param(v, s, levels=4, block=8,
                                          interpret=interpret)
    return fn, (v, scalars)


def _build_flash_attention(dtype, interpret=None):
    from .flash_attention import flash_attention
    B, T, H, KVH, hd = 1, 8, 2, 1, 4
    q = jnp.cos(jnp.arange(B * T * H * hd, dtype=dtype)).reshape(
        B, T, H, hd) * 0.1
    k = jnp.sin(jnp.arange(B * T * KVH * hd, dtype=dtype)).reshape(
        B, T, KVH, hd) * 0.1
    v = k + 0.5
    fn = lambda q, k, v: flash_attention(q, k, v, block_q=4, block_kv=4,
                                         interpret=interpret)
    return fn, (q, k, v)


def _build_lru_scan(dtype, interpret=None):
    from .lru_scan import lru_scan
    B, T, W = 2, 8, 4
    a = jnp.full((B, T, W), 0.9, dtype)
    b = jnp.sin(jnp.arange(B * T * W, dtype=dtype)).reshape(B, T, W)
    h0 = jnp.zeros((B, W), dtype)
    fn = lambda a, b, h: lru_scan(a, b, h, chunk=4, interpret=interpret)
    return fn, (a, b, h0)


CONTRACTS: Dict[str, LoweringContract] = {c.name: c for c in [
    LoweringContract(
        name="rz_round", module="repro.kernels.rz_step",
        build=_build_rz_round, dynamic_gather=True,   # PWL binary search
        tol=(("float64", 1e-12), ("float32", 1e-4))),
    LoweringContract(
        name="lattice_round", module="repro.kernels.binomial_step",
        build=_build_lattice_round),
    LoweringContract(
        name="lattice_round_param", module="repro.kernels.binomial_step",
        build=_build_lattice_round_param),
    # the LM-side kernels accumulate in float32 by construction (flash
    # attention softmax stats, LRU scratch carry) — f32-only contracts
    LoweringContract(
        name="flash_attention", module="repro.kernels.flash_attention",
        build=_build_flash_attention, dtypes=("float32",),
        tol=(("float32", 2e-6),)),
    LoweringContract(
        name="lru_scan", module="repro.kernels.lru_scan",
        build=_build_lru_scan, dtypes=("float32",),
        tol=(("float32", 2e-6),)),
]}


# --------------------------------------------------------------------- #
# static analysis
# --------------------------------------------------------------------- #
def trace_kernel(contract: LoweringContract, dtype,
                 interpret: bool | None = True):
    """The kernel's closed jaxpr at ``dtype`` (default: interpret trace —
    identical structure to the compiled one, minus the backend lowering,
    so it is traceable on any platform)."""
    fn, example = contract.build(jnp.dtype(dtype), interpret)
    return jax.make_jaxpr(fn)(*example)


def jaxpr_summary(jaxpr) -> Tuple[set, set]:
    """``(primitive names, outvar dtypes)`` over the whole call tree."""
    prims: set = set()
    dtypes: set = set()
    _walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, prims, dtypes)
    return prims, dtypes


def _walk(jaxpr, prims: set, dtypes: set) -> None:
    is_leaf = lambda x: isinstance(x, (jcore.Jaxpr, jcore.ClosedJaxpr))
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                dtypes.add(str(aval.dtype))
        for val in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(val, is_leaf=is_leaf):
                if isinstance(sub, jcore.ClosedJaxpr):
                    _walk(sub.jaxpr, prims, dtypes)
                elif isinstance(sub, jcore.Jaxpr):
                    _walk(sub, prims, dtypes)


def check_static_contract(contract: LoweringContract, dtype) -> list:
    """All violations of ``contract`` in the kernel's trace at ``dtype``.

    Empty list = conforming.  Each violation is one human-readable
    string; the conformance test asserts the list is empty so a failure
    names every violation at once.
    """
    dtype = jnp.dtype(dtype)
    prims, seen = jaxpr_summary(trace_kernel(contract, dtype))
    bad = []
    forbidden = prims & FORBIDDEN_PRIMITIVES
    if forbidden:
        bad.append(f"forbidden primitives {sorted(forbidden)}")
    allowed = {str(dtype)} | ALLOWED_INT_DTYPES
    stray = seen - allowed
    if stray:
        bad.append(f"dtypes {sorted(stray)} outside policy "
                   f"{sorted(allowed)}")
    gathers = prims & GATHER_PRIMITIVES
    if gathers and not contract.dynamic_gather:
        bad.append(f"undeclared dynamic gathers {sorted(gathers)} "
                   "(set dynamic_gather=True if intended)")
    return bad


def run_kernel(contract: LoweringContract, dtype, *, interpret: bool):
    """Execute the example workload; returns flat numpy leaves (the
    interpret-vs-compiled differencing surface)."""
    import numpy as np
    fn, example = contract.build(jnp.dtype(dtype), interpret)
    out = jax.jit(fn)(*example)
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]

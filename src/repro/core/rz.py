"""Vectorised Roux–Zastawniak pricing engine (single device).

Carries the whole live tree level as fixed-capacity PWL SoA tensors
(:mod:`repro.core.pwl`) and walks levels N+1 -> 0 in ``lax.fori_loop``
rounds.  Every level update is the paper's per-node recursion,
data-parallel over nodes:

    w = max(z[i+1], z[i]);  v = cone(w / r);  z = max/min(u, v)

The node axis is static per round; nodes beyond the current level are
masked (their lanes hold a benign affine function so no NaNs are ever
produced, and they are never read by valid parents since node i's children
are i and i+1).  Both backends walk the statically re-balanced round
schedule of ``core/partition.py::kernel_round_plan`` (§4.2 lane
shedding — ~N^2/2 lane-levels) and carry the seller and buyer sides
FUSED as one (2, P) state (``rz_level_step_lanes`` with a traced
``seller`` flag array): per-side max/min is a select, so each level
costs one pass, not two.

``price_rz`` is the public single-contract entry point;
``price_rz_batch`` vmaps it over a batch of contracts (strike / cost-rate /
spot grids — the "pricing desk" serving workload).  Capacity overflow is
reported via the returned ``max_pieces``; callers assert it fits.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import pwl as P
from .lattice import LatticeModel
from .payoff import PayoffProcess
from .platform import active_platform, resolve_interpret, tc_max_steps

__all__ = ["price_rz", "price_rz_batch", "rz_backward", "rz_level_step",
           "rz_level_step_lanes", "rz_backward_pallas", "RZResult",
           "RZ_BACKENDS", "require_tc_depth"]

RZ_BACKENDS = ("jnp", "pallas")

# why backend="pallas" runs in interpret mode only (docs/KNOWN_ISSUES.md)
RZ_COMPILED_REFUSAL = (
    "the TC Pallas round (kernels/rz_step.py::rz_round) has no compiled "
    "Mosaic lowering yet: under the x64 switch its lowering recurses "
    "without end in convert_element_type (core/pwl.py::_searchsorted); "
    "with x64 off Mosaic refuses the merge-path gathers of "
    "core/pwl.py::_merge_take ('Only 2D gather is supported'); and its "
    "per-block pieces output BlockSpec((1,)) breaks the rank-1 block "
    "rule. Use backend='jnp' on an accelerator, or interpret=True.")


def require_tc_depth(n_steps: int) -> None:
    """Refuse a TC tree deeper than the platform's float64 prices
    (``core/platform.py::tc_max_steps``), before anything is traced."""
    limit = tc_max_steps()
    if limit is not None and n_steps > limit:
        raise NotImplementedError(
            f"the TC engine prices at most n_steps={limit} on "
            f"{active_platform()!r}, not {n_steps}: float64 there is a "
            "pair of float32 words (about 48 bits), too coarse for the PWL "
            "algebra's 1e-9 slope tolerance in deeper trees (on a TPU v5e "
            "the N=64 smoke grid needed 55 knots where the CPU needs 23). "
            "Price deeper TC trees on a CPU host.")


@dataclasses.dataclass
class RZResult:
    ask: float
    bid: float
    max_pieces: int


def _benign(capacity: int, dtype) -> P.PWL:
    return P.make_affine(jnp.zeros((), dtype), jnp.zeros((), dtype), capacity, dtype)


def _select(mask, f_new: P.PWL, f_old: P.PWL) -> P.PWL:
    """Per-lane select between two PWL batches.

    ``mask`` broadcasts right-aligned against the batch dims (so a plain
    ``(P,)`` lane mask also serves a fused ``(2, P)`` seller+buyer
    state); the knot leaves carry one extra capacity axis, where the mask
    gains a trailing axis instead.
    """
    batch_ndim = f_new.sl.ndim
    pick = lambda a, b: jnp.where(
        mask[..., None] if a.ndim == batch_ndim + 1 else mask, a, b)
    return P.PWL(pick(f_new.xs, f_old.xs), pick(f_new.ys, f_old.ys),
                 pick(f_new.sl, f_old.sl), pick(f_new.sr, f_old.sr),
                 pick(f_new.m, f_old.m))


def _shift_up(f: P.PWL) -> P.PWL:
    """Lane i <- lane i+1 (the up-move child) along the node axis.

    The node axis is the LAST batch axis (``sl.ndim - 1``): a plain level
    state is ``(P,)``, the fused seller+buyer walk carries ``(2, P)``,
    and each side's lanes roll independently.
    """
    axis = f.sl.ndim - 1
    sh = lambda a: jnp.roll(a, -1, axis=axis)
    return P.PWL(sh(f.xs), sh(f.ys), sh(f.sl), sh(f.sr), sh(f.m))


def rz_level_step_lanes(z: P.PWL, lvl, params, *, capacity: int, seller,
                        payoff: PayoffProcess, dtype, idx_offset=0):
    """One backward level update, returning *per-lane* piece counts.

    z: PWL batch whose LAST batch axis is the node axis (P lanes);  lvl:
    scalar level index (traced); params: dict with s0, sig_sqrt_dt, r, k.
    ``idx_offset`` maps local lane j to global tree column idx_offset + j
    (used by the sharded engine and the blocked Pallas kernel).

    ``seller`` is a python bool (single-side batch, the historical form)
    or a traced boolean array broadcastable over the batch dims — e.g.
    ``jnp.array([True, False])[:, None]`` with a ``(2, P)`` state walks
    the seller (max/expense) and buyer (min/-expense) recursions in ONE
    fused pass: on this CPU the PWL ops are op-overhead-bound, so halving
    the op count per level is nearly a 2x on the whole backward walk.

    Returns (z_new, pieces) with ``pieces`` an int32 array over the batch
    (0 on non-live lanes) so callers that only own a sub-range of the
    lanes (kernel halos) can mask before reducing.
    """
    P_nodes = z.sl.shape[-1]
    idx = idx_offset + jnp.arange(P_nodes, dtype=dtype)  # (P,), broadcasts
    live = idx <= lvl                                  # lvl+1 valid nodes
    s = params["s0"] * jnp.exp((2.0 * idx - lvl) * params["sig_sqrt_dt"])
    no_tc = lvl == 0                                   # no costs at t = 0
    a = jnp.where(no_tc, s, (1.0 + params["k"]) * s)
    b = jnp.where(no_tc, s, (1.0 - params["k"]) * s)

    w, m1 = P.envelope2(_shift_up(z), z, capacity, take_max=True)
    w = P.scale(w, 1.0 / params["r"])
    v, m2 = P.cone_infconv(w, a, b, capacity)
    if isinstance(seller, bool):
        sign = 1.0 if seller else -1.0
    else:
        one = jnp.asarray(1.0, dtype)                  # keep the select in
        sign = jnp.where(seller, one, -one)            # `dtype`, not f64
    # the expense function's batch must match z's (v's) batch even when a
    # static `seller` leaves xi/zeta at the bare (P,) lane shape
    xi = jnp.broadcast_to(sign * payoff.xi(s), z.sl.shape)
    zeta = jnp.broadcast_to(sign * payoff.zeta(s), z.sl.shape)
    u = P.expense(xi, zeta, jnp.broadcast_to(a, z.sl.shape),
                  jnp.broadcast_to(b, z.sl.shape), capacity, dtype)
    z_new, m3 = P.envelope2(u, v, capacity, take_max=seller)

    z_out = _select(live, z_new, z)
    pieces = jnp.where(live, jnp.maximum(jnp.maximum(m1, m2), m3), 0)
    return z_out, pieces


def rz_level_step(z: P.PWL, lvl, params, *, capacity: int, seller: bool,
                  payoff: PayoffProcess, dtype, idx_offset=0):
    """One backward level update -> (z_new, max_pieces) (scalar reduce)."""
    z_out, pieces = rz_level_step_lanes(
        z, lvl, params, capacity=capacity, seller=seller, payoff=payoff,
        dtype=dtype, idx_offset=idx_offset)
    return z_out, jnp.max(pieces)


def _leaf_level(n_steps: int, params, capacity: int, dtype,
                lanes: int | None = None) -> P.PWL:
    """z at the extra instant t = N+1 with payoff (0, 0).

    ``lanes`` (>= n_steps + 2) overrides the node-axis extent — the
    blocked Pallas engine pads it to a multiple of its block size.
    """
    P_nodes = n_steps + 2 if lanes is None else lanes
    idx = jnp.arange(P_nodes, dtype=dtype)
    s = params["s0"] * jnp.exp((2.0 * idx - (n_steps + 1)) * params["sig_sqrt_dt"])
    a = (1.0 + params["k"]) * s
    b = (1.0 - params["k"]) * s
    zero = jnp.zeros((P_nodes,), dtype)
    return P.expense(zero, zero, a, b, capacity, dtype)


def rz_backward(s0, sigma, rate, maturity, k, *, n_steps: int, capacity: int,
                payoff: PayoffProcess, dtype=jnp.float64):
    """Traceable full backward recursion -> (ask, bid, max_pieces).

    Unlike :func:`price_rz` this is not jitted and ``payoff`` need not be
    hashable/static — its xi/zeta closures may capture traced values, which
    is what the scenario-grid engine (:mod:`repro.scenarios`) relies on to
    batch heterogeneous contracts through one compiled call.
    """
    from .partition import kernel_round_plan
    dt = maturity / n_steps
    params = dict(
        s0=s0, k=k,
        sig_sqrt_dt=sigma * jnp.sqrt(dt),
        r=jnp.exp(rate * dt),
    )
    # two structural speedups over the historical reference walk:
    #   * fused seller+buyer: one (2, P) state, per-side max/min selected
    #     by traced `seller` flags — half the ops per level of the old
    #     two-call body;
    #   * §4.2 lane shedding: the walk follows the same statically
    #     re-balanced round plan as the Pallas kernel (single-block
    #     rounds), so the lane extent shrinks with the live tree —
    #     ~N^2/2 lane-levels instead of dragging the full leaf width
    #     through every level (~N^2).
    plan = kernel_round_plan(n_steps)
    leaf = _leaf_level(n_steps, params, capacity, dtype, lanes=plan[0].lanes)
    z = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (2,) + a.shape),
                     leaf)
    sides = jnp.asarray([True, False])[:, None]        # seller, buyer
    pieces = jnp.zeros((), jnp.int32)

    for rnd in plan:
        z = jax.tree.map(lambda a, lanes=rnd.lanes: a[:, :lanes], z)
        lvl0 = jnp.asarray(float(rnd.lvl0), dtype)

        def body(j, carry, lvl0=lvl0):
            z, pieces = carry
            lvl = lvl0 - (j + 1).astype(dtype)
            z, pc = rz_level_step_lanes(z, lvl, params, capacity=capacity,
                                        seller=sides, payoff=payoff,
                                        dtype=dtype)
            return z, jnp.maximum(pieces, jnp.max(pc))

        z, pieces = jax.lax.fori_loop(0, rnd.depth, body, (z, pieces))

    root = lambda side: jax.tree.map(lambda a: a[side, 0], z)
    ask = P.eval_at(root(0), jnp.zeros((), dtype))
    bid = -P.eval_at(root(1), jnp.zeros((), dtype))
    return ask, bid, pieces


def rz_backward_pallas(s0, sigma, rate, maturity, k, *, n_steps: int,
                       capacity: int, payoff: PayoffProcess,
                       levels: int | None = None, block: int | None = None,
                       interpret: bool | None = None, dtype=jnp.float64):
    """Traceable TC backward recursion through the blocked Pallas kernel.

    Same contract as :func:`rz_backward` — (ask, bid, max_pieces) — but the
    level walk runs as ``kernels/rz_step.py`` rounds: each pallas_call
    advances a tile of lattice nodes ``D`` levels entirely in VMEM (the
    paper's §4 block/region rounds), with the round schedule — depth D and
    the re-balanced lane extent per round — picked statically by
    ``core/partition.py::kernel_round_plan``.

    Requires a payoff of the 4-parameter family (``payoff.params`` set):
    the kernel carries the payoff as scalar data, not closures.  ``block``
    of None runs one re-balanced block per round (no halo — the right
    choice whenever a whole level fits in VMEM); an explicit ``block``
    exercises the multi-block right-neighbour-halo scheme.

    The kernel runs in interpret mode only: compiled (``interpret``
    resolving to False, as on a TPU) it raises ``NotImplementedError``
    rather than fall back to interpret mode or the jnp engine.
    """
    from .partition import kernel_round_plan
    from ..kernels.rz_step import rz_round
    if not resolve_interpret(interpret):
        raise NotImplementedError(RZ_COMPILED_REFUSAL)
    if payoff.params is None:
        raise ValueError(
            f"backend='pallas' needs a 4-parameter-family payoff "
            f"(payoff.params set); {payoff.name!r} is closure-only. "
            "Use core.payoff.param_payoff / american_put / american_call / "
            "bull_spread, or backend='jnp'.")
    dt = maturity / n_steps
    params = dict(
        s0=s0, k=k,
        sig_sqrt_dt=sigma * jnp.sqrt(dt),
        r=jnp.exp(rate * dt),
    )
    plan = kernel_round_plan(n_steps, levels=levels, block=block)
    # fused sides: one (2, lanes) state, one pallas_call per round — the
    # kernel walks seller (max) and buyer (min) together, halving the op
    # and dispatch count exactly like the jnp backward
    leaf = _leaf_level(n_steps, params, capacity, dtype, lanes=plan[0].lanes)
    z = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (2,) + a.shape),
                     leaf)
    pieces = jnp.zeros((), jnp.int32)

    sc = [params["s0"], params["sig_sqrt_dt"], params["r"], params["k"],
          *payoff.params]
    for rnd in plan:
        # re-balance: shrink the lane extent to this round's live tree
        z = jax.tree.map(lambda a, lanes=rnd.lanes: a[:, :lanes], z)
        scalars = jnp.stack([jnp.asarray(v, dtype)
                             for v in (float(rnd.lvl0), *sc)])
        z, p = rz_round(z, scalars, levels=rnd.depth, block=rnd.block,
                        interpret=interpret)
        pieces = jnp.maximum(pieces, p)

    root = lambda side: jax.tree.map(lambda a: a[side, 0], z)
    ask = P.eval_at(root(0), jnp.zeros((), dtype))
    bid = -P.eval_at(root(1), jnp.zeros((), dtype))
    return ask, bid, pieces


@partial(jax.jit, static_argnames=("n_steps", "capacity", "payoff", "dtype",
                                   "backend", "levels", "block", "interpret"))
def _price_rz_jit(s0, sigma, rate, maturity, k, *, n_steps: int, capacity: int,
                  payoff: PayoffProcess, dtype=jnp.float64,
                  backend: str = "jnp", levels=None, block=None,
                  interpret: bool | None = None):
    if backend == "pallas":
        return rz_backward_pallas(s0, sigma, rate, maturity, k,
                                  n_steps=n_steps, capacity=capacity,
                                  payoff=payoff, levels=levels, block=block,
                                  interpret=interpret, dtype=dtype)
    if backend != "jnp":
        raise ValueError(f"unknown backend {backend!r}; use one of "
                         f"{RZ_BACKENDS}")
    return rz_backward(s0, sigma, rate, maturity, k, n_steps=n_steps,
                       capacity=capacity, payoff=payoff, dtype=dtype)


def price_rz(model: LatticeModel, payoff: PayoffProcess,
             capacity: int = 48, *, backend: str = "jnp",
             levels: int | None = None, block: int | None = None,
             interpret: bool | None = None) -> RZResult:
    """Jitted vectorised ask/bid under proportional transaction costs.

    ``backend="jnp"`` walks levels with ``lax.fori_loop`` over the full
    node axis; ``backend="pallas"`` runs the blocked VMEM rounds of
    :func:`rz_backward_pallas`.  Both report overflow identically via
    ``max_pieces`` / ``OverflowError``.  ``interpret=None`` resolves
    from the platform policy *here* — before the jit cache key — so a
    later ``set_platform`` never serves a stale compiled mode.
    """
    interpret = resolve_interpret(interpret)
    require_tc_depth(model.n_steps)
    ask, bid, pieces = _price_rz_jit(
        jnp.float64(model.s0), jnp.float64(model.sigma), jnp.float64(model.rate),
        jnp.float64(model.maturity), jnp.float64(model.cost_rate),
        n_steps=model.n_steps, capacity=capacity, payoff=payoff,
        backend=backend, levels=levels, block=block, interpret=interpret)
    res = RZResult(ask=float(ask), bid=float(bid), max_pieces=int(pieces))
    if res.max_pieces > capacity:
        raise OverflowError(
            f"PWL capacity overflow: needed {res.max_pieces} > K={capacity}; "
            "re-run with a larger capacity")
    return res


@partial(jax.jit, static_argnames=("n_steps", "capacity", "payoff"))
def price_rz_batch(s0, sigma, rate, maturity, k, *, n_steps: int,
                   capacity: int, payoff: PayoffProcess):
    """vmap over a batch of contracts; inputs are broadcastable 1-D arrays.

    Returns (ask, bid, max_pieces) arrays — the serving-engine workhorse.
    """
    require_tc_depth(n_steps)
    s0, sigma, rate, maturity, k = jnp.broadcast_arrays(
        *(jnp.atleast_1d(jnp.asarray(v, jnp.float64))
          for v in (s0, sigma, rate, maturity, k)))
    fn = lambda *args: _price_rz_jit(*args, n_steps=n_steps, capacity=capacity,
                                     payoff=payoff)
    return jax.vmap(fn)(s0, sigma, rate, maturity, k)

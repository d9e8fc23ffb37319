"""Scenario-grid pricing: batch whole grids of contracts through the engines.

The paper prices one American option per run; its parallelism is *within*
a contract (blocks/regions/rounds over the tree).  This module adds the
orthogonal, JAX-shaped axis: a **scenario grid** — the cartesian product
(or an explicit list) of market/contract parameters

    spot s0 x volatility sigma x rate x maturity x transaction-cost
    rate lambda x payoff family x strike(s)

is flattened into struct-of-arrays form and pushed through the lattice
engines in ONE compiled call (``vmap`` over contracts), optionally with
central-difference Greeks (delta, vega) fused into the same call.

Mixed payoff families batch together because the payoff is carried as
*data*, not code: every supported contract is an instance of the
4-parameter family

    xi(s)   = alpha * K1 + w1 * (s - K1)^+ + w2 * (s - K2)^+
    zeta(s) = zeta                                      (constant)

==============  =====  =====  ====  ====
payoff          alpha  zeta    w1    w2
==============  =====  =====  ====  ====
put(K1)           +1    -1      0     0
call(K1)          -1    +1      0     0
bull_spread       0      0     +1    -1
==============  =====  =====  ====  ====

Two engines are exposed:

  * ``price_grid_rz``    — Roux–Zastawniak ask/bid under proportional
    transaction costs (``core/rz.py`` / ``core/pwl.py``); exact for
    lambda = 0 too (ask = bid = the friction-free price).
  * ``price_grid_notc``  — friction-free binomial price; ``backend="jnp"``
    is the vectorised ``core/notc.py`` recursion, ``backend="pallas"``
    routes through the blocked lattice kernel
    (``kernels/binomial_step.py::lattice_round_param``).

Oracles: ``core/rz_ref.py`` (sequential PWL recursion) and
``core/notc.py::price_notc_np`` — see ``tests/test_scenarios.py``.

The tree depth ``n_steps`` is a *static* (shape-determining) parameter:
one grid = one compiled program.  ``repro.api.price_grid`` accepts a list
of step counts and prices one grid per distinct value.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .core.partition import (ShardPlan, plan_shards, scenario_costs,
                             shard_layout)
from .core.payoff import param_payoff
from .core.platform import default_dtype, resolve_interpret
from .core.rz import (RZ_BACKENDS, require_tc_depth, rz_backward,
                      rz_backward_pallas)

__all__ = ["ScenarioGrid", "GridResult", "ShardExecInfo",
           "price_grid_rz", "price_grid_notc", "price_grid_lsmc",
           "route_engine", "PAYOFF_FAMILIES", "payoff_params"]

PAYOFF_FAMILIES = ("put", "call", "bull_spread")

# finite-difference bump sizes (relative in s0, absolute in sigma)
_DELTA_REL_BUMP = 1e-4
_VEGA_BUMP = 1e-4


def payoff_params(kind: str):
    """(alpha, zeta, w1, w2) of the 4-parameter payoff family.

    The strikes K1/K2 are threaded separately (they scale with the
    scenario); these four numbers only select the family.
    """
    if kind == "put":
        return (1.0, -1.0, 0.0, 0.0)
    if kind == "call":
        return (-1.0, 1.0, 0.0, 0.0)
    if kind == "bull_spread":
        return (0.0, 0.0, 1.0, -1.0)
    raise ValueError(f"unknown payoff family {kind!r}; "
                     f"supported: {PAYOFF_FAMILIES}")


@dataclasses.dataclass(frozen=True)
class ScenarioGrid:
    """A flat SoA batch of pricing scenarios sharing one tree depth.

    All per-scenario fields are float64 numpy arrays of equal length
    ``n_scenarios``; ``shape`` is the logical (cartesian) grid shape the
    result surfaces are reshaped to (``(n_scenarios,)`` for explicit
    grids).  Build with :meth:`cartesian` or :meth:`explicit`.

    ``n_assets`` and ``exercise_steps`` are grid-wide contract-shape
    knobs (static like ``n_steps``): ``n_assets > 1`` means each row is
    a basket of that many i.i.d. GBM underlyings sharing the row's
    parameters, and ``exercise_steps`` (a tuple of lattice step indices,
    terminal step included) restricts exercise to a Bermudan schedule.
    ``exercise_steps=None`` means American.  Either departure from the
    1-D American default routes the grid to the ``lsmc`` engine — the
    lattice engines reject it (see :func:`route_engine`).
    """
    s0: np.ndarray
    sigma: np.ndarray
    rate: np.ndarray
    maturity: np.ndarray
    cost_rate: np.ndarray
    strike: np.ndarray
    strike2: np.ndarray
    payoff: tuple            # per-scenario family name, len n_scenarios
    n_steps: int
    shape: tuple             # logical grid shape, prod == n_scenarios
    axes: tuple = ()         # (name, values) pairs for cartesian grids
    n_assets: int = 1        # basket size (1 = the lattice engines' model)
    exercise_steps: Optional[tuple] = None   # Bermudan schedule, None=American

    def __post_init__(self):
        if self.exercise_steps is not None:
            from .core.lsmc import exercise_schedule
            object.__setattr__(self, "exercise_steps", exercise_schedule(
                self.n_steps, self.exercise_steps))
        if int(self.n_assets) < 1:
            raise ValueError(f"need n_assets >= 1, got {self.n_assets}")

    @property
    def n_scenarios(self) -> int:
        return self.s0.shape[0]

    def payoff_param_arrays(self):
        """(alpha, zeta, w1, w2) as float64 arrays over scenarios."""
        by_kind = {k: payoff_params(k) for k in set(self.payoff)}
        p = np.asarray([by_kind[k] for k in self.payoff], dtype=np.float64)
        return p[:, 0], p[:, 1], p[:, 2], p[:, 3]

    # ----------------------------------------------------------------- #
    @classmethod
    def cartesian(cls, *, s0=100.0, sigma=0.2, rate=0.1, maturity=0.25,
                  cost_rate=0.0, payoff="put", strike=100.0,
                  strike2=None, n_steps: int = 100, n_assets: int = 1,
                  exercise_steps=None) -> "ScenarioGrid":
        """Cartesian product of the given axes (scalars = length-1 axes).

        ``payoff`` entries are family names from ``PAYOFF_FAMILIES``;
        ``strike2`` (second strike of ``bull_spread``) defaults to
        ``strike + 10``.  ``n_assets``/``exercise_steps`` are grid-wide,
        not axes.
        """
        def ax(v, name):
            if isinstance(v, str):
                v = (v,)
            arr = tuple(np.atleast_1d(v).tolist())
            return (name, arr)

        axes = (ax(s0, "s0"), ax(sigma, "sigma"), ax(rate, "rate"),
                ax(maturity, "maturity"), ax(cost_rate, "cost_rate"),
                ax(payoff, "payoff"), ax(strike, "strike"))
        shape = tuple(len(vals) for _, vals in axes)
        rows = list(itertools.product(*(vals for _, vals in axes)))
        cols = {name: [r[i] for r in rows]
                for i, (name, _) in enumerate(axes)}
        k1 = np.asarray(cols["strike"], np.float64)
        if strike2 is None:
            k2 = k1 + 10.0
        else:
            k2 = np.broadcast_to(np.asarray(strike2, np.float64),
                                 k1.shape).copy()
        f64 = lambda n: np.asarray(cols[n], np.float64)
        return cls(s0=f64("s0"), sigma=f64("sigma"), rate=f64("rate"),
                   maturity=f64("maturity"), cost_rate=f64("cost_rate"),
                   strike=k1, strike2=k2, payoff=tuple(cols["payoff"]),
                   n_steps=int(n_steps), shape=shape, axes=axes,
                   n_assets=int(n_assets), exercise_steps=exercise_steps)

    @classmethod
    def explicit(cls, *, s0, sigma, rate, maturity, cost_rate=0.0,
                 payoff="put", strike=100.0, strike2=None,
                 n_steps: int = 100, n_assets: int = 1,
                 exercise_steps=None) -> "ScenarioGrid":
        """Element-wise scenario list; array arguments broadcast together."""
        arrs = [np.atleast_1d(np.asarray(v, np.float64))
                for v in (s0, sigma, rate, maturity, cost_rate, strike)]
        n = max(a.shape[0] for a in arrs)
        s0a, siga, ra, ma, ka, k1 = (np.broadcast_to(a, (n,)) for a in arrs)
        if isinstance(payoff, str):
            payoff = (payoff,) * n
        if len(payoff) != n:
            raise ValueError(f"payoff has {len(payoff)} entries, "
                             f"expected {n}")
        k2 = (k1 + 10.0 if strike2 is None else
              np.broadcast_to(np.asarray(strike2, np.float64), (n,)))
        return cls(s0=s0a.copy(), sigma=siga.copy(), rate=ra.copy(),
                   maturity=ma.copy(), cost_rate=ka.copy(), strike=k1.copy(),
                   strike2=np.asarray(k2, np.float64).copy(),
                   payoff=tuple(payoff), n_steps=int(n_steps), shape=(n,),
                   n_assets=int(n_assets), exercise_steps=exercise_steps)

    def pad_to(self, to: int) -> "ScenarioGrid":
        """Flat copy padded to ``to`` scenarios by repeating the last row.

        The serving layer pads micro-batches up to a small set of bucket
        sizes so a stream of differently-sized batches hits a handful of
        compiled programs; the padded grid is flat (``shape == (to,)``) and
        callers slice results back to the first ``n_scenarios`` rows.
        Repeating a real row keeps the pad lanes numerically benign (no
        fresh PWL knot patterns, no overflow surprises).
        """
        n = self.n_scenarios
        if to < n:
            raise ValueError(f"pad_to({to}) below batch size {n}")
        if to == n and self.shape == (n,):
            return self
        pad = to - n
        rep = lambda a: np.concatenate([a, np.repeat(a[-1:], pad)])
        return ScenarioGrid(
            s0=rep(self.s0), sigma=rep(self.sigma), rate=rep(self.rate),
            maturity=rep(self.maturity), cost_rate=rep(self.cost_rate),
            strike=rep(self.strike), strike2=rep(self.strike2),
            payoff=self.payoff + (self.payoff[-1],) * pad,
            n_steps=self.n_steps, shape=(to,),
            n_assets=self.n_assets, exercise_steps=self.exercise_steps)


@dataclasses.dataclass(frozen=True)
class ShardExecInfo:
    """How a grid call was laid out over (and measured on) a device mesh.

    ``plan`` is the :class:`~repro.core.partition.ShardPlan` the call
    ran under; ``simulated`` is True when no real mesh was available and
    the identical layout executed on the local device (bit-equal
    results; see ``resolve_grid_mesh``).  ``per_shard_pieces`` is the
    *measured* peak PWL knot count of each shard's rows (all zero on the
    friction-free path) and ``measured_work`` the cost model re-evaluated
    with those measured pieces — the signal the serving layer's
    rebalance hook feeds back into the next plan.
    """
    plan: ShardPlan
    mesh_shape: tuple
    simulated: bool
    per_shard_pieces: tuple
    per_shard_rows: tuple
    measured_work: tuple


@dataclasses.dataclass
class GridResult:
    """Ask/bid surfaces (and optional Greeks) over a scenario grid.

    All arrays have ``grid.shape``.  For the friction-free engine
    ask == bid == the binomial price (``price`` is an alias for ``ask``).
    Greeks are central finite differences fused into the same compiled
    call: ``delta_* = dP/ds0``, ``vega_* = dP/dsigma``.  ``shard_info``
    is set when the call ran over a device mesh (or its single-device
    simulation).

    ``max_pieces`` is the batch-wide peak PWL knot count (the scalar the
    OverflowError check reduces to); ``row_pieces`` is the pre-reduction
    *per-scenario* peak (shape ``grid.shape``, all zeros on the
    friction-free path).  Rows are independent lanes, so a scenario's
    ``row_pieces`` entry is exactly the ``max_pieces`` it would report
    priced alone — what lets the serving layer stamp each quote with its
    own count and lets streaming requotes reproduce a full reprice's
    ``max_pieces`` without repricing untouched rows.

    ``engine`` records which engine produced the result; ``stderr`` is
    the per-scenario Monte Carlo standard error (``lsmc`` only, None
    from the deterministic lattice engines).
    """
    grid: ScenarioGrid
    ask: np.ndarray
    bid: np.ndarray
    max_pieces: int = 0
    delta_ask: Optional[np.ndarray] = None
    delta_bid: Optional[np.ndarray] = None
    vega_ask: Optional[np.ndarray] = None
    vega_bid: Optional[np.ndarray] = None
    shard_info: Optional[ShardExecInfo] = None
    row_pieces: Optional[np.ndarray] = None
    stderr: Optional[np.ndarray] = None
    engine: Optional[str] = None

    @property
    def price(self) -> np.ndarray:
        return self.ask

    @property
    def spread(self) -> np.ndarray:
        return self.ask - self.bid


# PayoffProcess whose xi/zeta close over traced per-scenario params —
# now the shared core/payoff.py::param_payoff (kept under the old name).
_param_payoff = param_payoff


def route_engine(*, any_tc: bool, n_assets: int = 1,
                 exercise_steps=None) -> str:
    """The ``engine="auto"`` routing rule — single source of truth.

    Contract *shape* decides first: a basket (``n_assets > 1``) or an
    explicit Bermudan schedule is outside the lattice engines' domain
    and must go to ``lsmc``.  Otherwise the cost rate decides between
    the two lattice engines exactly as before this engine existed:
    ``rz`` when any row carries transaction costs, else ``notc``.  Used
    by ``api.price_grid``, the serving bucket router
    (``serve/core.py::SchedulerCore.submit``) and ``PricingService`` —
    all three dispatch through this one function.
    """
    if int(n_assets) > 1 or exercise_steps is not None:
        return "lsmc"
    return "rz" if any_tc else "notc"


def _require_lattice(grid: ScenarioGrid, engine: str):
    """Lattice engines only price 1-D American contracts — fail loudly
    (not wrongly) on a grid shaped for the MC engine."""
    if grid.n_assets > 1 or grid.exercise_steps is not None:
        raise ValueError(
            f"engine {engine!r} prices single-asset American contracts "
            f"only (got n_assets={grid.n_assets}, "
            f"exercise_steps={grid.exercise_steps!r}); use the 'lsmc' "
            "engine (price_grid_lsmc) for baskets/Bermudan schedules")


# --------------------------------------------------------------------- #
# Roux–Zastawniak grid engine (transaction costs; exact at lambda = 0)
# --------------------------------------------------------------------- #
def _rz_rows(s0, sigma, rate, maturity, k, alpha, zeta, w1, w2, k1, k2,
             *, n_steps: int, capacity: int):
    """Flat-batch RZ kernel: equal-length row arrays in, rows out.

    The shardable unit — the sharded path wraps exactly this function in
    ``shard_map`` (each device prices its slice of rows), the single
    path jits it directly.
    """
    def one(s0_, sig_, r_, t_, k_, al_, ze_, w1_, w2_, k1_, k2_):
        pay = _param_payoff(al_, ze_, w1_, w2_, k1_, k2_)
        return rz_backward(s0_, sig_, r_, t_, k_, n_steps=n_steps,
                           capacity=capacity, payoff=pay)
    return jax.vmap(one)(s0, sigma, rate, maturity, k,
                         alpha, zeta, w1, w2, k1, k2)


_rz_grid_jit = partial(jax.jit, static_argnames=("n_steps", "capacity"))(
    _rz_rows)


def _rz_rows_pallas(s0, sigma, rate, maturity, k, alpha, zeta, w1, w2, k1, k2,
                    *, n_steps: int, capacity: int, levels, block,
                    interpret: bool):
    def one(s0_, sig_, r_, t_, k_, al_, ze_, w1_, w2_, k1_, k2_):
        pay = _param_payoff(al_, ze_, w1_, w2_, k1_, k2_)
        return rz_backward_pallas(s0_, sig_, r_, t_, k_, n_steps=n_steps,
                                  capacity=capacity, payoff=pay,
                                  levels=levels, block=block,
                                  interpret=interpret)
    return jax.vmap(one)(s0, sigma, rate, maturity, k,
                         alpha, zeta, w1, w2, k1, k2)


_rz_grid_pallas = partial(jax.jit, static_argnames=(
    "n_steps", "capacity", "levels", "block", "interpret"))(_rz_rows_pallas)


def _grid_inputs(grid: ScenarioGrid):
    alpha, zeta, w1, w2 = grid.payoff_param_arrays()
    return tuple(jnp.asarray(a, jnp.float64) for a in (
        grid.s0, grid.sigma, grid.rate, grid.maturity, grid.cost_rate,
        alpha, zeta, w1, w2, grid.strike, grid.strike2))


def _with_bumps(inputs, greeks: bool):
    """Stack [base, s0+, s0-, sigma+, sigma-] along the scenario axis."""
    if not greeks:
        return inputs, 1
    s0, sigma = inputs[0], inputs[1]
    ds = _DELTA_REL_BUMP * s0
    dv = _VEGA_BUMP
    variants = [
        (s0, sigma), (s0 + ds, sigma), (s0 - ds, sigma),
        (s0, sigma + dv), (s0, sigma - dv),
    ]
    out = []
    for i, a in enumerate(inputs):
        if i == 0:
            out.append(jnp.concatenate([v[0] for v in variants]))
        elif i == 1:
            out.append(jnp.concatenate([v[1] for v in variants]))
        else:
            out.append(jnp.tile(a, 5))
    return tuple(out), 5


def _split_bumps(vals, n: int, copies: int, s0, shape):
    """(surface, d/ds0, d/dsigma) from the stacked FD evaluation."""
    r = lambda a: np.asarray(a).reshape(shape)
    base = r(vals[:n])
    if copies == 1:
        return base, None, None
    ds = (_DELTA_REL_BUMP * s0).reshape(shape)
    delta = (r(vals[n:2 * n]) - r(vals[2 * n:3 * n])) / (2.0 * ds)
    vega = (r(vals[3 * n:4 * n]) - r(vals[4 * n:5 * n])) / (2.0 * _VEGA_BUMP)
    return base, delta, vega


# --------------------------------------------------------------------- #
# device-mesh sharded dispatch (1-D scenario mesh, core/distributed.py)
# --------------------------------------------------------------------- #
# Rows of a flat grid are independent, so sharding is pure layout: a
# host-side plan (core/partition.py::plan_shards) permutes rows so each
# device's slice has near-equal *predicted* work, pads every slice to the
# plan's static lane count with duplicates of in-shard rows, and runs the
# same row kernel under shard_map.  Results gather back through the
# inverse permutation; pad lanes are duplicates, so max-reductions
# (``max_pieces``) and the OverflowError check see exactly the
# single-device values.

_SHARD_JIT_CACHE: dict = {}


def _sharded_jit(rows_fn, mesh, **static):
    """jit of ``rows_fn`` shard_mapped over ``mesh`` — cached per
    (kernel, mesh, static config) like jax's own jit cache."""
    from .core.distributed import sharded_rows
    key = (rows_fn, mesh, tuple(sorted(static.items())))
    f = _SHARD_JIT_CACHE.get(key)
    if f is None:
        f = jax.jit(sharded_rows(partial(rows_fn, **static), mesh))
        _SHARD_JIT_CACHE[key] = f
    return f


def _resolve_shard(grid: ScenarioGrid, n_rows: int, copies: int, *,
                   capacity: int, mesh, devices,
                   shard_plan: Optional[ShardPlan], costs=None):
    """Normalise sharding knobs to ``(mesh_or_None, plan_or_None)``.

    A caller-supplied ``shard_plan`` (the serving layer's rebalanced
    plan) must cover the *bumped* flat batch; otherwise a fresh
    cost-model plan is made here (``costs``, when given, overrides the
    default lattice cost model — the lsmc engine passes its own).
    ``(None, None)`` means take the single-device path.
    """
    from .core.distributed import resolve_grid_mesh
    mesh, n_shards = resolve_grid_mesh(devices, mesh)
    if shard_plan is None and n_shards <= 1:
        return None, None
    if shard_plan is None:
        if costs is None:
            costs = np.tile(scenario_costs(grid.n_steps, grid.cost_rate,
                                           capacity=capacity), copies)
        shard_plan = plan_shards(costs, n_shards)
    elif n_shards > 1 and shard_plan.n_shards != n_shards:
        # also on the simulated path: a mismatch must fail identically
        # on 1-device CI and on a real mesh
        raise ValueError(f"shard_plan has {shard_plan.n_shards} shards but "
                         f"devices/mesh asked for {n_shards}")
    if shard_plan.n_rows != n_rows:
        raise ValueError(f"shard_plan covers {shard_plan.n_rows} rows, "
                         f"batch has {n_rows} (greeks bumps included)")
    return mesh, shard_plan


def _run_rows(rows_fn, jit_fn, static: dict, inputs, mesh,
              plan: Optional[ShardPlan]):
    """Run the flat-batch row kernel; sharded when ``plan`` is present.

    Returns ``(outputs, positions)`` — ``positions`` (None on the single
    path) maps original row ``i`` to its slot in the laid-out outputs.
    With a plan but no mesh the identical layout runs on the local
    device (the *simulated* mesh of ``resolve_grid_mesh``).
    """
    if plan is None:
        return jit_fn(*inputs, **static), None
    gather, positions = shard_layout(plan)
    laid_out = tuple(a[gather] for a in inputs)
    if mesh is None:
        out = jit_fn(*laid_out, **static)
    else:
        out = _sharded_jit(rows_fn, mesh, **static)(*laid_out)
    return out, positions


def _shard_exec_info(plan: ShardPlan, mesh, grid: ScenarioGrid, copies: int,
                     pieces_rows: Optional[np.ndarray]) -> ShardExecInfo:
    """Measured per-shard stats for the rebalance hook (see
    :class:`ShardExecInfo`)."""
    cr = np.tile(np.atleast_1d(np.asarray(grid.cost_rate)), copies)
    if pieces_rows is None:
        pieces_rows = np.zeros(plan.n_rows)
    costs = scenario_costs(grid.n_steps, cr,
                           pieces=np.maximum(pieces_rows, 1.0))
    per_pieces, measured = [], []
    for rows in plan.shards:
        idx = list(rows)
        per_pieces.append(int(np.max(pieces_rows[idx])) if idx else 0)
        measured.append(float(np.sum(costs[idx])) if idx else 0.0)
    return ShardExecInfo(plan=plan, mesh_shape=(plan.n_shards,),
                         simulated=mesh is None,
                         per_shard_pieces=tuple(per_pieces),
                         per_shard_rows=plan.sizes,
                         measured_work=tuple(measured))


def price_grid_rz(grid: ScenarioGrid, *, capacity: int = 48,
                  greeks: bool = False, backend: str = "jnp",
                  levels: Optional[int] = None, block: Optional[int] = None,
                  interpret: Optional[bool] = None, mesh=None,
                  devices: Optional[int] = None,
                  shard_plan: Optional[ShardPlan] = None) -> GridResult:
    """Price every scenario of ``grid`` under transaction costs.

    One jitted, vmapped call over the whole (bumped, if ``greeks``) batch;
    returns ask/bid surfaces of ``grid.shape``.  Raises ``OverflowError``
    if any scenario needs more than ``capacity`` PWL knots (re-run with a
    larger capacity), mirroring :func:`repro.core.rz.price_rz`, and
    ``NotImplementedError`` for a tree deeper than the platform's
    float64 prices (``core/platform.py::tc_max_steps``).

    ``backend="jnp"`` walks levels with ``lax.fori_loop`` over the full
    node axis; ``backend="pallas"`` runs the blocked VMEM rounds of
    ``kernels/rz_step.py`` under the ``core/partition.py`` round schedule
    (``levels``/``block`` tune it; ``interpret`` as in the no-TC kernel).
    Both report ``max_pieces`` identically.

    ``mesh``/``devices`` shard the flat scenario batch over a 1-D device
    mesh under a cost-model :class:`~repro.core.partition.ShardPlan`
    (pass ``shard_plan`` to override, e.g. the serving layer's
    rebalanced plan); results, ``max_pieces`` and the OverflowError
    check are identical to the single-device call.

    ``interpret=None`` resolves from the platform policy
    (``core/platform.py``) before the jit cache key.
    """
    interpret = resolve_interpret(interpret)
    _require_lattice(grid, "rz")
    require_tc_depth(grid.n_steps)
    inputs, copies = _with_bumps(_grid_inputs(grid), greeks)
    if backend == "jnp":
        rows_fn, jit_fn = _rz_rows, _rz_grid_jit
        static = dict(n_steps=grid.n_steps, capacity=capacity)
    elif backend == "pallas":
        rows_fn, jit_fn = _rz_rows_pallas, _rz_grid_pallas
        static = dict(n_steps=grid.n_steps, capacity=capacity, levels=levels,
                      block=block, interpret=interpret)
    else:
        raise ValueError(f"unknown backend {backend!r}; use one of "
                         f"{RZ_BACKENDS}")
    mesh, plan = _resolve_shard(grid, inputs[0].shape[0], copies,
                                capacity=capacity, mesh=mesh,
                                devices=devices, shard_plan=shard_plan)
    (ask, bid, pieces), positions = _run_rows(rows_fn, jit_fn, static,
                                              inputs, mesh, plan)
    shard_info = None
    if plan is not None:
        ask, bid = np.asarray(ask)[positions], np.asarray(bid)[positions]
        pieces = np.asarray(pieces)[positions]
        shard_info = _shard_exec_info(plan, mesh, grid, copies, pieces)
    n = grid.n_scenarios
    max_pieces = int(jnp.max(jnp.asarray(pieces)))
    if max_pieces > capacity:
        raise OverflowError(
            f"PWL capacity overflow: needed {max_pieces} > K={capacity}; "
            "re-run with a larger capacity")
    a, da, va = _split_bumps(ask, n, copies, grid.s0, grid.shape)
    b, db, vb = _split_bumps(bid, n, copies, grid.s0, grid.shape)
    row_pieces = np.asarray(pieces)[:n].reshape(grid.shape).astype(int)
    return GridResult(grid=grid, ask=a, bid=b, max_pieces=max_pieces,
                      delta_ask=da, delta_bid=db, vega_ask=va, vega_bid=vb,
                      shard_info=shard_info, row_pieces=row_pieces,
                      engine="rz")


def rz_grid_cost(grid: ScenarioGrid, *, capacity: int = 48,
                 backend: str = "jnp", levels: Optional[int] = None,
                 block: Optional[int] = None,
                 interpret: Optional[bool] = None) -> Optional[dict]:
    """XLA ``cost_analysis`` of the compiled RZ rows program.

    The roofline hook the bench lanes use: exact flops/bytes of the same
    jitted program :func:`price_grid_rz` runs (single-device path), fed
    to :func:`repro.roofline.pricing.matrix_entry`.  ``None`` when the
    backend exposes no cost model.
    """
    from .roofline.pricing import compiled_cost
    interpret = resolve_interpret(interpret)
    _require_lattice(grid, "rz")
    inputs, _ = _with_bumps(_grid_inputs(grid), False)
    if backend == "jnp":
        fn = partial(_rz_rows, n_steps=grid.n_steps, capacity=capacity)
    elif backend == "pallas":
        fn = partial(_rz_rows_pallas, n_steps=grid.n_steps,
                     capacity=capacity, levels=levels, block=block,
                     interpret=interpret)
    else:
        raise ValueError(f"unknown backend {backend!r}; use one of "
                         f"{RZ_BACKENDS}")
    return compiled_cost(fn, *inputs)


# --------------------------------------------------------------------- #
# friction-free grid engine (core/notc.py recursion or the Pallas kernel)
# --------------------------------------------------------------------- #
def _notc_one_jnp(s0, sigma, rate, maturity, alpha, zeta, w1, w2, k1, k2,
                  *, n_steps: int):
    """Fixed-buffer backward induction with the payoff carried as data
    (the parameterised form of ``core.notc._notc_kernel``)."""
    dtype = jnp.float64
    dt = maturity / n_steps
    u = jnp.exp(sigma * jnp.sqrt(dt))
    r = jnp.exp(rate * dt)
    p = (r - 1.0 / u) / (u - 1.0 / u)
    idx = jnp.arange(n_steps + 1, dtype=dtype)

    def intrinsic(lvl):
        s = s0 * jnp.exp((2.0 * idx - lvl) * sigma * jnp.sqrt(dt))
        pay = (alpha * k1 + w1 * jnp.maximum(s - k1, 0.0)
               + w2 * jnp.maximum(s - k2, 0.0) + zeta * s)
        return jnp.where(idx <= lvl, jnp.maximum(pay, 0.0), 0.0)

    v0 = intrinsic(jnp.asarray(n_steps, dtype))

    def body(step, v):
        lvl = jnp.asarray(n_steps - 1 - step, dtype)
        cont = (p * jnp.roll(v, -1) + (1.0 - p) * v) / r
        return jnp.maximum(intrinsic(lvl), cont)

    return jax.lax.fori_loop(0, n_steps, body, v0)[0]


def _notc_rows_jnp(s0, sigma, rate, maturity, alpha, zeta, w1, w2, k1, k2,
                   *, n_steps: int):
    return jax.vmap(partial(_notc_one_jnp, n_steps=n_steps))(
        s0, sigma, rate, maturity, alpha, zeta, w1, w2, k1, k2)


_notc_grid_jnp = partial(jax.jit, static_argnames=("n_steps",))(
    _notc_rows_jnp)


def _notc_rows_pallas(s0, sigma, rate, maturity, alpha, zeta, w1, w2, k1, k2,
                      *, n_steps: int, levels: int, block: int,
                      interpret: bool, dtype: str):
    """Rows through the lattice kernel at ``dtype`` (the platform policy:
    float64 in interpret mode on the CPU, float32 where Mosaic compiles
    it).  The per-row lattice constants and leaf payoffs are formed at
    the input precision and rounded once at the kernel boundary; the
    prices come back as float64."""
    from .kernels.binomial_step import lattice_round_param

    def one(s0_, sig_, r_, t_, al_, ze_, w1_, w2_, k1_, k2_):
        dt = t_ / n_steps
        u = jnp.exp(sig_ * jnp.sqrt(dt))
        r = jnp.exp(r_ * dt)
        p_up = (r - 1.0 / u) / (u - 1.0 / u)
        sig = sig_ * jnp.sqrt(dt)
        P = -(-(n_steps + 1) // block) * block
        idx = jnp.arange(P, dtype=s0_.dtype)
        s_leaf = s0_ * jnp.exp((2.0 * idx - n_steps) * sig)
        pay = (al_ * k1_ + w1_ * jnp.maximum(s_leaf - k1_, 0.0)
               + w2_ * jnp.maximum(s_leaf - k2_, 0.0) + ze_ * s_leaf)
        v0 = jnp.maximum(pay, 0.0).astype(dtype)
        consts = [p_up, 1.0 / r, s0_, sig, al_, ze_, w1_, w2_, k1_, k2_]
        rounds = -(-n_steps // levels)

        def body(rr, v):
            lvl0 = jnp.asarray(n_steps - rr * levels, s0_.dtype)
            scalars = jnp.stack([lvl0, *consts]).astype(dtype)
            return lattice_round_param(v, scalars, levels=levels,
                                       block=block, interpret=interpret)

        return jax.lax.fori_loop(0, rounds, body, v0)[0]

    return jax.vmap(one)(s0, sigma, rate, maturity,
                         alpha, zeta, w1, w2, k1, k2).astype(jnp.float64)


_notc_grid_pallas = partial(jax.jit, static_argnames=(
    "n_steps", "levels", "block", "interpret", "dtype"))(_notc_rows_pallas)


def price_grid_notc(grid: ScenarioGrid, *, backend: str = "jnp",
                    greeks: bool = False, levels: int = 64,
                    block: int = 256, interpret: Optional[bool] = None,
                    mesh=None,
                    devices: Optional[int] = None,
                    shard_plan: Optional[ShardPlan] = None) -> GridResult:
    """Friction-free binomial prices for every scenario of ``grid``.

    ``backend="jnp"`` runs the vectorised ``core/notc.py`` recursion;
    ``backend="pallas"`` vmaps the blocked lattice kernel
    (``kernels/binomial_step.py``), exercising the paper's §4 block scheme
    per scenario.  ``grid.cost_rate`` is ignored (must be 0 for the result
    to be meaningful as a two-sided quote).  ``mesh``/``devices``/
    ``shard_plan`` shard the batch over a 1-D device mesh exactly as in
    :func:`price_grid_rz` (friction-free rows all cost the same, so the
    default plan is the even split).  ``interpret=None`` resolves from
    the platform policy (``core/platform.py``).
    """
    interpret = resolve_interpret(interpret)
    _require_lattice(grid, "notc")
    inputs, copies = _with_bumps(_grid_inputs(grid), greeks)
    # drop the cost-rate column (index 4) — this engine is friction-free
    args = inputs[:4] + inputs[5:]
    if backend == "jnp":
        rows_fn, jit_fn = _notc_rows_jnp, _notc_grid_jnp
        static = dict(n_steps=grid.n_steps)
    elif backend == "pallas":
        rows_fn, jit_fn = _notc_rows_pallas, _notc_grid_pallas
        static = dict(n_steps=grid.n_steps, levels=levels, block=block,
                      interpret=interpret, dtype=default_dtype().name)
    else:
        raise ValueError(f"unknown backend {backend!r}; use 'jnp' or 'pallas'")
    mesh, plan = _resolve_shard(grid, args[0].shape[0], copies,
                                capacity=1, mesh=mesh, devices=devices,
                                shard_plan=shard_plan)
    vals, positions = _run_rows(rows_fn, jit_fn, static, args, mesh, plan)
    shard_info = None
    if plan is not None:
        vals = np.asarray(vals)[positions]
        shard_info = _shard_exec_info(plan, mesh, grid, copies, None)
    n = grid.n_scenarios
    p, dp, vp = _split_bumps(vals, n, copies, grid.s0, grid.shape)
    cp = lambda a: None if a is None else a.copy()
    return GridResult(grid=grid, ask=p, bid=p.copy(), max_pieces=0,
                      delta_ask=dp, delta_bid=cp(dp),
                      vega_ask=vp, vega_bid=cp(vp), shard_info=shard_info,
                      row_pieces=np.zeros(grid.shape, dtype=int),
                      engine="notc")


# --------------------------------------------------------------------- #
# least-squares Monte Carlo grid engine (baskets / Bermudan schedules)
# --------------------------------------------------------------------- #
def price_grid_lsmc(grid: ScenarioGrid, *, n_paths: int = 4096,
                    seed: int = 0, basis: str = "poly", degree: int = 3,
                    antithetic: bool = True, greeks: bool = False,
                    mesh=None, devices: Optional[int] = None,
                    shard_plan: Optional[ShardPlan] = None) -> GridResult:
    """Longstaff–Schwartz Monte Carlo prices for every scenario of ``grid``.

    The engine for the contracts the lattice cannot shape: ``d =
    grid.n_assets`` underlyings per row (arithmetic basket payoff) and
    Bermudan ``grid.exercise_steps`` schedules — but it also prices the
    plain 1-D American grid, which is how the oracle tests lock it
    against ``rz_ref``/``notc`` (see ``tests/test_lsmc.py``).

    Deterministic for a given ``seed``: scenario row ``i`` draws from
    ``fold_in(PRNGKey(seed), i)`` (``core/lsmc.py::path_keys``), so
    results are bitwise reproducible and independent of padding or of
    the ``mesh``/``devices``/``shard_plan`` layout — the same
    shard-vs-single-device guarantee as the lattice engines, here by
    per-row key construction.  ``GridResult.stderr`` carries each
    scenario's Monte Carlo standard error.

    ``greeks`` reuses the fused central-difference bumps with **common
    random numbers** (bumped copies of a row share its key), the MC
    analogue of the lattice engines' fused FD Greeks.
    """
    from .core.lsmc import (LSMC_BASES, exercise_schedule, lsmc_rows,
                            lsmc_rows_jit, path_keys)
    if basis not in LSMC_BASES:
        raise ValueError(f"unknown basis {basis!r}; use one of {LSMC_BASES}")
    steps = exercise_schedule(grid.n_steps, grid.exercise_steps)
    inputs, copies = _with_bumps(_grid_inputs(grid), greeks)
    n = grid.n_scenarios
    # one key per scenario row, tiled over bump copies (common random
    # numbers: the FD difference cancels the MC noise, not adds to it)
    keys = jnp.tile(path_keys(seed, n), (copies, 1))
    inputs = inputs + (keys,)
    static = dict(n_steps=grid.n_steps, steps=steps, n_paths=int(n_paths),
                  n_assets=grid.n_assets, degree=int(degree), basis=basis,
                  antithetic=bool(antithetic))
    costs = np.tile(scenario_costs(grid.n_steps, grid.cost_rate,
                                   engine="lsmc", n_paths=n_paths,
                                   n_exercise=len(steps),
                                   n_assets=grid.n_assets), copies)
    mesh, plan = _resolve_shard(grid, inputs[0].shape[0], copies,
                                capacity=1, mesh=mesh, devices=devices,
                                shard_plan=shard_plan, costs=costs)
    (ask, bid, se), positions = _run_rows(lsmc_rows, lsmc_rows_jit, static,
                                          inputs, mesh, plan)
    shard_info = None
    if plan is not None:
        ask, bid = np.asarray(ask)[positions], np.asarray(bid)[positions]
        se = np.asarray(se)[positions]
        shard_info = _shard_exec_info(plan, mesh, grid, copies, None)
    a, da, va = _split_bumps(ask, n, copies, grid.s0, grid.shape)
    b, db, vb = _split_bumps(bid, n, copies, grid.s0, grid.shape)
    stderr = np.asarray(se)[:n].reshape(grid.shape)
    return GridResult(grid=grid, ask=a, bid=b, max_pieces=0,
                      delta_ask=da, delta_bid=db, vega_ask=va, vega_bid=vb,
                      shard_info=shard_info,
                      row_pieces=np.zeros(grid.shape, dtype=int),
                      stderr=stderr, engine="lsmc")

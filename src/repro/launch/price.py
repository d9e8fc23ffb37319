"""Distributed pricing launcher (the paper's workload as a service).

    PYTHONPATH=src python -m repro.launch.price --n-steps 500 \
        --contracts 8 [--data 1 --model 1] [--tc | --no-tc]

Contracts shard over the data axis; the lattice node axis shards over the
model axis with the paper's round/halo schedule (core/distributed.py).

Scenario-grid mode (one compiled call over the cartesian product of the
given axes, via ``repro.scenarios``):

    PYTHONPATH=src python -m repro.launch.price --grid \
        --n-steps 100 --s0 90,100,110 --sigmas 0.15,0.25 \
        --lambdas 0,0.005,0.01 --payoffs put,call,bull_spread [--greeks] \
        [--backend pallas [--levels L] [--block B]] [--devices W]

``--backend pallas`` routes the transaction-cost engine through the
blocked Pallas kernel rounds (kernels/rz_step.py); the friction-free
engine (all lambdas 0) likewise uses its Pallas lattice kernel.
``--devices W`` shards the scenario batch over a 1-D mesh of W devices
under the cost-model shard plan (core/partition.py::plan_shards); on
CPU, expose fake devices first with
``XLA_FLAGS=--xla_force_host_platform_device_count=W`` (asking for more
devices than the process has runs the identical plan single-device —
the simulated mesh, see docs/KNOWN_ISSUES.md).

Monte Carlo engine (grid mode): ``--engine lsmc`` — or ``--n-assets``
> 1 / ``--exercise-dates`` under ``--engine auto`` — routes the grid
through the least-squares Monte Carlo engine (core/lsmc.py)::

    PYTHONPATH=src python -m repro.launch.price --grid --engine lsmc \
        --n-steps 50 --s0 90,100,110 --paths 8192 \
        --exercise-dates 10,25,50 --n-assets 3 [--mc-seed 0] \
        [--basis laguerre --degree 4]

``--exercise-dates`` is a comma list of lattice step indices (must
include the terminal step ``--n-steps``); lsmc output adds the
per-scenario MC standard error column.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.distributed import build_notc_sharded, build_rz_sharded
from ..core.payoff import american_put, bull_spread
from .mesh import make_test_mesh


def _floats(csv: str):
    return tuple(float(x) for x in csv.split(","))


def _steps(csv):
    return (None if csv is None
            else tuple(int(x) for x in csv.split(",")))


def run_grid(args) -> None:
    from ..api import price_grid
    grid_kwargs = dict(
        s0=_floats(args.s0), sigma=_floats(args.sigmas),
        rate=_floats(args.rates), maturity=_floats(args.maturities),
        cost_rate=_floats(args.lambdas),
        payoff=tuple(args.payoffs.split(",")),
        strike=_floats(args.strikes), n_assets=args.n_assets,
        exercise_steps=_steps(args.exercise_dates))
    t0 = time.perf_counter()
    from ..configs.pricing import ExecutionConfig
    res = price_grid(n_steps=args.n_steps,
                     execution=ExecutionConfig(
                         engine=args.engine, backend=args.backend,
                         interpret=args.interpret, platform=args.platform,
                         devices=args.devices, n_paths=args.paths,
                         mc_seed=args.mc_seed, basis=args.basis,
                         degree=args.degree),
                     capacity=args.capacity, greeks=args.greeks,
                     levels=args.levels, block=args.block, **grid_kwargs)
    n = res.grid.n_scenarios
    dt = time.perf_counter() - t0
    if res.shard_info is not None:
        si = res.shard_info
        kind = "simulated" if si.simulated else "device"
        print(f"[{kind} mesh: {si.plan.n_shards} shards, "
              f"{si.plan.lanes} lanes/shard, rows {si.plan.sizes}, "
              f"predicted work spread {si.plan.work_spread:.1%}]")
    ask, bid = res.ask.ravel(), res.bid.ravel()
    se = None if res.stderr is None else res.stderr.ravel()
    g = res.grid
    for i in range(n):
        line = (f"{g.payoff[i]:>11s} K={g.strike[i]:6.1f} "
                f"S0={g.s0[i]:6.1f} sig={g.sigma[i]:.2f} "
                f"lam={g.cost_rate[i]:.3f}  ask={ask[i]:9.6f} "
                f"bid={bid[i]:9.6f}")
        if se is not None:
            line += f"  se={se[i]:.6f}"
        if args.greeks:
            line += (f"  delta={res.delta_ask.ravel()[i]:+.4f} "
                     f"vega={res.vega_ask.ravel()[i]:8.4f}")
        print(line)
    extra = (f", engine={res.engine}" if res.engine else "")
    print(f"\n{n} scenarios, N={args.n_steps}{extra}: {dt:.2f}s incl. "
          f"compile ({n / dt:.1f} contracts/s; re-run hits the compile "
          "cache)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-steps", type=int, default=500)
    ap.add_argument("--contracts", type=int, default=8)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--round-depth", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=48)
    ap.add_argument("--cost-rate", type=float, default=0.005)
    ap.add_argument("--payoff", default="put", choices=["put", "bull_spread"])
    ap.add_argument("--no-tc", action="store_true")
    # scenario-grid mode
    ap.add_argument("--grid", action="store_true",
                    help="price the cartesian scenario grid in one call")
    ap.add_argument("--s0", default="90,100,110")
    ap.add_argument("--sigmas", default="0.2")
    ap.add_argument("--rates", default="0.1")
    ap.add_argument("--maturities", default="0.25")
    ap.add_argument("--lambdas", default="0,0.005,0.01")
    ap.add_argument("--payoffs", default="put")
    ap.add_argument("--strikes", default="100")
    ap.add_argument("--greeks", action="store_true")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"],
                    help="grid-engine implementation: vectorised jnp "
                         "recursion or the blocked Pallas kernel rounds")
    ap.add_argument("--platform", default=None,
                    choices=["cpu", "gpu", "tpu"],
                    help="pin the platform policy (core/platform.py): "
                         "interpret mode, default dtype and XLA flags "
                         "(default: auto-detect)")
    ap.add_argument("--interpret", default="auto",
                    choices=["auto", "on", "off"],
                    help="Pallas execution mode; auto = platform policy "
                         "(interpret on CPU, compiled on GPU/TPU)")
    ap.add_argument("--levels", type=int, default=None,
                    help="Pallas round depth L (default: partition.py pick)")
    ap.add_argument("--block", type=int, default=None,
                    help="Pallas node-block size (default: one re-balanced "
                         "block per round)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the scenario batch over a 1-D mesh of this "
                         "many devices (grid mode; cost-model shard plan)")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "notc", "rz", "lsmc"],
                    help="grid engine (auto routes by contract shape then "
                         "cost rate; lsmc = least-squares Monte Carlo)")
    ap.add_argument("--paths", type=int, default=4096,
                    help="Monte Carlo paths per scenario (lsmc engine)")
    ap.add_argument("--exercise-dates", default=None,
                    help="comma list of Bermudan exercise step indices "
                         "(must include --n-steps; routes to lsmc)")
    ap.add_argument("--n-assets", type=int, default=1,
                    help="basket size per scenario (>1 routes to lsmc)")
    ap.add_argument("--mc-seed", type=int, default=0,
                    help="PRNG seed for the lsmc engine (deterministic)")
    ap.add_argument("--basis", default="poly",
                    choices=["poly", "laguerre"],
                    help="lsmc regression basis")
    ap.add_argument("--degree", type=int, default=3,
                    help="lsmc regression basis degree")
    args = ap.parse_args()
    args.interpret = {"auto": None, "on": True, "off": False}[args.interpret]
    from ..core.platform import use_compile_cache
    use_compile_cache()
    if args.platform is not None:
        from ..core.platform import set_platform
        set_platform(args.platform)

    if args.grid:
        run_grid(args)
        return

    mesh = make_test_mesh(args.data, args.model)
    n = args.contracts
    s0 = jnp.linspace(90.0, 110.0, n).astype(jnp.float64)
    sig = jnp.full((n,), 0.2)
    rate = jnp.full((n,), 0.1)
    mat = jnp.full((n,), 0.25)

    if args.no_tc:
        f = jax.jit(build_notc_sharded(mesh, n_steps=args.n_steps,
                                       strike=100.0,
                                       round_depth=args.round_depth))
        t0 = time.perf_counter()
        price = np.asarray(f(s0, sig, rate, mat))
        dt = time.perf_counter() - t0
        for i in range(n):
            print(f"S0={float(s0[i]):6.1f}  price={price[i]:.6f}")
    else:
        pay = american_put(100.0) if args.payoff == "put" else bull_spread()
        f = jax.jit(build_rz_sharded(
            mesh, n_steps=args.n_steps, payoff=pay, capacity=args.capacity,
            round_depth=args.round_depth))
        k = jnp.full((n,), args.cost_rate)
        t0 = time.perf_counter()
        ask, bid, pieces = f(s0, sig, rate, mat, k)
        ask, bid = np.asarray(ask), np.asarray(bid)
        dt = time.perf_counter() - t0
        for i in range(n):
            print(f"S0={float(s0[i]):6.1f}  ask={ask[i]:.6f}  "
                  f"bid={bid[i]:.6f}")
        print(f"max PWL knots: {int(pieces)} (capacity {args.capacity})")
    print(f"{n} contracts, N={args.n_steps}: {dt:.2f}s (incl. compile)")


if __name__ == "__main__":
    main()

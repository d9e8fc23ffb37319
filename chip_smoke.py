"""Smoke test of the pricer's main path on a TPU, through its public entry points.

Run from the repository root:

    python chip_smoke.py              # one chip: notc_chain, notc_pallas,
                                      # rz_grid, lsmc, gateway
    python chip_smoke.py --chips 4    # four chips: the scenario-mesh phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse             # tiny, on the CPU
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --chips 4

Everything runs in this one process (a child would find the chip held).
Each phase prints one JSON line ``{"phase", "compile_s", "run_s",
"max_err", "tol", ...}``; ``compile_s`` is the wall time inside the
phase in which JAX's backend compiler ran (concurrent compiles count
once) and ``run_s`` is the rest (tracing, host work, the device and the
oracles).  A failed check raises, so the script exits non-zero with a
traceback that names the phase.  The last line is ``{"ok": true,
"device": {"platform", "kind", "count"}}``.  Without ``--rehearse`` it
refuses to run unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

# real sizes; --rehearse shrinks every phase to what the CPU runs quickly.
# The TC depth is the serving depth 16 (launch/serve_pricing.py), the
# deepest tree the TC engine prices on a TPU, whose float64 is a float32
# pair (core/platform.py::tc_max_steps); rz_grid checks that the issue's
# depth 64 is refused there rather than priced wrongly.
SIZES = {
    False: dict(chain_rows=2048, chain_n=1500, samples=16, deep_n=20000,
                tc_rows=256, tc_n=16, tc_deep_n=64, capacity=48,
                mc_rows=64, mc_n=50,
                mc_paths=4096, gw_notc=64),
    True: dict(chain_rows=48, chain_n=60, samples=6, deep_n=600,
               tc_rows=32, tc_n=16, tc_deep_n=64, capacity=48,
               mc_rows=8, mc_n=10,
               mc_paths=1024, gw_notc=8),
}
ORACLE_TOL = 1e-9          # float64 engines against the float64 oracles
# float32 kernel against the float64 oracle: |err| <= F32_TOL * max(1, |ref|)
# at N=1500, DEEP_F32_TOL at the paper's N=20000 (see CHANGES.md)
F32_TOL = 1e-4
DEEP_F32_TOL = 2e-4
COST_RATES = (0.0, 0.0025, 0.005, 0.01)
BERMUDAN_DATES = 5

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_spans = []        # (start, end) of every backend compile


def _on_duration(event, duration, **_):
    if event == _COMPILE_EVENT:
        end = time.perf_counter()
        _compile_spans.append((end - duration, end))


def compile_seconds(since=0.0):
    """Wall seconds after ``since`` in which a backend compile ran
    (concurrent compiles count once)."""
    total, reach = 0.0, since
    for start, end in sorted(_compile_spans):
        start = max(start, reach)
        if end > start:
            total, reach = total + end - start, end
    return total


class CheckFailed(AssertionError):
    pass


def check(ok, what):
    if not ok:
        raise CheckFailed(what)


@contextlib.contextmanager
def phase(name):
    """Time one phase, then print its JSON line; the body fills the
    yielded dict (``max_err``, ``tol`` and anything else)."""
    record, t0 = {}, time.perf_counter()
    try:
        yield record
    except Exception as e:
        raise RuntimeError(f"phase {name!r} failed: {e}") from e
    compile_s = compile_seconds(since=t0)
    line = {"phase": name, "compile_s": round(compile_s, 3),
            "run_s": round(time.perf_counter() - t0 - compile_s, 3)}
    line.update(record)
    print(json.dumps(line), flush=True)


# ----------------------------------------------------------------- inputs
def _payoff(name, strike):
    from repro.core import american_call, american_put, bull_spread
    if name == "put":
        return american_put(strike)
    if name == "call":
        return american_call(strike)
    return bull_spread(strike, strike + 10.0)


def chain_rows(n_rows):
    """Frictionless chain: rows cycle through put/call/bull_spread x 8
    strikes x 16 spots x 6 vols (one year, 5% rate)."""
    i = np.arange(n_rows)
    return dict(
        s0=np.linspace(85.0, 115.0, 16)[(i // 24) % 16],
        sigma=np.array([0.15, 0.2, 0.25, 0.3, 0.4, 0.5])[(i // 384) % 6],
        rate=np.full(n_rows, 0.05), maturity=np.full(n_rows, 1.0),
        payoff=tuple(np.array(["put", "call", "bull_spread"])[i % 3]),
        strike=np.linspace(80.0, 120.0, 8)[(i // 3) % 8])


def tc_rows(n_rows):
    """TC grid: cost rates x put/call x strikes x spots, half a year."""
    n_cells = n_rows // (len(COST_RATES) * 2)
    n_strikes = 4 if n_cells >= 16 else 2
    n_spots = n_cells // n_strikes
    lam, pay, k, s = np.meshgrid(np.arange(len(COST_RATES)), [0, 1],
                                 np.linspace(90.0, 110.0, n_strikes),
                                 np.linspace(85.0, 115.0, n_spots),
                                 indexing="ij")
    lam, pay = lam.ravel(), pay.ravel()
    return dict(s0=s.ravel(), sigma=np.full(lam.size, 0.25),
                rate=np.full(lam.size, 0.05),
                maturity=np.full(lam.size, 0.5),
                cost_rate=np.asarray(COST_RATES)[lam],
                payoff=tuple(np.array(["put", "call"])[pay]),
                strike=k.ravel())


def _model(rows, j, n_steps):
    from repro.core import LatticeModel
    lam = float(rows["cost_rate"][j]) if "cost_rate" in rows else 0.0
    return LatticeModel(
        s0=float(rows["s0"][j]), sigma=float(rows["sigma"][j]),
        rate=float(rows["rate"][j]), maturity=float(rows["maturity"][j]),
        n_steps=n_steps, cost_rate=lam)


def on_host():
    """The oracles' payoffs are jnp functions: evaluate them on the host
    CPU, so the reference shares no rounding with the device under test."""
    import jax
    return jax.default_device(jax.devices("cpu")[0])


def notc_oracle(rows, idx, n_steps):
    from repro.core import price_notc_np
    with on_host():
        return {int(j): price_notc_np(_model(rows, j, n_steps),
                                      _payoff(rows["payoff"][j],
                                              float(rows["strike"][j])))
                for j in idx}


def _rel_err(got, ref):
    return abs(got - ref) / max(1.0, abs(ref))


# ----------------------------------------------------------------- phases
def phase_notc_chain(sz, state):
    from repro.api import price_flat
    rows = chain_rows(sz["chain_rows"])
    with phase("notc_chain") as rec:
        res = price_flat(**rows, n_steps=sz["chain_n"])
        ask = np.asarray(res.ask)
        check(res.engine == "notc", f"routed to {res.engine}, not notc")
        check(ask.shape == (sz["chain_rows"],) and np.isfinite(ask).all(),
              f"ask shape {ask.shape} or non-finite values")
        idx = np.linspace(0, sz["chain_rows"] - 1, sz["samples"]).astype(int)
        ref = notc_oracle(rows, idx, sz["chain_n"])
        err = max(abs(ask[j] - r) for j, r in ref.items())
        rec.update(rows=sz["chain_rows"], n_steps=sz["chain_n"],
                   sampled=len(ref), max_err=err, tol=ORACLE_TOL)
        check(err <= ORACLE_TOL, f"max |err| {err} > {ORACLE_TOL}")
    state.update(chain=rows, chain_ask=ask, chain_ref=ref)


def phase_notc_pallas(sz, state):
    from repro.api import ExecutionConfig, price_flat
    from repro.core import LatticeModel, american_put, price_notc_np
    rows, ref = state["chain"], state["chain_ref"]
    pallas = ExecutionConfig(backend="pallas")
    with phase("notc_pallas") as rec:
        check(not pallas.resolved().interpret or sz["rehearse"],
              "the lattice kernel resolved to interpret mode")
        ask = np.asarray(price_flat(**rows, n_steps=sz["chain_n"],
                                    execution=pallas).ask)
        check(np.isfinite(ask).all(), "non-finite kernel prices")
        err = max(_rel_err(ask[j], r) for j, r in ref.items())
        # the paper's appendix put (13.906 at N=20000 in the paper)
        paper = dict(s0=100.0, sigma=0.3, rate=0.06, maturity=3.0)
        deep = float(price_flat(**paper, payoff="put", strike=100.0,
                                n_steps=sz["deep_n"], execution=pallas).ask[0])
        with on_host():
            deep_ref = price_notc_np(
                LatticeModel(**paper, n_steps=sz["deep_n"]),
                american_put(100.0))
        deep_err = _rel_err(deep, deep_ref)
        rec.update(rows=len(ask), n_steps=sz["chain_n"], dtype=state["dtype"],
                   max_err=err, tol=F32_TOL, deep_n=sz["deep_n"],
                   deep_price=deep, deep_ref=deep_ref, deep_err=deep_err,
                   deep_tol=DEEP_F32_TOL)
        check(err <= F32_TOL, f"chain max rel err {err} > {F32_TOL}")
        check(deep_err <= DEEP_F32_TOL,
              f"N={sz['deep_n']} put: {deep} vs {deep_ref}")


def phase_rz_grid(sz, state):
    from repro.api import price_flat
    from repro.core import price_ref
    rows, n, cap = tc_rows(sz["tc_rows"]), sz["tc_n"], sz["capacity"]
    with phase("rz_grid") as rec:
        res = price_flat(**rows, n_steps=n, capacity=cap)
        ask, bid = np.asarray(res.ask), np.asarray(res.bid)
        pieces = np.asarray(res.row_pieces).ravel()
        check(res.engine == "rz", f"routed to {res.engine}, not rz")
        check(np.isfinite(ask).all() and np.isfinite(bid).all(),
              "non-finite quotes")
        check(int(pieces.max()) < cap, f"max_pieces {pieces.max()} >= {cap}")
        # at lambda=0 the two sides agree only to rounding (~1e-13), so
        # those rows are held to the oracle on both sides instead
        zero = np.flatnonzero(rows["cost_rate"] == 0.0)
        costly = rows["cost_rate"] > 0.0
        check(bool((ask[costly] >= bid[costly]).all()),
              f"ask < bid in {int((ask[costly] < bid[costly]).sum())} rows")
        ref0 = notc_oracle(rows, zero, n)
        err0 = max(max(abs(ask[j] - r), abs(bid[j] - r))
                   for j, r in ref0.items())
        # one row per nonzero cost rate against the sequential PWL oracle
        picks = [int(np.flatnonzero(rows["cost_rate"] == lam)[k])
                 for k, lam in enumerate(COST_RATES[1:])]
        err_ref = 0.0
        for j in picks:
            with on_host():
                r = price_ref(_model(rows, j, n), _payoff(
                    rows["payoff"][j], float(rows["strike"][j])))
            err_ref = max(err_ref, abs(ask[j] - r.ask), abs(bid[j] - r.bid))
        deep = _deep_tc_refused(rows, sz["tc_deep_n"], cap)
        rec.update(rows=len(ask), n_steps=n, capacity=cap,
                   max_pieces=int(pieces.max()),
                   max_err=max(err0, err_ref), tol=ORACLE_TOL,
                   lambda0_rows=len(zero), price_ref_rows=len(picks),
                   deep_n=sz["tc_deep_n"], deep_refused=deep)
        check(err0 <= ORACLE_TOL, f"lambda=0 rows off notc by {err0}")
        check(err_ref <= ORACLE_TOL, f"rows off price_ref by {err_ref}")
    state.update(tc=rows, tc_ask=ask, tc_bid=bid)


def _deep_tc_refused(rows, n_steps, cap):
    """Where the platform caps the TC depth below ``n_steps``, pricing
    at ``n_steps`` must raise before anything compiles; elsewhere there
    is nothing to check (None)."""
    from repro.api import price_flat
    from repro.core.platform import tc_max_steps
    limit = tc_max_steps()
    if limit is None or n_steps <= limit:
        return None
    pick = np.flatnonzero(rows["cost_rate"] > 0.0)[:2]   # routed to rz
    two = {k: np.asarray(v)[pick] if k != "payoff" else
           tuple(np.asarray(v)[pick]) for k, v in rows.items()}
    try:
        price_flat(**two, n_steps=n_steps, capacity=cap)
    except NotImplementedError:
        return True
    raise CheckFailed(f"n_steps={n_steps} priced past the TC limit {limit}")


def phase_lsmc(sz, state):
    from _stats import assert_within_se
    from repro.api import ExecutionConfig, price_flat
    m, n = sz["mc_rows"], sz["mc_n"]
    i = np.arange(m)
    rows = dict(s0=np.linspace(85.0, 115.0, 8)[i % 8],
                sigma=np.array([0.2, 0.3])[(i // 8) % 2],
                rate=np.full(m, 0.05), maturity=np.full(m, 0.5),
                payoff=("put",) * m,
                strike=np.linspace(90.0, 110.0, 4)[(i // 16) % 4])
    with phase("lsmc") as rec:
        res = price_flat(**rows, n_steps=n,
                         execution=ExecutionConfig(engine="lsmc",
                                                   n_paths=sz["mc_paths"]))
        ask, se = np.asarray(res.ask), np.asarray(res.stderr)
        coarse = notc_oracle(rows, i, n)
        # the CRR tree's own discretisation gap: N steps vs 8N steps
        fine = notc_oracle(rows, i, 8 * n)
        worst = 0.0
        for j in i:
            gap = abs(coarse[j] - fine[j])
            assert_within_se(ask[j], coarse[j], se[j], k=3.0, extra=gap,
                             label=f"lsmc row {j}")
            worst = max(worst, abs(ask[j] - coarse[j]) / (3.0 * se[j] + gap))
        rec.update(rows=m, n_steps=n, n_paths=sz["mc_paths"],
                   max_err=float(np.max(np.abs(ask - [coarse[j] for j in i]))),
                   tol="3*SE+CRR gap", worst_share_of_bound=worst)


def phase_gateway(sz, state):
    """Mixed requests through the thread-pool gateway.  ``max_batch`` is
    the TC grid's row count and the TC requests are that grid's rows, so
    their chunk is the program ``rz_grid`` compiled."""
    from repro.api import ExecutionConfig, price_flat
    from repro.serve.engine import PriceRequest
    from repro.serve.gateway import PricingGateway
    tc, chain = state["tc"], state["chain"]
    n_tc = len(tc["s0"])
    notc_idx = np.linspace(0, len(chain["s0"]) - 1, sz["gw_notc"]).astype(int)
    berm = dict(s0=100.0, sigma=0.25, rate=0.05, maturity=0.5,
                payoff="put", strike=100.0, n_steps=sz["mc_n"],
                exercise_steps=tuple(int(t) for t in np.linspace(
                    0, sz["mc_n"], BERMUDAN_DATES + 1)[1:]))

    def req(rows, j, n_steps, lam=0.0):
        k = float(rows["strike"][j])
        return PriceRequest(s0=float(rows["s0"][j]),
                            sigma=float(rows["sigma"][j]),
                            rate=float(rows["rate"][j]),
                            maturity=float(rows["maturity"][j]),
                            cost_rate=lam, payoff=str(rows["payoff"][j]),
                            strike=k, strike2=k + 10.0, n_steps=n_steps)

    reqs, want = [], []
    for j in range(n_tc):                 # interleave the three kinds
        lam = float(tc["cost_rate"][j])
        reqs.append(req(tc, j, sz["tc_n"], lam))
        want.append((state["tc_ask"][j], state["tc_bid"][j]))
        if j < len(notc_idx):
            c = int(notc_idx[j])
            reqs.append(req(chain, c, sz["chain_n"]))
            want.append((state["chain_ask"][c],) * 2)
    reqs.append(PriceRequest(**berm, cost_rate=0.0))

    async def drive():
        async with PricingGateway(pool="thread", replicas=1,
                                  max_batch=n_tc, deadline_ms=50.0,
                                  capacity=sz["capacity"]) as gw:
            rids = [await gw.submit(r) for r in reqs]
            quotes = [await gw.result(rid) for rid in rids]
            return quotes, gw.metrics()

    with phase("gateway") as rec:
        berm_direct = price_flat(**berm, execution=ExecutionConfig())
        want.append((float(berm_direct.ask[0]), float(berm_direct.bid[0])))
        quotes, metrics = asyncio.run(drive())
        err = max(max(abs(q.ask - a), abs(q.bid - bb))
                  for q, (a, bb) in zip(quotes, want))
        rec.update(requests=len(reqs), tc=n_tc, notc=len(notc_idx),
                   bermudan=1, max_err=err, tol=ORACLE_TOL,
                   failed=metrics["failed"], shed=metrics["shed"],
                   completed=metrics["completed"])
        check(metrics["failed"] == 0 and metrics["shed"] == 0,
              f"failed={metrics['failed']} shed={metrics['shed']}")
        check(metrics["completed"] >= len(reqs),
              f"completed {metrics['completed']} < {len(reqs)}")
        check(err <= ORACLE_TOL, f"gateway quotes off direct by {err}")


def phase_mesh(sz, state, n_dev):
    """notc_chain and rz_grid batches on a real ``n_dev``-device mesh
    against the single-device call."""
    import jax

    from repro.api import price_flat
    from repro.core.distributed import grid_mesh
    mesh = grid_mesh(n_dev)
    devices = {d.id for d in mesh.devices.flat}
    with phase("mesh") as rec:
        check(len(devices) == n_dev, f"mesh holds {len(devices)} devices")
        errs, bit_equal = {}, True
        for name, rows, kw in (
                ("notc", chain_rows(sz["chain_rows"]),
                 dict(n_steps=sz["chain_n"])),
                ("rz", tc_rows(sz["tc_rows"]),
                 dict(n_steps=sz["tc_n"], capacity=sz["capacity"]))):
            # the two programs compile concurrently (a TC one takes
            # minutes); XLA's compiler releases the GIL
            with ThreadPoolExecutor(2) as pool:
                one = pool.submit(price_flat, **rows, **kw)
                many = pool.submit(price_flat, **rows, **kw, mesh=mesh)
                one, many = one.result(), many.result()
            info = many.shard_info
            check(info is not None and not info.simulated,
                  f"{name}: the sharded call did not run on a real mesh")
            check(len(info.per_shard_rows) == n_dev
                  and min(info.per_shard_rows) > 0,
                  f"{name}: shard rows {info.per_shard_rows}")
            for side in ("ask", "bid"):
                a = np.asarray(getattr(one, side))
                b = np.asarray(getattr(many, side))
                errs[f"{name}_{side}"] = float(np.max(np.abs(a - b)))
                bit_equal &= bool(np.array_equal(a, b))
        err = max(errs.values())
        rec.update(devices=sorted(devices), simulated=False,
                   bit_equal=bit_equal, max_err=err, tol=ORACLE_TOL,
                   per_engine_err=errs,
                   platform=jax.devices()[0].platform)
        check(err <= ORACLE_TOL, f"mesh vs single-device off by {err}")


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the scenario-mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="skip the TPU check and shrink every phase "
                         "(a CPU rehearsal of the control flow)")
    args = ap.parse_args(argv)

    try:
        from repro.core.platform import default_dtype, use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing ({e})",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    sz = dict(SIZES[args.rehearse], rehearse=args.rehearse)
    state = {"dtype": default_dtype().name}
    print(json.dumps({"compile_cache": cache, "jax": jax.__version__,
                      "rehearse": args.rehearse}), flush=True)

    if args.chips == 1:
        phase_notc_chain(sz, state)
        phase_notc_pallas(sz, state)
        phase_rz_grid(sz, state)
        phase_lsmc(sz, state)
        phase_gateway(sz, state)
    else:
        phase_mesh(sz, state, args.chips)
    print(json.dumps({"total_compile_s": round(compile_seconds(), 3)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

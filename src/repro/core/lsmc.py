"""Parallel least-squares Monte Carlo (Longstaff–Schwartz) Bermudan engine.

The third pricing engine, opening the workload the binomial lattice
structurally cannot price: ``d > 1`` underlyings and Bermudan exercise
schedules.  Follows the multi-core LSMC decomposition of Doan et al.
2008 and the massively-parallel American-style MC pricing of
Pagès–Wilbertz 2011 (see PAPERS.md): paths are embarrassingly parallel,
scenarios vmap into one compiled call, and the flat scenario batch
shards over the existing 1-D grid mesh
(``core/distributed.py::grid_mesh``) with **no new collectives** — the
per-row reductions (mean / standard error) stay inside the row.

Model and estimator
-------------------
* ``d = n_assets`` independent GBMs share the row's ``(s0, sigma,
  rate)``; the payoff applies to the **arithmetic basket mean**
  ``b = mean_j S_j`` through the same 4-parameter payoff family the
  lattice engines batch as data (``core/payoff.py``).  For ``d = 1``
  this is exactly the single-asset model of the lattice engines — the
  overlapping domain the oracle tests lock against.
* Antithetic GBM path generation under an **explicit PRNG key per
  scenario row** (:func:`path_keys`): results are bitwise deterministic
  for a given ``seed`` and independent of batching/sharding layout.
* Regression basis: plain polynomial or Laguerre in the moneyness
  ``b / K1``, pluggable ``degree``; the continuation value is fit by
  masked ridge-regularised normal equations over in-the-money paths
  only (the classic Longstaff–Schwartz restriction), solved by an
  unrolled Cholesky factorisation.
* Backward induction runs over a static Bermudan ``exercise_steps``
  schedule (a subset of lattice steps, terminal step mandatory; step 0
  is handled deterministically as ``max(intrinsic(s0), MC estimate)``).
* Output per scenario: the price and its Monte Carlo **standard
  error** (antithetic pair-level, ``ddof=1``) — the honest tolerance
  every MC test asserts against (``tests/_stats.py``).

Transaction costs
-----------------
Under ``cost_rate = λ > 0`` the engine quotes the crude *premium
convention* ``ask = (1+λ)·P``, ``bid = (1−λ)·P`` (costs charged on the
option trade itself, not the hedge).  This is NOT the Roux–Zastawniak
hedging interval — the 1-D TC domain stays with the ``rz`` engine; see
``docs/KNOWN_ISSUES.md``.  ``λ = 0`` degenerates to ``ask = bid = P``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["LSMC_BASES", "exercise_schedule", "path_keys",
           "simulate_basket", "basis_matrix", "lsmc_rows", "lsmc_rows_jit"]

LSMC_BASES = ("poly", "laguerre")

# ridge added to the (moneyness-normalised) Gram matrix so an all-OTM
# date — a singular regression — degrades to beta = 0 instead of NaN
_RIDGE = 1e-10

# a path is in the money when its payoff clears this fraction of the
# moneyness scale.  Inside the fused program on a TPU (float64 as a
# float32 pair) out-of-the-money payoffs came out as tiny nonzero values
# in ~4% of (path, date) entries, and a bare `h > 0` counted those paths
# in the money.  1e-6 of the strike cleared every such residue on a v5e
# (the in-the-money counts then equal the CPU's at every date) and is
# far below any payoff that moves a regression.
_ITM_TOL = 1e-6


def exercise_schedule(n_steps: int,
                      exercise_steps: Optional[Sequence[int]]) -> Tuple[int, ...]:
    """Normalise a Bermudan schedule to an ascending tuple of step indices.

    ``None`` means American-on-the-lattice-clock: every step ``0..N``.
    An explicit schedule must stay within ``0..N`` and **include the
    terminal step** ``N`` (an option that can never pay at expiry is a
    different contract, almost certainly a bug).
    """
    if exercise_steps is None:
        return tuple(range(n_steps + 1))
    steps = tuple(sorted({int(s) for s in exercise_steps}))
    if not steps:
        raise ValueError("exercise_steps must not be empty")
    if steps[0] < 0 or steps[-1] > n_steps:
        raise ValueError(f"exercise_steps {steps} outside 0..{n_steps}")
    if steps[-1] != n_steps:
        raise ValueError(
            f"exercise_steps must include the terminal step {n_steps} "
            f"(got {steps})")
    return steps


def path_keys(seed: int, n_rows: int) -> jnp.ndarray:
    """Per-row PRNG key data, derived from ``seed`` by **row index**.

    Returned as a ``(n_rows, 2)`` uint32 array so keys travel as plain
    row data through the same gather/pad shard layout as every other
    column — which is exactly why sharded results are bit-equal to the
    single-device call (rows are independent, each carries its own
    key).  Row ``i`` always gets the same key for a given seed, so a
    contract's quote does not depend on how large the batch was padded.
    """
    key = jax.random.PRNGKey(int(seed))
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(int(n_rows), dtype=jnp.uint32))


def basis_matrix(x: jnp.ndarray, degree: int, kind: str) -> jnp.ndarray:
    """Regression design matrix over the moneyness ``x`` — ``(P, degree+1)``.

    ``kind="poly"``: monomials ``1, x, ..., x^degree``;
    ``kind="laguerre"``: Laguerre polynomials ``L_0..L_degree`` via the
    three-term recurrence (the Longstaff–Schwartz choice, numerically
    tamer than raw monomials at higher degree).
    """
    if degree < 0:
        raise ValueError("need degree >= 0")
    if kind == "poly":
        cols = [jnp.ones_like(x)]
        for d in range(1, degree + 1):
            cols.append(cols[-1] * x)
    elif kind == "laguerre":
        cols = [jnp.ones_like(x)]
        if degree >= 1:
            cols.append(1.0 - x)
        for k in range(1, degree):
            cols.append(((2 * k + 1 - x) * cols[-1] - k * cols[-2])
                        / (k + 1))
    else:
        raise ValueError(f"unknown basis {kind!r}; use one of {LSMC_BASES}")
    return jnp.stack(cols, axis=-1)


def simulate_basket(s0, sigma, rate, maturity, key, *, n_steps: int,
                    steps: Tuple[int, ...], n_paths: int, n_assets: int,
                    antithetic: bool):
    """Antithetic GBM basket paths at the schedule's positive steps.

    Returns ``(b, t)``: ``b`` is the arithmetic basket mean, shape
    ``(n_paths, n_sim)`` over the simulated exercise dates, ``t`` the
    corresponding year-fraction times ``(n_sim,)``.  ``steps`` entries
    at 0 are skipped (the t=0 state is the deterministic ``s0``).  With
    ``antithetic`` the first ``n_paths//2`` rows use draws ``+Z`` and
    the second half ``-Z`` (``n_paths`` must be even).
    """
    sim = tuple(s for s in steps if s > 0)
    if not sim:
        raise ValueError("schedule has no positive step to simulate")
    if antithetic and n_paths % 2:
        raise ValueError("antithetic sampling needs an even n_paths")
    dtype = jnp.float64
    frac = jnp.asarray(sim, dtype) / n_steps
    t = maturity * frac                                     # (n_sim,)
    dts = jnp.diff(t, prepend=jnp.zeros((1,), dtype))       # (n_sim,)
    m = n_paths // 2 if antithetic else n_paths
    z = jax.random.normal(key, (m, len(sim), n_assets), dtype)
    if antithetic:
        z = jnp.concatenate([z, -z], axis=0)
    drift = (rate - 0.5 * sigma * sigma) * dts
    shock = sigma * jnp.sqrt(dts)
    logs = jnp.cumsum(drift[None, :, None] + shock[None, :, None] * z,
                      axis=1)
    b = jnp.mean(s0 * jnp.exp(logs), axis=2)                # (P, n_sim)
    return b, t


def _spd_solve(a, b):
    """Solve ``a @ x = b`` for a small symmetric positive-definite ``a``.

    An unrolled Cholesky factorisation in plain jnp, so it compiles in
    any dtype on every backend (the TPU's LU decomposition takes float32
    only).  The ridge keeps every pivot positive.
    """
    n = a.shape[0]
    low = [[None] * n for _ in range(n)]
    for j in range(n):
        low[j][j] = jnp.sqrt(a[j, j] - sum(low[j][k] ** 2 for k in range(j)))
        for i in range(j + 1, n):
            low[i][j] = (a[i, j] - sum(low[i][k] * low[j][k]
                                       for k in range(j))) / low[j][j]
    y = []
    for i in range(n):
        y.append((b[i] - sum(low[i][k] * y[k] for k in range(i)))
                 / low[i][i])
    x = [None] * n
    for i in reversed(range(n)):
        x[i] = (y[i] - sum(low[k][i] * x[k] for k in range(i + 1, n))
                ) / low[i][i]
    return jnp.stack(x)


def _payoff_pos(b, alpha, zeta, w1, w2, k1, k2):
    """Intrinsic value of the 4-parameter payoff family, floored at 0
    (identical to the lattice engines' convention)."""
    pay = (alpha * k1 + w1 * jnp.maximum(b - k1, 0.0)
           + w2 * jnp.maximum(b - k2, 0.0) + zeta * b)
    return jnp.maximum(pay, 0.0)


def _lsmc_row(s0, sigma, rate, maturity, k, alpha, zeta, w1, w2, k1, k2,
              key, *, n_steps: int, steps: Tuple[int, ...], n_paths: int,
              n_assets: int, degree: int, basis: str, antithetic: bool):
    """One scenario row -> (ask, bid, stderr).  All hyperparameters are
    static; everything else is traced, so the whole batch vmaps."""
    b, t = simulate_basket(s0, sigma, rate, maturity, key, n_steps=n_steps,
                           steps=steps, n_paths=n_paths, n_assets=n_assets,
                           antithetic=antithetic)
    P = b.shape[0]
    h = _payoff_pos(b, alpha, zeta, w1, w2, k1, k2)         # (P, n_sim)
    v = h[:, -1]
    # moneyness scale for the regression — strike-normalised so the Gram
    # matrix is O(1) regardless of the contract's price level
    scale = jnp.where(k1 > 0.0, k1, jnp.where(s0 > 0.0, s0, 1.0))
    n_sim = b.shape[1]
    if n_sim > 1:
        df_step = jnp.exp(-rate * jnp.diff(t))              # (n_sim-1,)
        xs = (jnp.flip(b[:, :-1].T, 0), jnp.flip(h[:, :-1].T, 0),
              jnp.flip(df_step, 0))

        def body(val, x):
            bj, hj, dfj = x
            val = val * dfj
            phi = basis_matrix(bj / scale, degree, basis)    # (P, q)
            itm = hj > _ITM_TOL * scale
            a = phi * itm[:, None]
            # products and sums, not dots: the TPU runs a float64 dot as
            # float32 MXU passes at bfloat16 precision
            gram = (jnp.sum(a[:, :, None] * a[:, None, :], axis=0) / P
                    + _RIDGE * jnp.eye(degree + 1))
            beta = _spd_solve(gram, jnp.sum(a * (val * itm)[:, None],
                                            axis=0) / P)
            cont = jnp.sum(phi * beta, axis=1)
            return jnp.where(itm & (hj > cont), hj, val), None

        v, _ = jax.lax.scan(body, v, xs)
    v = v * jnp.exp(-rate * t[0])                           # first date -> 0
    if antithetic:
        m = P // 2
        pair = 0.5 * (v[:m] + v[m:])
        price = jnp.mean(pair)
        se = jnp.std(pair, ddof=1) / jnp.sqrt(1.0 * m)
    else:
        price = jnp.mean(v)
        se = jnp.std(v, ddof=1) / jnp.sqrt(1.0 * P)
    if steps[0] == 0:
        # exercise at t=0 is deterministic: the basket is s0 exactly
        price = jnp.maximum(_payoff_pos(s0, alpha, zeta, w1, w2, k1, k2),
                            price)
    # premium convention for cost_rate > 0 (see module docstring); the
    # reported stderr is that of the frictionless estimate
    return (1.0 + k) * price, (1.0 - k) * price, se


def lsmc_rows(s0, sigma, rate, maturity, k, alpha, zeta, w1, w2, k1, k2,
              keys, *, n_steps: int, steps: Tuple[int, ...], n_paths: int,
              n_assets: int, degree: int, basis: str, antithetic: bool):
    """Flat-batch LSMC kernel: equal-length row arrays in, rows out.

    The shardable unit, mirroring ``scenarios._rz_rows`` — the sharded
    path wraps exactly this function in ``shard_map`` (each device
    prices its slice of rows), the single path jits it directly.
    ``keys`` is the ``(rows, 2)`` uint32 per-row key column
    (:func:`path_keys`).
    """
    one = partial(_lsmc_row, n_steps=n_steps, steps=steps, n_paths=n_paths,
                  n_assets=n_assets, degree=degree, basis=basis,
                  antithetic=antithetic)
    return jax.vmap(one)(s0, sigma, rate, maturity, k,
                         alpha, zeta, w1, w2, k1, k2, keys)


lsmc_rows_jit = partial(jax.jit, static_argnames=(
    "n_steps", "steps", "n_paths", "n_assets", "degree", "basis",
    "antithetic"))(lsmc_rows)

"""Classic binomial American option pricing without transaction costs.

This is the paper's Appendix workload: scalar backward induction

    pi_N = intrinsic(S_N)
    pi_n(i) = max( intrinsic(S_n(i)),
                   ( p* pi_{n+1}(i+1) + (1-p*) pi_{n+1}(i) ) / r )

It doubles as (a) the friction-free sanity anchor for the transaction-cost
engine (k = 0 must make ask = bid = this price) and (b) the workload of the
Pallas lattice kernel (:mod:`repro.kernels.binomial_step`).

Two implementations:

  * :func:`price_notc_np`   — trivially simple numpy loop (oracle).
  * :func:`price_notc_jax`  — jitted JAX version with a fixed-width buffer
    and ``lax.fori_loop`` over levels (runs fine on CPU, targets TPU VPU).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .lattice import LatticeModel
from .payoff import PayoffProcess

__all__ = ["price_notc_np", "price_notc_jax", "intrinsic_grid"]


def intrinsic_grid(model: LatticeModel, payoff: PayoffProcess, level: int) -> np.ndarray:
    s = model.stock_level(level)
    return np.maximum(payoff.intrinsic(s), 0.0)


def price_notc_np(model: LatticeModel, payoff: PayoffProcess) -> float:
    """Numpy oracle — O(N^2), vectorised per level.

    Node ``i`` of level ``lvl`` sits at ``s0 * exp(m * sigma * sqrt(dt))``
    with ``m = 2i - lvl`` in ``[-N, N]``, so the payoff is evaluated once
    on those 2N+1 prices and each level reads every other entry (the
    same values :func:`intrinsic_grid` gives, without a payoff call per
    level).
    """
    n = model.n_steps
    r = model.r
    p = model.p_star
    m = np.arange(-n, n + 1, dtype=np.float64)
    s = model.s0 * np.exp(m * model.sigma
                          * math.sqrt(model.maturity / model.n_steps))
    pay = np.maximum(payoff.intrinsic(s), 0.0)
    v = pay[0::2]
    for lvl in range(n - 1, -1, -1):
        cont = (p * v[1:lvl + 2] + (1.0 - p) * v[:lvl + 1]) / r
        v = np.maximum(pay[n - lvl:n + lvl + 1:2], cont)
    return float(v[0])


@partial(jax.jit, static_argnames=("n_steps", "kind"))
def _notc_kernel(s0, sigma, rate, maturity, strike, *, n_steps: int, kind: str):
    """Fixed-buffer backward induction.  kind in {put, call}."""
    dt = maturity / n_steps
    u = jnp.exp(sigma * jnp.sqrt(dt))
    r = jnp.exp(rate * dt)
    p = (r - 1.0 / u) / (u - 1.0 / u)
    q = 1.0 - p

    idx = jnp.arange(n_steps + 1, dtype=jnp.float64)

    def intrinsic(lvl):
        s = s0 * jnp.exp((2.0 * idx - lvl) * sigma * jnp.sqrt(dt))
        pay = strike - s if kind == "put" else s - strike
        # mask out columns beyond the level
        return jnp.where(idx <= lvl, jnp.maximum(pay, 0.0), 0.0)

    v0 = intrinsic(jnp.float64(n_steps))

    def body(step, v):
        lvl = n_steps - 1 - step
        cont = (p * jnp.roll(v, -1) + q * v) / r
        return jnp.maximum(intrinsic(lvl.astype(jnp.float64)), cont)

    v = jax.lax.fori_loop(0, n_steps, body, v0)
    return v[0]


def price_notc_jax(model: LatticeModel, payoff: PayoffProcess) -> float:
    """Jitted JAX pricer for vanilla puts/calls (the Appendix workload)."""
    name = payoff.name
    if name.startswith("put"):
        kind, strike = "put", _strike_of(name)
    elif name.startswith("call"):
        kind, strike = "call", _strike_of(name)
    else:
        raise ValueError(f"price_notc_jax supports vanilla put/call, got {name}")
    out = _notc_kernel(
        jnp.float64(model.s0), jnp.float64(model.sigma), jnp.float64(model.rate),
        jnp.float64(model.maturity), jnp.float64(strike),
        n_steps=model.n_steps, kind=kind)
    return float(out)


def _strike_of(name: str) -> float:
    return float(name.split("K=")[1].rstrip(")"))

"""What the pricer refuses off the CPU, and where the entry points cache.

The accelerator cases pin the *policy* to ``tpu`` with
``set_platform("tpu", configure_jax=False)`` (jax keeps its CPU
backend) and reset it afterwards.
"""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.core import platform as plat

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def tpu_policy():
    plat.set_platform("tpu", configure_jax=False)
    try:
        yield
    finally:
        plat.set_platform(None)


def test_detect_platform_raises_on_unknown_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(RuntimeError, match="no platform policy"):
        plat.detect_platform()
    with pytest.raises(RuntimeError, match="'metal'"):
        plat.active_platform()


def test_tc_pallas_refuses_compiled_mode(tpu_policy):
    from repro.scenarios import ScenarioGrid, price_grid_rz
    grid = ScenarioGrid.cartesian(s0=(95.0, 105.0), cost_rate=0.005,
                                  n_steps=8)
    with pytest.raises(NotImplementedError, match="backend='jnp'"):
        price_grid_rz(grid, backend="pallas", capacity=16)


def test_tc_pallas_refusal_names_the_three_blockers():
    from repro.core.rz import RZ_COMPILED_REFUSAL, rz_backward_pallas
    from repro.core.payoff import american_put
    for blocker in ("convert_element_type", "Only 2D gather",
                    "BlockSpec((1,))"):
        assert blocker in RZ_COMPILED_REFUSAL
    with pytest.raises(NotImplementedError):
        rz_backward_pallas(100.0, 0.2, 0.05, 0.5, 0.01, n_steps=4,
                           capacity=8, payoff=american_put(100.0),
                           interpret=False)


def test_tc_depth_refused_beyond_the_tpu_limit(tpu_policy):
    from repro.core import LatticeModel, american_put
    from repro.core.rz import price_rz, price_rz_batch
    from repro.scenarios import ScenarioGrid, price_grid_rz
    limit = plat.tc_max_steps()
    assert limit is not None and plat.tc_max_steps("cpu") is None
    grid = ScenarioGrid.cartesian(s0=(95.0, 105.0), cost_rate=0.005,
                                  n_steps=limit + 1)
    with pytest.raises(NotImplementedError, match="pair of float32"):
        price_grid_rz(grid, capacity=16)
    model = LatticeModel(s0=100.0, sigma=0.2, rate=0.05, maturity=0.5,
                         n_steps=limit + 1, cost_rate=0.01)
    with pytest.raises(NotImplementedError, match=f"n_steps={limit}"):
        price_rz(model, american_put(100.0), capacity=16)
    with pytest.raises(NotImplementedError, match="CPU host"):
        price_rz_batch(100.0, 0.2, 0.05, 0.5, 0.01, n_steps=limit + 1,
                       capacity=16, payoff=american_put(100.0))


def test_tc_depth_within_the_tpu_limit_prices(tpu_policy):
    from repro.scenarios import ScenarioGrid, price_grid_rz
    grid = ScenarioGrid.cartesian(s0=(95.0, 105.0), cost_rate=0.005,
                                  n_steps=4)
    res = price_grid_rz(grid, capacity=16)
    assert (res.ask >= res.bid).all()


def test_process_pool_refused_off_the_cpu(tpu_policy):
    from repro.serve.gateway import PricingGateway
    from repro.serve.procpool import ProcessReplica, ReplicaPool
    with pytest.raises(RuntimeError, match="pool='thread'"):
        PricingGateway(pool="process", replicas=1)
    with pytest.raises(RuntimeError, match="holds the accelerator"):
        ReplicaPool("process").factory(0)
    with pytest.raises(RuntimeError, match="holds the accelerator"):
        ProcessReplica("p", start=True)


def test_thread_pool_still_builds_off_the_cpu(tpu_policy):
    from repro.serve.gateway import PricingGateway
    PricingGateway(pool="thread", replicas=1)


def _cache_dir_in_child(env_dir):
    """What the helper picks in a fresh CPU-only process (which never
    loads the TPU library), and the files a compile leaves there."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.core.platform import use_compile_cache\n"
            "d = use_compile_cache()\n"
            "print(d)\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        code += "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0))\n"
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_compile_cache_honours_the_environment(tmp_path):
    picked, configured = _cache_dir_in_child(tmp_path)
    assert picked == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "the compile left no cache entry"


def test_compile_cache_defaults_to_the_repo():
    picked, configured = _cache_dir_in_child(None)
    assert picked == configured == str(ROOT / ".jax_cache")

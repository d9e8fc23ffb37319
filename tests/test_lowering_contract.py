"""Lowering-contract conformance matrix (marker: ``lowering``).

Static half: every Pallas kernel's traced jaxpr must obey its declared
Mosaic/Triton compatibility contract (``repro.kernels.contracts``) — no
sort primitives, no float64/int64 under the float32 policy, only
declared dynamic-gather patterns — asserted on every platform, CPU
included, so a contract regression is caught long before a GPU/TPU lane
lowers the kernel for real.

Dynamic half: where the platform has a compiled Pallas lowering
(``supports_compiled_pallas()``), every kernel also runs
``interpret=False`` and must match the interpret oracle within its
declared per-dtype tolerance.  On CPU (jax 0.9:
``ValueError: Only interpret mode is supported on CPU backend.``) those
runs skip with that reason — the CPU CI lane covers the static
contracts and the interpret oracles; GPU/TPU lanes light up the real
lowerings with no test changes.
"""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 flag side effect)
from repro.core import platform as plat
from repro.kernels import contracts as C

pytestmark = pytest.mark.lowering

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _cases():
    return [pytest.param(c, dt, id=f"{c.name}-{dt}")
            for c in C.CONTRACTS.values() for dt in c.dtypes]


# --------------------------------------------------------------------- #
# registry coverage: closed over the repo
# --------------------------------------------------------------------- #
def test_registry_covers_every_pallas_call_module():
    """Every module with a ``pl.pallas_call(`` site has a contract (and
    every declared contract still points at a pallas_call site) — the
    AST pass in ``repro.analysis.source_scan`` replaces the old regex
    sweep this test used to carry inline."""
    from repro.analysis import source_scan
    findings = source_scan.scan_pallas_coverage()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_every_contract_declares_a_tolerance_per_dtype():
    for c in C.CONTRACTS.values():
        for dt in c.dtypes:
            assert c.tolerance(dt) > 0.0


# --------------------------------------------------------------------- #
# static contracts (run everywhere)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("contract,dtype", _cases())
def test_static_contract(contract, dtype):
    violations = C.check_static_contract(contract, dtype)
    assert not violations, f"{contract.name}@{dtype}: {violations}"


def test_walker_detects_sort():
    """Negative control: the jaxpr walker actually sees sort primitives."""
    jx = jax.make_jaxpr(jnp.sort)(jnp.zeros((8,), jnp.float32))
    prims, _ = C.jaxpr_summary(jx)
    assert prims & C.FORBIDDEN_PRIMITIVES


def test_walker_detects_weak_f64_leak():
    """Negative control: a weak-Python-float select leaks f64 at f32."""
    def leaky(x):
        return jnp.where(x > 0, 1.0, np.float64(2.0))  # f64 select
    _, dtypes = C.jaxpr_summary(jax.make_jaxpr(leaky)(
        jnp.zeros((4,), jnp.float32)))
    assert "float64" in dtypes


def test_walker_detects_int64_bookkeeping():
    """Negative control: x64-canonicalised arange shows up as int64."""
    _, dtypes = C.jaxpr_summary(jax.make_jaxpr(
        lambda x: x[jnp.arange(4)])(jnp.zeros((4,), jnp.float32)))
    assert "int64" in dtypes


# --------------------------------------------------------------------- #
# dynamic conformance: interpret oracle vs compiled lowering
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("contract,dtype", _cases())
def test_interpret_oracle_runs(contract, dtype):
    """The interpret path executes and returns finite values anywhere."""
    outs = C.run_kernel(contract, dtype, interpret=True)
    assert outs and all(np.isfinite(o).all() for o in outs)


@pytest.mark.parametrize("contract,dtype", _cases())
def test_compiled_matches_interpret(contract, dtype):
    if not plat.supports_compiled_pallas():
        pytest.skip(f"no compiled Pallas lowering on "
                    f"{plat.active_platform()} (jax: 'Only interpret "
                    "mode is supported on CPU backend.')")
    ref = C.run_kernel(contract, dtype, interpret=True)
    got = C.run_kernel(contract, dtype, interpret=False)
    tol = contract.tolerance(dtype)
    assert len(ref) == len(got)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, rtol=0, atol=tol)


# --------------------------------------------------------------------- #
# platform policy resolution
# --------------------------------------------------------------------- #
def test_policy_explicit_interpret_wins_everywhere():
    for p in plat.PLATFORMS:
        assert plat.resolve_interpret(True, p) is True
        assert plat.resolve_interpret(False, p) is False


def test_policy_defaults_per_platform():
    assert plat.resolve_interpret(None, "cpu") is True
    assert plat.resolve_interpret(None, "gpu") is False
    assert plat.resolve_interpret(None, "tpu") is False
    assert not plat.supports_compiled_pallas("cpu")
    assert plat.supports_compiled_pallas("gpu")
    assert plat.supports_compiled_pallas("tpu")
    assert plat.default_dtype("cpu") == jnp.dtype("float64")
    assert plat.default_dtype("gpu") == jnp.dtype("float32")
    assert plat.xla_flags("gpu")           # the triton/latency-hiding set
    assert plat.xla_flags("cpu") == ()


def test_set_platform_policy_only_roundtrip():
    """configure_jax=False changes policy resolution, not the backend."""
    detected = plat.detect_platform()
    try:
        plat.set_platform("tpu", configure_jax=False)
        assert plat.active_platform() == "tpu"
        assert plat.resolve_interpret(None) is False
        assert plat.platform_summary()["platform"] == "tpu"
        assert plat.platform_summary()["detected"] == detected
    finally:
        plat.set_platform(None)
    assert plat.active_platform() == detected


def test_set_platform_rejects_unknown():
    with pytest.raises(ValueError, match="unknown platform"):
        plat.set_platform("quantum", configure_jax=False)
    with pytest.raises(ValueError, match="unknown platform"):
        plat.resolve_interpret(None, "cuda")


def test_platform_summary_shape():
    s = plat.platform_summary()
    assert set(s) >= {"platform", "detected", "interpret",
                      "compiled_pallas", "default_dtype", "xla_flags",
                      "jax_version"}
    assert s["platform"] in plat.PLATFORMS


def test_scheduler_compile_key_distinguishes_interpret_modes():
    """interpret and compiled programs are distinct compiled-program
    keys, while None resolves to the policy value (no phantom misses)."""
    from repro.serve.core import SchedulerCore
    core = SchedulerCore(max_batch=4)
    core.compile_key_seen(8, 10, "rz", False, interpret=True)
    core.compile_key_seen(8, 10, "rz", False, interpret=False)
    assert len(core._compiled) == 2
    # None == the platform policy's resolved value -> hits one of the two
    core.compile_key_seen(8, 10, "rz", False, interpret=None)
    assert len(core._compiled) == 2
    snap = core.metrics_.snapshot()
    assert snap["compile_hits"] == 1 and snap["compile_misses"] == 2

"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with libtpu, and it compiles for a chip
that is described, not attached: what Mosaic or XLA would refuse on the
chip is refused here.  Only one process at a time may load libtpu, and
it keeps the library until it exits, so the topology is described
inside a module fixture (never at import), every case compiles in this
process, and the cases stay in this one file.  The persistent
compilation cache is off around the compiles: an entry written for a
described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lsmc as L
from repro.kernels import contracts as C
from repro.kernels.binomial_step import PARAM_SCALARS, lattice_round_param


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("nodes", [1536, 20224])   # N=1500, N=20000
def test_lattice_round_param_compiles_float32(one_chip, no_cache, nodes):
    fn = lambda v, s: lattice_round_param(v, s, levels=64, block=256,
                                          interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((nodes,), jnp.float32, one_chip),
        _spec((PARAM_SCALARS,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lsmc_rows_compile_float64(one_chip, no_cache):
    """The regression's normal equations compile at float64 (the TPU's
    LU decomposition takes float32 only; the engine solves by an
    unrolled Cholesky)."""
    rows, n_steps = 4, 8
    fn = lambda *a: L.lsmc_rows(
        *a, n_steps=n_steps, steps=L.exercise_schedule(n_steps, None),
        n_paths=64, n_assets=1, degree=3, basis="poly", antithetic=True)
    args = [_spec((rows,), jnp.float64, one_chip)] * 11
    args.append(_spec((rows, 2), jnp.uint32, one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Only 2D gather is supported' for the "
                          "merge-path gathers of core/pwl.py::_merge_take")
def test_rz_round_compiles_float32(one_chip, no_cache):
    """Compiled with x64 off: under the x64 switch the lowering first
    recurses without end in convert_element_type."""
    fn, args = C.CONTRACTS["rz_round"].build(jnp.dtype("float32"), False)
    specs = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), args)
    jax.config.update("jax_enable_x64", False)
    try:
        jax.jit(fn).lower(*specs).compile()
    finally:
        jax.config.update("jax_enable_x64", True)

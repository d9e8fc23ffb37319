"""Platform configuration layer: ``platform={cpu,gpu,tpu}`` policy.

Every Pallas call site in the repo takes an ``interpret=`` knob whose
default is ``None`` — *resolve from the platform policy* — instead of the
historical hard-coded ``interpret=True``.  This module owns that policy:

  * which platform is active (detected from jax, or pinned by
    :func:`set_platform` — the SNIPPETS/bayespec ``jax_platform_name``
    idiom);
  * whether Pallas kernels run compiled or in interpret mode there
    (:func:`resolve_interpret` / :func:`supports_compiled_pallas` — CPU
    has **no** compiled Pallas lowering in jax 0.9:
    ``pallas_call(interpret=False)`` raises ``ValueError: Only interpret
    mode is supported on CPU backend.``, so CPU policy is interpret);
  * the compiled-path dtype policy (:func:`default_dtype` — float64 on
    CPU where interpret mode is CPU-exact, float32 on GPU/TPU where the
    compiled lowerings carry no f64);
  * the XLA flags a platform wants (:func:`xla_flags` /
    :func:`apply_xla_flags` — the GPU set is the latency-hiding
    scheduler / async-collectives exemplar named by the ROADMAP);
  * where the persistent compilation cache lives
    (:func:`use_compile_cache`, called by the entry points only).

What each kernel *promises* to a compiled lowering (no sorts, int32
bookkeeping, declared dynamic gathers) is the per-kernel contract
registry in :mod:`repro.kernels.contracts`, asserted by
``tests/test_lowering_contract.py``; this module only decides which
lowering runs where.  See ``docs/PLATFORMS.md``.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib

import jax
import jax.numpy as jnp

__all__ = [
    "PLATFORMS", "PlatformPolicy", "POLICIES", "detect_platform",
    "active_platform", "set_platform", "resolve_interpret",
    "supports_compiled_pallas", "default_dtype", "xla_flags",
    "apply_xla_flags", "platform_summary", "use_compile_cache",
    "tc_max_steps",
]

PLATFORMS = ("cpu", "gpu", "tpu")

# The GPU flag set follows the bayespec exemplar in SNIPPETS.md: Triton
# fusions plus the latency-hiding scheduler / async collectives that the
# ROADMAP's backend-matrix item calls out.  TPU and CPU need no flags —
# Mosaic is the default TPU lowering and CPU is the interpret oracle.
_GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


@dataclasses.dataclass(frozen=True)
class PlatformPolicy:
    """Per-platform lowering/dtype defaults the pricing stack resolves."""
    platform: str
    interpret: bool            # default for every `interpret=None` knob
    compiled_pallas: bool      # does pallas_call(interpret=False) lower?
    default_dtype: str         # "float64" | "float32" dtype policy
    xla_flags: tuple[str, ...] = ()
    # deepest TC lattice the float64 PWL algebra prices to the oracle
    # tolerance here (None: no limit); see tc_max_steps()
    tc_max_steps: int | None = None


POLICIES: dict[str, PlatformPolicy] = {
    "cpu": PlatformPolicy("cpu", interpret=True, compiled_pallas=False,
                          default_dtype="float64"),
    "gpu": PlatformPolicy("gpu", interpret=False, compiled_pallas=True,
                          default_dtype="float32", xla_flags=_GPU_XLA_FLAGS),
    "tpu": PlatformPolicy("tpu", interpret=False, compiled_pallas=True,
                          default_dtype="float32", tc_max_steps=16),
}

# Explicit override installed by set_platform(); None = detect from jax.
_OVERRIDE: str | None = None


def _validate(platform: str) -> str:
    platform = str(platform).lower()
    if platform not in PLATFORMS:
        raise ValueError(
            f"unknown platform {platform!r}; expected one of {PLATFORMS}")
    return platform


def detect_platform() -> str:
    """Platform jax is actually executing on (``jax.default_backend()``).

    A backend with no policy here is an error, never a quiet ``"cpu"``.
    """
    backend = jax.default_backend()
    if backend not in PLATFORMS:
        raise RuntimeError(f"jax backend {backend!r} has no platform "
                           f"policy; expected one of {PLATFORMS}")
    return backend


def active_platform() -> str:
    """The platform policy resolution uses: override if set, else detected."""
    return _OVERRIDE if _OVERRIDE is not None else detect_platform()


def set_platform(platform: str | None, *, configure_jax: bool = True) -> str:
    """Pin the active platform (``None`` resets to auto-detect).

    With ``configure_jax=True`` (default) this also applies the
    platform's XLA flags and sets ``jax_platform_name`` — the bayespec
    idiom — which only takes full effect *before* the jax backend
    initialises; afterwards jax keeps its existing devices and only the
    policy side (interpret/dtype resolution) changes.  Pass
    ``configure_jax=False`` to change policy resolution alone (what the
    CPU test-suite does to exercise gpu/tpu policy branches).
    """
    global _OVERRIDE
    if platform is None:
        _OVERRIDE = None
        return detect_platform()
    platform = _validate(platform)
    _OVERRIDE = platform
    if configure_jax:
        apply_xla_flags(platform)
        jax.config.update("jax_platform_name", platform)
    return platform


def resolve_interpret(interpret: bool | None = None,
                      platform: str | None = None) -> bool:
    """Resolve an ``interpret=`` knob: explicit wins, else platform policy."""
    if interpret is not None:
        return bool(interpret)
    key = _validate(platform) if platform is not None else active_platform()
    return POLICIES[key].interpret


def supports_compiled_pallas(platform: str | None = None) -> bool:
    """True where ``pallas_call(interpret=False)`` has a real lowering."""
    key = _validate(platform) if platform is not None else active_platform()
    return POLICIES[key].compiled_pallas


def default_dtype(platform: str | None = None):
    """The platform's dtype policy (f64 interpret oracle, f32 compiled)."""
    key = _validate(platform) if platform is not None else active_platform()
    return jnp.dtype(POLICIES[key].default_dtype)


def tc_max_steps(platform: str | None = None) -> int | None:
    """Deepest TC lattice (``n_steps``) the platform's float64 prices.

    The PWL algebra tells a kink from rounding noise with a relative
    slope tolerance of 1e-9 (``core/pwl.py::_REL``), which needs IEEE
    binary64.  XLA on a TPU keeps float64 as a pair of float32 words
    (about 48 bits, float32's exponent range): there the noise passes
    for kinks as the tree deepens, the knot count grows past the
    capacity and prices drift.  None means no limit.
    """
    key = _validate(platform) if platform is not None else active_platform()
    return POLICIES[key].tc_max_steps


def xla_flags(platform: str | None = None) -> tuple[str, ...]:
    key = _validate(platform) if platform is not None else active_platform()
    return POLICIES[key].xla_flags


def apply_xla_flags(platform: str | None = None) -> str:
    """Append the platform's XLA flags to ``XLA_FLAGS`` (idempotent).

    XLA reads the env var at backend initialisation, so call this before
    the first jax computation (``launch/price.py --platform`` does).
    Returns the resulting ``XLA_FLAGS`` value.
    """
    current = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in xla_flags(platform) if f not in current]
    if missing:
        current = " ".join(filter(None, [current, *missing]))
        os.environ["XLA_FLAGS"] = current
    return current


def platform_summary() -> dict:
    """One-dict description of the resolved policy (benches embed this)."""
    key = active_platform()
    pol = POLICIES[key]
    return {
        "platform": key,
        "detected": detect_platform(),
        "interpret": pol.interpret,
        "compiled_pallas": pol.compiled_pallas,
        "default_dtype": pol.default_dtype,
        "xla_flags": list(pol.xla_flags),
        "jax_version": jax.__version__,
    }


# <repo>/.jax_cache: a fixed path (the cache key includes it), git-ignored
_REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins: nothing else is set.  Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache``.  Entry points (``chip_smoke.py``,
    ``launch/price.py``, ``launch/serve_pricing.py``) call this before
    their first compile; library imports and the test suite do not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)

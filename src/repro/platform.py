"""Backend bootstrapping for examples, benchmarks, and the service
(DESIGN.md §17.4).

One module owns the "pick the fastest backend and configure XLA for it"
idiom (the bayespec ``set_platform`` + olmax XLA-env recipes from
SNIPPETS.md), so call sites stop hand-rolling environment mutation:

  * :func:`set_platform` -- pin jax to cpu/gpu/tpu and (for GPU) install
    the Triton-fusion / latency-hiding XLA flags.  Only effective before
    the jax backend initializes, like every jax platform knob.
  * :func:`bootstrap` -- the ``ServiceConfig.platform="auto"`` entry:
    ``"auto"`` keeps whatever backend jax already picked (jax prefers
    accelerators on its own; we only *report* it), any concrete name pins
    it via :func:`set_platform`.
  * :func:`force_host_device_count` / :func:`subprocess_env` -- the
    forced-multi-device idiom: N XLA host devices on CPU for shard_map
    testing, either in-process (before jax init) or as an environment for
    a child process (the distributed harness's CPU workers).
  * :func:`enable_compile_cache` -- the persistent compilation cache at a
    fixed path, for entry points that compile large programs.
"""
from __future__ import annotations

import os
import pathlib

# the checkout root (src/repro/platform.py -> ../..)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]

# <https://jax.readthedocs.io/en/latest/gpu_performance_tips.html>
GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true "
    "--xla_gpu_triton_gemm_any=True "
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true"
)

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _append_xla_flags(flags: str, env: dict | None = None) -> None:
    target = os.environ if env is None else env
    existing = target.get("XLA_FLAGS", "")
    parts = [p for p in existing.split() if p]
    for flag in flags.split():
        if flag not in parts:
            parts.append(flag)
    target["XLA_FLAGS"] = " ".join(parts)


def set_platform(platform: str) -> None:
    """Pin jax to ``cpu`` / ``gpu`` / ``tpu``.  Takes effect only before
    the first jax computation initializes the backend; on GPU also
    installs the Triton-fusion XLA flags (idempotent append)."""
    if platform == "gpu":
        _append_xla_flags(GPU_XLA_FLAGS)
    import jax
    jax.config.update("jax_platform_name", platform)


def current() -> str:
    """The backend jax actually resolved (initializes it if needed)."""
    import jax
    return jax.default_backend()


def bootstrap(platform: str = "auto") -> str:
    """Resolve a ``ServiceConfig.platform`` value and return the active
    backend name.  ``"auto"`` trusts jax's own accelerator preference
    (tpu > gpu > cpu) and just reports the outcome; a concrete name pins
    it.  Safe to call more than once with the same value."""
    if platform and platform != "auto":
        set_platform(platform)
    return current()


def force_host_device_count(n: int, env: dict | None = None) -> None:
    """Ask XLA for ``n`` host (CPU) devices -- the laptop-scale stand-in
    for a multi-device mesh (ROADMAP shard benchmarks).  Mutates
    ``os.environ`` (must run before jax init) or, given ``env``, a child
    process environment."""
    _append_xla_flags(f"{_HOST_COUNT_FLAG}={n}", env)


def subprocess_env(n_devices: int, base: dict | None = None) -> dict:
    """A copy of the environment with ``n_devices`` forced host devices:
    the benchmarks' subprocess idiom (the parent process has usually
    already initialized a single-device backend, so the flag can only
    apply in a child)."""
    env = dict(os.environ if base is None else base)
    force_host_device_count(n_devices, env)
    return env


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the path is part of every entry's key, so a
    directory that moved between runs would never hit.  Call before the
    first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path

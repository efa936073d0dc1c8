"""Performance-environment setup: XLA flags for overlap, set before init.

The sharded-offload pipeline leans on two pieces of XLA scheduling: the
latency-hiding scheduler (so the gradient all-reduce overlaps the
reverse-sweep prefetches) and async collectives on their own stream.
Both are process-global ``XLA_FLAGS`` that must be in the environment
*before* the first jax backend initialisation — the same constraint
NeMo's ``PerfEnvPlugin`` handles by mutating ``os.environ`` in the
launcher before the trainer touches the accelerator.

``configure_perf_env`` merges the flags into ``XLA_FLAGS`` without
clobbering anything the user already set (user-set flags win), and
warns when it can tell the jax backends are already initialised — at
that point the flags are recorded but will not take effect until the
next process.

``configure_compile_cache`` is the one place an entry point turns on
JAX's persistent compile cache.
"""
from __future__ import annotations

import os
import sys
import warnings
from typing import Iterable, List, Mapping, Optional

# Latency-hiding / async-collective flags (SNIPPETS.md snippet 1): the
# all-reduce runs on a high-priority async stream while the scheduler
# reorders transfers behind compute — exactly what lets Level-2
# prefetch traffic and gradient collectives share the interconnect.
GPU_PERF_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def _jax_initialized() -> bool:
    """Best-effort: True when a jax backend has already been created in
    this process (flags set now will not reach it)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return False
    backends = getattr(xb, "_backends", None)
    return bool(backends)


def perf_flags(platform: Optional[str] = None,
               host_device_count: Optional[int] = None,
               extra: Iterable[str] = ()) -> List[str]:
    """The flag list ``configure_perf_env`` would apply, for inspection."""
    flags: List[str] = []
    if platform == "gpu":
        flags.extend(GPU_PERF_FLAGS)
    if host_device_count is not None:
        if host_device_count < 1:
            raise ValueError(
                f"host_device_count must be >= 1, got {host_device_count}")
        flags.append(
            f"--xla_force_host_platform_device_count={host_device_count}")
    flags.extend(extra)
    return flags


def configure_perf_env(platform: Optional[str] = None,
                       host_device_count: Optional[int] = None,
                       extra: Iterable[str] = (),
                       env: Optional[Mapping[str, str]] = None) -> List[str]:
    """Merge overlap flags into ``XLA_FLAGS``; returns the flags applied.

    ``platform=None`` auto-detects from ``JAX_PLATFORM_NAME`` /
    ``JAX_PLATFORMS`` (GPU flags only apply on gpu — they are inert but
    noisy elsewhere).  ``host_device_count`` adds
    ``--xla_force_host_platform_device_count`` for forced CPU meshes.
    Flags whose name is already present in ``XLA_FLAGS`` are left alone.
    """
    if env is None:
        env = os.environ
    if platform is None:
        platform = (env.get("JAX_PLATFORM_NAME")
                    or env.get("JAX_PLATFORMS") or "").split(",")[0] or None
    wanted = perf_flags(platform, host_device_count, extra)
    existing = env.get("XLA_FLAGS", "")
    present = {_flag_name(f) for f in existing.split()}
    applied = [f for f in wanted if _flag_name(f) not in present]
    if not applied:
        return []
    env["XLA_FLAGS"] = (existing + " " + " ".join(applied)).strip()
    if env is os.environ and _jax_initialized():
        warnings.warn(
            "perf_env: jax backends are already initialised; XLA_FLAGS "
            f"{[_flag_name(f) for f in applied]} will only take effect in "
            "the next process", stacklevel=2)
    return applied


def set_host_device_count(n: int, env: Optional[Mapping[str, str]] = None
                          ) -> List[str]:
    """Force ``n`` CPU devices (smoke-testing meshes without hardware)."""
    return configure_perf_env(host_device_count=n, env=env)


# The persistent compile cache's default home.  The directory is part of
# the cache key, so it is fixed: a path that moves between runs never hits.
DEFAULT_COMPILE_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def configure_compile_cache(env: Optional[Mapping[str, str]] = None
                            ) -> Optional[str]:
    """Turn on JAX's persistent compile cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing (returns ``None``).  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored); returns that path."""
    if env is None:
        env = os.environ
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE

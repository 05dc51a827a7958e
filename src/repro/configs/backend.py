"""JAX backend knobs for drivers and CI lanes (guarded execution §11).

Every function here only takes effect at the BEGINNING of a program —
before the first jax array is created — so drivers call them right after
parsing flags and before importing anything that touches jax arrays.
``set_cpu_cores`` must run before ``import jax`` entirely (XLA reads the
flag once at backend init); the others are safe any time pre-trace.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count

# <repo>/src/repro/configs/backend.py -> <repo>
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this checkout.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself); otherwise the fixed ``<repo>/.jax_cache`` — a fixed path,
    because the directory is part of the cache key."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Sets no directory in code when ``JAX_COMPILATION_CACHE_DIR`` is set.
    Call at the start of a driver, before its first compile."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def jax_enable_x64(use_x64: bool) -> None:
    """Switch the default array precision to 64-bit (or back to 32)."""
    import jax
    jax.config.update("jax_enable_x64", bool(use_x64))


# GPU flags appended (idempotently) by set_platform.  The async-collective
# pair makes the substep pipeline's issue-before-consume ordering
# (DESIGN.md §12) an actual overlap on GPU: collectives run on their own
# high-priority stream while the latency-hiding scheduler slots the
# independent compute between issue and first use — without them the
# reordered HLO still executes serially on one stream.
_GPU_FLAGS = (
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def set_platform(platform: str = "cpu") -> None:
    """Pin the backend to 'cpu', 'gpu', or 'tpu'."""
    import jax
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        # https://jax.readthedocs.io/en/latest/gpu_performance_tips.html
        cur = os.environ.get("XLA_FLAGS", "")
        add = [f for f in _GPU_FLAGS if f not in cur.split()]
        os.environ["XLA_FLAGS"] = " ".join([cur] + add).strip()


def set_cpu_cores(n: int) -> None:
    """Expose ``n`` host CPU devices (XLA host-platform device count).

    Call BEFORE importing jax anywhere in the process — the flag is read
    once when the CPU backend initializes."""
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(f"only {total} CPUs available, will use {total - 1}",
                      Warning)
        n = total - 1
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}").strip()


def set_debug_nan(flag: bool) -> None:
    """Raise on the first NaN any jitted computation produces.

    The brute-force debugging lane: complements the packed health word
    (which classifies and recovers instead of crashing) when a fault needs
    to be pinned to the exact primitive that produced it.
    https://jax.readthedocs.io/en/latest/debugging/flags.html
    """
    import jax
    jax.config.update("jax_debug_nans", bool(flag))

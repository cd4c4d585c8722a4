"""JAX platform pinning and compile-cache placement, one shared copy.

``JAX_PLATFORMS=cpu`` in the environment is enough to keep a process off
the accelerator.  ``force_cpu_platform`` is for programs that must pin in
code — N workers sharing one host cannot share one chip — and that need a
virtual device count, which has to land in ``XLA_FLAGS`` before the first
backend or device query.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int = 1) -> None:
    """Pin JAX to a virtual ``n_devices``-device CPU platform.

    Must be called before anything initializes a JAX backend (first
    ``jax.devices()``/``jit`` call); a pre-existing device-count flag is
    replaced, not silently kept.  Calling too late raises RuntimeError
    (unless the live backend already satisfies the request) instead of
    silently doing nothing.
    """
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        devs = jax.devices()
        if devs[0].platform == "cpu" and len(devs) >= n_devices:
            return  # idempotent: already pinned satisfactorily
        raise RuntimeError(
            "force_cpu_platform called after a JAX backend initialized "
            f"({devs[0].platform} x{len(devs)}); pin before first device use "
            "or run in a fresh process"
        )

    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG in flags:
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)
    else:
        flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    os.environ["XLA_FLAGS"] = flags

    jax.config.update("jax_platforms", "cpu")


def enable_persistent_cache() -> None:
    """Turn on JAX's persistent compilation cache, placed from outside.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code.  Where it is not, the cache lives at the
    fixed ``<checkout>/.jax_cache`` (gitignored) — the path is part of the
    cache key's neighbourhood, so a directory that moves never hits.  The
    whole fused round compiles in minutes at the flagship size, and a
    worker restarted after a kill compiles the same program again; with
    the cache its second life loads it instead.  Keyed on HLO content, so
    code changes recompile.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(repo_root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

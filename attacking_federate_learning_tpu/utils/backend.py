"""Device selection and the persistent compile cache for entry points.

Two rules (on-chip-measurement guide): a path that asks for the TPU
fails when the initialized backend is anything else — it never falls
back to the CPU — and every result line names the device it ran on.
One process per chip: a parent that has initialized a backend holds the
chip, so nothing here probes the device from a child process.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def select_platform(platform: str):
    """Pin the JAX platform over any inherited ``JAX_PLATFORMS`` (env
    for children, live config for this process — jax reads the env var
    at import time only).  Must run before the first jax op."""
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def device_stamp() -> dict:
    """The device as JAX reports it; rides on every result line."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(who: str) -> dict:
    """Initialize the backend and return its :func:`device_stamp`;
    exit non-zero with one line naming the backend found when it is
    not a TPU.  ``who`` names the caller in that line."""
    try:
        stamp = device_stamp()
    except RuntimeError as e:       # backend initialization failed
        reason = str(e).strip().splitlines()[0]
        raise SystemExit(f"{who}: no TPU backend ({reason})")
    if stamp["platform"] != "tpu":
        raise SystemExit(
            f"{who}: needs a TPU, found backend '{stamp['platform']}' "
            f"({stamp['device_kind']} x{stamp['count']}); no CPU fallback")
    return stamp


def enable_compile_cache(path=None):
    """Persistent XLA compile cache shared by every entry point.  A
    user-set ``JAX_COMPILATION_CACHE_DIR`` wins verbatim and no other
    directory is set in code; otherwise the fixed ``<checkout>/.jax_cache``
    (the path is part of what makes a cache findable, so it never moves
    with the host — jax's cache key already covers the device topology,
    for XLA:CPU the host's CPU feature list, so a foreign host's
    executables miss instead of loading).  jax.config.update is the
    import-order-proof way to apply the setting."""
    import jax

    if path is None:
        path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or os.path.join(_REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    # Compile observability (utils/costs.py): every entry point that
    # enables the cache also counts its hits/misses — installed here,
    # before the first compile, rather than per caller.
    from attacking_federate_learning_tpu.utils.costs import (
        install_cache_counters
    )

    install_cache_counters()

"""JAX platform and compile-cache set-up: the one copy every entry point
(tests, benches, graft dry-run, multi-process workers, chip_smoke.py)
shares.

On the CPU the program runs with ``JAX_PLATFORMS=cpu`` and a forced
count of virtual devices (tests/conftest.py asks for eight).  On a
machine with a TPU nothing is forced: JAX picks the chip, and one
process owns it."""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def force_cpu(device_count: int | None = None) -> None:
    """Pin the live jax config to the CPU platform and (optionally)
    force `device_count` virtual CPU devices.  Must run before any jax
    backend initialisation; safe to call more than once.  The
    device-count flag is appended only when absent so an inherited
    XLA_FLAGS (e.g. pytest's 8-device setting) wins."""
    if device_count is not None:
        flag = "--xla_force_host_platform_device_count"
        flags = os.environ.get("XLA_FLAGS", "")
        if flag not in flags:
            os.environ["XLA_FLAGS"] = \
                f"{flags} {flag}={device_count}".strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


def force_cpu_if_selected(device_count: int | None = None) -> bool:
    """Apply force_cpu() iff the caller's env selects the CPU platform
    (the JAX_PLATFORMS gate every hermetic entry point shares — one
    copy, so the detection rule cannot drift per call site).  Returns
    whether it fired."""
    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        force_cpu(device_count)
        return True
    return False


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache``: a fixed path (the directory is part of
    the cache key's lookup, so one that moves never hits), listed in
    .gitignore."""
    return str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return where it
    lives.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads
    it, and nothing else is set in code; otherwise the cache goes to
    ``<checkout>/.jax_cache`` — never a temporary, per-process or
    per-time path."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path

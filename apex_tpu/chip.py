"""What every program that measures on the chip agrees on: which device
it ran on, that it refuses to run anywhere else, and where compiled code
is kept.

A CPU run says what a program counts and nothing about time, so a
measurement path (``chip_smoke.py``, ``benchmark/run.py``) that finds no TPU
stops before it compiles anything — there is no switch that lets it
pass off-chip. Library code keeps its CPU/interpret paths (tier-1 needs
them); this module is for entry points.
"""
from __future__ import annotations

import os
from typing import Dict

import jax

#: The compile cache's home when the environment does not name one. The
#: path is part of the cache key, so it is fixed: inside the checkout
#: (the chip tool copies the tree to the same place on every machine)
#: and listed in ``.gitignore``.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def device_record() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` as jax reports the devices —
    stamped on every record a measurement prints."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(what: str) -> Dict[str, object]:
    """The device record, or ``SystemExit`` with a one-line reason when
    jax's default backend is not a TPU (a failed TPU start-up that lands
    on the CPU would otherwise "work")."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, but jax.default_backend() is "
            f"{backend!r} ({len(jax.devices())} device(s)); a measurement "
            "path does not fall back to another backend")
    return device_record()


def use_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was
    placed from outside and jax reads the variable itself: nothing is
    set in code. Otherwise the cache lives in :data:`CACHE_DIR` and
    keeps every program (no minimum compile time), so a second run in
    the same tree compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir

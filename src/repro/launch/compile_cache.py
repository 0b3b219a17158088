"""Where JAX's persistent compilation cache lives: one rule for every
launcher that runs on the chip (``chip_smoke.py``, ``repro.launch.serve``).

The cache key includes its directory, so the directory must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing else is set here), otherwise
the fixed ``.jax_cache`` directory at the root of the checkout, which git
ignores.  Tests never call this: a compile for a described (unattached)
chip is written to the cache but cannot be read back without the chip.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)

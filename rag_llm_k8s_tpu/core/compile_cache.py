"""Placement of JAX's persistent compilation cache.

Every entry point that compiles for a device (``server/main.main``,
``chip_smoke.py``, ``benchmark/run.py``, ``scripts/validate_8b.py``, the hardware
test lane's fixture) calls :func:`ensure_compile_cache` FIRST. The directory
is part of a cache entry's key, so it must never move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is
  configured here, so whoever runs the program decides where the cache lives;
- unset: the one fixed path ``<checkout>/.jax_cache`` (gitignored).
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    # JAX's own variable, not a knob of this program: read only to stay out
    # of its way  # ragcheck: disable=CONFIG-DRIFT
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cache_entry_count(path: str) -> int:
    """Executables currently in the cache directory (0 when absent) — the
    cold-vs-warm evidence entry points print."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0

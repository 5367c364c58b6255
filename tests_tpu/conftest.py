"""Hardware test lane: runs on the REAL TPU chip (no platform forcing).

The main suite (`tests/`) pins an 8-virtual-device CPU platform for
mesh/sharding coverage; this lane is the complement — it executes the Pallas
kernels and the engine on actual hardware so on-chip correctness is a
repeatable artifact, not a commit-message claim. Run via ``make tpu-test``
or ``python -m pytest tests_tpu/ -q`` (skips itself entirely off-TPU).
"""

import pytest


@pytest.fixture(scope="session", autouse=True)
def tpu_backend():
    """Every test of this lane needs the chip. Decided HERE, when the first
    test runs — not while pytest collects: touching the backend at import or
    in a collection hook takes the chip in whatever process merely lists
    the tests."""
    import jax

    from rag_llm_k8s_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    if jax.default_backend() != "tpu":
        pytest.skip(f"needs TPU (backend={jax.default_backend()})")

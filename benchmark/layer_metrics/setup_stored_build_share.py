"""Of the executables the declared programs built before the window, the share
the executable store held: 100 × ``rag_compile_events_total{cache="stored"}``
over every outcome (``stored | hit | miss | off``) of every program but
``undeclared`` (a lazy jit is never kept there). A stored executable was
loaded without tracing, lowering or compiling (``core/compile_cache.py``,
``obs/tracing.py build_span``): 0 on a checkout's first run and on a program
from before the store, 100 on every later run. None on a program whose counter
carries no ``cache``, or that built nothing declared."""

from benchmark.lib import setup_series

DECLARED = ("generate", "generate_spec", "generate_rag", "generate_prefixed", "segment_kv",
            "score_exact", "retrieve", "encode", "continuous")


def read(ctx):
    family = "rag_compile_events_total"
    stored = setup_series.total(ctx["before"], family, "cache", ("stored",))
    built = setup_series.total(ctx["before"], family, "program", DECLARED)
    if stored is None or not built:
        return None
    return 100.0 * stored / built

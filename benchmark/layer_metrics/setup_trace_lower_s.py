"""Seconds of set-up spent tracing and lowering programs, every executable the
process built before the window: ``rag_compile_seconds_total{stage="trace"}``
+ ``{stage="lower"}`` over all programs (``obs/tracing.py build_span``'s own
clock; a lazy jit's lowering under ``program="undeclared"``). Paid cold and
warm alike: the persistent cache is asked only after both. None on a program
whose counter carries no ``stage``."""

from benchmark.lib import setup_series


def read(ctx):
    return setup_series.total(ctx["before"], "rag_compile_seconds_total", "stage",
                              ("trace", "lower"))

"""Seconds of set-up in the backend's compile, or in the persistent cache's
read where the entry was found: ``rag_compile_seconds_total{stage="compile"}``
over all programs, every executable built before the window. None on a
program whose counter carries no ``stage``."""

from benchmark.lib import setup_series


def read(ctx):
    return setup_series.total(ctx["before"], "rag_compile_seconds_total", "stage", ("compile",))

"""Seconds of the uploads before the window that were ingest and not builds:
``rag_ingest_stage_seconds_sum{stage}`` over ``extract``, ``chunk``, ``embed``
and ``index`` (add + save). ``warm``, the builds after an ingest, is left out:
its seconds are in ``setup_trace_lower_s`` and ``setup_compile_s``. None on a
program without the family."""

from benchmark.lib import setup_series


def read(ctx):
    return setup_series.total(ctx["before"], "rag_ingest_stage_seconds_sum", "stage",
                              ("extract", "chunk", "embed", "index"))

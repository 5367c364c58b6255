"""Executables built before the window that the persistent compile cache did
not hold and now keeps: ``rag_compile_events_total{cache="miss"}`` over all
programs (JAX records a miss where it writes the entry; a compile under its
one-second floor is kept nowhere and reads ``off``). 0 on a warm cache, so a
cold ``setup_s`` reading names itself. None on a program whose counter carries
no ``cache``."""

from benchmark.lib import setup_series


def read(ctx):
    return setup_series.total(ctx["before"], "rag_compile_events_total", "cache", ("miss",))

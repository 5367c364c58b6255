"""Device time of attention over the ring and the summary plane in one decode
step, with the pooling of the step's chunk: self time under
``decode/.../attn/ring`` and ``attn/pool`` (the decode
kernel's one walk over a row's live summaries and live ring slots, or the XLA
form; the 16 ring slots pooled into the chunk's summary) over the decode steps
of the same slice. Projections, rotation, the ring's write and the output
projection are outside it (``lib/ring_scopes.py`` makes the split). None where
the program opens no such scope."""

from benchmark.lib import phases, ring_scopes


def read(ctx):
    by = ring_scopes.of(ctx)
    if by is None:
        return None
    split = by.get("decode", {})
    seconds = sum(split.get(fine, 0.0) for fine in ring_scopes.FINE)
    return phases.ms_per(seconds, phases.of(ctx)["steps"].get("decode"))

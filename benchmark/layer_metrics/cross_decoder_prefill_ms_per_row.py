"""Device time of the cross-decoder half (the layers that own no state: the
gated memory units and the cross-attention layers, with their norms and
SwiGLUs) in one prompt's prefill: self time of the operations of ``prefill``
whose scope path holds ``cross`` (``lib/cross_scopes.py``), over the prefill
rows of the same slice. A program that stops a fresh prompt's prefill half way
runs that half at ONE position, so this reads a few milliseconds of weight
streaming whatever the prompt's length; one that runs the whole stack at every
position would read the half's share of the prefill, hundreds. None where the
program opens no such scope."""

from benchmark.lib import cross_scopes, phases


def read(ctx):
    split = cross_scopes.of(ctx)
    if split is None or "prefill" not in split["half"]:
        return None
    return phases.ms_per(split["half"]["prefill"], phases.of(ctx)["prefill_rows"])

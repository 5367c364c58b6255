"""Device time of the Mamba-2 recurrence in the prefill of one prompt row:
self time under ``prefill/.../attn/ssd`` (the chunked matmul form of
``ops/ssd.py`` over every Mamba-2 layer: the products a chunk, the decay
masks, the state carried chunk to chunk; whatever implements it) over the
prefill rows of the same slice. The projections, the convolution and the
gated group norm are not in it. ``lib/path_scopes.py`` makes the split. None
where the program opens no such scope."""

from benchmark.lib import path_scopes, phases


def read(ctx):
    if path_scopes.of(ctx) is None:
        return None
    return phases.ms_per(path_scopes.seconds(ctx, "prefill", "attn/ssd"), phases.of(ctx)["prefill_rows"])

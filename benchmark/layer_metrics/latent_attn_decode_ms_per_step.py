"""Device time of the latent attention in one decode step: self time under
``decode/attn`` (projections through the query and KV low ranks, the rotation,
the latent cache's write, the absorbing matmuls and the kernel over the
cache, ``attn/latent``) over the decode steps of the same slice
(``lib/phases.py``). Every layer of a step is in it, the leading dense one too."""

from benchmark.lib import phases


def read(ctx):
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    return phases.ms_per(reduced["seconds_by_scope"].get("decode/attn"), reduced["steps"].get("decode"))

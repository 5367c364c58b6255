"""Device time of the router in one prompt's prefill: self time under
``prefill/.../mlp/router`` (``models/latent_moe.py SparseMLP``: the router's
dot, and ``ops/moe.py route``: the scores, the choice by score plus bias,
group-limited where there are groups, and the weights at the chosen experts)
over the prefill rows of the same slice (the rows ``prefill_device_ms_per_row``
counts): the held experts, the zero experts' term and the dense FFNs beside it
are left out. ``lib/fine_scopes.py`` makes the split. None where the slice
holds no such time (a program without the scope) or no prefill row."""

from benchmark.lib import fine_scopes, phases


def read(ctx):
    by = fine_scopes.of(ctx)
    if by is None:
        return None
    return phases.ms_per(by.get("prefill", {}).get("router"), phases.of(ctx)["prefill_rows"])

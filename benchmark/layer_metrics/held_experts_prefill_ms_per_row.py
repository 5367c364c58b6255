"""Device time of the held experts in one prompt's prefill: self time under
``prefill/.../mlp/experts`` (``ops/moe.py held_expert_ffn``: the sort by
expert, the dispatch gather, the three grouped matmuls over the experts this
chip holds, and the combine of their rows back into the batch) over the
prefill rows of the same slice (the rows ``prefill_device_ms_per_row``
counts): the router, the zero experts' term and the dense FFNs beside it are
left out. ``lib/fine_scopes.py`` makes the split. None where the slice holds
no such time (a program without the scope) or no prefill row."""

from benchmark.lib import fine_scopes, phases


def read(ctx):
    by = fine_scopes.of(ctx)
    if by is None:
        return None
    return phases.ms_per(by.get("prefill", {}).get("experts"), phases.of(ctx)["prefill_rows"])

"""Device time of the shortcut-connected expert branch in one prompt's
prefill: self time under ``prefill/.../mlp/router``, ``mlp/experts`` (gather,
the three grouped matmuls over the held experts at 6144 <-> 2048, the
scatter-add into the batch's rows) and ``mlp/zero`` over the prefill rows of
the same slice (the rows ``prefill_device_ms_per_row`` counts): the layer a
change to ``ops/moe.py held_expert_ffn`` moves, apart from the dense FFNs
beside it. ``lib/fine_scopes.py`` makes the split. None where the slice
holds no such time (a program without these scopes) or no prefill row."""

from benchmark.lib import fine_scopes, phases


def read(ctx):
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    return phases.ms_per(fine_scopes.branch_seconds(ctx, "prefill"), reduced["prefill_rows"])

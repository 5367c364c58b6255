"""Device time of the feed-forward halves of one decode step: self time under
``decode/mlp`` (in the MoE layers ``mlp/router``, ``mlp/experts``: gather,
three grouped matmuls over the held experts that were hit, scatter, and
``mlp/shared``; in the leading dense layer its SwiGLU) over the decode steps
of the same slice (``lib/phases.py``)."""

from benchmark.lib import phases


def read(ctx):
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    return phases.ms_per(reduced["seconds_by_scope"].get("decode/mlp"), reduced["steps"].get("decode"))

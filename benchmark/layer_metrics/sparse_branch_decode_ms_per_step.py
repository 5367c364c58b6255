"""Device time of the shortcut-connected expert branch in one decode step:
self time under ``decode/.../mlp/router``, ``mlp/experts`` and ``mlp/zero``
(``obs/tracing.FINE_SCOPES``) over the decode steps of the same slice: the
branch apart from the dense FFNs (``mlp/dense``) that dominate ``decode/mlp``
(``moe_ffn_decode_ms_per_step``). ``lib/fine_scopes.py`` makes the split and
says what a fusion is filed under. None where the slice holds no such time (a
program without these scopes) or no decode step."""

from benchmark.lib import fine_scopes, phases


def read(ctx):
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    return phases.ms_per(fine_scopes.branch_seconds(ctx, "decode"), reduced["steps"].get("decode"))

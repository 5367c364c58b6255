"""Device time of the state layers' one-position update in one decode step:
self time under ``decode/.../attn/scan`` (``exp(dt A)``, the state's update in
float32 and its contraction with C, every row, every state layer) and
``decode/.../attn/conv`` (the convolution over the kept inputs and the roll of
that state), over the decode steps of the same slice. It does not grow with
the context. None where the program opens no such scope."""

from benchmark.lib import phases, ssm_scopes


def read(ctx):
    seconds = ssm_scopes.seconds(ctx, "decode")
    if seconds is None:
        return None
    return phases.ms_per(seconds, phases.of(ctx)["steps"].get("decode"))

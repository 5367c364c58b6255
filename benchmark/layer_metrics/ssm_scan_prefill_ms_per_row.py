"""Device time of the state layers' scan and convolution in the prefill of one
prompt row: self time under ``prefill/.../attn/scan`` (the selective-scan
kernel, or the XLA form) and ``prefill/.../attn/conv`` (the depthwise causal
convolution, its ``silu`` and the roll of its state), summed over the state
layers, over the prefill rows of the same slice. The mixer's projections,
inner norms and output projection are outside it (``lib/ssm_scopes.py`` makes
the split). None where the program opens no such scope."""

from benchmark.lib import phases, ssm_scopes


def read(ctx):
    seconds = ssm_scopes.seconds(ctx, "prefill")
    if seconds is None:
        return None
    return phases.ms_per(seconds, phases.of(ctx)["prefill_rows"])

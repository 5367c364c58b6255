"""Device time of the conv layers' gated short convolution in the prefill of
one prompt row: self time under ``prefill/.../attn/conv`` (the gate in front
of the taps, the three taps, the roll of the two kept inputs and the gate
behind them: one elementwise pass over ``[S, 3 x hidden]`` a conv layer),
summed over the conv layers, over the prefill rows of the same slice. The
operator's two projections are outside it (``lib/ssm_scopes.py`` makes the
split, its names an argument). None where the program opens no such scope."""

from benchmark.lib import phases, ssm_scopes


def read(ctx):
    by = ssm_scopes.of(ctx)
    if by is None:
        return None
    return phases.ms_per(by.get("prefill", {}).get("conv"), phases.of(ctx)["prefill_rows"])

"""Device time of the conv layers' gated short convolution in one decode
step: self time under ``decode/.../attn/conv`` (the two gates, the three taps
over the two kept inputs and the new one, and the roll of that state, every
conv layer) over the decode steps of the same slice. It does not grow with
the context. None where the program opens no such scope."""

from benchmark.lib import phases, ssm_scopes


def read(ctx):
    by = ssm_scopes.of(ctx)
    if by is None:
        return None
    return phases.ms_per(by.get("decode", {}).get("conv"), phases.of(ctx)["steps"].get("decode"))

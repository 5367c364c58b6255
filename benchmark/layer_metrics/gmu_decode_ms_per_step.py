"""Device time of the gated memory units in one decode step: self time under
``decode/.../attn/gmu`` (each unit's two projections and the product with the
memory the step's memory layer just emitted), over the decode steps of the
same slice: weight streaming, 26 M parameters a unit. None where the program
opens no such scope."""

from benchmark.lib import cross_scopes


def read(ctx):
    return cross_scopes.decode_ms_per_step(ctx, ("gmu",))

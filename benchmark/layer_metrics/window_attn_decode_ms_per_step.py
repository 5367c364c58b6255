"""Device time of the SLIDING layers' attention in one decode step: self time
under ``decode/.../attn/window`` (the decode kernel's walk over each sliding
layer's K/V plane, handed ``[max(kv_start, kv_len - W), kv_len)``; or the XLA
form) over the decode steps of the same slice. Projections, rotation, the
cache's write and the gate are outside it (``lib/attn_scopes.py`` makes the
split). None where the program opens no such scope."""

from benchmark.lib import attn_scopes


def read(ctx):
    return attn_scopes.decode_ms_per_step(ctx, "window")

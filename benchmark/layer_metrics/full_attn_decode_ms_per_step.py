"""Device time of the FULL layers' attention in one decode step: self time
under ``decode/.../attn/global`` (the decode kernel's walk over each full
layer's K/V plane, the row's whole window ``[kv_start, kv_len)``) over the
decode steps of the same slice: what ``window_attn_decode_ms_per_step`` is
read against, a layer for a layer. None where the program opens no such scope."""

from benchmark.lib import attn_scopes


def read(ctx):
    return attn_scopes.decode_ms_per_step(ctx, "global")

"""Device time of every read of the ONE K/V plane that grows with the context
in one decode step: self time under ``decode/.../attn/cross`` (each cross
layer's walk of the full layer's plane) and ``decode/.../attn/global`` (the
full layer's own), over the decode steps of the same slice. It grows with the
context; the window layers' walks (``window_attn_decode_ms_per_step``) and the
states do not. None where the program opens no ``attn/cross``."""

from benchmark.lib import cross_scopes


def read(ctx):
    return cross_scopes.decode_ms_per_step(ctx, ("cross", "global"))

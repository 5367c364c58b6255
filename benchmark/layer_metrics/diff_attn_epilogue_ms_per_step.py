"""Device time of differential attention's epilogue in one decode step: self
time under ``decode/.../attn/diff`` (lambda from its four vectors, the
subtraction of a pair's second softmax from its first, the 128-wide RMS norm
and the ``1 - lambda_init`` scale; every attention layer, window, full and
cross), over the decode steps of the same slice. What the mechanism costs on
top of grouped-query attention, the doubled score matmul apart. None where the
program opens no such scope."""

from benchmark.lib import cross_scopes


def read(ctx):
    return cross_scopes.decode_ms_per_step(ctx, ("diff",))

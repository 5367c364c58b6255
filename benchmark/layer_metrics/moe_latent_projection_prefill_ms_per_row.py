"""Device time of a latent expert layer's two projections in the prefill of
one prompt row: self time under ``prefill/.../mlp/latent`` (the stream down to
``moe_latent_size`` in front of the held experts, their weighted partial sum
back up behind them, every expert layer) over the prefill rows of the same
slice. ``lib/path_scopes.py``. None where the program opens no such scope
(``attn/latent`` is latent ATTENTION: another path, not read here)."""

from benchmark.lib import path_scopes, phases


def read(ctx):
    if path_scopes.of(ctx) is None:
        return None
    return phases.ms_per(path_scopes.seconds(ctx, "prefill", "mlp/latent"), phases.of(ctx)["prefill_rows"])

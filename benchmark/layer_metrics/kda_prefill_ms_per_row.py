"""Device time of the linear-attention layers' mixers in the prefill of one
prompt row: self time under ``prefill/.../attn/kda`` (the projections, the
convolution of q, k and v, the decay's, beta's and the gate's low-rank
projections, the chunked recurrence, the gated output norm and ``W_o``, every
linear layer) over the prefill rows of the same slice. ``lib/kda_scopes.py``
makes the split. None where the program opens no such scope."""

from benchmark.lib import kda_scopes, phases


def read(ctx):
    if kda_scopes.of(ctx) is None:
        return None
    return phases.ms_per(kda_scopes.seconds(ctx, "prefill"), phases.of(ctx)["prefill_rows"])

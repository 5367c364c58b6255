"""Device time of the linear-attention layers' mixers in one step of the
answer's loop: self time under ``decode/.../attn/kda`` over the decode steps
of the same slice, or under ``verify/.../attn/kda`` (``commit``'s replay
among it) over the verify steps where the window speculates. It does not grow
with the context. ``lib/kda_scopes.py``. None where the program opens no such
scope or the slice holds no step."""

from benchmark.lib import kda_scopes, phases


def read(ctx):
    if kda_scopes.of(ctx) is None:
        return None
    phase = kda_scopes.step_phase(ctx)
    if phase is None:
        return None
    return phases.ms_per(kda_scopes.seconds(ctx, phase), phases.of(ctx)["steps"].get(phase))

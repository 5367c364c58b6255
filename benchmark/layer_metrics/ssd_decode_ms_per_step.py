"""Device time of the Mamba-2 recurrence in one step of the answer's loop:
self time under ``decode/.../attn/ssd`` (every Mamba-2 layer's
single-position update of its float32 state and the contraction with C) over
the decode steps of the same slice, or under ``verify/.../attn/ssd`` (a
verify step's one chunk and ``commit``'s replay) over the verify steps where
the window speculates. It does not grow with the context.
``lib/path_scopes.py``. None where the program opens no such scope or the
slice holds no step."""

from benchmark.lib import path_scopes, phases


def read(ctx):
    if path_scopes.of(ctx) is None:
        return None
    phase = path_scopes.step_phase(ctx)
    if phase is None:
        return None
    return phases.ms_per(path_scopes.seconds(ctx, phase, "attn/ssd"), phases.of(ctx)["steps"].get(phase))

"""Device seconds of ``<phase>/.../<sub>`` by ONE fine scope anywhere beneath
it (``obs/tracing.FINE_SCOPES``), for the readers of the state-space-duality
latent-expert family: ``attn/ssd`` (a Mamba-2 layer's recurrence in all its
forms, ``commit``'s replay among them), ``attn/conv``, ``attn/gate``,
``attn/global``, and under ``mlp`` ``latent`` (the two projections between
the stream and the experts' latent), ``router``, ``experts``, ``shared``.

``lib/fine_scopes.py``, ``ssm_scopes.py`` and ``kda_scopes.py`` are this join
for other names and phases and may not be edited; this file is the same join
with the sub-scope, the names and three phases as data (one more copy for
ROADMAP C15 to fold: the others can be read through it). ``lib/phases.py``
files an operation under the first sub-scope it knows, its ``module_scopes``
keeps the whole path, so operations are joined to their paths again here from
``phases.load``'s plain data, with self times from ``lib/trace.self_times``.
An operation of a sub-scope under none of its names is filed under
``<sub>/other``. A program that opens no such scope gives no such key and the
readers return None.
"""

import bisect
import json
import os

from benchmark.lib import phases, serve, trace

NAMES = {"attn": ("ssd", "conv", "gate", "global"), "mlp": ("latent", "router", "experts", "shared")}
PHASES = ("prefill", "decode", "verify")


def fine_scope(op_name: str):
    """``<phase>/.../<sub>/.../<name>/...`` -> ``(phase, "<sub>/<name>")``
    (``<sub>/other`` under none of the sub-scope's names); None for any other
    operation."""
    phase, sub = phases.scope_of(op_name)
    if phase not in PHASES or sub not in NAMES:
        return None
    parts = op_name.split("/")
    return phase, sub + "/" + next((p for p in parts[parts.index(sub) + 1:] if p in NAMES[sub]), "other")


def seconds_by_fine_scope(data: dict) -> dict:
    """``{phase: {"<sub>/<name>": leaf self seconds}}`` of ``phases.load``'s data."""
    runs = sorted(data["modules"], key=lambda m: m[1])
    starts = [m[1] for m in runs]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][0] if i >= 0 and t < runs[i][1] + runs[i][2] else None

    keyed = []
    for label, start, dur, *named in data["ops"]:
        instr = phases._INSTR.match(label).group(1)
        module = named[0] if named else module_at(start)
        where = fine_scope(data["scopes"].get(module, {}).get(instr, ""))
        keyed.append([(where, bool(phases._CONTAINERS.match(label))), start, dur])
    out = {}
    for (where, container), sec in trace.self_times(keyed).items():
        if where is not None and not container:
            by = out.setdefault(where[0], {})
            by[where[1]] = by.get(where[1], 0.0) + sec
    return out


def of(ctx):
    """The traced run's split, made once for the readers that share ``ctx``;
    None where there is no trace. Prints the ``path_scopes`` information line
    on first use."""
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    if "path_scopes" not in ctx:
        by = seconds_by_fine_scope(phases.load(trace.find_xplane(os.path.join(serve.STATE_DIR, "trace"))))
        print(json.dumps({"event": "path_scopes", "steps": reduced["steps"], "prefill_rows": reduced["prefill_rows"],
                          "seconds_by_fine_scope": {p: dict(sorted(s.items())) for p, s in sorted(by.items())}}),
              flush=True)
        ctx["path_scopes"] = by
    return ctx["path_scopes"]


def seconds(ctx, phase: str, path: str):
    """Self seconds under ``<phase>/.../<path>`` (``attn/ssd``, ``mlp/latent``);
    None where the slice holds no such operation."""
    return ((of(ctx) or {}).get(phase) or {}).get(path)


def step_phase(ctx):
    """The loop phase the traced slice's steps ran in: ``verify`` where the
    window speculates, else ``decode``; None where it holds neither."""
    steps = (phases.of(ctx) or {}).get("steps", {})
    live = [p for p in ("decode", "verify") if steps.get(p)]
    return max(live, key=lambda p: steps[p]) if live else None

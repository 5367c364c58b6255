"""Device seconds of a linear-attention layer's mixer by its FINE scopes
(``obs/tracing.FINE_SCOPES``: ``attn/kda`` around the whole mixer, and beneath
it ``attn/kda/conv``, ``attn/kda/gate`` and ``attn/kda/delta``: the
recurrence in all its forms, ``commit``'s replay among them), for the
``kda_*`` readers of ``layer_metrics/``.

``lib/ssm_scopes.py`` is this join for the phases ``decode`` and ``prefill``
and may not be edited; a window that speculates runs the ``verify`` loop, so
this file is the same join over three phases and a path TWO names deep (one
more copy for ROADMAP C15 to fold). An operation under ``attn/kda`` and none
of the three is filed under ``kda/other`` (the projections, the L2 norms, the
gated output norm). A program that opens no such scope (every family before
this one) gives an empty split and the readers return None.
"""

import bisect
import json
import os

from benchmark.lib import phases, serve, trace

WHOLE = "kda"
PARTS = ("delta", "conv", "gate")
PHASES = ("prefill", "decode", "verify")


def fine_scope(op_name: str):
    """``<phase>/.../attn/kda[/<part>]/...`` -> ``(phase, "kda/<part>")``
    (``kda/other`` under none of ``PARTS``); None for any other operation."""
    phase, sub = phases.scope_of(op_name)
    if phase not in PHASES or sub != "attn":
        return None
    parts = op_name.split("/")
    rest = parts[parts.index("attn") + 1:]
    if WHOLE not in rest:
        return None
    inner = rest[rest.index(WHOLE) + 1:]
    return phase, WHOLE + "/" + next((p for p in inner if p in PARTS), "other")


def seconds_by_fine_scope(data: dict) -> dict:
    """``{phase: {"kda/<part>": leaf self seconds}}`` of ``phases.load``'s data."""
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
    None where there is no trace. Prints the ``kda_scopes`` information line
    on first use."""
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    if "kda_scopes" not in ctx:
        by = seconds_by_fine_scope(phases.load(trace.find_xplane(os.path.join(serve.STATE_DIR, "trace"))))
        print(json.dumps({"event": "kda_scopes", "steps": reduced["steps"], "prefill_rows": reduced["prefill_rows"],
                          "seconds_by_fine_scope": {p: dict(sorted(s.items())) for p, s in sorted(by.items())}}),
              flush=True)
        ctx["kda_scopes"] = by
    return ctx["kda_scopes"]


def seconds(ctx, phase: str, part: str = ""):
    """Self seconds under ``<phase>/.../attn/kda`` (``part``: under that part
    of it alone); None where the slice holds no such operation."""
    split = (of(ctx) or {}).get(phase, {})
    picked = [v for k, v in split.items() if not part or k == f"{WHOLE}/{part}"]
    return sum(picked) if picked else None


def step_phase(ctx):
    """The loop phase the traced slice's steps ran in: ``verify`` where the
    window speculates, else ``decode``; None where it holds neither."""
    steps = (phases.of(ctx) or {}).get("steps", {})
    live = [p for p in ("decode", "verify") if steps.get(p)]
    return max(live, key=lambda p: steps[p]) if live else None

"""Device seconds of ``<phase>/.../mlp`` by FINE scope (``obs/tracing.FINE_SCOPES``:
``mlp/router``, ``mlp/experts``, ``mlp/zero``, ``mlp/dense``, ``mlp/shared``),
for the readers that take the expert branch apart from the dense FFNs beside
it (``layer_metrics/sparse_branch_*``).

``lib/phases.py`` files an operation under the first sub-scope it knows
(``mlp``); its ``module_scopes`` keeps the whole path, so the join of
operations to their paths is made again here from ``phases.load``'s plain data
(``ops``, ``modules``, ``scopes``), with self times from
``lib/trace.self_times``, as ``reduce_phases`` does. A fusion the compiler
wrote carries the path of ONE of its fused instructions (the first of the
sub-scope most of them carry), so a fusion that mixes the branch with a dense
FFN is filed whole under one of them; what ``mlp`` holds under NO fine scope
(the join's add, copies the compiler gave the phase) is on the
``sparse_branch`` information line beside the split, under ``none``.

``phases._INSTR`` and ``phases._CONTAINERS`` are that file's private names:
the label's instruction and the events that only contain others. A
``benchmark`` PR may export them; this file is the one place they are used.
"""

import bisect
import json
import os

from benchmark.lib import phases, serve, trace

BRANCH = ("router", "experts", "zero")  # the shortcut-connected expert branch
FINE = BRANCH + ("dense", "shared")
PHASES = ("decode", "prefill")


def fine_scope(op_name: str):
    """``<phase>/.../mlp/<fine>/...`` -> ``(phase, fine)`` for a phase of
    ``PHASES``; ``(phase, "")`` for an operation of its ``mlp`` under no fine
    scope; None for any other operation."""
    phase, sub = phases.scope_of(op_name)
    if phase not in PHASES or sub != "mlp":
        return None
    parts = op_name.split("/")
    return phase, next((p for p in parts[parts.index("mlp") + 1:] if p in FINE), "")


def seconds_by_fine_scope(data: dict) -> dict:
    """``{phase: {fine: leaf self seconds}}`` of ``phases.load``'s data."""
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
    """The traced run's split, made once for the readers that share ``ctx``
    (it loads the capture a second time: ``phases.of`` keeps the reduction,
    not the data); None where there is no trace. Prints the ``sparse_branch``
    information line on first use."""
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    if "fine_scopes" not in ctx:
        by = seconds_by_fine_scope(phases.load(trace.find_xplane(os.path.join(serve.STATE_DIR, "trace"))))
        print(json.dumps({"event": "sparse_branch", "decode_steps": reduced["steps"].get("decode"),
                          "prefill_rows": reduced["prefill_rows"],
                          "mlp_seconds_by_fine_scope": {
                              phase: {k or "none": v for k, v in sorted(split.items())}
                              for phase, split in sorted(by.items())}}), flush=True)
        ctx["fine_scopes"] = by
    return ctx["fine_scopes"]


def branch_seconds(ctx, phase: str):
    """Self seconds of the expert branch in ``phase``, or None."""
    by = of(ctx)
    return None if by is None else sum(by.get(phase, {}).get(k, 0.0) for k in BRANCH)

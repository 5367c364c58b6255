"""Device seconds of ``<phase>/.../attn`` by the hybrid state-space family's
FINE scopes (``obs/tracing.FINE_SCOPES``: ``attn/scan`` around a state layer's
selective scan, the kernel or the XLA form, in a decode step the one-position
update and the contraction with C; ``attn/conv`` around its causal
convolution and the roll of that state), for
``layer_metrics/ssm_scan_prefill_ms_per_row`` and ``ssm_decode_ms_per_step``.
The family's attention layers open ``attn/global``, which ``lib/attn_scopes.py``
already reads.

``lib/attn_scopes.py`` and ``lib/ring_scopes.py`` are this join for other
families' names and may not be edited; this file is the same join with the
names as an argument (it edits nothing there): ``lib/phases.py`` files an
operation under the first sub-scope it knows (``attn``), its ``module_scopes``
keeps the whole path, so operations are joined to their paths again here from
``phases.load``'s plain data, with self times from ``lib/trace.self_times``.
What ``attn`` holds under NO fine scope of the names (the projections, the
inner norms, the attention layers' part) is on the ``ssm_scopes`` information
line under ``none``. A program that opens no such scope (every family before
this one) gives an empty split and the readers return None.
"""

import bisect
import json
import os

from benchmark.lib import phases, serve, trace

FINE = ("scan", "conv")
PHASES = ("decode", "prefill")


def fine_scope(op_name: str, names=FINE):
    """``<phase>/.../attn/<fine>/...`` -> ``(phase, fine)`` for a phase of
    ``PHASES``; ``(phase, "")`` for an operation of its ``attn`` under none of
    ``names``; None for any other operation."""
    phase, sub = phases.scope_of(op_name)
    if phase not in PHASES or sub != "attn":
        return None
    parts = op_name.split("/")
    return phase, next((p for p in parts[parts.index("attn") + 1:] if p in names), "")


def seconds_by_fine_scope(data: dict, names=FINE) -> dict:
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
        where = fine_scope(data["scopes"].get(module, {}).get(instr, ""), names)
        keyed.append([(where, bool(phases._CONTAINERS.match(label))), start, dur])
    out = {}
    for (where, container), sec in trace.self_times(keyed).items():
        if where is not None and not container:
            by = out.setdefault(where[0], {})
            by[where[1]] = by.get(where[1], 0.0) + sec
    return out


def of(ctx):
    """The traced run's split, made once for the readers that share ``ctx``;
    None where there is no trace, or where the program opens none of the
    scopes. Prints the ``ssm_scopes`` information line on first use."""
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    if "ssm_scopes" not in ctx:
        by = seconds_by_fine_scope(phases.load(trace.find_xplane(os.path.join(serve.STATE_DIR, "trace"))))
        print(json.dumps({"event": "ssm_scopes", "decode_steps": reduced["steps"].get("decode"),
                          "prefill_rows": reduced["prefill_rows"],
                          "attn_seconds_by_fine_scope": {
                              phase: {k or "none": v for k, v in sorted(split.items())}
                              for phase, split in sorted(by.items())}}), flush=True)
        ctx["ssm_scopes"] = by
    return ctx["ssm_scopes"]


def seconds(ctx, phase: str):
    """Self seconds under ``<phase>/.../attn/{scan,conv}``; None where the
    slice holds no operation under either."""
    by = of(ctx)
    split = (by or {}).get(phase, {})
    if not any(fine in split for fine in FINE):
        return None
    return sum(split.get(fine, 0.0) for fine in FINE)

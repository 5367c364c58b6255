"""Device seconds by the decoder-hybrid-decoder family's scopes
(``obs/tracing.FINE_SCOPES``: ``attn/cross`` around a cross-attention layer's
attention over the shared plane, ``attn/gmu`` around a gated memory unit,
``attn/diff`` around differential attention's subtraction, lambda and norm;
and ``cross`` ABOVE the sub-scopes, around the whole cross-decoder half), for
``layer_metrics/cross_decoder_prefill_ms_per_row``,
``shared_kv_decode_ms_per_step``, ``gmu_decode_ms_per_step`` and
``diff_attn_epilogue_ms_per_step``.

The split beneath ``attn`` is ``lib/ssm_scopes.py``'s join with these names
(it takes the names as an argument; nothing there is edited). The cross-decoder
half's time in a prefill is a join of its own: an operation belongs to it
where its scope path holds the component ``cross`` anywhere behind the phase
(the half's own scope, or a fusion the compiler filed under a cross layer's
attention), whatever sub-scope it is filed under: the half's norms and SwiGLUs
count with its mixers. A program that opens no such scope (every other family)
gives empty splits and the readers return None.
"""

import bisect
import json
import os

from benchmark.lib import phases, serve, ssm_scopes, trace

FINE = ("cross", "global", "gmu", "diff", "window", "scan", "conv")
HALF = "cross"


def in_cross_decoder(op_name: str):
    """The phase of an operation of the cross-decoder half, else None."""
    parts = (op_name or "").split("/")
    for i, part in enumerate(parts):
        if part in phases.PHASES:
            return part if HALF in parts[i + 1:] else None
    return None


def half_seconds(data: dict) -> dict:
    """``{phase: leaf self seconds of the cross-decoder half}`` of ``phases.load``'s data."""
    runs = sorted(data["modules"], key=lambda m: m[1])
    starts = [m[1] for m in runs]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][0] if i >= 0 and t < runs[i][1] + runs[i][2] else None

    keyed = []
    for label, start, dur, *named in data["ops"]:
        instr = phases._INSTR.match(label).group(1)
        module = named[0] if named else module_at(start)
        where = in_cross_decoder(data["scopes"].get(module, {}).get(instr, ""))
        keyed.append([(where, bool(phases._CONTAINERS.match(label))), start, dur])
    out = {}
    for (where, container), sec in trace.self_times(keyed).items():
        if where is not None and not container:
            out[where] = out.get(where, 0.0) + sec
    return out


def of(ctx):
    """``{"fine": {phase: {scope: s}}, "half": {phase: s}}`` of the traced
    run, made once for the readers that share ``ctx``; None where there is no
    trace. Prints the ``cross_scopes`` information line on first use."""
    reduced = phases.of(ctx)
    if reduced is None:
        return None
    if "cross_scopes" not in ctx:
        data = phases.load(trace.find_xplane(os.path.join(serve.STATE_DIR, "trace")))
        split = {"fine": ssm_scopes.seconds_by_fine_scope(data, FINE), "half": half_seconds(data)}
        print(json.dumps({"event": "cross_scopes", "decode_steps": reduced["steps"].get("decode"),
                          "prefill_rows": reduced["prefill_rows"], "cross_decoder_seconds": split["half"],
                          "attn_seconds_by_fine_scope": {
                              phase: {k or "none": v for k, v in sorted(by.items())}
                              for phase, by in sorted(split["fine"].items())}}), flush=True)
        ctx["cross_scopes"] = split
    return ctx["cross_scopes"]


def decode_ms_per_step(ctx, scopes):
    """Self milliseconds under ``decode/.../attn/<scope>`` for the ``scopes``
    a decode step of the same slice; None where the slice holds no operation
    under the FIRST of them (the family's own) or no step."""
    split = of(ctx)
    by = (split or {}).get("fine", {}).get("decode", {})
    if scopes[0] not in by:
        return None
    return phases.ms_per(sum(by.get(s, 0.0) for s in scopes), phases.of(ctx)["steps"].get("decode"))

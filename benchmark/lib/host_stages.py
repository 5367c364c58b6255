"""The host side of a dispatch, from the program's own clocks (PR 51).

Two sources. The program's counters: ``rag_generate_dispatch_stage_seconds
{path, stage}`` holds one sample a stage a dispatch (``gather``: the
scheduler's window; ``launch``: host preparation up to the enqueue; ``device``:
the end of ``launch`` to the end of ``fetch``; ``deliver``: the trim, the
folds and the riders' release), in every run; ``stage_delta`` reads its growth
over the window. And the capture of a ``--trace 1`` run: every span of the
program is a ``TraceAnnotation`` on the capture's host planes, so the device's
idle gaps can be filed by the span the host was in; ``of(ctx)`` reduces that
once for the readers, as ``lib/phases.py of(ctx)`` does its own.

The filing is finer than ``lib/trace.attribute_gaps``'s, which gives a whole
gap to the span that covers most of it: that is the outermost span wherever
two stages share a gap, and a round's boundary (the riders' ``generate`` around
the scheduler's ``gather`` and the worker's ``launch``) would go to ``generate``
whole, as it does in the ledger's ``idle_gaps``. Here a gap is cut wherever a
span begins or ends, and each piece goes to the INNERMOST span that covers it
(the first of the names ``attribute`` is handed, innermost first; by any span
of that name: eight callers inside ``generate`` cover a piece once). A gap that
lies whole inside one innermost span is filed as the old rule files it, so a
``benchmark`` PR can fold ``lib/trace.py``'s and ``lib/phases.py``'s copies
(each over its module's own names) into ``attribute``.

Plain data throughout (``lib/trace.load_xplane``'s form), so the tests reduce
a small recorded cut (``benchmark/tests/recorded_host_stages.json``).
"""

from __future__ import annotations

import bisect
import json
import os
import re
import time

from benchmark.lib import trace

# the program's host spans, innermost first (obs/tracing.py: ``span``,
# ``dispatch_record``, ``annotate``; engine/batching.py; server/app.py)
SPANS = ("deliver", "fetch", "launch", "retrieve_batch", "gather", "dispatch",
         "detokenize", "generate", "assemble", "retrieve")
NO_SPAN = "no span"
SHORT_GAP_NS = trace.SHORT_GAP_NS
# a gap under ``fetch`` is the device's own: the host is already waiting for it
DEVICE_HELD = ("fetch",)

FAMILY = "rag_generate_dispatch_stage_seconds"
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def stage_delta(ctx, stage: str, path: str = None):
    """``(seconds, dispatches)`` that ``stage`` grew by between the window's
    edges, over every path or one; None where the program has no such family
    (a program from before PR 51)."""
    total = {"sum": 0.0, "count": 0.0}
    found = False
    for key in ctx["after"]:
        for part in total:
            if not key.startswith(f"{FAMILY}_{part}{{"):
                continue
            labels = dict(_LABEL.findall(key))
            if labels.get("stage") == stage and path in (None, labels.get("path")):
                total[part] += ctx["stats"].delta(ctx["before"], ctx["after"], key)
                found = True
    return (total["sum"], total["count"]) if found else None


def gaps_between(busy: list) -> list:
    """``[(start, end)]`` between consecutive busy intervals: the slice's
    overhang before its first and after its last operation is not the host's."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def attribute(gaps: list, spans: list, names) -> dict:
    """Nanoseconds of ``gaps`` by name: each gap cut at the edges of the spans
    inside it, each piece under the first of ``names`` (innermost first) with
    a span that covers it, ``NO_SPAN`` where none does. ``spans`` is
    ``[[name, start_ns, duration_ns], ...]``."""
    covers = []  # (name, starts, ends) of each name's merged spans
    for name in names:
        merged = trace.union([s, s + d] for m, s, d in spans if m == name)
        covers.append((name, [iv[0] for iv in merged], [iv[1] for iv in merged]))
    out = {}
    for g0, g1 in gaps:
        cuts = {g0, g1}
        for _, starts, ends in covers:
            for edges in (starts, ends):
                cuts.update(edges[bisect.bisect_right(edges, g0):bisect.bisect_left(edges, g1)])
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            owner = NO_SPAN
            for name, starts, ends in covers:
                i = bisect.bisect_right(starts, mid) - 1
                if i >= 0 and mid < ends[i]:
                    owner = name
                    break
            out[owner] = out.get(owner, 0.0) + (b - a)
    return out


def reduce_host_stages(planes: dict, names=SPANS) -> dict:
    """Between the first and the last operation of chip 0 in the slice: idle
    seconds in gaps of at least 50 us by the innermost span over each piece,
    the seconds in shorter gaps, and ``host_held_idle_share``: percent of that
    stretch the gaps NOT under ``fetch`` take. All zero where the capture has
    no device operation or no stretch between two."""
    chips = trace.device_planes(planes)
    ops = trace.op_events(planes, chips[0]) if chips else []
    busy = trace.union([s, s + d] for _, s, d in ops)
    spans = [ev for plane, lines in planes.items() if plane.startswith("/host:")
             for evs in lines.values() for ev in evs if ev[0] in names]
    gaps = gaps_between(busy)
    long_gaps = [g for g in gaps if g[1] - g[0] >= SHORT_GAP_NS]
    idle = attribute(long_gaps, spans, names)
    span_ns = busy[-1][1] - busy[0][0] if busy else 0.0
    held_ns = sum(v for k, v in idle.items() if k not in DEVICE_HELD)
    return {
        "span_s": span_ns / 1e9,
        "idle_s": {k: v / 1e9 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "short_gaps_s": sum(g1 - g0 for g0, g1 in gaps if g1 - g0 < SHORT_GAP_NS) / 1e9,
        "gaps": len(long_gaps),
        "host_spans": {n: sum(1 for ev in spans if ev[0] == n) for n in names
                       if any(ev[0] == n for ev in spans)},
        "host_held_s": held_ns / 1e9,
        "host_held_idle_share": 100.0 * held_ns / span_ns if span_ns else 0.0,
    }


def of(ctx):
    """The traced run's host stages, reduced once for the readers (kept in
    the ``ctx`` they share); None where there is no trace. Prints the
    ``host_stages`` information line on first use. A capture without a device
    plane (the CPU rehearsal) is read with the XLA:CPU client's operations in
    the chip's place, as ``run.py`` reads it."""
    if ctx.get("trace") is None:
        return None
    if "host_stages" not in ctx:
        from benchmark.lib import serve

        t0 = time.monotonic()
        path = trace.find_xplane(os.path.join(serve.STATE_DIR, "trace"))
        planes = trace.load_xplane(path, keep_host=SPANS)
        if not trace.device_planes(planes):
            planes = trace.load_xplane(path, keep_host=SPANS, cpu_as_device=True)
        reduced = reduce_host_stages(planes)
        reduced["seconds_to_reduce"] = round(time.monotonic() - t0, 1)
        print(json.dumps({"event": "host_stages", **reduced}), flush=True)
        ctx["host_stages"] = reduced
    return ctx["host_stages"]

"""From a profiler trace to numbers: device busy time, the operations that
took it, the idle gaps and what the host was doing in each.

``load_xplane`` turns an ``.xplane.pb`` into plain data, ``{plane: {line:
[[name, start_ns, duration_ns], ...]}}``; everything else works on that form,
which is also the form of the small recorded trace the tests reduce
(``benchmark/tests/recorded_trace.json``).
"""

from __future__ import annotations

import glob
import os
import re

# host spans the program opens around its stages (obs/tracing.span ->
# jax.profiler.TraceAnnotation), innermost-first where they can nest
HOST_SPANS = ("detokenize", "generate", "assemble", "retrieve")
NO_SPAN = "no span: HTTP / coalescer"
SHORT_GAPS = "between device ops, each under 50 us"
SHORT_GAP_NS = 50e3
OPS_LINE = "XLA Ops"
# control-flow and program-level events that only contain other events
_CONTAINERS = re.compile(r"^(while|conditional|call|async-|tuple|get-tuple-element)")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


_HLO = re.compile(r"^%?(\S+) = .*?([a-z]+\d*\[[\d,]*\])")


def op_label(name: str) -> str:
    """The trace names a device operation by its whole HLO line. Keep the
    instruction's name and result type, and mark a Mosaic custom call:
    ``flash_attention.11 bf16[32,4096,128] tpu_custom_call``."""
    m = _HLO.match(name)
    if not m:
        return name
    mosaic = " tpu_custom_call" if 'custom_call_target="tpu_custom_call"' in name else ""
    return f"{m.group(1)} {m.group(2)}{mosaic}"


def load_xplane(path: str, keep_host=HOST_SPANS, cpu_as_device: bool = False) -> dict:
    """Device planes whole, host planes cut to the program's named spans.
    ``cpu_as_device`` (the CPU rehearsal only) files the XLA:CPU client's
    operation events as a pretended ``/device:TPU:0``, so the rehearsal
    walks the same reduction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = set(keep_host)
    planes = {}
    if cpu_as_device:
        ops = [
            [ev.name, float(ev.start_ns), float(ev.duration_ns)]
            for plane in data.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines if line.name.startswith("tf_XLAPjRtCpuClient")
            for ev in line.events
            if ev.duration_ns > 0 and not ev.name.startswith(("ThreadpoolListener", "end: "))
        ]
        planes["/device:TPU:0"] = {OPS_LINE: ops}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = {}
        for line in plane.lines:
            rows = [
                [op_label(ev.name) if device else ev.name,
                 float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
                if device or ev.name in keep
            ]
            if rows:
                lines.setdefault(line.name, []).extend(rows)
        if lines:
            planes[plane.name] = lines
    return planes


def device_planes(planes: dict) -> list:
    """Names of the planes that are chips (one per TPU core), in order."""
    names = [p for p in planes if re.match(r"^/device:TPU:\d+$", p)]
    return sorted(names, key=lambda p: int(p.rsplit(":", 1)[1]))


def op_events(planes: dict, plane: str) -> list:
    """The chip's operation events: its ``XLA Ops`` line where the trace has
    one, otherwise every line of the plane."""
    lines = planes[plane]
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    return [ev for evs in lines.values() for ev in evs]


def union(intervals) -> list:
    """Merged ``[start, end]`` intervals, ascending."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events) -> dict:
    """Seconds per operation name, each event's children taken out of it:
    a ``while`` that holds a decode loop keeps only what no child covers."""
    totals = {}
    stack = []  # [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            totals[done[0]] = totals.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    for done in stack:
        totals[done[0]] = totals.get(done[0], 0.0) + done[2]
    return {k: max(v, 0.0) / 1e9 for k, v in totals.items()}


def op_group(label: str) -> str:
    """``fusion.123 bf16[1,16,4096]`` -> ``fusion``: one kind of operation."""
    return re.sub(r"\.\d+$", "", label.split(" ")[0]) or label


def is_mosaic(label: str) -> bool:
    """A Pallas kernel: a custom call whose target is Mosaic's."""
    return label.endswith(" tpu_custom_call")


def is_all_reduce(label: str) -> bool:
    return label.startswith(("all-reduce", "all_reduce"))


def kernel_table(events) -> dict:
    """``{"<kernel> <result type>": [calls, seconds]}`` over the Mosaic
    calls, the kernel named as the trace names it (its Python function)."""
    out = {}
    for label, _, dur in events:
        if is_mosaic(label):
            name, typ = label.split(" ")[:2]
            row = out.setdefault(f"{op_group(name)} {typ}", [0, 0.0])
            row[0] += 1
            row[1] += dur / 1e9
    return out


def host_spans(planes: dict) -> list:
    return [
        ev for plane, lines in planes.items() if plane.startswith("/host:")
        for evs in lines.values() for ev in evs if ev[0] in HOST_SPANS
    ]


def attribute_gaps(busy: list, spans: list, t0: float, t1: float) -> dict:
    """Idle seconds by the host span that covers most of each gap."""
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:  # launch gaps: no host span explains them
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0.0) + (g1 - g0)
            continue
        best, best_cover = NO_SPAN, 0.0
        for name in HOST_SPANS:
            cover = sum(
                max(0.0, min(s + d, g1) - max(s, g0))
                for n, s, d in spans if n == name)
            # the innermost span wins a tie (a generate inside a retrieve)
            if cover > best_cover * 1.001:
                best, best_cover = name, cover
        out[best] = out.get(best, 0.0) + (g1 - g0)
    return {k: v / 1e9 for k, v in out.items()}


def reduce_trace(planes: dict, chips: int, top: int = 10) -> dict:
    """Everything the per-layer readers and the result line take from a
    trace. The window is what the device planes' events span; ``busy_s`` is
    averaged over the chips used, shares are of device 0's busy time."""
    names = device_planes(planes)[:chips]
    if not names:
        raise ValueError("the trace has no /device:TPU plane")
    per_chip = [op_events(planes, n) for n in names]
    spans = host_spans(planes)
    everything = [ev for evs in per_chip for ev in evs] + spans
    t0 = min(ev[1] for ev in everything)
    t1 = max(ev[1] + ev[2] for ev in everything)
    busy = [union([s, s + d] for _, s, d in evs) for evs in per_chip]
    busy_s = [sum(e - s for s, e in iv) / 1e9 for iv in busy]
    own = self_times(per_chip[0])
    leaf = {k: v for k, v in own.items() if not _CONTAINERS.match(k)}
    total = sum(leaf.values()) or 1.0
    groups = {}
    for name, sec in leaf.items():
        groups[op_group(name)] = groups.get(op_group(name), 0.0) + sec
    gaps = attribute_gaps(busy[0], spans, t0, t1)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "busy_s_per_chip": busy_s,
        "mosaic_share": sum(v for k, v in leaf.items() if is_mosaic(k)) / total,
        "all_reduce_share": sum(v for k, v in leaf.items() if is_all_reduce(k)) / total,
        "kernels": kernel_table(per_chip[0]),
        "device_ops": [[k, v] for k, v in by_time(leaf)],
        "device_op_groups": [[k, v] for k, v in by_time(groups)],
        "idle_gaps": [[k, v] for k, v in by_time(gaps)],
        "planes": names,
    }

"""The interior of the device programs, from the traced run's ``.xplane.pb``:
seconds per phase (``retrieve``, ``prefill``, ``decode``, ``verify``, ...) and
per sub-scope (``attn``, ``mlp``, ...), steps and prefill rows counted in the
same slice, and the idle gaps by the host span that covers them.

Where the scope of a device operation comes from. The trace names an
operation by its HLO line, which carries no ``op_name``, and the event has no
stat that does (PR 24, first chip call). The profiler does store every traced
executable's optimized HLO in the trace's ``/host:metadata`` plane, keyed
``<module>(<program id>)`` exactly like the events of the device's ``XLA
Modules`` line: each instruction's ``metadata.op_name`` there holds the scope
path the program traced it under (``jit(gen_rag)/prefill/.../attn/...``,
``obs/tracing.phase_scope``). ``jax.profiler.ProfileData`` does not expose
that plane's contents, so the few protobuf fields needed are read off the
wire here. An operation is joined to its scope by (module, instruction name).

The compiler makes instructions of its own (copies, layout changes, fusions
whose root it wrote) that carry no ``op_name``. Two rules give those, and
only those, a home (an operation the program traced outside every scope
keeps its own path and stays ``unscoped``): a fusion takes the scope most of
its fused instructions carry; any other takes the phase of its computation
when every scoped instruction there has the same one (the body of a decode
loop). What is left is ``unscoped``: on the chip, the relayouts the compiler
puts in front of the loops, 0.2% of the time (PERF.md section 6, PR 24).

What the times are divided by comes from the program's own statements, never
from a kernel's name or an operation's shape, so a PR that swaps a kernel or
changes a layout moves the metric only by the time it saves; and from the
device's own events, never from a host span, because a slice cuts the first
and the last program it holds anywhere and the trace drops a span it does
not hold whole (``closed4``'s 2.5 s slice holds a whole ``dispatch`` one time
in twenty-five). The unit is the operation traced with exactly ONE loop
beneath its phase (``<phase>/.../while/body/...``, no second ``while``, no
branch): the median executions of those instructions in a program.

- Under ``decode`` or ``verify``, whose scope is opened around the step loop,
  such an operation runs once a **step** (the final norm, the head's matmul,
  the output's update; the layers' operations sit one loop deeper), on every
  backend. The median, so that one the compiler moved into the layers' loop
  does not move the count. (The sampler cannot be the mark: on the chip the
  compiler fuses a greedy sampler into the head's fusion, and no instruction
  is filed under ``sample``.)
- Under ``prefill`` it is an operation of the layers' loop, and runs once a
  trip of that loop: ``layer_loop_trips`` times a pass, which the cell's
  family states (``families/<model_type>.py``; the published depth where
  every layer is in the one loop), so a pass the slice cut counts by the
  layers of it that ran. The **rows** of a pass are in the scope path too:
  ``engine/engine.py`` opens a generate program's prefill as
  ``prefill/rows<N>``, the batch the executable was built for. (A prefill
  chunked by an outer scan would put the layers two loops deep; no cell
  runs one, and ``prefill_rows`` would read 0 there, not a wrong number.)

Everything below ``load`` works on plain data, the form of the recorded
trace the tests reduce (``benchmark/tests/recorded_phases.json``):

    {"modules": [[name, start_ns, duration_ns], ...],     device 0, XLA Modules
     "ops":     [[label, start_ns, duration_ns], ...],     device 0, XLA Ops
                (on the CPU a fourth field names the operation's module)
     "scopes":  {module: {instruction: op_name}},
     "host":    [[span, start_ns, duration_ns], ...]}
"""

from __future__ import annotations

import bisect
import json
import os
import re
import statistics
import time

from benchmark.lib import trace

PHASES = ("retrieve", "prefill", "decode", "verify", "sample", "score", "mixed")
SUB_SCOPES = ("embed", "knn", "attn", "mlp", "lm_head", "norm_rope", "sample")
UNSCOPED = "unscoped"
# host spans kept from the trace, innermost first (obs/tracing.span)
HOST_SPANS = ("fetch", "launch", "dispatch", "detokenize", "generate", "assemble", "retrieve")
NO_SPAN, SHORT_GAPS, SHORT_GAP_NS = trace.NO_SPAN, trace.SHORT_GAPS, trace.SHORT_GAP_NS
_CONTAINERS = trace._CONTAINERS  # events that only contain other events
_CONTAINER_OPCODES = ("while", "conditional", "call")


# ---------------------------------------------------------------------------
# protobuf, as far as needed: XSpace > XPlane > XEventMetadata > XStat (bytes)
# and HloProto > HloModuleProto > HloComputationProto > HloInstructionProto
# ---------------------------------------------------------------------------


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield num, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _packed(view) -> list:
    """The varints of a packed repeated field."""
    out, i = [], 0
    while i < len(view):
        value, i = _varint(view, i)
        out.append(value)
    return out


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def hlo_modules(xplane_path: str) -> dict:
    """``{"<module>(<program id>)": HloModuleProto bytes}`` from the trace's
    ``/host:metadata`` plane (XPlane.event_metadata[*].stats "Hlo Proto")."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:  # XSpace.planes
            continue
        name, metas = None, []
        for n, v in _fields(plane):
            if n == 2:
                name = _text(v)
                if name != "/host:metadata":
                    break
            elif n == 4:  # map<int64, XEventMetadata> entry
                metas.append(v)
        if name != "/host:metadata":
            continue
        for entry in metas:
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            module, proto = None, None
            for n, v in _fields(meta):
                if n == 2:  # XEventMetadata.name
                    module = _text(v)
                elif n == 5:  # XEventMetadata.stats: the one bytes stat is the HloProto
                    proto = dict(_fields(v)).get(6, proto)
            if module and proto is not None:
                out[module] = dict(_fields(proto)).get(1)  # HloProto.hlo_module
    return {k: v for k, v in out.items() if v is not None}


def scope_of(op_name: str):
    """``(phase, sub-scope)`` of a scope path: the first component that is a
    phase, and the first sub-scope after it ("" for none; the first, so that
    a module of the encoder that happens to be called ``attn`` stays inside
    ``retrieve/embed``)."""
    parts = (op_name or "").split("/")
    for i, part in enumerate(parts):
        if part in PHASES:
            return part, next((p for p in parts[i + 1:] if p in SUB_SCOPES), "")
    return UNSCOPED, ""


_ROWS = re.compile(r"^rows(\d+)$")


def one_loop_beneath(op_name: str):
    """``(phase, around, rows)`` of an operation traced with exactly one loop
    beneath its phase, in the loop's body and in no branch (the module
    docstring's unit): ``around`` says the phase was opened around that loop
    (a step loop), ``rows`` is the path's ``rows<N>`` or None. Else None."""
    parts = (op_name or "").split("/")
    for i, part in enumerate(parts):
        if part in PHASES:
            rest = parts[i + 1:]
            loops = [j for j, p in enumerate(rest) if p == "while"]
            if len(loops) != 1 or rest[loops[0] + 1:loops[0] + 2] != ["body"] or "cond" in rest:
                return None
            rows = [int(m.group(1)) for m in map(_ROWS.match, rest) if m]
            return part, loops[0] == 0, (rows[0] if rows else None)
    return None


def module_scopes(module_proto) -> dict:
    """``{instruction name: op_name}`` over the computations a program steps
    through, with the two rules of the module docstring applied to what the
    compiler wrote. An instruction with no scope maps to ""."""
    comps = {}  # id -> [(name, opcode, op_name, [called ids])]
    entry_id = None
    for num, comp in _fields(module_proto):
        if num == 6:
            entry_id = comp
        if num != 3:  # HloModuleProto.computations
            continue
        cid, rows = None, []
        for n, v in _fields(comp):
            if n == 5:
                cid = v
            elif n == 2:  # HloComputationProto.instructions
                name = opcode = op_name = ""
                called = []
                for k, x in _fields(v):
                    if k == 1:
                        name = _text(x)
                    elif k == 2:
                        opcode = _text(x)
                    elif k == 7:  # OpMetadata.op_name
                        op_name = _text(dict(_fields(x)).get(2, b""))
                        if "/" not in op_name:
                            # an argument's name, which a relayout of the
                            # argument inherits: no path the program traced
                            op_name = ""
                    elif k == 38:  # called_computation_ids, packed or one by one
                        called += [x] if isinstance(x, int) else _packed(x)
                rows.append((name, opcode, op_name, called))
        comps[cid] = rows

    def carried(cid, seen=()):
        """Scope paths the instructions of a called computation carry."""
        out = []
        for _, _, op_name, called in comps.get(cid, ()):
            if scope_of(op_name)[0] != UNSCOPED:
                out.append(op_name)
            for c in called:
                if c not in seen:
                    out += carried(c, seen + (cid,))
        return out

    scopes, todo, done = {}, [entry_id], set()
    while todo:
        cid = todo.pop()
        if cid in done or cid not in comps:
            continue
        done.add(cid)
        own = {}
        for name, opcode, op_name, called in comps[cid]:
            if opcode in _CONTAINER_OPCODES:
                todo += called
            elif not op_name and called:
                inner = carried(called[0])
                if inner:  # a fusion takes the scope most of its instructions carry
                    keys = [scope_of(p) for p in inner]
                    best = max(set(keys), key=keys.count)
                    op_name = inner[keys.index(best)]
            own[name] = op_name
        phases = {scope_of(p)[0] for p in own.values()} - {UNSCOPED}
        if len(phases) == 1:  # one phase's computation: what the compiler added is its too
            phase = phases.pop()
            own = {k: v or phase for k, v in own.items()}
        scopes.update({k: (v if scope_of(v)[0] != UNSCOPED else "") for k, v in own.items()})
    return scopes


# ---------------------------------------------------------------------------
# the trace file -> plain data
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^%?([^\s=]+)")


def load(xplane_path: str) -> dict:
    """Device 0's operations and module runs, the scopes of the modules that
    ran, and the program's host spans. Without a ``/device:TPU`` plane (the
    CPU rehearsal) the XLA:CPU runtime's operation events stand in, from
    whichever of its threads ran them, each one naming its module in its
    stats (a fourth field of the operation; no module runs are needed then).
    Threads overlap, so a rehearsal's seconds are not self times: it walks
    the readers, it measures nothing."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    modules, ops, host = [], [], []
    device = next((p for p in data.planes if p.name == "/device:TPU:0"), None)
    if device is not None:
        for line in device.lines:
            if line.name == "XLA Modules":
                modules = [[ev.name, float(ev.start_ns), float(ev.duration_ns)] for ev in line.events]
            elif line.name == trace.OPS_LINE:
                ops = [[trace.op_label(ev.name), float(ev.start_ns), float(ev.duration_ns)]
                       for ev in line.events]
    keep = set(HOST_SPANS)
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if device is None and line.name.startswith("tf_XLA"):
                for ev in line.events:
                    st = dict(ev.stats) if ev.duration_ns > 0 else {}
                    if "hlo_module" in st:
                        ops.append([ev.name, float(ev.start_ns), float(ev.duration_ns),
                                    f"{st['hlo_module']}({st.get('program_id', 0)})"])
            else:
                host += [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                         for ev in line.events if ev.name in keep]
    ran = {m[0] for m in modules} | {op[3] for op in ops if len(op) > 3}
    scopes = {name: module_scopes(proto) for name, proto in hlo_modules(xplane_path).items()
              if name in ran}
    return {"modules": modules, "ops": ops, "scopes": scopes, "host": host}


# ---------------------------------------------------------------------------
# plain data -> numbers
# ---------------------------------------------------------------------------


def attribute_gaps(busy: list, spans: list, t0: float, t1: float) -> dict:
    """Idle seconds by the innermost host span that covers most of each gap
    (``lib/trace.attribute_gaps`` with this file's spans)."""
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    out = {}
    for i in range(0, len(edges), 2):
        g0, g1 = edges[i], edges[i + 1]
        if g1 <= g0:
            continue
        if g1 - g0 < SHORT_GAP_NS:
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0.0) + (g1 - g0)
            continue
        best, best_cover = NO_SPAN, 0.0
        for name in HOST_SPANS:
            cover = sum(max(0.0, min(s + d, g1) - max(s, g0)) for n, s, d in spans if n == name)
            if cover > best_cover * 1.001:  # the innermost span wins a tie
                best, best_cover = name, cover
        out[best] = out.get(best, 0.0) + (g1 - g0)
    return {k: v / 1e9 for k, v in out.items()}


def reduce_phases(data: dict, layer_loop_trips: int, top: int = 10) -> dict:
    """Self time of every leaf operation filed under its scope, and what the
    programs themselves say to divide it by (the module docstring)."""
    runs = sorted(data["modules"], key=lambda m: m[1])
    starts = [m[1] for m in runs]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][0] if i >= 0 and t < runs[i][1] + runs[i][2] else None

    keyed = []  # the operation's scope rides in its name through self_times
    looped = {}  # (module, phase, around, rows) -> {instruction, one loop beneath: executions}
    calls = {}  # (phase, sub, kernel, type) -> calls of a Mosaic kernel
    for label, start, dur, *named in data["ops"]:
        instr = _INSTR.match(label).group(1)
        module = named[0] if named else module_at(start)
        op_name = data["scopes"].get(module, {}).get(instr, "")
        phase, sub = scope_of(op_name)
        typ = label.split(" ")[1] if " " in label else ""
        group = f"{trace.op_group(label)} {typ}".strip()
        container = bool(_CONTAINERS.match(label))
        keyed.append([(phase, sub, group, container), start, dur])
        unit = None if container else one_loop_beneath(op_name)
        if unit:
            per = looped.setdefault((module, *unit), {})
            per[instr] = per.get(instr, 0) + 1
        if trace.is_mosaic(label):
            key = (phase, sub, trace.op_group(label), typ)
            calls[key] = calls.get(key, 0) + 1
    leaf = {}
    for k, sec in trace.self_times(keyed).items():
        if not k[3]:
            leaf[k[:3]] = leaf.get(k[:3], 0.0) + sec
    total = sum(leaf.values())
    by_phase, by_scope = {}, {}
    for (phase, sub, _), sec in leaf.items():
        by_phase[phase] = by_phase.get(phase, 0.0) + sec
        path = f"{phase}/{sub}" if sub else phase
        by_scope[path] = by_scope.get(path, 0.0) + sec
    steps, prefill_rows = {}, 0.0
    for (_, phase, around, rows), per in looped.items():
        n = statistics.median(per.values())
        if around:
            steps[phase] = steps.get(phase, 0.0) + n
        elif phase == "prefill" and rows:
            prefill_rows += rows * n / layer_loop_trips
    spans = data["host"]
    busy = trace.union([op[1], op[1] + op[2]] for op in data["ops"])
    everything = data["ops"] + spans
    t0 = min(ev[1] for ev in everything)
    t1 = max(ev[1] + ev[2] for ev in everything)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "seconds": {k: v for k, v in by_time(by_phase)},
        "seconds_by_scope": {k: v for k, v in by_time(by_scope)},
        "leaf_self_s": total,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "unscoped_share": by_phase.get(UNSCOPED, 0.0) / total if total else None,
        "top_ops": [["/".join(x for x in k[:2] if x) + "/" + k[2], sec]
                    for k, sec in by_time(leaf)[:top]],
        "steps": steps,  # passes of each phase's loop
        "prefill_rows": prefill_rows,  # rows of each pass, by the layers of it that ran
        # a request's retrieval is over when its ``retrieve`` span closes; the
        # device work it paid for lies inside the span, so both are counted in
        # the same slice
        "retrievals": sum(1 for sp in spans if sp[0] == "retrieve"),
        # information: which consumer each Mosaic kernel served
        "kernel_calls": {"/".join(x for x in k[:2] if x) + f"/{k[2]} {k[3]}": n
                         for k, n in sorted(calls.items(), key=lambda kv: -kv[1])},
        "idle_gaps": {k: v for k, v in by_time(attribute_gaps(busy, spans, t0, t1))},
    }


def ms_per(seconds, count):
    """Milliseconds per counted unit; None where the slice holds no such
    time or nothing to count it by."""
    if not seconds or not count:
        return None
    return seconds / count * 1e3


def step_ms(reduced, phase: str):
    """Device milliseconds of one step of a loop phase, or None."""
    if reduced is None:
        return None
    return ms_per(reduced["seconds"].get(phase), reduced["steps"].get(phase))


def of(ctx):
    """The traced run's phases, reduced once for all the readers (kept in the
    ``ctx`` they share); None where there is no trace. Prints the ``phases``
    information line on first use."""
    if ctx.get("trace") is None:
        return None
    if "phases" not in ctx:
        from benchmark.lib import serve

        t0 = time.monotonic()
        path = trace.find_xplane(os.path.join(serve.STATE_DIR, "trace"))
        reduced = reduce_phases(load(path), int(ctx["layer_loop_trips"]))
        reduced["seconds_to_reduce"] = round(time.monotonic() - t0, 1)
        print(json.dumps({"event": "phases", **reduced}), flush=True)
        ctx["phases"] = reduced
    return ctx["phases"]

"""Request-scoped tracing: contextvar-propagated span trees per request.

Every ``/generate`` request gets a trace id and a tree of stage spans
(tokenize → retrieve-coalesce wait → fused embed+kNN → prefix resolve →
prefill+decode → detokenize). Spans are recorded in the request thread at
the same boundaries the response's ``timings`` block is measured at, so
the span durations and the timings agree by construction (the acceptance
contract: top-level spans sum to within 5% of ``timings.total_ms``).

Where a stage runs as ONE fused device program (the whole generate loop is
a single executable — by design, see engine/engine.py), the host cannot
observe finer structure wall-clock. Every device program launched for
``/generate`` therefore keeps a record of its own (``dispatch_record``): a
``dispatch`` span with the children ``launch`` (host preparation up to the
enqueue), ``fetch`` (the blocking device→host read of the output tokens) and
``deliver`` (the EOS trim, the stats and goodput folds, and on the batched
path the release of every rider), behind a ``gather`` span where a scheduler
coalesced it (the window, from the first request in hand to the last
aboard). On a request thread (``fused``, ``prefixed``, ``direct``) the
``dispatch`` sits under the request's ``generate``; on the scheduler's worker
(``batched``), where no request's trace is current, it is a tree of its own in
a second ring (``GET /debug/traces?kind=dispatch``) that names its riders'
trace ids, and each rider's ``generate`` span carries its ``seq``. In every
run, traced or not, its four stages (``gather``, ``launch``, ``device`` = the
end of ``launch`` to the end of ``fetch``, ``deliver``) feed
``rag_generate_dispatch_stage_seconds{path, stage}`` and the response's
``timings`` (``dispatch_seq``, ``dispatch_rows``, ``queue_wait_ms``,
``launch_ms``, ``device_ms``, ``deliver_ms``). The retrieve coalescer's worker
opens ``retrieve_batch`` around its one batched call. The interior of a
program is named on the DEVICE's clock instead:

- every span body is wrapped in ``jax.profiler.TraceAnnotation``, so an
  xprof capture (``/profile``) shows the named stages on the host timeline
  next to the device's;
- every operation of a compiled program carries a phase from ``PHASES``
  (and a sub-scope from ``SUB_SCOPES``) in its ``op_name``: ``phase_scope``
  below is opened where the programs are traced (engine/engine.py,
  models/llama.py, models/bge_m3.py, ops/knn.py, engine/continuous.py), so
  the capture's operations read ``…/decode/attn/…`` rather than
  ``fusion.335``. A scope is op metadata: it adds no operation and costs
  nothing at run time. The persistent compile cache does not key on
  metadata, so a change to scopes alone needs a fresh cache directory to
  show (docs/OBSERVABILITY.md).

(``rag_time_to_first_token_seconds`` / ``rag_decode_inter_token_seconds``
are fed by the continuous engine only; in the one-shot shape they stay
empty — no first token is visible to the host there.)

Finished traces are emitted as structured JSON logs (logger
``rag_llm_k8s_tpu.trace``, DEBUG) and kept in an in-memory ring buffer
served by ``GET /debug/traces``; a client posting ``{"trace": true}`` gets
its own tree inline in the response.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

logger = logging.getLogger("rag_llm_k8s_tpu.trace")

try:  # device-timeline names for xprof captures; absent off-JAX is fine
    import jax
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # noqa: BLE001 — tracing must work without jax
    jax = None
    _TraceAnnotation = None

# The one vocabulary of scopes inside compiled programs. A phase is what a
# device program is doing for a request; a sub-scope is which part of the
# model does it. ``score`` is the exact-path audit scorer (never serving
# work); ``mixed`` is the continuous engine's window, whose prefill and
# decode lanes share operations and cannot be told apart inside one.
PHASES = ("retrieve", "prefill", "decode", "verify", "sample", "score", "mixed")
SUB_SCOPES = ("embed", "knn", "attn", "mlp", "lm_head", "norm_rope")
# opened BENEATH a sub-scope by the latent-attention sparse-expert family
# (models/latent_moe.py): ``attn/latent`` (the attention over the latent
# cache itself, its absorbing matmuls included), ``mlp/router``,
# ``mlp/experts`` (gather, grouped matmuls, combine), ``mlp/shared``,
# ``mlp/zero`` (the zero-computation experts' term) and ``mlp/dense`` (a
# dense SwiGLU: a leading dense layer's, a shortcut-connected layer's two).
# The windowed-attention family (models/windowed_moe.py) opens ``attn/window``
# and ``attn/global`` around the attention of a sliding and of a full layer
# (kernel or XLA form, nothing else), ``attn/gate`` around the per-head output
# gate, and the ``mlp/*`` scopes above. The block-window family
# (models/block_window.py) opens ``attn/ring`` around attention over a
# window's exact keys and the pooled summaries of the windows before it (one
# softmax, one call) and ``attn/pool`` around the pooling of chunks into
# summaries. The hybrid state-space family (models/hybrid_ssm.py) opens
# ``attn/scan`` around a state layer's selective scan (the kernel or the XLA
# form; in a decode step the one-position update and the contraction with C),
# ``attn/conv`` around its causal convolution and the roll of that state, and
# ``attn/global`` around its attention layers' attention. The gated
# delta-rule family (models/delta_moe.py) opens ``attn/kda`` around a
# linear-attention layer's mixer, and beneath it ``attn/kda/conv`` (the causal
# convolution of q, k and v), ``attn/kda/gate`` (the decay's, beta's and the
# output gate's projections) and ``attn/kda/delta`` (the recurrence: the chunk
# form, the single-token step, a verify step's chunk and ``commit``'s
# replay); its full layers open ``attn/latent`` and its FFNs ``mlp/*``. The
# decoder-hybrid-decoder family (models/cross_decoder.py) opens ``attn/scan``,
# ``attn/conv``, ``attn/window`` and ``attn/global`` as above, ``attn/cross``
# around a cross-attention layer's attention over the shared plane,
# ``attn/gmu`` around a gated memory unit, ``attn/diff`` around differential
# attention's subtraction, lambda and norm, and ``cross`` ABOVE the sub-scopes
# around its cross-decoder half (the layers that own no state). The
# state-space-duality family (models/ssd_moe.py) opens ``attn/conv`` and
# ``attn/global`` as above, ``attn/ssd`` around a Mamba-2 layer's recurrence
# (the chunked matmul form, the single-position step, a verify step's chunk
# and ``commit``'s replay: other work than ``scan``'s, so another name),
# ``attn/gate`` around the gated group norm, and under ``mlp`` ``router``,
# ``experts``, ``shared`` and ``latent`` (``mlp/latent``: the two projections
# between the stream and the experts' latent; ``attn/latent`` is latent
# ATTENTION, and the path tells them apart). A
# reader that files an operation under the first sub-scope it knows keeps
# reading ``attn`` and ``mlp``; one that knows these sees the finer split.
FINE_SCOPES = ("latent", "router", "experts", "shared", "zero", "dense", "window", "global", "gate",
               "ring", "pool", "scan", "conv", "kda", "delta", "cross", "gmu", "diff", "ssd")
SCOPE_NAMES = frozenset(PHASES + SUB_SCOPES + FINE_SCOPES)


def phase_scope(path: str, rows: Optional[int] = None):
    """``jax.named_scope(path)`` for a name of the vocabulary, or several
    joined by ``/`` (``retrieve/embed``); any other name raises, so a trace
    never grows a scope its readers do not know. Usable as a context manager
    or as a decorator of the function whose operations it names.

    ``rows`` (a generate program's ``prefill``) adds the component
    ``rows<N>``: the batch the executable was built for, stated where every
    operation of it carries it, so a capture that cuts a program anywhere
    still says how many prompts its prefill served."""
    unknown = [n for n in path.split("/") if n not in SCOPE_NAMES]
    if unknown:
        raise ValueError(
            f"scope {unknown[0]!r} is not in the vocabulary {sorted(SCOPE_NAMES)}"
        )
    return jax.named_scope(path if rows is None else f"{path}/rows{int(rows)}")


# Which attention kernel ``models/llama.py attend`` built into a program, by the
# mode it served (prefill | decode | chunk). The choice is made where a
# program is TRACED — by static shapes, once a compiled program — so this
# counts traces and costs a dispatch nothing. Process-wide, like the jit
# caches the programs live in; ``/metrics`` serves it as
# ``rag_attend_kernel_builds_total{mode, kernel}``.
_kernel_builds: Dict[Tuple[str, str], int] = {}
_kernel_builds_lock = threading.Lock()


def count_kernel_build(mode: str, kernel: str, n: int = 1) -> None:
    """Called where a program is traced. Inside a ``build_span`` the increment
    is also noted on the build: an executable kept in the store carries the
    increments its trace made, and a later boot that loads it replays them
    (``n``), so the counter reads the same warm as cold."""
    with _kernel_builds_lock:
        _kernel_builds[(mode, kernel)] = _kernel_builds.get((mode, kernel), 0) + n
    traced = getattr(_building, "kernels", None)
    if traced is not None:
        traced[(mode, kernel)] = traced.get((mode, kernel), 0) + n


def kernel_builds() -> Dict[Tuple[str, str], int]:
    with _kernel_builds_lock:
        return dict(_kernel_builds)


_IMPORTED_AT = time.time()


def process_start_time() -> float:
    """Epoch seconds at which this process started: its age by /proc (Linux),
    elsewhere this module's import, which is later."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])  # starttime
        with open("/proc/uptime", encoding="ascii") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _IMPORTED_AT


# Every executable the program builds, by what it is for. One name a builder
# (engine/engine.py ``_build_*``, server/app.py's fused embed+kNN, the
# encoder's jit; the continuous engine's programs share one); ``undeclared``
# is what the listener below files a build under that no site declared.
BUILD_PROGRAMS = (
    "generate", "generate_spec", "generate_rag", "generate_prefixed",
    "segment_kv", "score_exact", "retrieve", "encode", "continuous",
    "undeclared",
)
BUILD_STAGES = ("trace", "lower", "compile", "other")

# The census of builds: seconds by (program, stage), executables by (program,
# cache outcome: hit | miss (compiled AND written) | off: not asked, or too
# quick to keep). Process-wide as ``_kernel_builds`` is, so the weights' jits
# count before any service exists; ``/metrics`` serves it as
# ``rag_compile_seconds_total{program, stage}`` and
# ``rag_compile_events_total{program, cache}``.
_compile_seconds: Dict[Tuple[str, str], float] = {}
_compile_events: Dict[Tuple[str, str], int] = {}
_census_lock = threading.Lock()
# this thread's open build: ``stage`` is the stage ``build_span`` is in (None
# outside a build), ``cache`` what the persistent cache last answered here
_building = threading.local()


def compile_census() -> Tuple[Dict[Tuple[str, str], float], Dict[Tuple[str, str], int]]:
    """``(seconds by (program, stage), executables by (program, cache))``."""
    with _census_lock:
        return dict(_compile_seconds), dict(_compile_events)


def _count_build(program: str, seconds: Dict[str, float], cache=False) -> None:
    """Add ``seconds`` by stage, and one executable unless ``cache`` is False
    (``hit`` | ``miss`` | None: the persistent cache said neither)."""
    with _census_lock:
        for stage, s in seconds.items():
            _compile_seconds[(program, stage)] = _compile_seconds.get((program, stage), 0.0) + s
        if cache is not False:
            key = (program, cache or "off")
            _compile_events[key] = _compile_events.get(key, 0) + 1


def build_span(program: str, key, make, identity=None, **ints):
    """Build one executable where every site that builds one does: ``make()``
    gives ``(jitted, avals)``, and ``jitted.trace(*avals).lower().compile()``
    runs under a span ``build/<program>`` with each stage on the host's
    monotonic clock. The span (on the current trace if there is one, and a
    ``TraceAnnotation`` either way, so a build inside a capture sits on its
    host timeline) carries ``trace_s``, ``lower_s``, ``compile_s``, ``other_s``
    (its wall time less the three: ``make()``, the store's key and write),
    ``cache_hit`` (2 where the executable store held it | 1 | 0 | -1 where the
    persistent cache said neither) and ``ints`` (the key's ``rows``, ``bucket``,
    ``max_new``); the census counts the same. ``program`` is a name of
    ``BUILD_PROGRAMS``; any other raises.

    ``identity`` is what the site's traced function closes over (its
    configuration objects, by ``repr``). With it, and where a compile cache
    directory is placed, the build is keyed WITHOUT tracing
    (``core/compile_cache.py entry_for``): an entry found is loaded, nothing
    is traced, lowered or compiled (``trace_s = lower_s = 0``, the read under
    ``compile_s``, outcome ``stored``) and the ``count_kernel_build``
    increments its original trace made are replayed; an entry not found is
    built as ever and kept. Without it the build is as it always was."""
    if program not in BUILD_PROGRAMS or program == "undeclared":
        raise ValueError(f"build program {program!r} is not in the vocabulary {BUILD_PROGRAMS}")
    from rag_llm_k8s_tpu.core import compile_cache  # imports jax, which this module may lack

    outer = getattr(_building, "stage", None), getattr(_building, "kernels", None)
    with span(f"build/{program}", **ints) as sp:
        t0 = time.monotonic()
        try:
            staged, avals = make()
            entry = compile_cache.entry_for(program, key, avals, identity)
            _building.stage, _building.cache, _building.kernels = "compile", None, None
            t = time.monotonic()
            held = entry.load() if entry is not None else None
            if held is not None:  # the read is the whole build
                staged, cache = held[0], "stored"
                for mode, kernel, n in held[1]:
                    count_kernel_build(mode, kernel, n)
                secs = {"trace": 0.0, "lower": 0.0, "compile": time.monotonic() - t}
            else:
                _building.kernels, secs = {}, {}
                for stage in ("trace", "lower", "compile"):
                    t = time.monotonic()
                    _building.stage, _building.cache = stage, None
                    lowered = staged  # after the loop: what ``compile`` was called on
                    staged = staged.trace(*avals) if stage == "trace" else getattr(staged, stage)()
                    secs[stage] = time.monotonic() - t
                cache = _building.cache
                if entry is not None:
                    entry.save(staged, compile_cache.lowered_text_sha256(lowered),
                               [(m, k, n) for (m, k), n in _building.kernels.items()])
        finally:
            _building.stage, _building.kernels = outer
        secs["other"] = time.monotonic() - t0 - sum(secs.values())
        _count_build(program, secs, cache)
        if sp is not None:
            sp.attrs.update({f"{k}_s": v for k, v in secs.items()})
            sp.attrs["cache_hit"] = {"stored": 2.0, "hit": 1.0, "miss": 0.0}.get(cache, -1.0)
    logger.debug("build %s %r: %s cache=%s", program, key, secs, cache)
    return staged


_CACHE_ANSWERS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def _on_cache_answer(event: str, **_) -> None:
    answer = _CACHE_ANSWERS.get(event)
    if answer is not None:  # the compile that asked ends on this thread
        _building.cache = answer


def _on_stage_seconds(event: str, seconds: float, fun_name: str = "", **_) -> None:
    """A lowering or a backend compile (or cache read) that ``build_span`` is
    not clocking on this thread is a lazy ``jax.jit`` nobody declared: the
    census files it under ``undeclared``. Its ``fun_name`` goes to the log and
    not into a label, so the families stay bounded. (``jaxpr_trace_duration``
    events nest, an outer trace holding its inner jits', and are not summed.)"""
    stage = _STAGE_EVENTS.get(event)
    if stage is None or getattr(_building, "stage", None) == stage:
        return
    _count_build("undeclared", {stage: seconds},
                 getattr(_building, "cache", None) if stage == "compile" else False)
    _building.cache = None
    logger.debug("undeclared %s of %s: %.3f s", stage, fun_name, seconds)


if jax is not None:  # once a process, where the program is first imported
    jax.monitoring.register_event_listener(_on_cache_answer)
    jax.monitoring.register_event_duration_secs_listener(_on_stage_seconds)


@dataclass
class Span:
    name: str
    start_s: float  # monotonic
    end_s: Optional[float] = None
    parent: Optional[int] = None  # index into Trace.spans
    attrs: Dict[str, float] = field(default_factory=dict)

    def duration_ms(self) -> float:
        return ((self.end_s if self.end_s is not None else self.start_s)
                - self.start_s) * 1e3


class Trace:
    """One request's span tree. NOT thread-safe on purpose: a trace belongs
    to the request thread that started it (contextvar propagation); stages
    that run on worker threads are accounted for by the request-thread span
    that waits on them (e.g. retrieve-coalesce wait)."""

    def __init__(self, trace_id: Optional[str] = None,
                 parent_span_id: Optional[str] = None):
        # W3C trace-context width (32 lowercase hex — uuid4().hex exactly):
        # the id round-trips through a ``traceparent`` header unchanged, so
        # a UI-originated trace and the server's span tree correlate. The
        # server-side span id identifies THIS hop (obs/logging.py emits it
        # on every structured log line and in the response traceparent).
        self.trace_id = trace_id or uuid.uuid4().hex
        self.span_id = uuid.uuid4().hex[:16]
        self.parent_span_id = parent_span_id
        self.started_at = time.time()
        self.t0 = time.monotonic()
        self.end_s: Optional[float] = None
        self.spans: List[Span] = []
        self._stack: List[int] = []  # open span indices (nesting)
        self.attrs: Dict[str, object] = {}

    # -- recording -------------------------------------------------------
    def begin(self, name: str) -> int:
        parent = self._stack[-1] if self._stack else None
        self.spans.append(Span(name, time.monotonic(), parent=parent))
        idx = len(self.spans) - 1
        self._stack.append(idx)
        return idx

    def end(self, idx: int) -> None:
        self.spans[idx].end_s = time.monotonic()
        if self._stack and self._stack[-1] == idx:
            self._stack.pop()

    def add_span(self, name: str, start_s: float, duration_s: float,
                 parent: Optional[int] = None, **attrs) -> int:
        """Record an already-measured interval (e.g. the tokenize share a
        coalesced worker measured and returned as a number) as a span."""
        if parent is None and self._stack:
            parent = self._stack[-1]
        sp = Span(name, start_s, start_s + duration_s, parent=parent)
        sp.attrs.update({k: float(v) for k, v in attrs.items()})
        self.spans.append(sp)
        return len(self.spans) - 1

    # -- export ----------------------------------------------------------
    def total_ms(self) -> float:
        end = self.end_s if self.end_s is not None else time.monotonic()
        return (end - self.t0) * 1e3

    def to_dict(self) -> Dict:
        children: Dict[Optional[int], List[int]] = {}
        for i, sp in enumerate(self.spans):
            children.setdefault(sp.parent, []).append(i)

        def node(i: int) -> Dict:
            sp = self.spans[i]
            d = {
                "name": sp.name,
                "start_ms": round((sp.start_s - self.t0) * 1e3, 3),
                "duration_ms": round(sp.duration_ms(), 3),
            }
            if sp.attrs:
                d["attrs"] = {k: round(v, 3) for k, v in sp.attrs.items()}
            kids = [node(j) for j in children.get(i, [])]
            if kids:
                d["spans"] = kids
            return d

        out = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "started_at": self.started_at,
            "total_ms": round(self.total_ms(), 3),
            "spans": [node(i) for i in children.get(None, [])],
        }
        if self.parent_span_id:
            out["parent_span_id"] = self.parent_span_id
        if self.attrs:
            out["attrs"] = self.attrs
        return out


_current: "contextvars.ContextVar[Optional[Trace]]" = contextvars.ContextVar(
    "rag_trace", default=None
)


def current_trace() -> Optional[Trace]:
    return _current.get()


def start_trace(trace_id: Optional[str] = None,
                parent_span_id: Optional[str] = None) -> Trace:
    """Open a trace on this thread; pair with ``finish_trace``.
    ``trace_id``/``parent_span_id`` come from an incoming W3C
    ``traceparent`` header when the request carried one
    (obs/logging.py:parse_traceparent)."""
    tr = Trace(trace_id, parent_span_id=parent_span_id)
    _current.set(tr)
    return tr


def finish_trace(tr: Trace, buffer: "Optional[TraceBuffer]" = None) -> Dict:
    """Close the trace: close dangling spans, emit the structured JSON log,
    push into the ring buffer, clear the contextvar. Returns the tree."""
    now = time.monotonic()
    tr.end_s = now
    for idx in reversed(tr._stack):  # an exception can leave spans open
        if tr.spans[idx].end_s is None:
            tr.spans[idx].end_s = now
    tr._stack.clear()
    if _current.get() is tr:
        _current.set(None)
    tree = tr.to_dict()
    if logger.isEnabledFor(logging.DEBUG):
        logger.debug("%s", json.dumps(tree, separators=(",", ":")))
    if buffer is not None:
        buffer.add(tree)
    return tree


@contextmanager
def span(name: str, **attrs):
    """Record a stage span on the current trace (no-op cost when no trace
    is active beyond the TraceAnnotation), and name the wrapped device work
    on the xprof timeline either way."""
    tr = _current.get()
    idx = None
    if tr is not None:
        idx = tr.begin(name)
        if attrs:
            tr.spans[idx].attrs.update({k: float(v) for k, v in attrs.items()})
    ann = _TraceAnnotation(name) if _TraceAnnotation is not None else None
    if ann is not None:
        ann.__enter__()
    try:
        yield tr.spans[idx] if idx is not None else None
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        if tr is not None and idx is not None:
            tr.end(idx)


def annotate(name: str):
    """A bare ``TraceAnnotation``: a name on the profiler's host timeline for
    an interval that ends before the tree it belongs to begins (the
    scheduler's ``gather``, recorded afterwards with ``add_span``) or that has
    no tree (the coalescer worker's ``retrieve_batch``)."""
    return _TraceAnnotation(name) if _TraceAnnotation is not None else nullcontext()


DISPATCH_STAGES = ("gather", "launch", "device", "deliver")
_dispatch_seq = itertools.count(1)  # process-wide: one number a dispatch


class DispatchSink(NamedTuple):
    """Where a service keeps its dispatches: the family
    ``rag_generate_dispatch_stage_seconds{path, stage}`` and the ring behind
    ``GET /debug/traces?kind=dispatch``. ``RagService`` hands one to its
    ``_dispatch`` and to the ``BatchScheduler`` it was given."""

    stage_seconds: object  # obs.metrics labeled histogram family
    ring: "TraceBuffer"


class DispatchRecord:
    """One dispatch's clock, as ``dispatch_record`` yields it. ``launch_s``,
    ``device_s`` and ``fetch_end`` hold once ``settle()`` has read the
    dispatch span's children (the scheduler calls it before it releases a
    rider, who reads them); ``deliver_s`` once the dispatch has exited."""

    __slots__ = ("seq", "rows", "gather_s", "launch_s", "device_s", "deliver_s",
                 "fetch_end", "built", "_trace", "_idx")
    # the keys of ``link()``: the response's ``timings`` take them as they are,
    # the request's ``generate`` span without the ``dispatch_`` prefix
    LINK_KEYS = ("dispatch_seq", "dispatch_rows", "queue_wait_ms", "launch_ms",
                 "device_ms", "deliver_ms")

    def __init__(self, rows: int, gather_s: float, trace: Trace, idx: int):
        self.seq = next(_dispatch_seq)
        self.rows, self.gather_s = int(rows), float(gather_s)
        self.launch_s = self.device_s = self.deliver_s = 0.0
        self.fetch_end: Optional[float] = None
        self.built = False
        self._trace, self._idx = trace, idx

    def _children(self, name: str) -> List[Span]:
        return [sp for sp in self._trace.spans[self._idx + 1:]
                if sp.parent == self._idx and sp.name == name and sp.end_s is not None]

    def settle(self) -> None:
        """``launch`` = the dispatch's start to the end of its first ``launch``
        span, ``device`` = from there to the end of its last ``fetch``. An
        engine call that raised before either ended leaves the time so far
        under the stage it was in and nothing under the later ones."""
        if self.fetch_end is not None:
            return
        start = self._trace.spans[self._idx].start_s
        launches, fetches = self._children("launch"), self._children("fetch")
        launch_end = launches[0].end_s if launches else time.monotonic()
        self.fetch_end = max(fetches[-1].end_s, launch_end) if fetches else launch_end
        self.launch_s, self.device_s = launch_end - start, self.fetch_end - launch_end

    def _close(self) -> None:
        self.settle()
        self.deliver_s = time.monotonic() - self.fetch_end
        self.built = any(sp.name.startswith("build/") for sp in self._trace.spans[self._idx + 1:])
        self._trace.spans[self._idx].attrs.update(seq=float(self.seq), built=float(self.built))

    def stages(self) -> Dict[str, float]:
        return dict(zip(DISPATCH_STAGES,
                        (self.gather_s, self.launch_s, self.device_s, self.deliver_s)))

    def link(self, queue_wait_s: float = 0.0) -> Dict[str, float]:
        """The request's side of the link, for its ``generate`` span and its
        response's ``timings``: which dispatch it rode, with how many rows,
        and its own four intervals. ``deliver_ms`` runs to the instant of this
        call on the caller's thread (a rider woken first waits less than the
        dispatch's ``deliver`` stage, one woken last as long)."""
        return dict(zip(self.LINK_KEYS, (
            float(self.seq), float(self.rows), queue_wait_s * 1e3, self.launch_s * 1e3,
            self.device_s * 1e3, (time.monotonic() - self.fetch_end) * 1e3)))


@contextmanager
def dispatch_record(path: str, rows: int, reason: Optional[str] = None,
                    gather_s: float = 0.0, riders: Sequence[str] = (),
                    sink: Optional[DispatchSink] = None):
    """Around one engine call that launches a device program for
    ``/generate``: the ``dispatch`` span, on the thread's current trace where
    there is one; where there is none (the scheduler's worker) on a trace of
    its own, begun ``gather_s`` ago with the ``gather`` span that ended before
    it, carrying ``seq``, ``path``, ``rows``, ``reason``, ``riders`` (the trace
    ids of the requests aboard) and ``built`` (1 where a ``build/<program>``
    span fell inside: a cold shape met in serving), and finished into
    ``sink.ring``. On exit, raised or not, one sample a stage of
    ``DISPATCH_STAGES`` goes to ``sink.stage_seconds{path, stage}``: the four
    sum to the wall time from ``gather_s`` before the entry to the exit.
    Yields the ``DispatchRecord``."""
    tr = _current.get()
    own = tr is None
    if own:
        tr = start_trace()
        tr.t0 -= gather_s
        tr.started_at -= gather_s
        if gather_s > 0.0:
            tr.add_span("gather", tr.t0, gather_s)
    rec = DispatchRecord(rows, gather_s, tr, len(tr.spans))  # the span opened next
    try:
        with span("dispatch", rows=rows):
            try:
                yield rec
            finally:
                rec._close()
    finally:
        if sink is not None:
            for stage, seconds in rec.stages().items():
                sink.stage_seconds.labels(path=path, stage=stage).observe(seconds)
        if own:
            tr.attrs.update(
                kind="dispatch", seq=rec.seq, path=path, rows=int(rows), reason=reason,
                riders=list(riders), built=int(rec.built))
            finish_trace(tr, sink.ring if sink is not None else None)


class TraceBuffer:
    """Fixed-capacity ring of finished trace trees (``/debug/traces``)."""

    def __init__(self, capacity: int = 128):
        self._lock = threading.Lock()
        self._buf: "deque[Dict]" = deque(maxlen=capacity)

    def add(self, tree: Dict) -> None:
        with self._lock:
            self._buf.append(tree)

    def list(self, limit: Optional[int] = None) -> List[Dict]:
        """Newest-last. ``limit`` trims to the newest N; non-positive
        limits mean "no trim" (a negative slice would silently DROP the
        oldest entry instead)."""
        with self._lock:
            items = list(self._buf)
        return items[-limit:] if limit is not None and limit > 0 else items

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

"""Continuous request batching for the serving engine.

The reference serves strictly sequentially: a single-threaded Flask dev server
runs one ``model.generate`` at a time (/root/reference/llm/rag.py:204) — a
second concurrent user waits for the whole first generation. Here concurrent
requests coalesce into batched decodes (BASELINE.json config #5: "batched
concurrent /query requests"): a dispatcher thread drains the queue, groups
waiting requests up to the engine's batch cap, and runs them as ONE device
program — decode cost is dominated by weight reads from HBM, so a batch of 8
costs barely more than a batch of 1.

Requests submit from any thread and block on their own event; results fan
back out in submission order. Grouping respects ``max_new_tokens``/seed so
every request in a batch shares one executable.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.obs import tracing

logger = logging.getLogger(__name__)


def _join_worker(worker: threading.Thread, counter, what: str, timeout: float = 5.0):
    """Join a scheduler/coalescer worker, loudly: a worker that outlives the
    join window (wedged in a device call) used to vanish in silence — the
    drains still unblock every caller, but the leak should be visible on a
    dashboard (``rag_scheduler_join_timeouts_total``) and in the logs."""
    worker.join(timeout=timeout)
    if worker.is_alive():
        logger.warning(
            "%s worker still alive after join(%gs); queued callers have "
            "been failed fast but the worker thread may be wedged",
            what, timeout,
        )
        if counter is not None:
            counter.inc()


@dataclass
class _Pending:
    prompt: List[int]
    max_new: Optional[int]
    seed: Optional[int]
    done: threading.Event = field(default_factory=threading.Event)
    result: Optional[List[int]] = None
    error: Optional[BaseException] = None
    t_enqueue: float = field(default_factory=time.monotonic)  # wait anchor
    rows: int = 0  # size of the dispatch this request rode (set by the worker)
    wait_s: float = 0.0  # enqueue -> dispatch
    trace_id: Optional[str] = None  # the submitting request's, for the dispatch's riders
    record: Optional[tracing.DispatchRecord] = None  # the dispatch it rode (set by the worker)


@dataclass
class _PendingItem:
    value: object
    done: threading.Event = field(default_factory=threading.Event)
    result: object = None
    error: Optional[BaseException] = None
    t_enqueue: float = field(default_factory=time.monotonic)  # wait anchor


class Coalescer:
    """Generic blocking coalescer: concurrent ``submit(x)`` calls are grouped
    and served by ONE ``batch_fn([x, ...])`` call on a worker thread.

    This is the serving fix for the *retrieval* stage: without it, N
    concurrent queries dispatch N separate fused embed+kNN device calls that
    serialize on the device queue (and pay a device→host fetch each).
    Coalesced, the first query runs while the rest
    accumulate, and the entire remainder runs as one batched device call —
    the same continuous-batching effect the decode path already gets from
    :class:`BatchScheduler`, applied to embed+kNN.

    ``max_wait_ms`` can stay tiny (even 0): while the worker is busy with one
    batch, later arrivals queue up and form the next batch naturally.

    ``pending_hint`` (optional, settable after construction): a callable
    returning how many requests are currently in flight toward this stage.
    When set, the drain loop stops waiting as soon as every in-flight
    request has joined the batch — a solo query pays ~ the small
    ``hint_grace_ms`` instead of the full window, while a burst still
    coalesces fully. The grace exists because the hint counts only
    requests that have ENTERED the serving pipeline: a cold burst's
    stragglers may still be in HTTP parsing when the first request's
    batch forms, and trusting a hint of 1 instantly would re-create the
    batch-of-1 burst regression the window prevents. The window deadline
    stays the upper bound (a hinted request that errors before submitting
    just costs the old fixed wait).
    """

    def __init__(
        self, batch_fn, max_batch: int, max_wait_ms: float = 2.0, pending_hint=None,
        hint_grace_ms: float = 4.0,
    ):
        self.batch_fn = batch_fn
        self.stage = "retrieve"  # the counters' label, and the worker's ``<stage>_batch`` span
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.pending_hint = pending_hint
        self.hint_grace_ms = hint_grace_ms
        # optional obs Histogram (settable after construction, like
        # pending_hint): per-item enqueue→dispatch wait — the coalesce
        # window's real cost per request on a dashboard
        self.wait_histogram = None
        # optional obs Counter — shutdown join timeouts (see _join_worker)
        self.join_timeout_counter = None
        # optional obs counter FAMILIES (settable after construction), as
        # BatchScheduler's: rag_coalesce_dispatch_rows_total{stage, rows}
        # counts the items of each batch by its size, and
        # rag_coalesce_dispatch_reason_total{stage, reason} why the drain
        # loop stopped ("full" | "hint" | "deadline")
        self.dispatch_counter = None
        self.reason_counter = None
        self._queue: "queue.Queue[_PendingItem]" = queue.Queue()
        self._stop = threading.Event()
        self._lifecycle_lock = threading.Lock()
        self._worker = threading.Thread(target=self._run, daemon=True, name="coalescer")
        self._worker.start()

    def submit(self, value, timeout: Optional[float] = None):
        item = _PendingItem(value=value)
        with self._lifecycle_lock:  # stop-check + enqueue must be atomic
            if self._stop.is_set():
                raise RuntimeError("coalescer is shut down")
            self._queue.put(item)
        if not item.done.wait(timeout):
            raise TimeoutError("coalesced call timed out")
        if item.error is not None:
            raise item.error
        return item.result

    def shutdown(self):
        self._stop.set()
        self._queue.put(None)
        _join_worker(self._worker, self.join_timeout_counter, "coalescer")

    def _run(self):
        try:
            while not self._stop.is_set():
                first = self._queue.get()
                if first is None:
                    continue
                batch = [first]
                # absolute deadline: the window bounds the FIRST item's wait;
                # a per-get timeout would reset on every arrival and stretch
                # the worst case to (max_batch-1) x window under trickle load
                now = time.monotonic()
                deadline = now + self.max_wait_ms / 1e3
                hint_from = now + min(self.hint_grace_ms, self.max_wait_ms) / 1e3
                reason = "full"  # why the drain stops, unless a break says otherwise
                while len(batch) < self.max_batch:
                    hint = self.pending_hint
                    now = time.monotonic()
                    if (
                        hint is not None and now >= hint_from
                        and len(batch) >= hint()
                    ):
                        # everything in flight toward this stage is already
                        # aboard — waiting longer can only add latency. The
                        # grace window has passed, so a cold burst's
                        # stragglers have had time to register themselves.
                        reason = "hint"
                        break
                    # with a hint, sleep only until the grace boundary first
                    # — a timeout there re-evaluates the hint, not the batch
                    wait_until = (
                        hint_from if hint is not None and now < hint_from
                        else deadline
                    )
                    remaining = wait_until - now
                    try:
                        # past the deadline, still DRAIN whatever is already
                        # queued (zero wait) — with max_wait_ms=0 this is
                        # the whole contract: items that accumulated while
                        # the worker was busy form one batch
                        nxt = (
                            self._queue.get(timeout=remaining)
                            if remaining > 0 else self._queue.get_nowait()
                        )
                    except queue.Empty:
                        if wait_until < deadline:
                            continue  # grace elapsed; re-check the hint
                        reason = "deadline"
                        break
                    if nxt is None:
                        reason = None  # the shutdown wake-up: not a decision
                        break
                    batch.append(nxt)
                hist = self.wait_histogram
                if hist is not None:
                    now = time.monotonic()
                    for b in batch:
                        hist.observe(now - b.t_enqueue)
                if self.dispatch_counter is not None:
                    self.dispatch_counter.labels(
                        stage=self.stage, rows=str(len(batch))).inc(len(batch))
                if self.reason_counter is not None and reason is not None:
                    self.reason_counter.labels(stage=self.stage, reason=reason).inc()
                try:
                    with tracing.annotate(f"{self.stage}_batch"):
                        results = self.batch_fn([b.value for b in batch])
                    if len(results) != len(batch):
                        raise RuntimeError(
                            f"batch_fn returned {len(results)} results for "
                            f"{len(batch)} items"
                        )
                    for b, r in zip(batch, results):
                        b.result = r
                except BaseException as e:  # noqa: BLE001 — deliver to all waiters
                    for b in batch:
                        b.error = e
                finally:
                    for b in batch:
                        b.done.set()
        finally:
            # close the door, then fail everything still queued so no caller
            # blocks forever on a dead worker (submits use timeout=None)
            self._stop.set()
            err = RuntimeError("coalescer is shut down")
            with self._lifecycle_lock:
                while True:
                    try:
                        queued = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if queued is not None:
                        queued.error = err
                        queued.done.set()


class BatchScheduler:
    def __init__(
        self,
        engine: InferenceEngine,
        max_wait_ms: float = 5.0,
        pending_hint=None,  # see Coalescer.pending_hint — same contract
    ):
        self.engine = engine
        self.max_wait_ms = max_wait_ms
        self.pending_hint = pending_hint
        # optional obs Histogram — see Coalescer.wait_histogram
        self.wait_histogram = None
        # optional obs Counter — shutdown join timeouts (see _join_worker)
        self.join_timeout_counter = None
        # optional obs counter FAMILIES (settable after construction):
        # rag_generate_dispatch_rows_total{path="batched", rows} counts the
        # answers of each dispatch by its size, and
        # rag_generate_dispatch_reason_total{reason} why the drain loop
        # stopped — "full", "hint" (every in-flight request aboard),
        # "deadline" (the window ran out), "incompatible" (the next request
        # needs another executable and leads the next round)
        self.dispatch_counter = None
        self.reason_counter = None
        # optional obs.tracing.DispatchSink (settable after construction):
        # where each dispatch's stage seconds and its own span tree go
        self.dispatch_sink = None
        # size of the batch currently inside engine.generate (0 between
        # dispatches) — the rag_batch_occupancy gauge reads this; plain
        # int assignment, so no lock needed for the scrape-time read
        self.in_flight = 0
        self._queue: "queue.Queue[_Pending]" = queue.Queue()
        self._stop = threading.Event()
        # serializes submit's stop-check+enqueue against shutdown's final
        # drain — without it an item can land in the queue after the drain
        # and block its (timeout=None) caller forever
        self._lifecycle_lock = threading.Lock()
        self._worker = threading.Thread(target=self._run, daemon=True, name="batch-scheduler")
        self._worker.start()

    # ------------------------------------------------------------------
    def submit(
        self,
        prompt: List[int],
        max_new_tokens: Optional[int] = None,
        seed: Optional[int] = None,
        timeout: Optional[float] = None,
        deadline=None,  # Optional[resilience.Deadline]
        info: Optional[dict] = None,  # accepted for scheduler-API parity;
        # only the continuous scheduler has per-request engine facts to fill
        tenant: Optional[str] = None,  # parity again: the continuous path
        # stamps tenant into the flight journal / goodput ledger; one-shot
        # batches carry no per-request ledger rows to attribute
    ) -> List[int]:
        """Blocking: enqueue and wait for this prompt's continuation.

        A ``deadline`` bounds the wait (the caller's remaining budget); the
        batch itself cannot be cancelled mid-generate — one-shot generation
        is a single device call — so expiry surfaces as the caller's
        :class:`DeadlineExceeded` while the batch completes for its
        surviving members."""
        if timeout is None and deadline is not None:
            timeout = deadline.wait_timeout()
        tr = tracing.current_trace()
        item = _Pending(prompt=list(prompt), max_new=max_new_tokens, seed=seed,
                        trace_id=tr.trace_id if tr is not None else None)
        with self._lifecycle_lock:  # stop-check + enqueue must be atomic
            if self._stop.is_set():
                raise RuntimeError("scheduler is shut down")
            self._queue.put(item)
        if not item.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if item.error is not None:
            raise item.error
        if info is not None and item.record is not None:
            # dispatch_seq, dispatch_rows, queue_wait_ms, launch_ms, device_ms,
            # deliver_ms: which dispatch this request rode, and its intervals
            info.update(item.record.link(item.wait_s))
        return item.result

    def shutdown(self):
        self._stop.set()
        self._queue.put(None)  # wake the worker
        _join_worker(self._worker, self.join_timeout_counter, "batch-scheduler")

    # ------------------------------------------------------------------
    def _run(self):
        carry: Optional[_Pending] = None
        try:
            carry = self._run_loop()
        finally:
            # the worker is exiting for WHATEVER reason (shutdown() or an
            # unguarded exception): close the door first, or submits racing
            # this drain would enqueue after it and block forever
            self._stop.set()
            # fail everything still queued or carried so no caller blocks
            # forever on a scheduler that has stopped (the server submits
            # with timeout=None)
            err = RuntimeError("scheduler is shut down")
            leftovers = [carry] if carry is not None else []
            with self._lifecycle_lock:
                while True:
                    try:
                        queued = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if queued is not None:
                        leftovers.append(queued)
            for it in leftovers:
                it.error = err
                it.done.set()

    def _run_loop(self) -> Optional[_Pending]:
        """Returns the un-acked in-hand item (if any) when stopping."""
        carry: Optional[_Pending] = None
        while not self._stop.is_set():
            first = carry if carry is not None else self._queue.get()
            carry = None
            if first is None:
                continue
            # the coalescing window on the profiler's clock, from the instant
            # a request is in hand: never open while the queue is empty
            t_first = time.monotonic()
            with tracing.annotate("gather"):
                batch, reason, carry = self._gather(first)
            self._dispatch(batch, reason, t_first)
        return carry

    def _gather(self, first: _Pending):
        """Drain compatible requests behind ``first`` within the coalescing
        window: ``(batch, reason, carry)``, ``reason`` why the drain stopped
        and ``carry`` the request that leads the next round, if one does."""
        carry: Optional[_Pending] = None
        batch = [first]
        cap = self.engine.engine_config.max_batch_size
        # an ABSOLUTE deadline (a per-get timeout resets on every arrival:
        # worst case (cap-1) x window under trickle load)
        deadline = time.monotonic() + self.max_wait_ms / 1e3
        reason = "full"  # why the drain stops, unless a break says otherwise
        while len(batch) < cap:
            hint = self.pending_hint
            if hint is not None and len(batch) >= hint():
                # every in-flight request is already aboard (solo query:
                # immediately) — don't burn the window waiting for nobody
                reason = "hint"
                break
            remaining = deadline - time.monotonic()
            try:
                # past the deadline, still drain already-queued items
                # (zero wait) — they accumulated while this worker ran
                nxt = (
                    self._queue.get(timeout=remaining)
                    if remaining > 0 else self._queue.get_nowait()
                )
            except queue.Empty:
                reason = "deadline"
                break
            if nxt is None:
                reason = None  # the shutdown wake-up: not a decision
                break
            if nxt.max_new == first.max_new and nxt.seed == first.seed:
                batch.append(nxt)
            else:
                # different executable: lead the NEXT round (a tail
                # re-queue would reorder it behind later arrivals and
                # could starve it under sustained mixed load)
                carry = nxt
                reason = "incompatible"
                break
        return batch, reason, carry

    def _dispatch(self, batch: List[_Pending], reason: Optional[str], t_first: float) -> None:
        """One device program for ``batch``, under its own record
        (``tracing.dispatch_record``): ``gather`` runs from ``t_first`` to the
        record's entry, and ``deliver`` ends behind the last rider's release."""
        first, rows = batch[0], len(batch)
        hist = self.wait_histogram
        now = time.monotonic()
        for b in batch:
            b.rows, b.wait_s = rows, now - b.t_enqueue
            if hist is not None:
                hist.observe(b.wait_s)
        if self.dispatch_counter is not None:
            self.dispatch_counter.labels(path="batched", rows=str(rows)).inc(rows)
        if self.reason_counter is not None and reason is not None:
            self.reason_counter.labels(reason=reason).inc()
        self.in_flight = rows
        with tracing.dispatch_record(
            "batched", rows, reason, time.monotonic() - t_first,
            riders=[b.trace_id for b in batch if b.trace_id], sink=self.dispatch_sink,
        ) as rec:
            try:
                outs = self.engine.generate(
                    [b.prompt for b in batch],
                    max_new_tokens=first.max_new,
                    seed=first.seed,
                )
                for b, out in zip(batch, outs):
                    b.result = out
            except BaseException as e:  # noqa: BLE001 — deliver to all waiters
                for b in batch:
                    b.error = e
            finally:
                self.in_flight = 0
                rec.settle()  # the riders read its launch and device seconds
                with tracing.span("deliver"):
                    for b in batch:
                        b.record = rec
                        b.done.set()

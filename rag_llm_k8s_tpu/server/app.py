"""HTTP serving app — the reference's surface, TPU-backed.

(The reference uses Flask; Flask is absent from this environment, so the app
is built directly on werkzeug — Flask's own WSGI substrate — preserving the
exact HTTP contract.)

Route parity with /root/reference/llm/rag.py:
- ``POST /upload_pdf`` (rag.py:122-144): same multipart contract, same success/
  error JSON and status codes;
- ``POST /generate`` (rag.py:146-181): same ``{"prompt": ...}`` request, same
  ``{"generated_text", "context"}`` response (plus an additive ``timings``
  field), errors → 500 ``{"error"}``. Also served as ``POST /query`` — the
  name BASELINE.json uses for the same endpoint (SURVEY.md terminology note);
- ``GET /index_info`` (rag.py:183-197): same payload (+ ``generation``).

New, absent from the reference (survey §5 gaps):
- ``GET /healthz``: readiness gated on warmed (pre-compiled) executables;
- ``GET /metrics``: per-stage latency + token counters.

Fixed reference defects (survey §3.1/§5): ingest is idempotent (content-hash
dedup in the store) so pod restarts don't duplicate the index; index mutation
is single-writer; persistence is atomic.
"""

from __future__ import annotations

import io
import json
import logging
import math
import os
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import numpy as np

from rag_llm_k8s_tpu.core.config import AppConfig
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.obs import devices as obs_devices
from rag_llm_k8s_tpu.obs import flight as obs_flight
from rag_llm_k8s_tpu.obs import goodput as obs_goodput
from rag_llm_k8s_tpu.obs import logging as obs_logging
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.obs import shadow as obs_shadow
from rag_llm_k8s_tpu.obs import slo as obs_slo
from rag_llm_k8s_tpu.obs import tenants as obs_tenants
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.rag import lookahead as lookahead_mod
from rag_llm_k8s_tpu.rag.chunking import split_text
from rag_llm_k8s_tpu.rag.pdf import extract_text
from rag_llm_k8s_tpu.rag.prompt import assemble_context, assemble_prompt, extract_answer
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.resilience.admission import AdmissionController, AdmissionRejected
from rag_llm_k8s_tpu.resilience.breaker import CircuitBreaker
from rag_llm_k8s_tpu.resilience.deadline import Deadline, DeadlineExceeded
from rag_llm_k8s_tpu.resilience.lifecycle import LifecycleCoordinator
from rag_llm_k8s_tpu.utils.tokens import truncate_keep_eos

logger = logging.getLogger(__name__)
# one structured line per answered/failed request — emitted INSIDE the traced
# region, so the JSON formatter (obs/logging.py) stamps it with the request's
# trace_id/span_id and a grep of one trace id yields that request's story
access_logger = logging.getLogger("rag_llm_k8s_tpu.access")


def _package_version() -> str:
    from rag_llm_k8s_tpu import __version__

    return __version__


def _engine_mode(scheduler) -> str:
    """Serving mode for /healthz fleet segmentation: continuous (slot
    engine) vs coalesce (group-at-start) vs one-shot (no scheduler)."""
    if scheduler is None:
        return "one-shot"
    from rag_llm_k8s_tpu.engine.continuous import ContinuousScheduler

    if isinstance(scheduler, ContinuousScheduler):
        # interleaved chunked prefill changes the serving shape enough
        # (mixed windows, incremental admission) that fleet dashboards
        # segment it separately
        if getattr(scheduler.engine, "interleave_on", False):
            return "continuous-interleaved"
        return "continuous"
    from rag_llm_k8s_tpu.engine.batching import BatchScheduler

    if isinstance(scheduler, BatchScheduler):
        return "coalesce"
    return type(scheduler).__name__


def make_segment_source(llm_tokenizer, max_bucket: int):
    """The chunk→prompt-segment token source handed to the store's sidecar.

    A standalone closure ON PURPOSE: the store outlives services (a script
    reuses one store across engine configurations; production swaps services
    on reload), and attaching a BOUND METHOD would make the store retain the
    whole service → engine → params graph after teardown — measured as a
    ~2.5 GB HBM leak that OOMed the 8B build. This closure retains only the
    (host-side) tokenizer. ``cache_key`` lets the store keep its tokenized
    rows across re-attaches from services sharing the same tokenizer."""

    def segment_ids(metadata: Dict) -> List[int]:
        seg = (
            f"Document '{metadata.get('filename')}' "
            f"(chunk {metadata.get('chunk_id')}): {metadata.get('text')}\n\n"
        )
        return llm_tokenizer.encode(seg)[:max_bucket]

    segment_ids.cache_key = ("segment_ids_v1", id(llm_tokenizer), max_bucket)
    return segment_ids


class _FanoutHistogram:
    """One observation into several histogram children (the fused retrieve
    dispatch is simultaneously the embed dispatch — both stage views get
    the same per-request coalesce wait)."""

    def __init__(self, *hists):
        self._hists = hists

    def observe(self, value: float) -> None:
        for h in self._hists:
            h.observe(value)


class RagService:
    """The retrieve-then-generate pipeline behind the routes."""

    def __init__(
        self,
        config: AppConfig,
        engine: InferenceEngine,
        llm_tokenizer,
        encoder: EncoderRunner,
        encoder_tokenizer,
        store: VectorStore,
        scheduler=None,  # optional BatchScheduler: coalesces concurrent queries
    ):
        self.config = config
        self.engine = engine
        self.llm_tokenizer = llm_tokenizer
        self.encoder = encoder
        self.encoder_tokenizer = encoder_tokenizer
        self.store = store
        self.scheduler = scheduler
        # ONE registry per service: everything this service and its engines
        # report lands in the same scrape (obs/metrics.py); the legacy
        # facade keeps the seed's service.metrics API working unchanged
        self.metrics = obs_metrics.MetricsRegistry()
        self.traces = tracing.TraceBuffer(128)
        # every dispatch launched where no request's trace is current (the
        # scheduler's worker): its own tree, GET /debug/traces?kind=dispatch
        self.dispatches = tracing.TraceBuffer(128)
        self.started_at = time.monotonic()
        # warmup()'s own span tree, kept here (the ring of 128 would evict
        # it) and served by GET /debug/traces under "boot"
        self.boot_trace: Optional[Dict] = None
        self._ready_seconds = 0.0
        # resilience layer (ISSUE 4): the readiness breaker over engine
        # resets, and the bounded admission gate in front of BOTH engine
        # modes — constructed before observability so the gauges can read
        # their live state
        res = config.resilience
        self.breaker = CircuitBreaker(
            threshold=res.breaker_reset_threshold, window_s=res.breaker_window_s
        )
        self.admission = AdmissionController(
            max_concurrency=res.admission_max_concurrency,
            max_queue=res.admission_max_queue,
            retry_after_s=res.admission_retry_after_s,
            breaker=self.breaker,
        )
        if scheduler is not None and hasattr(scheduler, "breaker"):
            scheduler.breaker = self.breaker  # resets feed readiness
        # tenant attribution (ISSUE 18): every request's tenant id interns
        # through this cardinality-bounded tracker at the HTTP edge (top-K
        # by request count + __other__ overflow); the rag_tenant_* families
        # bind to it below, so their children can never exceed top_k + 1
        # no matter how many distinct ids arrive
        tn_cfg = getattr(config, "tenants", None)
        self.tenants_enabled = (
            bool(tn_cfg.enabled) if tn_cfg is not None else True
        )
        self.tenant_tracker = obs_metrics.TenantTracker(
            top_k=int(getattr(tn_cfg, "top_k", 8) or 8)
        )
        # per-scrape memo for the rag_kv_tier_* callback fan-out (see
        # _pcache_tier_stats); must exist before any scrape can fire
        self._tier_stats_memo = None
        self._chunk_counters_memo = None
        # same pattern for the ~20 rag_goodput_*/rag_cost_* callbacks: one
        # merged ledger snapshot serves the whole scrape
        self._goodput_memo = None
        # engine flight recorder + incident bundles (obs/flight.py): the
        # journal is process-wide (decision points across the substrate
        # write to it long before any service exists), so the service only
        # APPLIES its config and owns the incident spool
        fl = getattr(config, "flight", None)
        # durable flight WAL (ISSUE 19): when armed, every journal event
        # also lands fsynced on disk — the crash-consistent record a warm
        # restart resumes in-flight work from. Construction failure
        # (read-only dir, bad mount) degrades to ring-only, never fatal.
        self.flight_wal = None
        if fl is not None:
            if getattr(fl, "wal", False):
                try:
                    self.flight_wal = obs_flight.FlightWAL(
                        fl.wal_dir,
                        segment_events=fl.wal_segment_events,
                        max_segments=fl.wal_segments,
                    )
                except OSError:
                    logger.exception(
                        "flight WAL unavailable at %s; running ring-only",
                        fl.wal_dir,
                    )
            obs_flight.configure(
                enabled=fl.enabled, capacity=fl.capacity,
                arrival_ids=fl.arrival_ids, wal=self.flight_wal,
            )
        self.incidents = (
            obs_flight.IncidentSpooler(
                fl.spool_dir, fl.spool_max, fl.cooldown_s
            )
            if fl is not None else None
        )
        # shadow-traffic quality auditor (obs/shadow.py): a sampled
        # fraction of completed requests re-runs on the EXACT path (the
        # one-shot engine's teacher-forced scorer — reuse off, speculation
        # off, native-dtype KV; the continuous pool's blocks are never
        # touched) and every divergence is attributed to the
        # approximations that served the request. Rides the lookahead
        # executor's headroom gate so audits never compete with live
        # traffic. On by default (ShadowConfig).
        self.shadow = None
        self._shadow_stats_memo = None
        sh_cfg = getattr(config, "shadow", None)
        if sh_cfg is not None and sh_cfg.enabled and engine is not None \
                and hasattr(engine, "score_exact"):
            self.shadow = obs_shadow.ShadowAuditor(
                sh_cfg,
                score_fn=engine.score_exact,
                headroom_fn=self._lookahead_headroom,
                on_result=self._on_shadow_result,
                on_burst=lambda: self.record_incident("quality_divergence"),
            )
        self._init_observability()
        # incident triggers (obs/flight.py): the breaker flip and the
        # reset storm snapshot the journal that explains them; the
        # pool-exhaustion shed fires from the admission gate, and deadline
        # expiry from the HTTP edge (WsgiApp.ep_generate). All hooks run
        # outside the breaker/gate locks and never propagate.
        self.breaker.on_open = lambda: self.record_incident("breaker_open")
        self.breaker.on_reset = self._maybe_reset_storm
        self.admission.incident_hook = self.record_incident
        # crash-safe lifecycle (ISSUE 19): SIGTERM / POST /drain flips the
        # gate to shed queued+new work with 503 "draining", waits out the
        # in-flight under res.drain_deadline_s, persists (WAL sync + the
        # warmth manifest), then exits. exit_fn stays None here — only the
        # real entrypoint (server/main.py) arms an actual process exit;
        # tests observe the drained state instead.
        self.lifecycle = LifecycleCoordinator(
            admission=self.admission,
            deadline_s=res.drain_deadline_s,
            retry_after_s=res.drain_retry_after_s,
            persist_fn=self._persist_for_restart,
            incident_hook=self.record_incident,
        )
        self.ready = False
        # per-stage in-flight counters, fed to the coalescers as
        # ``pending_hint``: each batching stage stops waiting out its window
        # the moment every request in flight toward it has joined the batch.
        # A solo query then pays ~0 ms of coalescing window (was a fixed
        # 25 + 30 ms) while a burst still coalesces fully — the hint only
        # ever ENDS a wait early; the window deadline remains the bound.
        self._inflight_lock = threading.Lock()
        self._inflight_retrieve = 0
        self._inflight_generate = 0
        # compiled fused embed+kNN executables, keyed (bucket, index_pad, k, B)
        self._fused_retrieve: Dict[tuple, object] = {}
        # concurrent serving: coalesce the embed+kNN stage too — without
        # this, N concurrent queries serialize N fused-retrieve device calls
        # ahead of the (already coalesced) generate stage. UNCONDITIONAL
        # since the paged-KV round: schedulerless serving (the one-shot
        # engine without a BatchScheduler) used to dispatch one encoder
        # forward per concurrent /generate, and the round-5 capture
        # (before PR 1, in git history) showed that contention as
        # embed_retrieve growing ~30x from solo to sustained load — the
        # query-path embeds now always ride the coalescer's batched
        # EncoderRunner dispatch, and each request's enqueue→dispatch wait
        # is visible as rag_coalesce_wait_seconds{stage="embed"}.
        self._retrieve_cap = 8
        if encoder is not None:
            from rag_llm_k8s_tpu.engine.batching import Coalescer

            # 25 ms window: a COLD burst's requests arrive within ~ms of each
            # other, and without a window the first one forms a batch of 1
            # whose (serial) generate then blocks the other N-1 for a whole
            # round — measured +1 s on the burst-8 p50. Sustained load would
            # batch naturally at window 0 (busy-worker accumulation), but the
            # cold burst is the latency-defining case; without the hint a solo
            # query would pay this 25 ms plus the generate scheduler's window
            # (server/main.py) as the price of burst robustness.
            self.retrieve_coalescer = Coalescer(
                lambda items: self._retrieve_many(items, allow_device=True),
                max_batch=self._retrieve_cap, max_wait_ms=25.0,
                pending_hint=lambda: self._inflight_retrieve,
            )
            # the fused retrieve IS the embed dispatch: one wait sample
            # feeds both stage views (retrieve keeps continuity with older
            # dashboards; embed is the encoder-contention panel)
            self.retrieve_coalescer.wait_histogram = _FanoutHistogram(
                self._m_coalesce_wait.labels(stage="retrieve"),
                self._m_coalesce_wait.labels(stage="embed"),
            )
            self.retrieve_coalescer.join_timeout_counter = self._m_join_timeouts
            self.retrieve_coalescer.dispatch_counter = self._m_coalesce_rows
            self.retrieve_coalescer.reason_counter = self._m_coalesce_reason
        else:
            self.retrieve_coalescer = None
        if scheduler is not None:
            if getattr(scheduler, "pending_hint", False) is None:
                # the generate scheduler is constructed by the caller; give
                # it the same early-exit hint unless the caller set its own
                scheduler.pending_hint = lambda: self._inflight_generate
        # paged-KV backpressure (engine/kv_pool.py): while the scheduler
        # engine's pool has zero free blocks, the admission gate sheds
        # would-be-queued requests with 429 reason="pool_exhausted" instead
        # of stacking them behind a device that cannot grow
        pool = getattr(getattr(scheduler, "engine", None), "kv_pool", None)
        if pool is not None:
            self.admission.saturation_hint = lambda: pool.available() == 0
            # KV tiering: tier occupancy refines the shed — while non-hot
            # registered blocks exist, a dry pool is demotable cache
            # warmth (the scheduler reclaims it on its next admission
            # sweep), so the request queues instead of bouncing a 429
            sched_eng = getattr(scheduler, "engine", None)
            if hasattr(sched_eng, "reclaimable_blocks"):
                self.admission.reclaimable_hint = (
                    lambda: sched_eng.reclaimable_blocks() > 0
                )
        # tier state flows cache → pool: after any retier sweep that moved
        # entries, mirror each registered chain's hotness tier onto the
        # pool registrations (scheduler thread via run_on_engine)
        pcache = getattr(engine, "prefix_cache", None)
        if pcache is not None and getattr(pcache, "tiering", None) is not None:
            pcache.on_retier = self._pool_retier
        # ONE EOS policy for ingest and query truncation alike: default the
        # runner's eos from the tokenizer so the two paths cannot diverge
        if encoder is not None and getattr(encoder, "eos_id", None) is None:
            encoder.eos_id = getattr(encoder_tokenizer, "eos_id", None)
        # single-fetch serving (EngineConfig.rag_fused): the store keeps a
        # device-resident chunk-token sidecar so solo queries can assemble
        # their prompt ON DEVICE from the retrieved ids (engine.generate_rag)
        self._a_ids_cache: Optional[List[int]] = None
        self._segment_source = None
        if (
            engine is not None
            and store is not None
            and getattr(engine.engine_config, "rag_fused", False)
        ):
            self._segment_source = make_segment_source(
                llm_tokenizer, max(engine.engine_config.prompt_buckets)
            )
            store.attach_token_source(self._segment_source)
        # retrieval lookahead (rag/lookahead.py): embed+KNN launches before
        # the admission gate can queue a request and runs concurrently with
        # in-flight decode; the serving tail JOINS the future. Sessions
        # speculate turn N+1's retrieval while turn N decodes, and resolved
        # retrievals pre-stage their chunk KV into the prefix cache / pool
        # blocks. Env-gated (TPU_RAG_LOOKAHEAD), off by default.
        self.lookahead = None
        self._session_lock = threading.Lock()
        self._sessions: "OrderedDict[str, Tuple[float, List[str]]]" = OrderedDict()
        la_cfg = getattr(config, "lookahead", None)
        if (
            la_cfg is not None and la_cfg.enabled
            and encoder is not None and store is not None
        ):
            from rag_llm_k8s_tpu.rag.lookahead import LookaheadExecutor

            def _la_retrieve(text: str):
                # the SAME entry points the sequential path uses — results
                # (and therefore greedy streams) are identical by
                # construction; coalesced, so lookahead embeds batch with
                # live traffic's. TTL-bounded: a wedged coalescer worker
                # must not pin the bounded lookahead pool forever (the
                # surfaced TimeoutError fails the future; joiners fall
                # back to inline retrieval) — a future older than the TTL
                # is sweep-fodder anyway
                if self.retrieve_coalescer is not None:
                    return self.retrieve_coalescer.submit(
                        text, timeout=float(la_cfg.ttl_s)
                    )
                return self._retrieve(text)

            self.lookahead = LookaheadExecutor(
                la_cfg,
                retrieve_fn=_la_retrieve,
                prestage_fn=self._lookahead_prestage,
                release_fn=self._lookahead_release,
                headroom_fn=self._lookahead_headroom,
                index_gen_fn=lambda: self.store.ntotal,
                # KV tiering: stats() folds the cache's swap-in counters
                # into the swap-in hide rate that stats() reports —
                # the FRESH reader, not the scrape memo (stats() callers
                # expect current counters)
                tier_stats_fn=self._pcache_tier_stats_fresh,
                # the service's registry from the start: binding the
                # process-wide default first would permanently retain the
                # first executor (and this whole service graph) in the
                # default registry's inflight-gauge closure
                registry=self.metrics,
            )
            self.lookahead.join_timeout_counter = self._m_join_timeouts

    @property
    def flight(self):
        """The LIVE process recorder, read at use time — a later service's
        ``configure(capacity=...)`` rebuilds the singleton, and a captured
        instance would hand timelines/bundles a dead, frozen ring (the
        same rule the ``rag_flight_events_total`` callback follows)."""
        return obs_flight.recorder()

    # -- observability ---------------------------------------------------
    def _init_observability(self) -> None:
        """Register this service's metric families and fold the engines'
        live stats into the same registry (one scrape sees everything:
        request/stage histograms, coalesce waits, TTFT/inter-token from the
        engines, compile time, occupancy/queue gauges, index size)."""
        reg = self.metrics
        self._m_request = reg.histogram(
            "rag_request_duration_seconds",
            "end-to-end /generate duration, server side",
            buckets=obs_metrics.REQUEST_BUCKETS,
        )
        self._m_stage = reg.labeled_histogram(
            "rag_stage_duration_seconds",
            "per-stage serving duration (stage label)",
        )
        for s in ("retrieve", "assemble", "prefix_resolve", "generate",
                  "detokenize"):
            self._m_stage.labels(stage=s)
        self._m_coalesce_wait = reg.labeled_histogram(
            "rag_coalesce_wait_seconds",
            "enqueue-to-dispatch wait in the coalescing stages (stage label)",
        )
        for s in ("retrieve", "embed", "generate"):
            self._m_coalesce_wait.labels(stage=s)
        # every device program launched for /generate, by the path that
        # launched it and the rows it carried (rows <= max_batch_size, so the
        # label is bounded), incremented by those rows: answers by the size
        # of the dispatch they rode. Which path a burst took — one batch, or
        # one request alone on the fused path while the rest wait — is read
        # from here, not guessed from latencies.
        self._m_dispatch_rows = reg.labeled_counter(
            "rag_generate_dispatch_rows_total",
            "answers dispatched to the device, by path "
            "(fused|prefixed|batched|direct) and rows of the dispatch",
        )
        self._m_dispatch_reason = reg.labeled_counter(
            "rag_generate_dispatch_reason_total",
            "why the batch scheduler's drain loop stopped "
            "(full|hint|deadline|incompatible)",
        )
        # the same two for the stage where a split round is born: the
        # retrieve coalescer's batches by size, and why each drain stopped
        self._m_coalesce_rows = reg.labeled_counter(
            "rag_coalesce_dispatch_rows_total",
            "items dispatched by a coalescing stage (stage label), by rows of the batch",
        )
        self._m_coalesce_reason = reg.labeled_counter(
            "rag_coalesce_dispatch_reason_total",
            "why a coalescing stage's drain loop stopped (full|hint|deadline)",
        )
        # each dispatch's own clock, one sample a stage a dispatch: gather
        # (the scheduler's window; 0 on the batch-1 paths), launch, device
        # (the end of launch to the end of fetch), deliver. The four sum to
        # the dispatch's wall time (obs/tracing.py dispatch_record).
        self._dispatch_sink = tracing.DispatchSink(
            reg.labeled_histogram(
                "rag_generate_dispatch_stage_seconds",
                "seconds of each dispatch by path (fused|prefixed|batched|direct) "
                "and stage (gather|launch|device|deliver)",
            ),
            self.dispatches,
        )
        # which attention kernel each compiled program was built with
        # (obs/tracing.count_kernel_build, where LlamaModel._attend chooses
        # by static shape): children appear as programs are traced, synced
        # from the process-wide tally at scrape time (_sync_kernel_builds)
        self._m_kernel_builds = reg.labeled_counter(
            "rag_attend_kernel_builds_total",
            "attention dispatches traced into compiled programs, by mode "
            "(prefill|decode|chunk) and the kernel chosen",
        )
        # every executable the process has built (obs/tracing.build_span and
        # its listener): synced from the process-wide census the same way
        self._m_compile_events = reg.labeled_counter(
            "rag_compile_events_total",
            "executables built, by program (obs/tracing.BUILD_PROGRAMS) and "
            "where they came from (stored: loaded from the executable store, "
            "nothing traced; else what the persistent cache said: hit|miss|off: neither)",
        )
        self._m_compile_seconds = reg.labeled_counter(
            "rag_compile_seconds_total",
            "seconds building executables, by program and stage "
            "(trace|lower|compile|other; compile is the backend's compile, "
            "the persistent cache's read or the executable store's)",
        )
        self._m_ingest_stage = reg.labeled_histogram(
            "rag_ingest_stage_seconds",
            "one ingest's stages (extract|chunk|embed|index|warm)",
            buckets=obs_metrics.REQUEST_BUCKETS,
        )
        reg.gauge(
            "rag_ready_seconds",
            "process start to ready (0 until warmup() has returned)",
            fn=lambda: self._ready_seconds,
        )
        # present in every mode so dashboards stay uniform; only the
        # continuous engine's host loop can actually observe it (exact
        # submit→first-token), so it stays empty under coalesce serving
        reg.histogram(
            "rag_time_to_first_token_seconds",
            "submit-to-first-token (queue + coalesce + prefill + fetch)",
            buckets=obs_metrics.REQUEST_BUCKETS,
        )
        reg.gauge(
            "rag_batch_occupancy",
            "requests currently occupying the serving batch/slots",
            fn=self._batch_occupancy,
        )
        reg.gauge(
            "rag_admission_queue_depth",
            "requests queued toward the generate scheduler",
            fn=self._queue_depth,
        )
        # live engine stats as callback metrics: read at scrape time, zero
        # writes on the engine hot path. BOTH serving engines sum (the
        # scheduler's plus the one-shot engine serving over-bucket prompts
        # through chunked prefill) — long-prompt requests stay visible.
        reg.gauge("index_vectors",
                  fn=lambda: self.store.ntotal if self.store is not None else 0)
        reg.counter("engine_generate_calls",
                    fn=lambda: self._engine_stat("generate_calls"))
        reg.counter("engine_prefill_tokens",
                    fn=lambda: self._engine_stat("prefill_tokens"))
        reg.counter("engine_decode_tokens",
                    fn=lambda: self._engine_stat("decode_tokens"))
        # speculative decoding: emitted / verify_steps = measured acceptance
        reg.counter("engine_spec_verify_steps",
                    fn=lambda: self._engine_stat("spec_verify_steps"))
        reg.counter("engine_spec_emitted_tokens",
                    fn=lambda: self._engine_stat("spec_emitted_tokens"))
        # what the serving engines' family counts on the device (models/
        # families.py ``Family.counter_names``: a sparse-expert family's routed
        # and computed assignments; nothing for a dense one)
        for name in sorted({n for e in self._engines().values()
                            for n in getattr(getattr(e, "stats", None), "family_counters", ())}):
            reg.counter("engine_" + name, fn=lambda n=name: self._family_counter(n))
        # paged continuous draft-and-verify (TPU_RAG_SPEC_PAGED,
        # docs/SPECULATIVE.md): draft-token outcomes summed over the
        # serving engines — families exist in every mode (zeros while
        # speculation is off) so dashboards stay uniform
        spec_fam = reg.labeled_counter(
            "rag_spec_tokens_total",
            "draft tokens judged by paged verify steps (outcome: accepted "
            "— emitted exactly as drafted; rejected — replaced by the "
            "correction target)",
        )
        spec_fam.labels_callback(
            lambda: self._engine_stat("spec_accepted_tokens"),
            outcome="accepted",
        )
        spec_fam.labels_callback(
            lambda: (
                self._engine_stat("spec_drafted_tokens")
                - self._engine_stat("spec_accepted_tokens")
            ),
            outcome="rejected",
        )
        sched_eng = getattr(self.scheduler, "engine", None)
        if int(getattr(sched_eng, "B", 0) or 0) > 0:
            # continuous mode only: a labeled family with ZERO children
            # would appear in the JSON snapshot but not the text
            # exposition (the equivalence test_obs pins), so the family
            # exists exactly where rows exist. Rows are BUCKETED, never
            # per-row: a B=256 deployment must not register 256 children
            # per scrape — the registry's cardinality is a fleet-wide
            # scrape cost, and the adaptive-K controller only needs the
            # cohort view (a collapsing bucket mean is the same remedy
            # signal the RUNBOOK's speculation entry reads)
            spec_rows = reg.labeled_gauge(
                "rag_spec_acceptance_rate",
                "decayed draft-acceptance rate (accepted/offered EMA) "
                "averaged over the ACTIVE slots in each row bucket (row: "
                "row_lt_8 | row_lt_64 | row_ge_64; 0 while the bucket has "
                "no active rows or no evidence) — the adaptive-K "
                "controller's input: rows below "
                "TPU_RAG_SPEC_PAGED_MIN_ACCEPT degrade to K=1",
            )

            def _bucket_mean(lo: int, hi: int, e=sched_eng) -> float:
                # reading the slot list from the scrape thread is safe:
                # the engine replaces slots wholesale (never mutates one
                # into an inconsistent state) and a stale EMA read is
                # gauge-grade
                vals = [
                    float(s.spec_ema or 0.0)
                    for s in e.slots[lo:hi] if s.active
                ]
                return sum(vals) / len(vals) if vals else 0.0

            for name, lo, hi in (
                ("row_lt_8", 0, 8),
                ("row_lt_64", 8, 64),
                ("row_ge_64", 64, 1 << 30),
            ):
                if lo < int(sched_eng.B):
                    spec_rows.labels_callback(
                        lambda lo=lo, hi=hi: _bucket_mean(lo, hi), row=name
                    )
        # KV prefix cache: prompt tokens whose prefill was skipped because
        # their KV spliced from a cached block — computed (prefill_tokens)
        # + skipped = logical prompt total
        reg.counter("prefill_tokens_skipped",
                    fn=lambda: self._engine_stat("prefill_tokens_skipped"))
        reg.counter("prefix_cache_hits",
                    fn=lambda: self._pcache_stat("prefix_cache_hits"))
        reg.counter("prefix_cache_misses",
                    fn=lambda: self._pcache_stat("prefix_cache_misses"))
        reg.gauge("prefix_cache_entries",
                  fn=lambda: self._pcache_stat("prefix_cache_entries"))
        reg.gauge("prefix_cache_bytes",
                  fn=lambda: self._pcache_stat("prefix_cache_bytes"))
        # hotness-aware KV tiering (engine/tiering.py, docs/KV_POOL.md):
        # per-tier residency + transition/swap-in accounting, all
        # callback-valued off PrefixCache.tier_stats() and the pool's tier
        # ledger — families exist in every mode (zeros while tiering is
        # off) so dashboards stay uniform
        tier_entries = reg.labeled_gauge(
            "rag_kv_tier_entries",
            "cached chunk entries per hotness tier (hot bf16-native | "
            "warm int8 | cold host-spilled)",
        )
        tier_bytes = reg.labeled_gauge(
            "rag_kv_tier_bytes",
            "bytes held per tier: hot/warm are device (HBM) bytes, cold "
            "is host-spill RAM",
        )
        for t in ("hot", "warm", "cold"):
            tier_entries.labels_callback(
                lambda t=t: self._pcache_tier_stats().get(
                    f"tier_{t}_entries", 0.0
                ),
                tier=t,
            )
            src = "tier_cold_host_bytes" if t == "cold" else f"tier_{t}_bytes"
            tier_bytes.labels_callback(
                lambda src=src: self._pcache_tier_stats().get(src, 0.0),
                tier=t,
            )
        tier_tr = reg.labeled_counter(
            "rag_kv_tier_transitions_total",
            "tier transitions (change: demote_warm — in-place int8 "
            "quantization; demote_cold — host spill; promote — back to "
            "native residency)",
        )
        for change, key in (
            ("demote_warm", "demotes_warm"),
            ("demote_cold", "demotes_cold"),
            ("promote", "promotes"),
        ):
            tier_tr.labels_callback(
                lambda key=key: self._pcache_tier_stats().get(key, 0.0),
                change=change,
            )
        tier_swap = reg.labeled_counter(
            "rag_kv_tier_swap_ins_total",
            "cold-tier host→HBM swap-ins (trigger: lookahead — prefetched "
            "off the critical path, overlapped with decode; demand — paid "
            "on a serving tail)",
        )
        for trig, key in (
            ("lookahead", "swap_ins_lookahead"),
            ("demand", "swap_ins_demand"),
        ):
            tier_swap.labels_callback(
                lambda key=key: self._pcache_tier_stats().get(key, 0.0),
                trigger=trig,
            )
        reg.counter(
            "rag_kv_tier_swap_in_fallbacks_total",
            "failed host→HBM swap-ins that fell back to "
            "recompute-from-tokens (the chunk rebuilt like any miss; its "
            "host buffer released)",
            fn=lambda: self._pcache_tier_stats().get("swap_in_fallbacks", 0.0),
        )
        reg.gauge(
            "rag_kv_tier_host_spill_bytes",
            "host RAM held by cold-spilled chunk KV (bounded by "
            "TPU_RAG_KV_TIERING_HOST_MB; oldest spills evict past it)",
            fn=lambda: self._pcache_tier_stats().get("tier_cold_host_bytes", 0.0),
        )
        # chunk-granular prefix reuse (reuse="chunk", docs/PREFIX_CACHE.md
        # "chunk-granular reuse"): per-segment resolve outcomes — family
        # exists in every mode (zeros outside chunk reuse)
        chunk_reuse = reg.labeled_counter(
            "rag_prefix_chunk_reuse_total",
            "chunk-granular prefix-reuse outcomes per resolved segment "
            "(chain_exact — bit-identical canonical content, incl. memo "
            "re-serves of exact spans; spliced — drifted reuse at the "
            "same offset or a memo re-serve of corrected content; "
            "rerotated — position-shifted via RoPE re-rotation; "
            "recompute — miss / cold chunk / splice-fault fallback)",
        )
        for oc in ("chain_exact", "spliced", "rerotated", "recompute"):
            chunk_reuse.labels_callback(
                lambda oc=oc: self._pcache_chunk_counters().get(oc, 0.0),
                outcome=oc,
            )
        tier_pool = reg.labeled_gauge(
            "rag_kv_tier_pool_blocks",
            "paged-pool blocks by holder tier: hot/warm are registered "
            "prefix chains (warm = reclaimable under pressure), rows are "
            "live decode rows",
        )
        for t in ("hot", "warm", "rows"):
            tier_pool.labels_callback(
                lambda t=t: float(self._pool_tier_occupancy().get(t, 0)),
                tier=t,
            )
        # HTTP outcome accounting (route = matched path, code = status):
        # the availability SLO's good/total source, and the 5xx-rate panel
        self._m_http = reg.labeled_counter(
            "rag_http_requests_total",
            "served requests by route and status code",
        )
        # resilience accounting (ISSUE 4) — registered here for EVERY
        # serving mode so dashboards stay uniform; the continuous scheduler
        # rebinds onto the same families below and feeds the decode-side
        # children (stage="decode"/"queue", the reset/retry counters)
        self._m_adm_rejected = reg.labeled_counter(
            "rag_admission_rejected_total",
            "requests shed at the admission gate (reason: queue_full | "
            "breaker_open | pool_exhausted | fair_share | draining; "
            "tenant: edge-interned, so the series count stays bounded "
            "at reasons x (top-K tenants + __other__))",
        )
        for r in ("queue_full", "breaker_open", "pool_exhausted",
                  "fair_share"):
            self._m_adm_rejected.labels(reason=r, tenant="__other__")
        self.admission.reject_counter = self._m_adm_rejected
        self._m_deadline = reg.labeled_counter(
            "rag_deadline_exceeded_total",
            "requests failed by their end-to-end deadline (stage label)",
        )
        for s in ("queue", "retrieve", "assemble", "generate", "decode"):
            self._m_deadline.labels(stage=s)
        self.admission.deadline_counter = self._m_deadline
        self._m_degraded = reg.labeled_counter(
            "rag_degraded_responses_total",
            "answers served through a quality-degrading fallback (reason: "
            "prefix_cache | sidecar)",
        )
        for r in ("prefix_cache", "sidecar"):
            self._m_degraded.labels(reason=r)
        reg.counter(
            "rag_engine_resets_total",
            "engine state resets (EngineStateLost / failed decode steps)",
        )
        retries_fam = reg.labeled_counter(
            "rag_inflight_retries_total",
            "in-flight requests resubmitted after an engine reset "
            "(outcome: resubmitted | succeeded | gave_up)",
        )
        # children exist in every mode so the JSON snapshot and the text
        # exposition stay name-equivalent (tests/test_obs.py pins it)
        for o in ("resubmitted", "succeeded", "gave_up"):
            retries_fam.labels(outcome=o)
        join_counter = reg.counter(
            "rag_scheduler_join_timeouts_total",
            "scheduler shutdowns whose worker thread outlived join(timeout)",
        )
        reg.gauge(
            "rag_breaker_open",
            "1 while the engine-reset circuit breaker holds readiness at "
            "503 (Kubernetes is draining this pod)",
            fn=lambda: float(self.breaker.open),
        )
        reg.gauge(
            "rag_breaker_recent_resets",
            "engine resets inside the breaker window right now",
            fn=lambda: float(self.breaker.recent_resets()),
        )
        # engine flight recorder (obs/flight.py): journal volume + spooled
        # post-mortem bundles. The counter reads the PROCESS recorder live
        # (never a captured instance — configure() can rebuild the ring).
        reg.counter(
            "rag_flight_events_total",
            "events appended to the flight journal (ring-bounded; the "
            "counter keeps growing past the ring)",
            fn=lambda: float(obs_flight.recorder().events_emitted),
        )
        self._m_incidents = reg.labeled_counter(
            "rag_incident_bundles_total",
            "incident bundles written to the on-disk spool (trigger: "
            "breaker_open | reset_storm | pool_exhausted_shed | "
            "deadline_exceeded; cooldown-suppressed repeats not counted)",
        )
        for t in obs_flight.TRIGGERS:
            self._m_incidents.labels(trigger=t)
        # shadow quality auditor (obs/shadow.py, docs/OBSERVABILITY.md
        # "Shadow quality auditor"): sampled exact-path re-execution of
        # completed requests — audit outcomes, divergence rate, logit-err
        # and first-divergence distributions, and per-approximation
        # attribution. Families exist in every mode (zeros while the
        # auditor is off) so dashboards stay uniform; counters are
        # callback-valued off one memoized stats snapshot per scrape.
        q_audits = reg.labeled_counter(
            "rag_quality_audits_total",
            "shadow audits by outcome (clean — delivered stream matches "
            "the exact path's argmax chain; diverged — it doesn't; "
            "skipped — selected but unjudgeable, see "
            "rag_quality_skipped_total; failed — the audit itself crashed)",
        )
        for oc in ("clean", "diverged", "skipped", "failed"):
            q_audits.labels_callback(
                lambda oc=oc: self._shadow_stats().get(f"audits_{oc}", 0.0),
                outcome=oc,
            )
        q_skip = reg.labeled_counter(
            "rag_quality_skipped_total",
            "sampler-selected audits that could not run (reason: sampled "
            "— non-greedy stream has no deterministic exact reference; "
            "empty | no_prompt | oversize — nothing comparable; backlog | "
            "headroom — live traffic kept the device busy)",
        )
        for r in obs_shadow.SKIP_REASONS:
            q_skip.labels_callback(
                lambda r=r: self._shadow_stats().get(f"skip_{r}", 0.0),
                reason=r,
            )
        reg.gauge(
            "rag_quality_divergence_rate",
            "diverged / (clean + diverged) over all judged shadow audits "
            "— 0.0 is the byte-identity contracts holding on live traffic",
            fn=lambda: self._shadow_stats().get("divergence_rate", 0.0),
        )
        q_attr = reg.labeled_counter(
            "rag_quality_attribution_total",
            "judged shadow audits per ACTIVE approximation in the "
            "request's fingerprint (approximation: prefix_reuse | "
            "warm_tier | splice | rerotate | boundary_fixup | spec_verify "
            "| none; outcome: clean | diverged) — a diverging "
            "approximation names itself here",
        )
        for a in obs_shadow.APPROXIMATIONS + ("none",):
            for oc in ("clean", "diverged"):
                q_attr.labels_callback(
                    lambda a=a, oc=oc: self._shadow_stats().get(
                        f"attr_{a}_{oc}", 0.0
                    ),
                    approximation=a, outcome=oc,
                )
        self._m_quality_err = reg.histogram(
            "rag_quality_logit_err",
            "per-audit minimal explaining logit perturbation (0.0 on "
            "clean audits; the 0.15 bucket bound IS the pinned warm/"
            "splice tolerance the quality_p99_logit_err SLO evaluates at)",
            buckets=tuple(float(b) for b in obs_shadow.ERR_BUCKETS),
        )
        self._m_quality_first_div = reg.histogram(
            "rag_quality_first_divergence_token",
            "emitted position of the first exact-vs-delivered token "
            "disagreement, per diverged shadow audit (early divergence = "
            "prompt-side approximation; late = accumulated drift)",
            buckets=tuple(float(b) for b in obs_shadow.POS_BUCKETS),
        )
        # goodput ledger (obs/goodput.py, docs/GOODPUT.md): per-window
        # chip-time attribution fractions, rolling MFU / bandwidth
        # utilization per executable kind, and the NinjaLLM cost framing
        # (tokens per dollar) — all callback-valued off one memoized
        # merged-ledger snapshot per scrape, summed over the serving
        # engines; families exist in every mode (zeros while the ledger
        # is off) so dashboards stay uniform
        gp_chip = reg.labeled_counter(
            "rag_goodput_chip_seconds_total",
            "chip-seconds attributed per goodput category — the six WINDOW "
            "categories only, each a true monotone counter summing to busy "
            "time (idle = wall − busy can shrink while both engines run "
            "concurrently, so it lives in rag_goodput_busy_frac and the "
            "/debug/goodput report, never in a counter)",
        )
        for c in obs_goodput.WINDOW_CATEGORIES:
            gp_chip.labels_callback(
                lambda c=c: self._goodput_stats().get(f"chip_s_{c}", 0.0),
                category=c,
            )
        gp_frac = reg.labeled_gauge(
            "rag_goodput_window_frac",
            "fraction of BUSY chip time per attribution category (the six "
            "window categories sum to 1 while anything has run)",
        )
        for c in obs_goodput.WINDOW_CATEGORIES:
            gp_frac.labels_callback(
                lambda c=c: self._goodput_stats().get(f"frac_{c}", 0.0),
                category=c,
            )
        reg.gauge(
            "rag_goodput_busy_frac",
            "busy / wall chip time since the ledger started (1 - this is "
            "the idle fraction)",
            fn=lambda: self._goodput_stats().get("busy_frac", 0.0),
        )
        gp_mfu = reg.labeled_gauge(
            "rag_goodput_mfu",
            "rolling model-FLOPs utilization per executable kind (useful "
            "token lanes only — padding lanes execute but earn nothing; "
            "peaks from TPU_RAG_GOODPUT_PEAK_TFLOPS or the generic default)",
        )
        gp_bw = reg.labeled_gauge(
            "rag_goodput_bandwidth_util",
            "rolling HBM-bandwidth utilization estimate per executable "
            "kind (roofline bytes model over measured window time)",
        )
        for k in obs_goodput.KINDS:
            gp_mfu.labels_callback(
                lambda k=k: self._goodput_stats().get(f"mfu_{k}", 0.0),
                kind=k,
            )
            gp_bw.labels_callback(
                lambda k=k: self._goodput_stats().get(f"bw_{k}", 0.0),
                kind=k,
            )
        reg.counter(
            "rag_cost_usd_total",
            "chip rental spend so far at TPU_RAG_CHIP_HOUR_USD over WALL "
            "time (an idle chip still bills; 0 while no price is set)",
            fn=lambda: self._goodput_stats().get("cost_usd_total", 0.0),
        )
        reg.gauge(
            "rag_cost_tokens_per_usd",
            "useful decode tokens per dollar of wall-clock chip rental "
            "(the NinjaLLM tokens/s/$ gate's numerator; 0 while no price)",
            fn=lambda: self._goodput_stats().get("tokens_per_usd", 0.0),
        )
        # tenant-dimensional attribution (ISSUE 18, docs/OBSERVABILITY.md
        # "Tenant attribution"): who is spending the chips, by the tenant
        # label the edge interned. Every labeled family here is BOUND to
        # the TenantTracker, so demotion prunes its children synchronously
        # and the rag_tenant_tracked callback re-asserts the bound on every
        # scrape — cardinality is top_k + __other__ by construction, not by
        # operator discipline. Counters are push-valued at the edge (HTTP
        # outcome, completion rollup, shed), never per-tenant callbacks.
        trk = self.tenant_tracker
        self._m_tenant_http = reg.labeled_counter(
            "rag_tenant_http_requests_total",
            "served requests by tenant and status code (tenant values are "
            "tracker-interned: top-K by request count, everything else "
            "folds into __other__) — the per-tenant availability SLO's "
            "good/total source",
        )
        self._m_tenant_req = reg.labeled_histogram(
            "rag_tenant_request_seconds",
            "end-to-end /generate duration per tracked tenant (the "
            "per-tenant latency SLO's SLI source)",
            buckets=obs_metrics.REQUEST_BUCKETS,
        )
        self._m_tenant_chip = reg.labeled_counter(
            "rag_tenant_chip_seconds_total",
            "chip-seconds attributed to completed requests per tenant — "
            "the goodput ledger's per-request attribution rolled up by the "
            "tenant that paid for it (sums to the ledger's attributed "
            "total over the same requests)",
        )
        self._m_tenant_cost = reg.labeled_counter(
            "rag_tenant_cost_usd_total",
            "chip rental spend attributed per tenant at "
            "TPU_RAG_CHIP_HOUR_USD (0 while no price is set)",
        )
        self._m_tenant_tokens = reg.labeled_counter(
            "rag_tenant_tokens_total",
            "delivered decode tokens per tenant",
        )
        self._m_tenant_sheds = reg.labeled_counter(
            "rag_tenant_sheds_total",
            "admission-gate sheds per tenant (the reason detail lives in "
            "rag_admission_rejected_total; this family answers WHO was "
            "shed)",
        )
        self.admission.tenant_shed_counter = self._m_tenant_sheds
        for tfam in (self._m_tenant_http, self._m_tenant_req,
                     self._m_tenant_chip, self._m_tenant_cost,
                     self._m_tenant_tokens, self._m_tenant_sheds):
            trk.bind(tfam)
        reg.gauge(
            "rag_tenant_tracked",
            "tenants currently holding tracked (non-__other__) label slots "
            "(<= TPU_RAG_TENANT_TOP_K); reading it also re-asserts the "
            "cardinality bound over every bound family and reconciles the "
            "per-tenant SLO spec set",
            fn=self._tenant_scrape_sync,
        )
        # per-device HBM + prefix-cache residency (obs/devices.py): the
        # dashboard view of an eviction storm under HBM pressure
        obs_devices.register_device_gauges(reg, self._prefix_bytes_by_device)
        for e in self._engines().values():
            bind = getattr(e, "bind_metrics", None)
            if bind is not None:
                bind(reg)
        self._m_join_timeouts = join_counter  # shared by every worker shutdown
        if self.scheduler is not None:
            sched_bind = getattr(self.scheduler, "bind_metrics", None)
            if sched_bind is not None:  # continuous: resets/retries/deadline
                sched_bind(reg)
            if hasattr(self.scheduler, "join_timeout_counter"):
                self.scheduler.join_timeout_counter = join_counter
        if self.scheduler is not None and hasattr(self.scheduler, "wait_histogram"):
            self.scheduler.wait_histogram = (
                self._m_coalesce_wait.labels(stage="generate")
            )
        if self.scheduler is not None and hasattr(self.scheduler, "dispatch_counter"):
            self.scheduler.dispatch_counter = self._m_dispatch_rows
            self.scheduler.reason_counter = self._m_dispatch_reason
            self.scheduler.dispatch_sink = self._dispatch_sink
        # the decision layer: SLO specs evaluated over sliding windows of
        # the histograms/counters registered above; exports rag_slo_* gauges
        # into the same registry and backs GET /slo (obs/slo.py)
        self.slo = obs_slo.SloEngine(
            reg,
            specs=obs_slo.default_specs(getattr(self.config, "slo", None)),
        )

    def _engines(self) -> Dict[int, object]:
        """The serving engines, deduped by identity (see the summing note
        in ``_init_observability``)."""
        engines: Dict[int, object] = {}
        if self.engine is not None:
            engines[id(self.engine)] = self.engine
        sched_engine = getattr(self.scheduler, "engine", None)
        if sched_engine is not None:
            engines[id(sched_engine)] = sched_engine
        return engines

    def _sync_kernel_builds(self) -> None:
        """One callback child per (mode, kernel) the process has traced so
        far, and per (program, stage) / (program, cache) it has built; the
        label sets are bounded by the kernels ``_attend`` can name and by
        ``tracing.BUILD_PROGRAMS``."""
        for mode, kernel in tracing.kernel_builds():
            self._m_kernel_builds.labels_callback(
                lambda key=(mode, kernel): tracing.kernel_builds().get(key, 0),
                mode=mode, kernel=kernel,
            )
        seconds, events = tracing.compile_census()
        for program, stage in seconds:
            self._m_compile_seconds.labels_callback(
                lambda key=(program, stage): tracing.compile_census()[0].get(key, 0.0),
                program=program, stage=stage,
            )
        for program, cache in events:
            self._m_compile_events.labels_callback(
                lambda key=(program, cache): tracing.compile_census()[1].get(key, 0),
                program=program, cache=cache,
            )

    def _engine_stat(self, name: str) -> float:
        return float(sum(
            getattr(e.stats, name, 0) for e in self._engines().values()
            if getattr(e, "stats", None) is not None
        ))

    def _family_counter(self, name: str) -> float:
        return float(sum(
            getattr(getattr(e, "stats", None), "family_counters", {}).get(name, 0)
            for e in self._engines().values()
        ))

    def _pcache_stat(self, name: str) -> float:
        total = 0.0
        for e in self._engines().values():
            pcache = getattr(e, "prefix_cache", None)
            if pcache is not None:
                total += pcache.counters().get(name, 0)
        return total

    def _pcache_chunk_counters(self) -> Dict[str, float]:
        """Summed ``PrefixCache.chunk_reuse_counters()`` over the serving
        engines (the rag_prefix_chunk_reuse_total family's source; zeros
        outside reuse="chunk"). Memoized for a beat like the tier-stats
        snapshot: the 4 outcome callbacks read this per scrape, and each
        fresh compute takes every cache's resolve-path lock — one snapshot
        serves the whole scrape (benign race on the memo)."""
        now = time.monotonic()
        cached = self._chunk_counters_memo
        if cached is not None and now - cached[0] < 0.25:
            return cached[1]
        out: Dict[str, float] = {}
        for e in self._engines().values():
            pcache = getattr(e, "prefix_cache", None)
            if pcache is not None and hasattr(pcache, "chunk_reuse_counters"):
                for k, v in pcache.chunk_reuse_counters().items():
                    out[k] = out.get(k, 0.0) + v
        self._chunk_counters_memo = (now, out)
        return out

    def _pool_tier_occupancy(self) -> Dict[str, int]:
        """The scheduler engine's registered-block tier ledger (scrape
        thread safe — the pool guards it; empty dict when dense)."""
        eng = getattr(self.scheduler, "engine", None)
        occ = getattr(eng, "tier_occupancy", None)
        return occ() if occ is not None else {}

    def _pcache_tier_stats(self) -> Dict[str, float]:
        """Summed ``PrefixCache.tier_stats()`` over the serving engines
        (the rag_kv_tier_* families' source; zeros when tiering is off).
        Memoized for a beat: ~13 label callbacks read this per scrape, and
        each fresh compute takes every cache's lock — one snapshot serves
        the whole scrape instead of contending 13× with the resolve path
        (benign race on the memo: worst case two computes)."""
        now = time.monotonic()
        cached = self._tier_stats_memo
        if cached is not None and now - cached[0] < 0.25:
            return cached[1]
        out = self._pcache_tier_stats_fresh()
        self._tier_stats_memo = (now, out)
        return out

    def _pcache_tier_stats_fresh(self) -> Dict[str, float]:
        """The unmemoized compute — programmatic readers (the lookahead
        executor's ``stats()``, tests) expect CURRENT counters, not the
        scrape memo's up-to-250ms-old snapshot."""
        out: Dict[str, float] = {}
        for e in self._engines().values():
            pcache = getattr(e, "prefix_cache", None)
            if pcache is not None and hasattr(pcache, "tier_stats"):
                for k, v in pcache.tier_stats().items():
                    out[k] = out.get(k, 0.0) + v
        return out

    # -- goodput ledger (obs/goodput.py) ---------------------------------
    def _goodput_price(self) -> float:
        """The chip-hour price, read from the engine LEDGERS first (the
        same source the per-request cost_usd figures use — a service
        whose engines were constructed with a priced EngineConfig must
        not serve aggregate cost metrics from a different knob), with
        the service config as the engine-less fallback."""
        prices = [
            getattr(e, "ledger").chip_hour_usd
            for e in self._engines().values()
            if getattr(e, "ledger", None) is not None
        ]
        if prices and max(prices) > 0:
            return max(prices)
        gp = getattr(getattr(self.config, "engine", None), "goodput", None)
        return float(getattr(gp, "chip_hour_usd", 0.0) or 0.0)

    def _goodput_state(self) -> Dict:
        """Merged ledger state over the serving engines (continuous +
        one-shot — both attribute their own windows)."""
        states = []
        for e in self._engines().values():
            led = getattr(e, "ledger", None)
            if led is not None:
                states.append(led.state())
        return obs_goodput.merge_states(states)

    def _goodput_stats(self) -> Dict[str, float]:
        """Flat per-scrape snapshot behind the ~20 rag_goodput_*/rag_cost_*
        callbacks — memoized for a beat like the tier-stats snapshot (one
        merge serves the whole scrape; benign race on the memo)."""
        now = time.monotonic()
        cached = self._goodput_memo
        if cached is not None and now - cached[0] < 0.25:
            return cached[1]
        report = obs_goodput.render_report(
            self._goodput_state(), chip_hour_usd=self._goodput_price()
        )
        out: Dict[str, float] = {"busy_frac": report["busy_frac"]}
        for c, v in report["categories"].items():
            out[f"chip_s_{c}"] = v["chip_s"]
            if c != "idle":
                out[f"frac_{c}"] = v["frac"]
        for k, v in report["kinds"].items():
            out[f"mfu_{k}"] = v["mfu"]
            out[f"bw_{k}"] = v["bw_util"]
        out["cost_usd_total"] = report["cost"]["wall_usd"]
        out["tokens_per_usd"] = report["cost"]["tokens_per_usd"]
        self._goodput_memo = (now, out)
        return out

    def goodput_report(self) -> Dict:
        """The live capacity picture ``GET /debug/goodput`` serves —
        rendered by the SAME function ``scripts/flightview.py --goodput``
        applies to a journal/bundle offline, so the two cannot drift."""
        return obs_goodput.render_report(
            self._goodput_state(), chip_hour_usd=self._goodput_price()
        )

    # -- incident bundles (obs/flight.py) --------------------------------
    def _maybe_reset_storm(self) -> None:
        """Breaker reset hook: the SECOND reset inside the window is the
        storm signal (one reset is routine, self-healing recovery) — the
        bundle captures the journal while the storm's causal prefix is
        still in the ring, before the breaker even flips."""
        if self.breaker.recent_resets() >= 2:
            self.record_incident("reset_storm")

    def record_incident(self, trigger: str) -> Optional[str]:
        """Spool one self-contained incident bundle: the recent journal,
        the full metrics snapshot, a config fingerprint, and the trace
        ring — everything a post-mortem needs with no live pod. Returns
        the bundle id (None when cooldown-suppressed / spooling is off)."""
        spool = self.incidents
        if spool is None:
            return None

        def _ctx():
            return {
                "journal": self.flight.snapshot(),
                "metrics": self.metrics.snapshot(),
                "config_fingerprint": obs_flight.config_fingerprint(
                    self.config
                ),
                "traces": self.traces.list(32),
                "meta": {
                    "version": _package_version(),
                    "engine_mode": _engine_mode(self.scheduler),
                },
            }

        bid = spool.trigger(trigger, _ctx)
        if bid is not None:
            self._m_incidents.labels(trigger=trigger).inc()
        return bid

    # -- crash-safe lifecycle (ISSUE 19) ---------------------------------
    def _persist_for_restart(self) -> None:
        """The drain coordinator's persist step: fsync the WAL tail (the
        last windows' token_emit deltas become durable) and write the
        warmth manifest next to it — everything the NEXT incarnation needs
        to come back warm. Best-effort: a failed persist degrades the
        restart to cold, never blocks the exit."""
        wal = self.flight_wal
        if wal is not None:
            wal.sync()
        try:
            self._write_warmth_manifest()
        except Exception:  # noqa: BLE001 — persist must not stall the exit
            logger.exception("warmth manifest write failed")

    def _write_warmth_manifest(self) -> Optional[str]:
        """Durably write the prefix cache's hottest (key, ids) records
        into the WAL dir (``durable_write`` — a reader sees old or new,
        never torn). Returns the path, or None when there is nothing to
        write (no WAL, rehydration disabled, no cache)."""
        fl = getattr(self.config, "flight", None)
        wal = self.flight_wal
        if wal is None or fl is None or fl.wal_restore_chunks <= 0:
            return None
        cache = getattr(self.engine, "prefix_cache", None)
        if cache is None or not hasattr(cache, "warmth_manifest"):
            return None
        entries = cache.warmth_manifest(top_n=fl.wal_restore_chunks)
        path = os.path.join(fl.wal_dir, "warmth_manifest.json")
        obs_flight.durable_write(path, {
            "schema_version": obs_flight.SCHEMA_VERSION,
            "ts": time.time(),
            "entries": entries,
        })
        return path

    def _rehydrate_warmth(self, fl) -> int:
        """Re-prefill the warmth manifest's segments through the prefix
        cache's ordinary resolve path (``prefix_for`` — the miss path IS
        the populate path), hottest first, capped at
        ``wal_restore_chunks``. Returns segments staged."""
        if fl.wal_restore_chunks <= 0:
            return 0
        cache = getattr(self.engine, "prefix_cache", None)
        if cache is None or not hasattr(cache, "prefix_for"):
            return 0
        path = os.path.join(fl.wal_dir, "warmth_manifest.json")
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return 0  # no manifest (first boot / SIGKILL before any drain)
        staged = 0
        for ent in doc.get("entries", ())[:fl.wal_restore_chunks]:
            key, ids = ent.get("key"), ent.get("ids")
            if not key or not ids:
                continue
            try:
                got = cache.prefix_for([(str(key), [int(x) for x in ids])])
            except Exception:  # noqa: BLE001 — warmth is opportunistic
                logger.exception("warmth rehydrate failed (key=%s)", key)
                break
            if got is not None:
                staged += 1
                obs_flight.emit("restore", phase="rehydrate", key=str(key),
                                tokens=len(ids))
        return staged

    def restore_from_wal(self, wait: bool = False) -> Dict:
        """Warm restart: pre-stage the warmth manifest, then scan the
        previous incarnation's WAL epoch for requests that died in flight
        and resubmit each through the scheduler's fold path
        (``resume_emitted`` — the WAL-proven emitted tokens fold in, the
        greedy continuation stays byte-identical to an uninterrupted
        run). Their original callers are gone; completing them makes the
        journal whole (``complete.stream_fnv``) and the prefill work
        heats the cache for their retries. Returns a summary; with
        ``wait=True`` blocks for the resumed completions and includes
        their delivered streams (keyed by ORIGINAL rid — the chaos test's
        oracle hook)."""
        fl = getattr(self.config, "flight", None)
        wal = self.flight_wal
        summary: Dict = {"resumed": 0, "skipped": 0, "rehydrated": 0,
                         "results": {}}
        if wal is None or fl is None or not fl.wal_restore:
            return summary
        summary["rehydrated"] = self._rehydrate_warmth(fl)
        epochs = obs_flight.scan_wal(fl.wal_dir)
        dead = [e for e in sorted(epochs) if e < wal.epoch]
        if not dead:
            return summary
        # only the LATEST dead epoch: anything older and unfinished was
        # either restored into it (and re-journaled there as a fresh
        # arrival + token_emit) or lost to segment pruning
        from rag_llm_k8s_tpu.sim import replay as sim_replay

        orig_epoch = dead[-1]
        records = sim_replay.extract_inflight(epochs[orig_epoch])["inflight"]
        sched = self.scheduler
        if records and not hasattr(sched, "_fold_emitted"):
            for rec in records:
                summary["skipped"] += 1
                obs_flight.emit("restore", phase="skip",
                                orig_rid=rec["rid"], reason="no_scheduler")
            return summary
        threads = []
        lock = threading.Lock()
        for rec in records:
            if rec["synthetic_prompt"]:
                # the dead recorder kept lengths only (arrival_ids off):
                # a resume would continue a filler prompt, not the
                # request — journal the gap instead of faking the stream
                summary["skipped"] += 1
                obs_flight.emit("restore", phase="skip",
                                orig_rid=rec["rid"],
                                reason="synthetic_prompt")
                continue
            summary["resumed"] += 1
            obs_flight.emit("restore", phase="resume",
                            orig_rid=rec["rid"], orig_epoch=orig_epoch,
                            n_emitted=len(rec["emitted"]))

            def _resume(rec=rec):
                try:
                    toks = sched.submit(
                        rec["prompt"], max_new_tokens=rec["max_new"],
                        seed=rec.get("seed"), tenant=rec.get("tenant"),
                        resume_emitted=rec["emitted"],
                    )
                except Exception:  # noqa: BLE001 — one lost resume ≠ a failed boot
                    logger.exception("WAL resume failed (orig_rid=%s)",
                                     rec["rid"])
                    return
                with lock:
                    summary["results"][rec["rid"]] = toks

            th = threading.Thread(target=_resume, daemon=True,
                                  name=f"wal-restore-{rec['rid']}")
            th.start()
            threads.append(th)
        if wait:
            for th in threads:
                th.join()
        return summary

    def _pool_retier(self) -> None:
        """Cache→pool tier mirror (PrefixCache.on_retier): re-tag every
        registered chain with its chain's current hotness tier on the
        dispatcher thread — a chain gone cold DROPS its registration
        (blocks back to the pool; its KV survives in the host spill, one
        prestage re-scatter away)."""
        sched = self.scheduler
        cache = getattr(self.engine, "prefix_cache", None)
        if cache is None or not hasattr(sched, "run_on_engine"):
            return

        def _retier_task(e, _cache=cache):
            retier = getattr(e, "retier_registrations", None)
            if retier is not None:
                retier(_cache.chain_tier)

        sched.run_on_engine(_retier_task)

    # -- shadow quality auditor (obs/shadow.py) --------------------------
    def _shadow_stats(self) -> Dict[str, float]:
        """Flat snapshot behind the ~20 rag_quality_* callbacks, memoized
        for a beat like the tier-stats snapshot (one auditor-lock take
        serves the whole scrape; benign race on the memo)."""
        if self.shadow is None:
            return {}
        now = time.monotonic()
        cached = self._shadow_stats_memo
        if cached is not None and now - cached[0] < 0.25:
            return cached[1]
        out = self.shadow.stats()
        self._shadow_stats_memo = (now, out)
        return out

    def _on_shadow_result(self, request_id, ev: Dict) -> None:
        """Auditor result hook (worker thread): journal the audit as a
        flight event — the facts ``flightview --quality`` rebuilds the
        report from — feed the quality histograms (the SLO's SLI source),
        and journal the divergence itself when there is one."""
        obs_flight.emit("shadow_audit", request_id, **ev)
        oc = ev.get("outcome")
        if oc in ("clean", "diverged"):
            self._m_quality_err.observe(float(ev.get("err", 0.0)))
        if oc == "diverged":
            self._m_quality_first_div.observe(float(ev.get("pos", 0)))
            obs_flight.emit(
                "quality_divergence", request_id,
                pos=ev.get("pos"), err=ev.get("err"),
                approx=ev.get("approx") or [],
            )

    @staticmethod
    def _approx_fingerprint(gen_info: Optional[Dict], cp=None
                            ) -> Tuple[str, ...]:
        """One request's approximation fingerprint: the prefix cache's
        per-resolve marks (CachedPrefix.approx) plus whatever the engine
        stamped into the ``info`` out-param (speculation, via the per-
        request ledger stats on the continuous path)."""
        ap = set()
        if cp is not None:
            ap.update(getattr(cp, "approx", ()) or ())
        gi = gen_info or {}
        ap.update(gi.get("approx", ()) or ())
        gp = gi.get("goodput") or {}
        if gp.get("spec_drafted"):
            ap.add("spec_verify")
        return tuple(sorted(ap))

    def _shadow_observe(self, served_by, out_ids, gen_info: Optional[Dict],
                        prompt_ids=None, prompt_fn=None, cp=None,
                        tenant: Optional[str] = None) -> None:
        """Offer one delivered response to the shadow auditor (sampling,
        backlog and headroom discipline live in the auditor). Non-greedy
        streams are ineligible — without the row's keyed draws the exact
        path has no deterministic reference — and are counted as such
        only when the sampler actually selected them. Never raises: an
        audit must not fail the response it rides on."""
        sh = self.shadow
        if sh is None:
            return
        try:
            s = getattr(served_by, "sampling", None)
            eligible = not (
                s is not None and s.do_sample and s.temperature > 0.0
            )
            sh.observe(
                emitted=list(out_ids),
                approx=self._approx_fingerprint(gen_info, cp),
                request_id=(gen_info or {}).get("request_id"),
                prompt_ids=prompt_ids,
                prompt_fn=prompt_fn,
                eligible=eligible,
                tenant=tenant,
            )
        except Exception:  # noqa: BLE001 — auditing must not fail serving
            logger.exception("shadow observe failed")

    def quality_report(self) -> Dict:
        """The live quality picture ``GET /debug/quality`` serves. The
        ``report`` half is rendered by the SAME function
        ``scripts/flightview.py --quality`` applies to a journal/bundle's
        ``shadow_audit`` events offline, so the two cannot drift;
        ``sampling`` carries the auditor-local facts (seen/selected) the
        journal deliberately does not."""
        sh = self.shadow
        if sh is None:
            return {
                "enabled": False,
                "report": obs_shadow.render_report(obs_shadow.new_state()),
            }
        stats = sh.stats()
        return {
            "enabled": True,
            "report": obs_shadow.render_report(sh.state()),
            "sampling": {
                "sample_rate": sh.config.sample_rate,
                "seen": int(stats.get("seen", 0)),
                "selected": int(stats.get("selected", 0)),
                "backlog_depth": int(stats.get("backlog_depth", 0)),
            },
        }

    def _prefix_bytes_by_device(self) -> Dict[int, int]:
        """{device_id: prefix-cache bytes} summed over the serving engines
        (rag_prefix_cache_device_bytes; empty when the cache is off)."""
        out: Dict[int, int] = {}
        for e in self._engines().values():
            pcache = getattr(e, "prefix_cache", None)
            if pcache is not None and hasattr(pcache, "bytes_by_device"):
                for did, nbytes in pcache.bytes_by_device().items():
                    out[did] = out.get(did, 0) + nbytes
        return out

    def observe_http(self, route: str, code: int,
                     tenant: Optional[str] = None,
                     duration_s: Optional[float] = None) -> None:
        """One served request's outcome (called once per request by the
        route handlers — the availability SLO differences this family).
        ``tenant`` (edge-interned) additionally feeds the per-tenant
        outcome counter and, with ``duration_s``, the per-tenant latency
        histogram — the two families the per-tenant SLO specs window."""
        self._m_http.labels(route=route, code=str(int(code))).inc()
        if tenant is not None:
            self._m_tenant_http.labels(
                tenant=tenant, code=str(int(code))
            ).inc()
            if duration_s is not None:
                self._m_tenant_req.labels(tenant=tenant).observe(duration_s)

    # -- tenant attribution (ISSUE 18, obs/tenants.py) -------------------
    def _tenant_scrape_sync(self) -> float:
        """The ``rag_tenant_tracked`` gauge's probe, with two side effects
        that belong on the scrape cadence: re-assert the cardinality bound
        over every tracker-bound family (healing the intern-vs-labels
        race), and reconcile the SLO engine's per-tenant spec set against
        the tracked tenants."""
        trk = self.tenant_tracker
        trk.prune()
        tracked = trk.tracked()
        slo = getattr(self, "slo", None)
        if slo is not None:
            slo.set_tenants(tracked)
        return float(len(tracked))

    def _tenant_complete(self, tenant: str, gen_info: Optional[Dict],
                         n_tokens: int) -> None:
        """Fold one completed request into the per-tenant rollup counters.
        Push-based at completion time (the request's OWN goodput
        attribution), so summed per-tenant chip-seconds equal the ledger's
        attributed total over the same requests — the conservation
        property tests/test_tenants.py pins."""
        try:
            self._m_tenant_tokens.labels(tenant=tenant).inc(float(n_tokens))
            gp = (gen_info or {}).get("goodput") or {}
            chip_ms = float(gp.get("chip_ms", 0.0) or 0.0)
            if chip_ms > 0:
                self._m_tenant_chip.labels(tenant=tenant).inc(chip_ms / 1e3)
            cost = float(gp.get("cost_usd", 0.0) or 0.0)
            if cost > 0:
                self._m_tenant_cost.labels(tenant=tenant).inc(cost)
        except Exception:  # noqa: BLE001 — attribution must not fail serving
            logger.exception("tenant rollup failed")

    def _tenant_ledger_rollups(self) -> Dict[str, Dict[str, float]]:
        """Merged per-tenant ledger rollups over the serving engines (the
        live half of ``GET /debug/tenants``; additive keys sum, the
        goodput fraction is recomputed after the merge)."""
        out: Dict[str, Dict[str, float]] = {}
        for e in self._engines().values():
            led = getattr(e, "ledger", None)
            ts = getattr(led, "tenant_state", None)
            if ts is None:
                continue
            for t, row in ts().items():
                dst = out.setdefault(t, {})
                for k, v in row.items():
                    if k != "goodput_frac":
                        dst[k] = dst.get(k, 0.0) + float(v)
        for row in out.values():
            row["goodput_frac"] = round(
                min(1.0, row.get("useful_s", 0.0)
                    / max(row.get("chip_s", 0.0), 1e-30)), 6
            )
        return out

    def tenant_report(self) -> Dict:
        """The per-tenant cost/usage picture ``GET /debug/tenants``
        serves. The ``report`` half folds the flight journal through
        obs/tenants.py — the SAME stdlib-only module
        ``scripts/flightview.py --tenants`` loads by file path over an
        exported journal, so the two render byte-identical reports over
        the same events. ``tracker``/``ledger``/``slo`` carry live-only
        facts (the interning table, in-memory rollups, burn rates) the
        journal deliberately does not."""
        report = obs_tenants.render_report(
            obs_tenants.state_from_events(self.flight.snapshot()),
            chip_hour_usd=self._goodput_price(),
        )
        self.slo.set_tenants(self.tenant_tracker.tracked())
        return {
            "enabled": self.tenants_enabled,
            "report": report,
            "tracker": self.tenant_tracker.snapshot(),
            "ledger": self._tenant_ledger_rollups(),
            "slo": self.slo.evaluate().get("tenants", {}),
        }

    def _batch_occupancy(self) -> float:
        """Continuous mode: active device slots; coalescing mode: the size
        of the batch currently inside engine.generate (BatchScheduler
        tracks it at dispatch — NOT the answer()-entry claim, which would
        count requests still in retrieve/assemble as batch pressure);
        schedulerless serving falls back to the in-flight generate claim."""
        sched = self.scheduler
        slots = getattr(getattr(sched, "engine", None), "slots", None)
        if slots is not None:
            return float(sum(1 for s in slots if s.active))
        in_flight = getattr(sched, "in_flight", None)
        if in_flight is not None:
            return float(in_flight)
        return float(self._inflight_generate)

    def _queue_depth(self) -> float:
        """Requests waiting toward the device: the admission gate's bounded
        line PLUS the scheduler queue behind it — together, the pressure the
        429 threshold acts on."""
        q = getattr(self.scheduler, "_queue", None)
        depth = float(q.qsize()) if q is not None else 0.0
        return depth + float(self.admission.queue_depth())

    def _observe_request(self, timings: Dict[str, float]) -> None:
        """Feed the request/stage histograms from one answered query's
        timings block (the same numbers the response carries) — called
        EXACTLY ONCE per answered request, which is what keeps stage
        counts equal to request counts. The assemble/detokenize stages
        have no public timings key (the response's keys only ever grow:
        ``chip_ms`` and ``goodput_frac`` joined them with the goodput ledger,
        the six of ``DispatchRecord.LINK_KEYS`` with the dispatch's record), so
        their span sites record private ``_*_s`` entries that are popped
        and observed here: a fallback path that re-runs a stage just
        overwrites the entry, never double-counts it."""
        if "total_ms" in timings:
            self._m_request.observe(timings["total_ms"] / 1e3)
        stage_keys = {
            "embed_retrieve_ms": "retrieve",
            "prefix_resolve_ms": "prefix_resolve",
            "generate_ms": "generate",
        }
        for key, stage in stage_keys.items():
            if key in timings:
                self._m_stage.labels(stage=stage).observe(timings[key] / 1e3)
        for key, stage in (("_assemble_s", "assemble"),
                           ("_detokenize_s", "detokenize")):
            v = timings.pop(key, None)
            if v is not None:
                self._m_stage.labels(stage=stage).observe(v)

    # -- embedding ------------------------------------------------------
    def embed_texts(self, texts: List[str]) -> np.ndarray:
        limit = self.config.encoder.max_encode_len
        eos = getattr(self.encoder_tokenizer, "eos_id", None)
        token_lists = [
            truncate_keep_eos(self.encoder_tokenizer.encode(t), limit, eos)
            for t in texts
        ]
        return self.encoder.encode(token_lists)

    # -- ingest ---------------------------------------------------------
    @contextmanager
    def _ingest_stage(self, stage: str):
        """One stage of an ingest: a span on the upload's trace and a sample
        of ``rag_ingest_stage_seconds{stage}``."""
        t0 = time.monotonic()
        with tracing.span(stage):
            yield
        self._m_ingest_stage.labels(stage=stage).observe(time.monotonic() - t0)

    def ingest_pdf_bytes(self, data: bytes, filename: str) -> int:
        """Extract → chunk → batch-embed → index. Returns chunk count."""
        t0 = time.monotonic()
        with self._ingest_stage("extract"):
            text = extract_text(data)
        with self._ingest_stage("chunk"):
            chunks = split_text(
                text, self.config.retrieval.chunk_size, self.config.retrieval.chunk_overlap
            )
        if not chunks:
            return 0
        with self._ingest_stage("embed"):
            vectors = self.embed_texts(chunks)
        with self._ingest_stage("index"):
            metadata = [
                {"filename": filename, "chunk_id": i, "text": c} for i, c in enumerate(chunks)
            ]
            added = self.store.add(list(vectors), metadata)
            if added and self.store.path:
                self.store.save()
        if added and self.ready:
            with self._ingest_stage("warm"):
                self._warm_after_ingest()
        self.metrics.observe("ingest_seconds", time.monotonic() - t0)
        self.metrics.inc("ingested_chunks", added)
        logger.info("ingested %s: %d chunks (%d new)", filename, len(chunks), added)
        return len(chunks)

    def _warm_after_ingest(self) -> None:
        """Pre-warm the fused retrieval executable, but ONLY when the index
        snapshot outgrew its padded bucket (a new executable is needed
        O(log N) times ever — bulk ingest must not pay a device call per
        document), then what serves on top of it."""
        try:
            cap = self.store.device_snapshot()[0].shape[0]
            k_eff = min(self.config.retrieval.k, self.store.ntotal)
            grew = not any(
                k[1] == cap and k[2] == k_eff for k in self._fused_retrieve
            )
            if grew:
                self._retrieve("warmup")
                if self.retrieve_coalescer is not None:
                    self._retrieve_many(["warmup"] * self._retrieve_cap)
            # single-fetch serving: sync the token sidecar EVERY ingest
            # (an O(batch) splice — token_snapshot; a full rebuild only
            # when the (cap, Lc) bucket outgrew) and get-or-build the
            # assembly executables, so neither the sidecar rebuild nor
            # an Lc-growth compile ever lands inside a user's query
            self._warm_rag_executables(k_eff)
            # KV prefix cache: compile this corpus's segment-KV builder
            # bucket now, not inside the first query that misses
            self._warm_prefix_segments()
        except Exception:  # noqa: BLE001 — warmup must not fail ingest
            logger.exception("post-ingest retrieval warmup failed")

    def ingest_directory(self, pdf_dir: Optional[str] = None) -> int:
        """Boot-time ingest parity (rag.py:88-112) — but idempotent."""
        pdf_dir = pdf_dir or self.config.server.pdf_dir
        if not os.path.isdir(pdf_dir):
            logger.warning("No PDF directory at %s", pdf_dir)
            return 0
        files = [f for f in sorted(os.listdir(pdf_dir)) if f.endswith(".pdf")]
        for fname in files:
            try:
                with open(os.path.join(pdf_dir, fname), "rb") as f:
                    self.ingest_pdf_bytes(f.read(), fname)
            except Exception:  # noqa: BLE001 — one bad PDF must not crashloop boot
                logger.exception("failed to ingest %s; skipping", fname)
        if not files:
            logger.warning("No PDF files found in %s", pdf_dir)
        return len(files)

    # -- single-fetch serving (device-side prompt assembly) -------------
    def _segment_ids(self, metadata: Dict) -> List[int]:
        """One chunk's prompt segment as LLM token ids — the store's token
        source AND the host fallback's segment builder, so device-assembled
        and host-assembled prompts are token-identical by construction.
        Score-free header (the live retrieval score cannot be pre-tokenized
        at ingest; the response's context text keeps real scores). Capped at
        the largest prompt bucket: a longer segment could never fit anyway.
        Delegates to the standalone ``make_segment_source`` closure (the
        store must never hold a bound method of this service — see there)."""
        if self._segment_source is None:
            self._segment_source = make_segment_source(
                self.llm_tokenizer, max(self.engine.engine_config.prompt_buckets)
            )
        return self._segment_source(metadata)

    def _a_ids(self) -> List[int]:
        """BOS + "{system}\\n\\nContext: " — the fixed prompt head."""
        if self._a_ids_cache is None:
            head = f"{self.config.system_message}\n\nContext: "
            ids = self.llm_tokenizer.encode(head)
            bos = self.config.model.bos_token_id
            if not ids or ids[0] != bos:
                ids = [bos] + ids
            self._a_ids_cache = ids
        return self._a_ids_cache

    def _b_ids(self, user_prompt: str) -> List[int]:
        """"\\n\\nUser: {q}\\n\\nChatbot:" — the per-query prompt tail."""
        return self.llm_tokenizer.encode(f"\n\nUser: {user_prompt}\n\nChatbot:")

    def _fused_ok(self) -> bool:
        """Single-fetch path applicability (cheap, called per retrieve)."""
        from rag_llm_k8s_tpu.engine.batching import BatchScheduler

        ec = self.engine.engine_config
        return (
            getattr(ec, "rag_fused", False)
            # the prefix-cache path supersedes device assembly (it needs the
            # retrieve results host-side to resolve segments, and the KV it
            # reuses saves more than the overlapped ids fetch) — don't build
            # executables/sidecars the prefixed path will never consume
            and not self._prefix_enabled()
            and isinstance(self.scheduler, BatchScheduler)
            and 0 < self.store.ntotal <= ec.rag_fused_max_vectors
        )

    def _warm_rag_executables(self, k_eff: int) -> None:
        """Build the chunk-token sidecar and AOT-compile the single-fetch
        RAG executables for the store's current shapes — from warmup() and
        the post-ingest growth hook, never a user query."""
        if not self._fused_ok():
            return
        S = max(self.engine.engine_config.prompt_buckets)
        if len(self._a_ids()) + 1 + 16 > S:
            # mirror of the SERVE gate in _answer_fused (head + tail + 16
            # room): skip only when no tail could ever fit — any stricter
            # and a short-tail query would engage the fused path with no
            # warmed executable and pay the compile inside the request
            return
        toks, _ = self.store.token_snapshot()
        self.engine.warm_rag(
            a_len=len(self._a_ids()),
            cap=int(toks.shape[0]),
            Lc=int(toks.shape[1]),
            kk=k_eff,
            n=min(self.config.retrieval.context_top_n, k_eff),
        )

    # -- fused query embed + kNN ---------------------------------------
    def _retrieve(self, text: str):
        """Embed the query AND rank it against the index in ONE compiled
        device call. The naive chain (encoder dispatch → host round-trip →
        kNN dispatch) pays two device-call latencies per query — fusing
        keeps the query vector on device between the encoder and the kNN
        kernel (survey §7 hard part (e)) and halves dispatch overhead."""
        return self._retrieve_many([text])[0]

    def _fused_retrieve_fn(self, S: int, k_eff: int, B_pad: int, emb, norms):
        """Get-or-build the compiled fused embed+kNN executable for one
        (bucket, index capacity, k, padded batch) shape; ``emb`` / ``norms``
        are the index snapshot it will be called with."""
        import jax
        import jax.numpy as jnp

        from rag_llm_k8s_tpu.engine.engine import param_avals
        from rag_llm_k8s_tpu.ops.knn import knn_topk

        key = (S, emb.shape[0], k_eff, B_pad)
        fn = self._fused_retrieve.get(key)
        if fn is None:
            model = self.encoder.model

            @tracing.phase_scope("retrieve")
            def fused(params, tokens, mask, emb, norms):
                vec = model.apply({"params": params}, tokens, mask)
                d, i = knn_topk(vec.astype(jnp.float32), emb, norms, k=k_eff)
                # pack (dists, idx) into ONE [B, 2k] array: two
                # np.asarray fetches pay two device→host round trips
                # where one will do. fp32 carries row indices exactly
                # up to 2^24 (16M vectors).
                return jnp.concatenate([d, i.astype(jnp.float32)], axis=1)

            i32 = jax.ShapeDtypeStruct((B_pad, S), jnp.int32)
            fn = self._fused_retrieve[key] = tracing.build_span(
                "retrieve", key,
                lambda: (jax.jit(fused), (param_avals(self.encoder.params), i32, i32,
                                          *param_avals((emb, norms)))),
                identity=("retrieve", self.encoder.build_identity, k_eff), rows=B_pad, bucket=S)
        return fn

    def _retrieve_many(self, texts: List[str], allow_device: bool = False):
        """Batched fused embed+kNN: N queries → ONE device call per length
        bucket (in practice one — queries are short). Query batches > 1 pad
        to the fixed ``_retrieve_cap`` so concurrency costs exactly ONE extra
        executable, not a ladder; the padded rows ride along free (the
        encoder forward at these lengths is weight-bandwidth-bound, so B=8
        costs barely more than B=1). Returns ``[(results, tokenize_ms)]``
        in input order.

        ``allow_device=True`` (the retrieve coalescer's mode): a SINGLETON
        batch on the single-fetch path returns the packed device handle
        unfetched — ``[("__device__", packed_dev, k_eff, tokenize_ms)]`` —
        so the retrieved ids can feed device-side prompt assembly without a
        host round trip. Batches > 1 (a burst) keep the host path: they
        batch through the scheduler, where the per-batch fetch amortizes."""
        import jax
        import jax.numpy as jnp

        from rag_llm_k8s_tpu.ops.knn import knn_topk

        n = self.store.ntotal
        if n == 0:
            return [([], 0.0)] * len(texts)
        k_eff = min(self.config.retrieval.k, n)
        emb, norms = self.store.device_snapshot()
        # the runner's own bucketing/truncation/EOS rules (its buckets are
        # already clamped to max_encode_len) — query and chunk embeddings go
        # through identical preparation
        prepped = []
        for text in texts:
            t0 = time.monotonic()
            tokens, mask = self.encoder.prepare_batch(self.encoder_tokenizer.encode(text))
            prepped.append((tokens, mask, (time.monotonic() - t0) * 1e3))

        if allow_device and len(texts) == 1 and self._fused_ok():
            tokens, mask, tok_ms = prepped[0]
            fn = self._fused_retrieve_fn(tokens.shape[1], k_eff, 1, emb, norms)
            packed_dev = fn(
                self.encoder.params, jnp.asarray(tokens), jnp.asarray(mask),
                emb, norms,
            )  # NOT fetched — the ids stay on device for prompt assembly
            return [("__device__", packed_dev, k_eff, tok_ms)]

        out: List = [None] * len(texts)
        by_bucket: Dict[int, List[int]] = {}
        for i, (tokens, _, _) in enumerate(prepped):
            by_bucket.setdefault(tokens.shape[1], []).append(i)
        for S, idxs in by_bucket.items():
            for start in range(0, len(idxs), self._retrieve_cap):
                group = idxs[start : start + self._retrieve_cap]
                B_pad = 1 if len(group) == 1 else self._retrieve_cap
                tokens = np.full((B_pad, S), self.config.encoder.pad_token_id, np.int32)
                mask = np.zeros((B_pad, S), np.int32)
                for row, i in enumerate(group):
                    tokens[row], mask[row] = prepped[i][0][0], prepped[i][1][0]

                fn = self._fused_retrieve_fn(S, k_eff, B_pad, emb, norms)
                packed = np.asarray(fn(
                    self.encoder.params, jnp.asarray(tokens), jnp.asarray(mask), emb, norms
                ))  # ONE fetch
                dists, idx = packed[:, :k_eff], packed[:, k_eff:].astype(np.int64)
                for row, i in enumerate(group):
                    out[i] = (
                        self.store.results_at(idx[row], dists[row]),
                        prepped[i][2],
                    )
        return out

    def _trace_retrieve(self, parent, t0: float, timings: Dict[str, float]) -> None:
        """Attach the retrieve stage's interior to the live ``retrieve``
        span: the device work ran on the coalescer worker (contextvars
        don't cross threads), so the tokenize / fused-embed+kNN split is
        synthesized from the SAME measurements the timings block carries
        (the embed_knn child includes the coalesce wait — the per-request
        wait distribution lives in ``rag_coalesce_wait_seconds``)."""
        tr = tracing.current_trace()
        if tr is None or parent is None:
            return
        # identity search: Span is a dataclass, so list.index would match
        # by VALUE and could pick a different span with equal fields
        pidx = next((i for i, s in enumerate(tr.spans) if s is parent), None)
        if pidx is None:
            return
        tok_s = timings.get("tokenize_ms", 0.0) / 1e3
        knn_s = timings.get("embed_retrieve_ms", 0.0) / 1e3
        tr.add_span("tokenize", t0, tok_s, parent=pidx)
        tr.add_span("embed_knn", t0 + tok_s, knn_s, parent=pidx)

    # -- query ----------------------------------------------------------
    @staticmethod
    def _fold_goodput(timings: Dict[str, float], gen_info: Dict) -> None:
        """Surface a request's goodput attribution in its timings block:
        chip_ms (the chip-seconds this request was attributed), its
        goodput_frac (useful share of that time), cost_usd when a
        chip-hour price is configured, and the per-request speculation
        stats (spec_accept_len_mean and drafted/accepted counts — an
        acceptance collapse is visible per response, not only in the
        EngineStats aggregates)."""
        gp = gen_info.get("goodput")
        if not gp:
            return
        for key in ("chip_ms", "goodput_frac", "cost_usd", "spec_drafted",
                    "spec_accepted", "spec_accept_len_mean"):
            if key in gp:
                timings[key] = float(gp[key])

    @staticmethod
    def _round_timings(timings: Dict[str, float]) -> Dict[str, float]:
        """The response's rounded timings view. cost_usd keeps 8 decimals
        — a per-query cost is micro-dollars and 2 decimals would zero it;
        goodput_frac keeps 4 so small useful shares stay readable."""
        digits = {"cost_usd": 8, "goodput_frac": 4, "spec_accept_len_mean": 4}
        return {k: round(v, digits.get(k, 2)) for k, v in timings.items()}

    def _deadline_check(self, dl: Optional[Deadline], stage: str) -> None:
        """One stage-boundary deadline check: count + raise on expiry."""
        if dl is not None and dl.expired():
            self._m_deadline.labels(stage=stage).inc()
            raise DeadlineExceeded(stage, dl.budget_ms)

    def _degrade(self, notes: List[str], reason: str) -> None:
        """Record one quality-degrading fallback (satellite: the broad
        except guards used to swallow these silently)."""
        self._m_degraded.labels(reason=reason).inc()
        if reason not in notes:
            notes.append(reason)

    @staticmethod
    def _finish(resp: Dict, notes: List[str]) -> Dict:
        """Stamp degraded-mode markers onto an outgoing response."""
        if notes:
            resp["degraded"] = True
            resp["degraded_reasons"] = list(notes)
        return resp

    # -- retrieval lookahead (rag/lookahead.py callbacks) ----------------
    def _lookahead_headroom(self) -> bool:
        """False while speculative lookahead work would pressure live
        traffic: breaker open, requests already queued at the admission
        gate, or (paged) a pool without a full row's worth of free blocks
        — the service-side face of the engine's ``admission_state``
        backpressure (the authoritative per-allocation gate runs on the
        dispatcher thread inside ``prestage_prefix``)."""
        if self.breaker.open:
            return False
        if self.admission.queue_depth() > 0:
            return False
        eng = getattr(self.scheduler, "engine", None)
        pool = getattr(eng, "kv_pool", None)
        if pool is not None:
            # read-only probe (ints under the GIL): never steal the blocks
            # the next admission's row growth needs
            if not pool.can_alloc(getattr(eng, "MB", 1)):
                return False
        return True

    def _lookahead_prestage(self, text: str, r):
        """Executor-worker callback: the moment a lookahead retrieval
        resolves, build/refresh the resolved chunks' segment KV into
        prefix-cache entries (``PrefixCache.stage`` — the miss path IS the
        populate path) and, on a paged continuous engine, register the
        chain's full pool blocks ahead of admission
        (``ContinuousEngine.prestage_prefix`` via ``run_on_engine`` — the
        engine is single-owner). Returns the staging handle a superseded
        speculation releases, or None when there is nothing to stage."""
        if not self._prefix_enabled():
            return None
        if isinstance(r, tuple) and len(r) == 4 and r[0] == "__device__":
            return None  # unfetched device handle: nothing host-side to key
        results = r[0] if isinstance(r, tuple) else r
        if not results:
            return None
        if not self._lookahead_headroom():
            return None
        ps = self._prompt_segments(text, results)
        if ps is None:
            return None
        _, segments, _ = ps
        cp, record = self.engine.prefix_cache.stage(segments)
        if cp is None:
            return None
        handle = {"record": record, "chain_key": cp.chain_key, "pool": None}
        sched = self.scheduler
        eng = getattr(sched, "engine", None)
        if (
            cp.chain_key is not None
            and getattr(eng, "paged", False)
            and hasattr(sched, "run_on_engine")
        ):
            # the TASK records ownership: only the call that actually
            # CREATED the registration may later release it ("resident"
            # means an earlier admission/prestage owns it), and it records
            # the registration GENERATION so the release can never free a
            # registration re-created at this key after ours was evicted.
            # A release task enqueued later runs after this one (FIFO on
            # the dispatcher), so it reads the settled value.
            # the registration carries the chain's CURRENT hotness tier
            # (KV tiering): admission reclaims non-hot registrations first
            cache = self.engine.prefix_cache
            tier = (
                cache.chain_tier(cp.chain_key)
                if hasattr(cache, "chain_tier") else "hot"
            )

            def _prestage_task(e, _h=handle, _cp=cp, _tier=tier):
                if e.prestage_prefix(_cp, tier=_tier) == "registered":
                    _h["pool"] = e.prestage_gen(_cp.chain_key)

            sched.run_on_engine(_prestage_task)
        return handle

    def _lookahead_release(self, handle: Dict) -> None:
        """Stale-prefetch cancellation: release every prefix-cache entry /
        assembled buffer / registered pool block a superseded speculation
        staged and nothing else consumed (ref-count-correct on both
        substrates — see ``PrefixCache.release_staged`` and
        ``ContinuousEngine.release_prestaged``)."""
        cache = getattr(self.engine, "prefix_cache", None)
        if cache is not None:
            cache.release_staged(handle.get("record"))
        ck = handle.get("chain_key")
        sched = self.scheduler
        if ck is not None and hasattr(sched, "run_on_engine"):
            # enqueue unconditionally: FIFO ordering after the prestage
            # task means handle["pool"] (the staged generation) is settled
            # when this runs; only_unused keeps a registration live traffic
            # has mapped since staging, and the generation guard keeps one
            # a later admission re-created (the speculation was right —
            # releasing it would cost every future admission its copy-free
            # share)
            sched.run_on_engine(
                lambda e: handle.get("pool") is not None
                and e.release_prestaged(
                    ck, only_unused=True, gen=handle["pool"]
                )
            )

    def _session_note(self, session_id: str, prompt: str) -> str:
        """Fold one turn's prompt into the session's conversation state and
        return the speculative next-turn retrieval query (the trailing
        turns joined — under topic coherence it retrieves the chunk set
        turn N+1 is most likely to need). Sessions are LRU-capped and
        TTL-swept host-side."""
        lc = self.config.lookahead
        now = time.monotonic()
        with self._session_lock:
            _, hist = self._sessions.pop(session_id, (now, []))
            hist = (hist + [prompt])[-max(1, lc.session_context_turns):]
            self._sessions[session_id] = (now, hist)
            for k in list(self._sessions):
                if k == session_id:
                    continue
                ts0, _ = self._sessions[k]
                if (
                    len(self._sessions) > lc.session_max
                    or now - ts0 > lc.session_ttl_s
                ):
                    del self._sessions[k]
                else:
                    break  # ordered by recency: the rest are fresher
            return " ".join(hist)

    def answer(
        self, user_prompt: str, deadline: Optional[Deadline] = None,
        session_id: Optional[str] = None, tenant: Optional[str] = None,
    ) -> Dict:
        timings: Dict[str, float] = {}
        notes: List[str] = []  # degraded-path reasons (response + counter)
        t_all = time.monotonic()
        with self._inflight_lock:
            self._inflight_retrieve += 1
            self._inflight_generate += 1
        in_retrieve = in_generate = True
        try:
            # embed + kNN run as ONE fused device call, so they cannot be
            # timed separately; the keys say so explicitly instead of
            # repurposing the old embed_ms/retrieve_ms split (which would
            # silently skew any cross-version comparison of stage timings)
            t0 = time.monotonic()
            la = self.lookahead
            fut = la.claim(user_prompt) if la is not None else None
            r = None
            with tracing.span("retrieve") as retrieve_span:
                if fut is not None:
                    # lookahead pipeline: the retrieval was launched before
                    # this request cleared admission — the critical path
                    # pays only the JOIN (≈0 when it resolved during the
                    # queue wait / other requests' decode)
                    was_hit = fut.resolved()
                    try:
                        with tracing.span("lookahead_join"):
                            r = la.join(
                                fut,
                                timeout=deadline.wait_timeout()
                                if deadline is not None else None,
                            )
                    except lookahead_mod.JoinTimeout:
                        # OUR wait expired — the request's own deadline
                        self._m_deadline.labels(stage="retrieve").inc()
                        raise DeadlineExceeded(
                            "retrieve",
                            deadline.budget_ms if deadline else None,
                        ) from None
                    except Exception:  # noqa: BLE001 — speculation must not fail the request
                        # includes a WORKER-side TimeoutError (bounded
                        # coalescer submit): a failed speculation retrieves
                        # inline, it never 504s a request whose own
                        # deadline has budget left
                        logger.warning(
                            "lookahead retrieval failed; retrieving inline",
                            exc_info=True,
                        )
                        r = None
                    else:
                        timings["lookahead_hit"] = 1.0 if was_hit else 0.0
                        # the worker's tokenize never touched this thread:
                        # the stage timing below is pure join wall-clock
                        if isinstance(r, tuple) and len(r) == 4 \
                                and r[0] == "__device__":
                            r = (r[0], r[1], r[2], 0.0)
                        elif isinstance(r, tuple) and len(r) == 2:
                            r = (r[0], 0.0)
                if r is None:
                    if la is not None:
                        la.note_miss()
                    # the wait side of the stage runs in THIS thread; the
                    # device work happens on the coalescer worker and its
                    # interior split re-attaches via _trace_retrieve below
                    if self.retrieve_coalescer is not None:
                        # deadline-bounded: a wedged coalescer worker must
                        # not pin this thread (and its admission slot)
                        # forever
                        try:
                            r = self.retrieve_coalescer.submit(
                                user_prompt,
                                timeout=deadline.wait_timeout()
                                if deadline is not None else None,
                            )
                        except TimeoutError:
                            self._m_deadline.labels(stage="retrieve").inc()
                            raise DeadlineExceeded(
                                "retrieve",
                                deadline.budget_ms if deadline else None,
                            ) from None
                    else:
                        r = self._retrieve(user_prompt)
            with self._inflight_lock:
                self._inflight_retrieve -= 1
            in_retrieve = False
            self._deadline_check(deadline, "retrieve")
            if session_id and la is not None:
                # multi-turn pipelining: speculate turn N+1's retrieval NOW
                # so its embed+KNN (and KV pre-staging) overlap this turn's
                # decode; superseded speculations release what they staged
                spec_text = self._session_note(session_id, user_prompt)
                if spec_text:
                    la.speculate(session_id, spec_text)

            fused_r = (
                r if isinstance(r, tuple) and len(r) == 4 and r[0] == "__device__"
                else None
            )
            if fused_r is not None:
                tokenize_ms = fused_r[3]
                timings["tokenize_ms"] = tokenize_ms
                timings["embed_retrieve_ms"] = (
                    (time.monotonic() - t0) * 1e3 - tokenize_ms
                )
                self._trace_retrieve(retrieve_span, t0, timings)
                # a fused request never reaches the scheduler: release the
                # generate claim NOW or the scheduler's pending_hint would
                # count this phantom for the whole multi-second generate,
                # forcing concurrent host-path batches to wait out their
                # full window (re-claimed below if we fall back)
                with self._inflight_lock:
                    self._inflight_generate -= 1
                in_generate = False
                resp = self._answer_fused(
                    user_prompt, fused_r, timings, t_all, notes, deadline,
                    tenant=tenant,
                )
                if resp is not None:
                    return self._finish(resp, notes)
                with self._inflight_lock:
                    self._inflight_generate += 1
                in_generate = True
                # head + tail didn't fit the bucket (or the sidecar failed):
                # materialize host results from the device handle and take
                # the ordinary path below
                k_eff = fused_r[2]
                packed = np.asarray(fused_r[1])
                results = self.store.results_at(
                    packed[0, k_eff:].astype(np.int64), packed[0, :k_eff]
                )
            else:
                results, tokenize_ms = r
                timings["tokenize_ms"] = tokenize_ms
                timings["embed_retrieve_ms"] = (
                    (time.monotonic() - t0) * 1e3 - tokenize_ms
                )
                self._trace_retrieve(retrieve_span, t0, timings)

            if not results:
                return self._finish(
                    {"generated_text": "No relevant information found in the index."},
                    notes,
                )

            with self._inflight_lock:
                # this request holds one generate claim; more means a burst
                # is in flight — bursts keep the coalesced batched path
                # (batched decode beats serial batch-1 prefixed generates),
                # mirroring how the single-fetch path treats bursts
                solo = self._inflight_generate <= 1
            if self._prefix_enabled() and solo:
                # KV prefix cache: the head + chunk segments' KV splices
                # from the device-resident cache and prefill touches only
                # the per-query tail. The path bypasses the scheduler
                # (batch-1 executable), so release the generate claim like
                # the fused path does; on fallback, re-claim.
                with self._inflight_lock:
                    self._inflight_generate -= 1
                in_generate = False
                resp = self._answer_prefixed(
                    user_prompt, results, timings, t_all, notes,
                    tenant=tenant,
                )
                if resp is not None:
                    return self._finish(resp, notes)
                with self._inflight_lock:
                    self._inflight_generate += 1
                in_generate = True

            t_as = time.monotonic()
            with tracing.span("assemble"):
                pw = (
                    self._piecewise_prompt(user_prompt, results)
                    if getattr(self.engine.engine_config, "rag_fused", False) else None
                )
                if pw is not None:
                    context, prompt_ids = pw
                else:
                    context, prompt_ids = self._budgeted_prompt(user_prompt, results)
            timings["_assemble_s"] = time.monotonic() - t_as
            self._deadline_check(deadline, "assemble")

            t0 = time.monotonic()
            gen_info: Dict[str, float] = {}
            served_engine = self.engine  # shadow audit: whose sampling rules
            with tracing.span("generate") as gen_span:
                if self.scheduler is not None and len(prompt_ids) <= self._scheduler_prompt_cap():
                    served_engine = (
                        getattr(self.scheduler, "engine", None) or self.engine
                    )
                    try:
                        out_ids = self.scheduler.submit(
                            prompt_ids, deadline=deadline, info=gen_info,
                            tenant=tenant,
                        )
                    except DeadlineExceeded as e:
                        # worker-side expiries (queue wait, mid-decode
                        # eviction) were counted where they were raised;
                        # the caller-side "generate" expiry counts here
                        if e.stage == "generate":
                            self._m_deadline.labels(stage="generate").inc()
                        raise
                    except TimeoutError:
                        if deadline is not None and deadline.expired():
                            self._m_deadline.labels(stage="generate").inc()
                            raise DeadlineExceeded(
                                "generate", deadline.budget_ms
                            ) from None
                        raise
                    # the dispatch itself is the scheduler worker's tree; the
                    # request's own says which one it rode, with how many
                    # rows, how long it queued and its share of the
                    # dispatch's stages (BatchScheduler fills all six)
                    link = {k: gen_info.pop(k) for k in tracing.DispatchRecord.LINK_KEYS
                            if k in gen_info}
                else:
                    # prompts beyond the scheduler's capability need chunked
                    # prefill, which fixed-length continuous slots cannot do —
                    # the one-shot engine runs them through the cache chunk by
                    # chunk instead of letting the scheduler truncate them.
                    # Release the generate claim first: this request never
                    # reaches the scheduler, so the pending_hint must not
                    # wait for it.
                    with self._inflight_lock:
                        self._inflight_generate -= 1
                    in_generate = False
                    with self._dispatch("direct") as rec:
                        out_ids = self.engine.generate(
                            [prompt_ids], info=gen_info
                        )[0]
                    link = rec.link()
                self._link_dispatch(gen_span, timings, link)
            if in_generate:
                with self._inflight_lock:
                    self._inflight_generate -= 1
                in_generate = False
            t_de = time.monotonic()
            with tracing.span("detokenize"):
                completion = self.llm_tokenizer.decode(out_ids)
            timings["_detokenize_s"] = time.monotonic() - t_de
            timings["generate_ms"] = (time.monotonic() - t0) * 1e3
            if "kv_blocks_allocated" in gen_info:
                # paged KV: the row's peak block footprint (per-request HBM
                # accounting next to the pool gauges)
                timings["kv_blocks_allocated"] = float(
                    gen_info["kv_blocks_allocated"]
                )
            self._fold_goodput(timings, gen_info)
            timings["total_ms"] = (time.monotonic() - t_all) * 1e3
        finally:
            # error paths (and the no-results return) must release their
            # claim or the hints would overcount forever after one failure
            with self._inflight_lock:
                if in_retrieve:
                    self._inflight_retrieve -= 1
                if in_generate:
                    self._inflight_generate -= 1

        self.metrics.observe("query_seconds", timings["total_ms"] / 1e3)
        self.metrics.inc("query_decode_tokens", len(out_ids))
        self._observe_request(timings)
        if tenant is not None:
            self._tenant_complete(tenant, gen_info, len(out_ids))
        # shadow quality audit (sampled): the delivered stream vs the
        # exact path — the prompt is the exact token list that served
        self._shadow_observe(
            served_engine, out_ids, gen_info, prompt_ids=prompt_ids,
            tenant=tenant,
        )
        resp = {
            "generated_text": extract_answer(completion),
            "context": context,
            "timings": self._round_timings(timings),
        }
        if "request_id" in gen_info:
            # continuous serving: the scheduler id keying this request's
            # flight-journal lifecycle (GET /debug/timeline/<id>; also
            # what {"timeline": true} resolves inline)
            resp["request_id"] = int(gen_info["request_id"])
        return self._finish(resp, notes)

    @contextmanager
    def _dispatch(self, path: str):
        """One batch-1 device program launched for ``/generate`` from the
        request thread (the scheduler worker opens its own in
        ``BatchScheduler``): its one row under ``path`` in
        ``rag_generate_dispatch_rows_total``, counted at the launch whether
        or not the program then succeeds (as the scheduler counts its
        batches), and the dispatch's record (``tracing.dispatch_record``: the
        ``dispatch`` span under the request's ``generate``, its stage
        seconds), which it yields."""
        self._m_dispatch_rows.labels(path=path, rows="1").inc()
        with tracing.dispatch_record(path, 1, sink=self._dispatch_sink) as rec:
            yield rec

    @staticmethod
    def _link_dispatch(gen_span, timings: Dict[str, float], link: Dict[str, float]) -> None:
        """Put a dispatch's ``link()`` (obs/tracing.py ``DispatchRecord``, or
        what ``BatchScheduler.submit`` returned of it) into the response's
        ``timings`` and, without the ``dispatch_`` prefix, onto the request's
        ``generate`` span."""
        timings.update(link)
        if gen_span is not None:
            gen_span.attrs.update(
                {k.removeprefix("dispatch_"): float(v) for k, v in link.items()})

    def _prefix_enabled(self) -> bool:
        """KV prefix cache applicability (engine/prefix_cache.py)."""
        return getattr(self.engine, "prefix_cache", None) is not None

    def _warm_prefix_segments(self) -> None:
        """AOT-compile the segment-KV builder executables for the buckets
        queries will hit (warmup + post-ingest hook): the head's bucket and
        a representative chunk's — reference-shaped corpora chunk uniformly,
        so row 0's bucket is the one retrieved segments land in. Without
        this, the first query per bucket pays the build compile inside
        ``prefix_resolve_ms`` (measured ~1 s even at tiny scale)."""
        if not self._prefix_enabled():
            return
        try:
            from rag_llm_k8s_tpu.utils.buckets import bucket_len

            pc = self.engine.engine_config.prefix_cache
            reps = [self._a_ids()]
            if self.store is not None and self.store.ntotal:
                cached = self.store.cached_token_row(0)
                if cached is not None:
                    reps.append(list(cached))
                else:
                    sample = self.store.info().get("sample_chunks") or []
                    if sample:
                        reps.append(self._segment_ids(sample[0]))
            seen = set()
            for ids in reps:
                if ids and len(ids) <= max(pc.segment_buckets):
                    b = bucket_len(len(ids), pc.segment_buckets)
                    if b not in seen:
                        seen.add(b)
                        self.engine._get_segment_kv(b)
        except Exception:  # noqa: BLE001 — warmup must not fail boot/ingest
            logger.exception("prefix segment warmup failed")

    def _answer_prefixed(self, user_prompt: str, results, timings, t_all,
                         notes: Optional[List[str]] = None,
                         tenant: Optional[str] = None):
        """The KV-prefix-cache tail of ``answer()``: resolve the canonical
        segments against the device-resident cache (misses build + populate
        as they go), splice the matched prefix into a fresh request cache
        and prefill ONLY the per-query tail (engine.generate_prefixed).
        Returns the response dict — with the per-request reuse fraction in
        the timings block — or None when the prompt can't take the prefixed
        path (no context room, over-capacity prefix, oversized tail); the
        caller falls back to the ordinary paths."""
        cache = getattr(self.engine, "prefix_cache", None)
        if cache is None:
            return None
        t_as = time.monotonic()
        with tracing.span("assemble"):
            ps = self._prompt_segments(user_prompt, results)
        timings["_assemble_s"] = time.monotonic() - t_as
        if ps is None:
            return None
        context, segments, b_ids = ps
        if not b_ids:
            return None
        t_r = time.monotonic()
        with tracing.span("prefix_resolve"):
            try:
                cp = cache.prefix_for(segments)
            except Exception:  # noqa: BLE001 — cache trouble must not 500 the query
                logger.exception("prefix-cache resolve failed; host fallback")
                # the fallback serves a correct answer WITHOUT the cached
                # KV: mark the response degraded so the quality/latency
                # loss is visible instead of silent (satellite: the broad
                # guard used to swallow this entirely)
                if notes is not None:
                    self._degrade(notes, "prefix_cache")
                return None
        if cp is None:
            return None
        # hit: a dict lookup (~0); miss: the segment-build prefill — keep it
        # out of generate_ms so the stage split stays honest either way
        timings["prefix_resolve_ms"] = (time.monotonic() - t_r) * 1e3
        t0 = time.monotonic()
        gen_info: Dict[str, float] = {}
        with tracing.span("generate") as gen_span:
            try:
                with self._dispatch("prefixed") as rec:
                    out_ids = self.engine.generate_prefixed(
                        b_ids, cp, info=gen_info
                    )
            except ValueError:
                return None  # tail over the suffix ladder: cold path serves
            self._link_dispatch(gen_span, timings, rec.link())
        t_de = time.monotonic()
        with tracing.span("detokenize"):
            completion = self.llm_tokenizer.decode(out_ids)
        timings["_detokenize_s"] = time.monotonic() - t_de
        timings["generate_ms"] = (time.monotonic() - t0) * 1e3
        total_prompt = cp.length + len(b_ids)
        timings["prefix_reuse_frac"] = cp.reused_tokens / max(total_prompt, 1)
        timings["prefill_tokens_skipped"] = float(cp.reused_tokens)
        # of the tokens the prefix cache RESOLVED, the fraction whose
        # prefill was actually skipped — under chunk reuse the boundary-
        # correction windows count as computed, so this is the honest
        # per-request savings number (prefix_reuse_frac counts the whole
        # resolved prefix against the whole prompt)
        timings["prefill_tokens_skipped_frac"] = cp.reused_tokens / max(
            cp.reused_tokens + cp.computed_tokens + len(b_ids), 1
        )
        self._fold_goodput(timings, gen_info)
        timings["total_ms"] = (time.monotonic() - t_all) * 1e3
        self.metrics.observe("query_seconds", timings["total_ms"] / 1e3)
        self.metrics.inc("query_decode_tokens", len(out_ids))
        self.metrics.inc("query_prefix_cached", 1)
        self._observe_request(timings)
        if tenant is not None:
            self._tenant_complete(tenant, gen_info, len(out_ids))
        # shadow quality audit: the prompt as served is the segment chain
        # + tail, and the resolve's CachedPrefix carries the fingerprint
        # (prefix_reuse / warm_tier / splice / rerotate / boundary_fixup)
        # any divergence is attributed to
        self._shadow_observe(
            self.engine, out_ids, gen_info,
            prompt_ids=[t for _, seg in segments for t in seg] + list(b_ids),
            cp=cp, tenant=tenant,
        )
        return {
            "generated_text": extract_answer(completion),
            "context": context,
            "timings": self._round_timings(timings),
        }

    def _answer_fused(self, user_prompt: str, fused_r, timings, t_all,
                      notes: Optional[List[str]] = None,
                      deadline: Optional[Deadline] = None,
                      tenant: Optional[str] = None):
        """The single-fetch tail of ``answer()``: device-side prompt assembly
        + generate from the unfetched retrieve handle (engine.generate_rag),
        with the ids fetch for the response's context text overlapped with
        generation on a side thread. Returns the response dict, or None when
        the prompt head + tail can't fit the bucket (caller falls back to
        the host path, which can chunk-prefill)."""
        if self._prefix_enabled():
            # cache lookup wins over device assembly: the prefixed path
            # reuses cached KV for the head + hot chunks, which saves far
            # more prefill than the overlapped ids fetch saves fetch time.
            # Yield so answer() materializes the retrieve results and takes
            # the prefixed tail (falling back further if that can't serve).
            return None
        _, packed_dev, k_eff, tokenize_ms = fused_r
        t_b = time.monotonic()
        b_ids = self._b_ids(user_prompt)
        a_ids = self._a_ids()
        S = max(self.engine.engine_config.prompt_buckets)
        # 16 tokens of guaranteed context room: below that the assembled
        # prompt is all head+tail and the host path (which can shrink BOTH
        # via its word-level trimming, then chunk-prefill) serves better.
        # Tails past the fixed fused bucket also route host-side.
        if (
            len(a_ids) + len(b_ids) + 16 > S
            or len(b_ids) > self.engine.RAG_TAIL_BUCKET
        ):
            return None
        try:
            # non-blocking: a sidecar build in progress (a racing ingest's
            # hook) must not stall this request — fall back to the host path
            snap = self.store.token_snapshot(blocking=False)
        except Exception:  # noqa: BLE001 — sidecar failure must not 500 the query
            logger.exception("chunk-token sidecar unavailable; host fallback")
            # a broken sidecar (vs a merely in-progress build, the `snap is
            # None` case below) is a real degradation: say so
            if notes is not None:
                self._degrade(notes, "sidecar")
            return None
        if snap is None:
            return None
        toks_dev, lens_dev = snap
        timings["tokenize_ms"] = tokenize_ms + (time.monotonic() - t_b) * 1e3
        n_ctx = min(self.config.retrieval.context_top_n, k_eff)

        box: Dict[str, object] = {}

        def _fetch_ids():
            try:
                box["packed"] = np.asarray(packed_dev)
            except BaseException as e:  # noqa: BLE001 — re-raised on join
                box["err"] = e

        th = threading.Thread(target=_fetch_ids, daemon=True, name="ids-fetch")
        th.start()
        t0 = time.monotonic()
        gen_info: Dict[str, float] = {}
        with tracing.span("generate") as gen_span:
            with self._dispatch("fused") as rec:
                out_ids = self.engine.generate_rag(
                    a_ids, b_ids, packed_dev, toks_dev, lens_dev, n_chunks=n_ctx,
                    info=gen_info,
                )
            self._link_dispatch(gen_span, timings, rec.link())
        t_de = time.monotonic()
        with tracing.span("detokenize"):
            completion = self.llm_tokenizer.decode(out_ids)
        timings["_detokenize_s"] = time.monotonic() - t_de
        timings["generate_ms"] = (time.monotonic() - t0) * 1e3
        # bound the ids-fetch join by the request's remaining deadline
        # budget (was a hardcoded 120 s — the serving path's only timeout);
        # floored at 1 s so a deadline spent during generate still gives
        # the nearly-always-finished fetch one beat to land
        join_t = (
            max(1.0, deadline.remaining()) if deadline is not None
            else self.config.resilience.deadline_ms / 1e3
        )
        th.join(timeout=join_t)
        if "packed" not in box:
            err = box.get("err")
            raise err if isinstance(err, BaseException) else RuntimeError(
                "retrieve ids fetch did not complete"
            )
        packed = box["packed"]
        results = self.store.results_at(
            packed[0, k_eff:].astype(np.int64), packed[0, :k_eff]
        )
        # mirror the device budget rule now that the kept chunk ids are known
        # host-side: context text renders only the chunks the prompt carried,
        # and the prefill accounting gets the gathered share
        n_kept, used, _ = self._kept_chunks(
            self.store.token_lengths(
                packed[0, k_eff : k_eff + n_ctx].astype(np.int64)
            ),
            S - len(a_ids) - len(b_ids),
        )
        context = assemble_context(results, n_kept)
        self.engine.record_prefill(used)
        self._fold_goodput(timings, gen_info)
        timings["total_ms"] = (time.monotonic() - t_all) * 1e3
        self.metrics.observe("query_seconds", timings["total_ms"] / 1e3)
        self.metrics.inc("query_decode_tokens", len(out_ids))
        self.metrics.inc("query_single_fetch", 1)
        self._observe_request(timings)
        if tenant is not None:
            self._tenant_complete(tenant, gen_info, len(out_ids))
        # shadow quality audit: the prompt was assembled ON DEVICE, so
        # its token ids are reconstructed from the host mirror (pinned
        # token-identical to the device assembly) — and only when the
        # sampler actually selects this request (prompt_fn defers the
        # re-tokenize the 95% unsampled case must not pay)
        self._shadow_observe(
            self.engine, out_ids, gen_info,
            prompt_fn=lambda: (
                (self._piecewise_prompt(user_prompt, results) or (None, None)
                 )[1]
            ),
            tenant=tenant,
        )
        return {
            "generated_text": extract_answer(completion),
            "context": context,
            "timings": self._round_timings(timings),
        }

    def _prompt_segments(self, user_prompt: str, results):
        """THE canonical prompt-segment layout: ``(context, segments,
        b_ids)`` where ``segments = [(stable_key, token_ids), ...]`` is the
        head followed by the kept chunk segments, under the budget rule
        (``_kept_chunks``). Chunk boundaries are fixed by this one function
        for every serving path — host piecewise assembly, the device
        assembly's host mirror AND the KV prefix cache (whose blocks are
        keyed ``(stable_key, position_slot)``, so alignment across requests
        is what makes reuse fire). Keys come from the store's content hash
        (restart-stable); a budget-truncated first chunk gets a distinct
        key — its KV is a different token stream. Returns None when head +
        tail leave no context room."""
        a_ids = self._a_ids()
        b_ids = self._b_ids(user_prompt)
        S = max(self.engine.engine_config.prompt_buckets)
        avail = S - len(a_ids) - len(b_ids)
        if avail < 16:
            return None
        top_n = self.config.retrieval.context_top_n
        segs: List[List[int]] = []
        keys: List[str] = []
        for r in results[:top_n]:
            # reuse the sidecar's cached tokenization when the result carries
            # its store row (avoids re-encoding multi-hundred-token segments
            # on every batched request)
            row = getattr(r, "row", -1)
            cached = (
                self.store.cached_token_row(row)
                if self.store is not None else None
            )
            segs.append(
                list(cached) if cached is not None else self._segment_ids(r.metadata)
            )
            ck = self.store.content_key(row) if self.store is not None else None
            keys.append(
                f"chunk:{ck}" if ck is not None
                else f"chunk:anon:{hash(tuple(segs[-1])) & 0xFFFFFFFFFFFF:012x}"
            )
        n_kept, _, trunc = self._kept_chunks([len(s) for s in segs], avail)
        kept = segs[:n_kept]
        kept_keys = keys[:n_kept]
        if trunc is not None:
            kept[0] = kept[0][:trunc]
            kept_keys[0] = f"{kept_keys[0]}:t{trunc}"
        segments = [(f"head:{len(a_ids)}", list(a_ids))]
        segments.extend(zip(kept_keys, kept))
        context = assemble_context(results, n_kept)
        return context, segments, b_ids

    def _piecewise_prompt(self, user_prompt: str, results):
        """Host-side mirror of the device prompt assembly (rag_fused mode):
        piecewise token concatenation — head ‖ kept chunk segments ‖ tail —
        under the SAME budget rule (keep the longest chunk prefix that fits;
        token-truncate the first chunk if it alone overflows), so batched
        host answers are token-identical to solo device answers. Returns
        None when head + tail leave no context room (legacy budgeted path
        handles it, including chunked prefill)."""
        ps = self._prompt_segments(user_prompt, results)
        if ps is None:
            return None
        context, segments, b_ids = ps
        ids: List[int] = []
        for _, seg in segments:
            ids.extend(seg)
        ids.extend(b_ids)
        return context, ids

    @staticmethod
    def _kept_chunks(seg_lens, avail: int):
        """THE context-budget rule, in one place — must stay bit-identical
        to the device assembly in ``engine._build_generate_rag`` (cumsum-
        prefix keep; token-truncate the first chunk if it alone overflows).
        Returns ``(n_kept, used_tokens, first_chunk_trunc_len_or_None)``."""
        used = 0
        n_kept = 0
        trunc = None
        for j, L in enumerate(seg_lens):
            if used + L <= avail:
                used += L
                n_kept += 1
            else:
                if j == 0:
                    trunc = max(avail, 0)
                    used = trunc
                    n_kept = 1
                break
        return n_kept, used, trunc

    def _scheduler_prompt_cap(self) -> int:
        """Longest prompt the serving scheduler can take WITHOUT truncating.
        Continuous slots expose their admissible bucket ladder (``buckets``);
        the coalescing scheduler delegates to the chunk-capable one-shot
        engine, so it has no cap of its own."""
        slot_buckets = getattr(self.scheduler.engine, "buckets", None)
        if slot_buckets is None:
            return 1 << 62  # coalescing path: engine.generate chunks as needed
        return max(slot_buckets)

    def _budgeted_prompt(self, user_prompt: str, results) -> tuple:
        """Assemble context + prompt ids, shrinking the context until the
        tokenized prompt fits the engine's largest bucket. Without this, a
        3×1000-word context can exceed the bucket and the engine would
        left-truncate away BOS + the system message (degraded answers).
        Shrink order: drop trailing chunks, then trim the last chunk's words.
        """
        budget = max(self.engine.engine_config.prompt_buckets)
        bos = self.config.model.bos_token_id
        used = [
            type(r)(metadata=dict(r.metadata), distance=r.distance)
            for r in results[: self.config.retrieval.context_top_n]
        ]
        dropped, trimmed_to = 0, None
        while True:
            context = assemble_context(used, len(used))
            prompt = assemble_prompt(user_prompt, context, self.config.system_message)
            ids = self.llm_tokenizer.encode(prompt)
            if not ids or ids[0] != bos:
                ids = [bos] + ids
            if len(ids) <= budget:
                if dropped or trimmed_to is not None:
                    logger.warning(
                        "prompt exceeded %d-token budget: dropped %d chunk(s)%s",
                        budget, dropped,
                        f", trimmed last chunk to {trimmed_to} words" if trimmed_to else "",
                    )
                return context, ids
            if len(used) > 1:
                used.pop()
                dropped += 1
            else:
                words = used[0].metadata.get("text", "").split()
                # proportional jump toward the budget (0.9 safety margin), so
                # trimming converges in a couple of re-encodes, not O(n) passes
                target = min(len(words) - 1, int(len(words) * budget / len(ids) * 0.9))
                if target < 10:
                    # irreducible: the QUESTION alone exceeds the bucket. The
                    # engine can chunk-prefill up to max_chunked_prompt, so
                    # hand the full prompt through (answer() routes over-
                    # bucket prompts to the chunk-capable engine) and only
                    # the engine's own loud cap ever truncates.
                    logger.warning(
                        "prompt irreducibly over the %d-token bucket; serving "
                        "via chunked prefill (%d tokens)", budget, len(ids),
                    )
                    return context, ids
                used[0].metadata["text"] = " ".join(words[:target])
                trimmed_to = target

    # -- lifecycle ------------------------------------------------------
    def warmup(self):
        """Pre-compile the hot executables, then mark ready (the reference has
        no readiness signal; first request pays full compile). ALL prompt
        buckets warm — RAG prompts with a full 3-chunk context land in the
        largest bucket, so warming only small buckets would leave the very
        first production query paying the big compile."""
        # the way to ready is a span tree of its own: one span a stage below,
        # the ``build`` spans under them (obs/tracing.build_span)
        tr = tracing.start_trace()
        tr.attrs["kind"] = "boot"
        try:
            self._warm_stages()
        finally:  # a boot that failed keeps its tree too
            tr.attrs["process_started_at"] = tracing.process_start_time()
            tr.attrs["ready_at"] = time.time()
            self.boot_trace = tracing.finish_trace(tr)
        self._ready_seconds = tr.attrs["ready_at"] - tr.attrs["process_started_at"]
        self.ready = True

    def _warm_stages(self) -> None:
        # warm the engine that actually serves: the scheduler's (continuous
        # slots or coalescing wrapper around self.engine); self.engine alone
        # only when no scheduler exists
        serving_engine = self.scheduler.engine if self.scheduler is not None else self.engine
        from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine

        # the continuous engine's warmup batch_sizes size its ADMISSION-
        # GROUP ladder: warm it to the slot count, or the first concurrent
        # burst pays per-(bucket, group) compiles mid-request after
        # /healthz already reports ready
        warm_bs = (
            (serving_engine.B,)
            if isinstance(serving_engine, ContinuousEngine) else (1,)
        )
        with tracing.span("warm_generate"):
            serving_engine.warmup(
                batch_sizes=warm_bs, buckets=serving_engine.engine_config.prompt_buckets
            )
        from rag_llm_k8s_tpu.engine.batching import BatchScheduler

        if isinstance(self.scheduler, BatchScheduler):
            # the coalescing scheduler pads grouped requests to the next
            # power of two: warm that ladder at the largest bucket (where
            # every full-context RAG prompt lands) or the first concurrent
            # burst pays a per-shape compile mid-request
            ec = serving_engine.engine_config
            # the ladder tops out at the engine's PADDED shape for a full
            # batch (next_pow2(max_batch_size)), not max_batch_size itself —
            # a cap of 6 pads 5-6-request bursts to batch 8
            top = serving_engine._bucket_batch(ec.max_batch_size)
            sizes, b = [], 2
            while b <= top:
                sizes.append(b)
                b *= 2
            if sizes:
                # Coverage trade-off: RAG prompts carry a full 3-chunk context
                # and land in the LARGEST bucket, so by default only that
                # bucket's batch ladder is warmed — a concurrent burst of
                # short, context-free prompts still pays a per-(batch,bucket)
                # compile mid-request. EngineConfig.warm_full_ladder (env
                # TPU_RAG_WARM_FULL_LADDER=1) warms every pair instead.
                if ec.warm_full_ladder:
                    warm_buckets = tuple(ec.prompt_buckets)
                else:
                    warm_buckets = (max(ec.prompt_buckets),)
                with tracing.span("warm_ladder"):
                    serving_engine.warmup(batch_sizes=tuple(sizes), buckets=warm_buckets)
        if serving_engine is not self.engine:
            # over-bucket prompts bypass the scheduler into the one-shot
            # engine's chunked prefill — warm one representative overflow
            # shape so the first long request doesn't pay the compile
            ec = self.engine.engine_config
            largest = max(ec.prompt_buckets)
            mn = max(1, min(self.engine.sampling.max_new_tokens,
                            ec.max_seq_len - largest))
            with tracing.span("warm_overflow"):
                self.engine._get_compiled(1, 2 * largest, mn, largest)
        if self.shadow is not None:
            # the auditor's exact scorer: one executable per padded length.
            # Same coverage rule as the batch ladder above — the largest
            # bucket (where full-context RAG prompts land) by default,
            # every bucket under warm_full_ladder — so sampled audits of
            # the traffic warmup prepares for never compile after ready
            ec = self.engine.engine_config
            with tracing.span("warm_score"):
                self.engine.warm_score_exact(
                    ec.prompt_buckets if ec.warm_full_ladder
                    else (max(ec.prompt_buckets),)
                )
        with tracing.span("warm_retrieve"):
            self.embed_texts(["warmup"])
            # compile the fused embed+kNN executable and upload the index
            # snapshot (no-op while the index is empty; ingest re-warms)
            self._retrieve("warmup")
            if self.retrieve_coalescer is not None and self.store.ntotal:
                # one extra executable: the padded concurrent-retrieval batch
                self._retrieve_many(["warmup"] * self._retrieve_cap)
        if self.store is not None and self.store.ntotal:
            # single-fetch serving: sidecar + generate_rag executables warm
            # here too — the first production solo query must not compile
            with tracing.span("warm_rag"):
                self._warm_rag_executables(min(self.config.retrieval.k, self.store.ntotal))
        if self._prefix_enabled():
            # KV prefix cache: compute + PIN the fixed head block (reused by
            # 100% of requests — it must never evict) and AOT-compile the
            # prefixed generate executables, so a cache hit never compiles
            # or prefills the head inside a user's request
            try:
                with tracing.span("warm_prefix"):
                    head_key = f"head:{len(self._a_ids())}"
                    self.engine.prefix_cache.pin(head_key)
                    self.engine.prefix_cache.prefix_for([(head_key, self._a_ids())])
                    self.engine.warm_prefixed()
                    self._warm_prefix_segments()
            except Exception:  # noqa: BLE001 — warmup must not fail boot
                logger.exception("prefix-cache warmup failed")

    def shutdown(self):
        """Stop the serving threads (coalescers/schedulers) and release the
        store's device sidecar (the store may outlive this service; its HBM
        must not). Idempotent."""
        if self.shadow is not None:
            # first: the audit worker drives the one-shot engine, which
            # must outlive any in-flight audit
            self.shadow.shutdown()
        if self.lookahead is not None:
            # before the coalescer: lookahead workers submit into it
            self.lookahead.shutdown()
        if self.retrieve_coalescer is not None:
            self.retrieve_coalescer.shutdown()
        if self.scheduler is not None:
            self.scheduler.shutdown()
        if self.store is not None and hasattr(self.store, "release_token_device"):
            self.store.release_token_device()
        if self.engine is not None and hasattr(self.engine, "drop_placed_sidecar"):
            self.engine.drop_placed_sidecar()


class WsgiApp:
    """A small WSGI app on werkzeug (Flask's substrate — Flask itself is not
    available in this environment; the HTTP contract is what matters for
    parity with the reference's Flask app, and it's preserved exactly)."""

    def __init__(self, service: RagService):
        import json as _json

        from werkzeug.exceptions import HTTPException, NotFound
        from werkzeug.routing import Map, Rule
        from werkzeug.wrappers import Request, Response

        self.service = service
        self._Request = Request
        self._Response = Response
        self._HTTPException = HTTPException
        self._NotFound = NotFound
        self._json = _json
        self.url_map = Map(
            [
                Rule("/upload_pdf", endpoint="upload_pdf", methods=["POST"]),
                Rule("/generate", endpoint="generate", methods=["POST"]),
                Rule("/query", endpoint="generate", methods=["POST"]),
                Rule("/index_info", endpoint="index_info", methods=["GET"]),
                Rule("/healthz", endpoint="healthz", methods=["GET"]),
                Rule("/drain", endpoint="drain", methods=["POST"]),
                Rule("/metrics", endpoint="metrics", methods=["GET"]),
                Rule("/slo", endpoint="slo", methods=["GET"]),
                Rule("/profile", endpoint="profile", methods=["POST"]),
                Rule("/debug/traces", endpoint="debug_traces", methods=["GET"]),
                Rule("/debug/faults", endpoint="debug_faults",
                     methods=["GET", "POST"]),
                Rule("/debug/timeline/<int:rid>", endpoint="debug_timeline",
                     methods=["GET"]),
                Rule("/debug/incidents", endpoint="debug_incidents",
                     methods=["GET"]),
                Rule("/debug/goodput", endpoint="debug_goodput",
                     methods=["GET"]),
                Rule("/debug/quality", endpoint="debug_quality",
                     methods=["GET"]),
                Rule("/debug/tenants", endpoint="debug_tenants",
                     methods=["GET"]),
            ]
        )
        # background xprof capture state (/profile {"seconds": N})
        self._profile_lock = threading.Lock()
        self._profile_until: Optional[float] = None

    # -- helpers --------------------------------------------------------
    def _jsonify(self, payload, status: int = 200):
        return self._Response(
            self._json.dumps(payload), status=status, mimetype="application/json"
        )

    def _debug_enabled(self) -> bool:
        """ONE armed-state contract for every ``/debug/*`` route: 403
        unless the process started with ``TPU_RAG_FAULTS`` set (the chaos
        harness) or ``TPU_RAG_DEBUG=1`` (read-only debug surface). The
        faults endpoint keeps its STRICTER own gate on top — TPU_RAG_DEBUG
        must never make a pod remotely fault-armable."""
        fl = getattr(self.service.config, "flight", None)
        return faults.endpoint_enabled() or bool(
            fl is not None and fl.debug_endpoints
        )

    def _debug_forbidden(self):
        return self._jsonify(
            {"error": "debug endpoints disabled "
                      "(set TPU_RAG_FAULTS or TPU_RAG_DEBUG)"},
            403,
        )

    def _request_deadline(self, data, headers):
        """Resolve one request's end-to-end deadline: body ``deadline_ms``
        wins, then the ``x-request-deadline-ms`` header, then the config
        default. Returns ``(Deadline, None)`` or ``(None, error_message)``
        for a malformed value (the route answers 400 — a client that ASKED
        for a budget must not silently get the default)."""
        raw = data.get("deadline_ms") if isinstance(data, dict) else None
        if raw is None:
            raw = headers.get("x-request-deadline-ms")
        if raw is None:
            ms = float(self.service.config.resilience.deadline_ms)
        else:
            try:
                ms = float(raw)
            except (TypeError, ValueError):
                return None, f"deadline_ms={raw!r} is not a number"
            # non-finite values pass the <= 0 check but poison every wait
            # downstream (inf overflows Event.wait; nan never compares)
            if not math.isfinite(ms) or ms <= 0:
                return None, f"deadline_ms={ms:g}: expected a finite value > 0"
        return Deadline(ms), None

    # -- endpoints ------------------------------------------------------
    def ep_upload_pdf(self, request):
        if "file" not in request.files:
            return self._jsonify({"error": "No file part"}, 400)
        file = request.files["file"]
        if file.filename == "":
            return self._jsonify({"error": "No selected file"}, 400)
        if file and file.filename.endswith(".pdf"):
            # traced into the ring as /generate is: the ingest's stages are
            # the tree's top-level spans (service.ingest_pdf_bytes)
            tr = tracing.start_trace()
            tr.attrs["kind"] = "upload"
            try:
                n = self.service.ingest_pdf_bytes(file.read(), file.filename)
            except Exception as e:  # noqa: BLE001 — parity: any failure → JSON error
                logger.exception("upload_pdf failed")
                tr.attrs["error"] = True
                return self._jsonify({"error": str(e)}, 500)
            finally:
                tracing.finish_trace(tr, self.service.traces)
            return self._jsonify(
                {"message": f"PDF processed and indexed successfully. {n} chunks created."}
            )
        return self._jsonify({"error": "Invalid file format"}, 400)

    def ep_generate(self, request):
        # W3C trace propagation (ISSUE 3): adopt the caller's trace id when
        # the request carries a valid ``traceparent`` (the web UI originates
        # one per click — deploy/web/app.py); a malformed header is treated
        # exactly like no header — a fresh trace, NEVER a 500. The same
        # trace_id then appears in the x-trace-id/traceparent response
        # headers, the inline {"trace": true} tree, and (via the contextvar)
        # every structured log line this request emits.
        ctx = obs_logging.parse_traceparent(request.headers.get("traceparent"))
        t0 = time.monotonic()
        route = request.path
        status = 200
        # every request is traced into the ring buffer (/debug/traces);
        # {"trace": true} additionally returns the span tree inline
        tr = tracing.start_trace(
            trace_id=ctx.trace_id if ctx else None,
            parent_span_id=ctx.span_id if ctx else None,
        )
        trace_id, span_id = tr.trace_id, tr.span_id
        la = self.service.lookahead
        launched_fut = None
        tenant = None
        try:
            data = request.get_json(force=True, silent=True) or {}
            user_prompt = data.get("prompt", "")
            session_id = data.get("session_id")
            if session_id is not None:
                session_id = str(session_id)
            # tenant attribution (ISSUE 18): body field wins, then the
            # x-tenant-id header, then "anon" — and the raw id is interned
            # through the cardinality-bounded tracker HERE, so everything
            # downstream (admission, journal, ledger, shadow, metrics)
            # only ever sees a tracked value or __other__
            if self.service.tenants_enabled:
                raw = data.get("tenant_id") \
                    or request.headers.get("x-tenant-id") \
                    or obs_tenants.DEFAULT_TENANT
                tenant = self.service.tenant_tracker.intern(str(raw))
                tr.attrs["tenant"] = tenant
            logger.debug("User query: %s", user_prompt)
            tr.attrs["prompt"] = user_prompt[:80]
            deadline, dl_err = self._request_deadline(data, request.headers)
            if la is not None and user_prompt and dl_err is None:
                # lookahead: start tokenize/embed+KNN NOW, before the
                # admission gate can queue this request — under load the
                # queue wait and other requests' decode hide the whole
                # retrieval, and answer() merely joins the future. Keep the
                # FUTURE (identity, not key): on shed, abandon releases it
                # only when this was the last pre-admission waiter — a shed
                # duplicate must not strand a concurrent request counting
                # on the same future, or alias a newer one at the same text
                launched_fut, _ = la.launch_tracked(
                    user_prompt, trigger="admission", session_id=session_id
                )
            if dl_err is not None:
                status = 400
                resp = self._jsonify({"error": dl_err}, 400)
            else:
                # the admission gate fronts the WHOLE pipeline (both engine
                # modes): over-cap traffic sheds here in microseconds with
                # 429/503 + Retry-After instead of queueing unboundedly
                with self.service.admission.admit(
                        deadline=deadline, tenant=tenant):
                    body = self.service.answer(
                        user_prompt, deadline=deadline,
                        session_id=session_id, tenant=tenant,
                    )
                # access line while the trace is still current (formatter
                # stamps trace_id/span_id from the contextvar)
                access_logger.info(
                    "request served", extra={
                        "route": route, "status": 200,
                        "duration_ms": round((time.monotonic() - t0) * 1e3, 2),
                    },
                )
                tree = tracing.finish_trace(tr, self.service.traces)
                tr = None
                if data.get("trace"):
                    body = dict(body)
                    body["trace"] = tree
                if data.get("timeline") and body.get("request_id") is not None:
                    # flight-journal opt-in: the request's own lifecycle
                    # chain rides home inline (continuous serving — other
                    # paths carry no scheduler id and return no timeline)
                    body = dict(body)
                    body["timeline"] = self.service.flight.timeline(
                        body["request_id"]
                    )
                resp = self._jsonify(body)
        except AdmissionRejected as e:
            if la is not None:
                # the shed request lets go of its future; the LAST waiter
                # letting go releases whatever it staged (counted as
                # waste, not a leak). abandon(None) is a no-op.
                la.abandon(launched_fut)
            status = e.status  # 429 = retry this pod; 503 = breaker/draining
            resp = self._jsonify(
                {
                    "error": "server overloaded" if e.status == 429
                    else "server draining",
                    "reason": e.reason,
                    "retry_after_s": round(e.retry_after_s, 3),
                },
                e.status,
            )
            resp.headers["Retry-After"] = str(max(1, int(e.retry_after_s + 0.5)))
        except DeadlineExceeded as e:
            if la is not None:
                # a queue-stage expiry never claimed its future: let go, or
                # under sustained overload unclaimed futures saturate the
                # inflight bound and silently disable lookahead (abandon is
                # a no-op on claimed/None futures, so post-claim stages and
                # the no-lookahead path are unaffected)
                la.abandon(launched_fut)
            status = 504
            # post-mortem capture: the journal still holds the causal
            # chain that spent this request's budget (cooldown-bounded)
            self.service.record_incident("deadline_exceeded")
            resp = self._jsonify(
                {"error": str(e), "stage": e.stage}, 504
            )
        except Exception as e:  # noqa: BLE001 — parity with rag.py:179-181
            if la is not None:
                la.abandon(launched_fut)  # same rule as the 504 path
            status = 500
            logger.exception("generate failed")
            resp = self._jsonify({"error": str(e)}, 500)
        finally:
            if tr is not None:  # non-200 path: keep the partial trace visible
                tr.attrs["error"] = True
                tr.attrs["status"] = status
                access_logger.info(
                    "request failed", extra={
                        "route": route, "status": status,
                        "duration_ms": round((time.monotonic() - t0) * 1e3, 2),
                    },
                )
                tracing.finish_trace(tr, self.service.traces)
        resp.headers["x-trace-id"] = trace_id
        resp.headers["traceparent"] = obs_logging.format_traceparent(
            trace_id, span_id
        )
        self.service.observe_http(
            route, status, tenant=tenant,
            duration_s=time.monotonic() - t0,
        )
        return resp

    def ep_index_info(self, request):
        try:
            return self._jsonify(self.service.store.info())
        except Exception as e:  # noqa: BLE001
            return self._jsonify({"error": str(e)}, 500)

    def ep_healthz(self, request):
        svc = self.service
        # the reset breaker gates READINESS only: an open breaker means the
        # device is resetting faster than it can serve — Kubernetes should
        # drain the pod (503 here) but NOT restart it (?live=1 stays 200;
        # a restart would replay warmup into the same sick device)
        breaker_open = svc.breaker.open
        # a draining lifecycle is the THIRD not-ready cause (ISSUE 19): the
        # endpoints controller must stop routing new work here while the
        # in-flight tail finishes — same 503-but-alive contract the open
        # breaker uses, so the kubelet never restarts a pod mid-drain
        lifecycle_draining = svc.lifecycle.draining
        draining = (breaker_open and svc.ready) or lifecycle_draining
        ready = svc.ready and not breaker_open and not lifecycle_draining
        live = bool(request.args.get("live"))
        body = {
            # ?live=1 is the LIVENESS form (deploy.yaml): 200 whenever the
            # process can answer HTTP at all — a pod still warming (or
            # re-warming after an engine reset) must be not-ready, not dead,
            # or the kubelet would restart it into the same warmup
            "status": ("alive" if live else "ok") if (ready or live)
            else ("draining" if draining else "warming"),
            # fleet-dashboard segmentation fields (ISSUE 2 satellite)
            "uptime_s": round(time.monotonic() - svc.started_at, 1),
            "version": _package_version(),
            "engine_mode": _engine_mode(svc.scheduler),
        }
        try:
            import jax

            devices = jax.devices()
            body["device_platform"] = devices[0].platform if devices else "none"
            body["device_count"] = len(devices)
        except Exception:  # noqa: BLE001 — health must answer even off-JAX
            body["device_platform"] = "unknown"
            body["device_count"] = 0
        body["ready"] = ready
        body["breaker_open"] = breaker_open
        body["breaker_recent_resets"] = svc.breaker.recent_resets()
        body["draining"] = lifecycle_draining
        return self._jsonify(body, 200 if (ready or live) else 503)

    def ep_drain(self, request):
        """Begin a graceful drain (the deploy.yaml preStop hook's target;
        also an operator's manual lever). Idempotent — a second POST
        reports the drain already in progress. The response returns
        immediately; the coordinator's watcher thread finishes the
        in-flight tail, persists, and exits on its own schedule."""
        lc = self.service.lifecycle
        started = lc.begin_drain("http")
        return self._jsonify({
            "state": lc.state,
            "started": started,
            "active": self.service.admission.active,
            "deadline_s": lc.deadline_s,
        }, 202 if started else 200)

    def ep_metrics(self, request):
        """One scrape sees everything (obs/metrics.py): the request/stage/
        TTFT/inter-token histograms, coalesce waits, compile counters,
        occupancy/queue gauges, engine stats and prefix-cache state — all
        families live in the service's registry, engine stats as callback
        metrics read at scrape time. Prometheus text exposition by default;
        the flat JSON snapshot stays available under Accept:
        application/json (same values — tests/test_obs.py pins it)."""
        reg = self.service.metrics
        self.service._sync_kernel_builds()
        if "application/json" in (request.headers.get("Accept") or ""):
            return self._jsonify(reg.snapshot())
        return self._Response(
            reg.render_prometheus(), status=200,
            content_type="text/plain; version=0.0.4; charset=utf-8",
        )

    def ep_slo(self, request):
        """Compliance + burn state as JSON (obs/slo.py) — computed from the
        SAME histograms/counters ``/metrics`` exposes, so the numbers an
        operator pages on and the numbers a dashboard plots cannot diverge.
        ``?force=1`` bypasses the short evaluation cache."""
        try:
            # per-tenant burn (ISSUE 18): reconcile the spec set against
            # the tracked tenants before evaluating, so the report's
            # "tenants" section covers exactly the tracker's current top-K
            self.service.slo.set_tenants(
                self.service.tenant_tracker.tracked()
            )
            report = self.service.slo.evaluate(
                force=bool(request.args.get("force"))
            )
            return self._jsonify(report)
        except Exception as e:  # noqa: BLE001
            logger.exception("slo evaluation failed")
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_traces(self, request):
        """Recent request span trees from the in-memory ring buffer, and
        under ``boot`` the tree of ``warmup()`` (None until it has returned);
        with ``?kind=dispatch`` the ring of the dispatches launched where no
        request's trace was current (the batch scheduler's), each tree with
        its ``seq``, ``path``, ``rows``, ``reason`` and its riders' trace ids.
        Same 403-unless-armed contract as every ``/debug`` route."""
        if not self._debug_enabled():
            return self._debug_forbidden()
        try:
            limit = request.args.get("limit", type=int)
            if request.args.get("kind") == "dispatch":
                return self._jsonify({"traces": self.service.dispatches.list(limit)})
            return self._jsonify({"traces": self.service.traces.list(limit),
                                  "boot": self.service.boot_trace})
        except Exception as e:  # noqa: BLE001
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_timeline(self, request, rid: int = 0):
        """One request's flight-journal lifecycle: the ordered event chain
        (admit → windows → eos/evict/preempt/resubmit → complete) with
        inter-event deltas, keyed by the scheduler request id the
        ``/generate`` response carries as ``request_id``."""
        if not self._debug_enabled():
            return self._debug_forbidden()
        try:
            tl = self.service.flight.timeline(int(rid))
            if not tl["events"]:
                return self._jsonify(
                    {"error": f"no journaled events for request {rid} "
                              "(completed past the ring, or never admitted)"},
                    404,
                )
            return self._jsonify(tl)
        except Exception as e:  # noqa: BLE001
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_incidents(self, request):
        """The incident-bundle spool: ``GET /debug/incidents`` lists
        bundles ({id, trigger, ts, path}), ``?id=<bundle_id>`` returns one
        bundle's full self-contained JSON (journal + metrics + config
        fingerprint + traces — feed it to scripts/flightview.py)."""
        if not self._debug_enabled():
            return self._debug_forbidden()
        try:
            spool = self.service.incidents
            if spool is None:
                return self._jsonify({"incidents": []})
            bid = request.args.get("id")
            if bid:
                bundle = spool.load(bid)
                if bundle is None:
                    return self._jsonify(
                        {"error": f"no incident bundle {bid!r}"}, 404
                    )
                return self._jsonify(bundle)
            return self._jsonify({"incidents": spool.list()})
        except Exception as e:  # noqa: BLE001
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_goodput(self, request):
        """The goodput/cost capacity picture (obs/goodput.py,
        docs/GOODPUT.md): per-category chip-time split, roofline
        classification + rolling MFU per executable kind, and
        cost-per-query percentiles. Same 403-unless-armed contract as
        every ``/debug`` route; ``scripts/flightview.py --goodput``
        renders the same report offline from a journal or incident
        bundle."""
        if not self._debug_enabled():
            return self._debug_forbidden()
        try:
            return self._jsonify(self.service.goodput_report())
        except Exception as e:  # noqa: BLE001
            logger.exception("goodput report failed")
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_quality(self, request):
        """The shadow auditor's quality report (obs/shadow.py,
        docs/OBSERVABILITY.md "Shadow quality auditor"): audit outcomes,
        divergence rate, logit-err / first-divergence distributions, and
        per-approximation attribution — the live measurement of every
        approximation contract in the serving path. Same 403-unless-armed
        contract as every ``/debug`` route;
        ``scripts/flightview.py --quality`` rebuilds the same report
        offline from a journal or incident bundle."""
        if not self._debug_enabled():
            return self._debug_forbidden()
        try:
            return self._jsonify(self.service.quality_report())
        except Exception as e:  # noqa: BLE001
            logger.exception("quality report failed")
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_tenants(self, request):
        """The per-tenant cost/usage/quality report (obs/tenants.py,
        docs/OBSERVABILITY.md "Tenant attribution"): journal-derived
        per-tenant arrivals/completions/sheds/tokens/chip-seconds/cost
        plus the live tracker table, ledger rollups and per-tenant SLO
        burn. Same 403-unless-armed contract as every ``/debug`` route;
        ``scripts/flightview.py --tenants`` rebuilds the report half
        byte-identically from an exported journal."""
        if not self._debug_enabled():
            return self._debug_forbidden()
        try:
            return self._jsonify(self.service.tenant_report())
        except Exception as e:  # noqa: BLE001
            logger.exception("tenant report failed")
            return self._jsonify({"error": str(e)}, 500)

    def ep_debug_faults(self, request):
        """Fault-injection control (resilience/faults.py) — enabled ONLY
        when the process started with ``TPU_RAG_FAULTS`` in its environment
        (a production pod is not remotely fault-armable by default).

        GET returns the armed state; POST ``{"site": s, "times": n}`` arms
        one site, POST ``{"clear": true}`` disarms everything.
        """
        if not faults.endpoint_enabled():
            return self._jsonify(
                {"error": "fault injection disabled (set TPU_RAG_FAULTS)"}, 403
            )
        try:
            if request.method == "POST":
                data = request.get_json(force=True, silent=True) or {}
                if data.get("clear"):
                    faults.clear()
                elif "site" in data:
                    faults.arm(str(data["site"]), int(data.get("times", 1)))
                else:
                    return self._jsonify(
                        {"error": "expected {'site': ..., 'times': N} or "
                                  "{'clear': true}"}, 400
                    )
            return self._jsonify(
                {"enabled": True, "armed": faults.armed(),
                 "sites": list(faults.SITES)}
            )
        except (TypeError, ValueError) as e:  # unknown site / bad count
            return self._jsonify({"error": str(e)}, 400)
        except Exception as e:  # noqa: BLE001
            return self._jsonify({"error": str(e)}, 500)

    def ep_profile(self, request):
        """Capture a jax.profiler device trace (xprof).

        Two modes (body keys):
        - ``{"seconds": N, "dir": str?}`` — NON-BLOCKING: starts a
          background capture window around live traffic and returns
          immediately; a timer thread stops the trace after N seconds.
          409 while a window is already open.
        - ``{"prompt": str?, "dir": str?}`` — legacy blocking mode: traces
          one sample query inside the handler.
        """
        try:
            import jax

            data = request.get_json(force=True, silent=True) or {}
            trace_dir = data.get("dir", "/tmp/tpu_rag_trace")

            def _busy_response():
                until = self._profile_until
                return self._jsonify(
                    {
                        "error": "a profile capture is already running",
                        # None for a blocking capture (end time unknown)
                        "until": until if until != float("inf") else None,
                    },
                    409,
                )

            if "seconds" in data:
                seconds = float(data["seconds"])
                if not 0 < seconds <= 300:
                    return self._jsonify(
                        {"error": "seconds must be in (0, 300]"}, 400
                    )
                with self._profile_lock:
                    if self._profile_until is not None:
                        return _busy_response()
                    jax.profiler.start_trace(trace_dir)
                    self._profile_until = time.time() + seconds

                def _stop():
                    try:
                        jax.profiler.stop_trace()
                    except Exception:  # noqa: BLE001 — stop must not kill the timer
                        logger.exception("profile stop failed")
                    finally:
                        with self._profile_lock:
                            self._profile_until = None

                t = threading.Timer(seconds, _stop)
                t.daemon = True
                t.start()
                return self._jsonify(
                    {
                        "trace_dir": trace_dir,
                        "seconds": seconds,
                        "message": "background capture started around live "
                        "traffic; open with tensorboard or xprof",
                    }
                )
            # legacy blocking mode shares the SAME single-capture guard:
            # jax.profiler allows only one active trace, so racing a window
            # capture would otherwise surface as a confusing 500
            with self._profile_lock:
                if self._profile_until is not None:
                    return _busy_response()
                self._profile_until = float("inf")  # blocking: end unknown
            try:
                prompt = data.get("prompt", "What is this document about?")
                with jax.profiler.trace(trace_dir):
                    result = self.service.answer(prompt)
            finally:
                with self._profile_lock:
                    self._profile_until = None
            return self._jsonify(
                {
                    "trace_dir": trace_dir,
                    "timings": result.get("timings"),
                    "message": "trace captured; open with tensorboard or xprof",
                }
            )
        except Exception as e:  # noqa: BLE001
            logger.exception("profile failed")
            return self._jsonify({"error": str(e)}, 500)

    # -- WSGI plumbing --------------------------------------------------
    def __call__(self, environ, start_response):
        request = self._Request(environ)
        adapter = self.url_map.bind_to_environ(environ)
        try:
            endpoint, args = adapter.match()
            response = getattr(self, f"ep_{endpoint}")(request, **args)
        except self._HTTPException as e:
            response = e
        return response(environ, start_response)

    def test_client(self):
        from werkzeug.test import Client

        return Client(self)

    def run(self, host: str = "0.0.0.0", port: int = 5001, threaded: bool = True):
        from werkzeug.serving import run_simple

        run_simple(host, port, self, threaded=threaded)


def create_app(service: RagService) -> WsgiApp:
    return WsgiApp(service)

"""Continuous (slot-based) batching: requests join the running decode batch.

The reference serves strictly sequentially — one ``model.generate`` at a
time on a single-threaded Flask dev server (/root/reference/llm/rag.py:204);
a request arriving mid-generation waits for the whole previous one. The
coalescing ``BatchScheduler`` (engine/batching.py) improved that to
group-at-start, but nothing could join a batch in flight.

Here decoding runs over ``B`` persistent KV **slots** with per-row cache
frontiers (``LlamaModel(row_frontier=True)``: each row's fed token is
scatter-written at its own ``kv_len``), so rows at different generation
depths decode together. Between device steps the scheduler admits waiting
requests into free slots — a request arriving mid-generation starts decoding
on the very next step instead of queueing behind the current batch.

Anatomy (all AOT-compiled, static shapes):
- ``_prefill(S)``: one B=1 forward over a bucketed prompt → that row's
  ``[L, 1, K, S, hd]`` KV block + the first sampled token;
- ``_insert(S)``: splice the KV block + per-row state into slot ``row``;
- ``_step``: ``decode_sync_steps`` decode tokens for all ``B`` slots (per-row
  windows mask inactive/mismatched rows) as one device program, returning a
  ``[k, B]`` token plane to the host — one transfer per window, overlapped
  with the next admission check. ``k = 1`` admits between every token;
  ``k > 1`` amortizes dispatch/fetch latency (decisive on a slow host link)
  for up to ``k`` steps of admission latency.

Trade-off vs the fused one-shot path (engine.py): per-window host sync and a
scatter cache write, in exchange for no head-of-line blocking. The one-shot
path remains the fastest way to run a KNOWN batch (the benchmark's cells do).
"""

from __future__ import annotations

import itertools
import logging
import queue
import random
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import MeshContext, serving_device_kind
from rag_llm_k8s_tpu.engine.engine import (
    EngineStats,
    _isin,
    build_identity,
    maybe_fuse_params,
    maybe_quantize_params,
    param_avals,
)
from rag_llm_k8s_tpu.engine.kv_pool import KVBlockPool, NULL_BLOCK, PoolExhausted
from rag_llm_k8s_tpu.engine.sampling import (
    accept_drafts,
    sample_targets_per_row,
    sample_token_per_row,
)
from rag_llm_k8s_tpu.engine.speculative import (
    adaptive_draft_len,
    fold_acceptance,
    prompt_lookup_draft,
)
from rag_llm_k8s_tpu.models.llama import (
    LlamaModel,
    make_kv_arena,
    make_kv_cache,
    mask_window,
)
from rag_llm_k8s_tpu.obs import flight
from rag_llm_k8s_tpu.obs import goodput as obs_goodput
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.obs.tracing import phase_scope
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.resilience.deadline import Deadline, DeadlineExceeded
# the scheduler's decision core lives behind the sim seam (ISSUE 17):
# admission verdicts, window planning, budget splits and preemption
# ordering are pure functions in sim/policy.py, shared verbatim with the
# replay driver and the pure-host simulator — this module keeps only the
# device execution and the stateful reclaim loops around them
from rag_llm_k8s_tpu.sim import policy as sim_policy
from rag_llm_k8s_tpu.utils.buckets import bucket_len

logger = logging.getLogger(__name__)

# request ids are PROCESS-global (not per-scheduler): the flight journal
# (obs/flight.py) keys every lifecycle event on this id, and two schedulers
# in one process (tests, a prefill/decode pair) must never alias each other's
# timelines. itertools.count is atomic under CPython — no lock needed.
_REQUEST_IDS = itertools.count(1)


def _tenant_attr(ledger, rid: int) -> Dict[str, str]:
    """``{"tenant": ...}`` for an admit-time flight emit when the edge
    stamped one on this request (goodput.note_tenant at submit), else
    empty — admit sites only know the rid, and an un-attributed journal
    must not grow ``tenant: None`` noise on every event."""
    t = ledger.tenant_of(rid) if ledger is not None else None
    return {"tenant": t} if t else {}


class EngineStateLost(RuntimeError):
    """A device failure invalidated donated engine buffers; the engine has
    been reset and every request that was in flight is gone."""


@dataclass
class _Slot:
    """Host-side view of one device slot."""

    request_id: int = -1
    tokens: List[int] = field(default_factory=list)
    remaining: int = 0
    active: bool = False
    # paged mode only: the host mirror of this row's logical frontier (an
    # UPPER bound — EOS mid-window stops the device early; the mirror only
    # drives block pre-allocation, over-allocation frees at retire), the
    # admission sequence (preemption picks the newest victims first), and
    # the prompt's true token count (resubmission bookkeeping)
    kv_ub: int = 0
    admit_seq: int = 0
    prompt_len: int = 0
    shared_tokens: int = 0  # tokens served by ref-shared prefix blocks
    # speculative decoding (spec_paged): the row's draft corpus — the
    # assembled prompt + every emitted token, the history prompt-lookup
    # matches over — and the decayed acceptance EMA that drives its
    # adaptive draft length (None = no evidence yet; engine/speculative.py)
    history: List[int] = field(default_factory=list)
    spec_ema: Optional[float] = None
    # interleaved chunked prefill (interleave_prefill): the slot is RESERVED
    # for an in-flight chunked admission — not yet decoding (active=False on
    # host AND device), but not free either. The admission record itself
    # lives in ``_chunk_admissions``; the flag keeps ``free_slots`` honest.
    prefilling: bool = False
    # flight-WAL watermark: how many of ``tokens`` have been journaled as
    # token_emit events (``_journal_emitted``); only the delta past it is
    # re-journaled each window, so the WAL carries each token once
    wal_mark: int = 0


class ContinuousEngine:
    """Owns the persistent slot state on device; NOT thread-safe by itself —
    the scheduler serializes all calls."""

    def __init__(
        self,
        config: LlamaConfig,
        params,
        sampling: SamplingConfig = SamplingConfig(),
        engine_config: EngineConfig = EngineConfig(),
        dtypes: DTypePolicy = DTypePolicy(),
        mesh: Optional[MeshContext] = None,
        pad_id: int = 0,
    ):
        from rag_llm_k8s_tpu.models import families

        # the slot engine and its paged pool hold per-head K/V planes only
        families.refuse_unsupported(config, engine_config, mesh, engine="continuous")
        self.config = config
        self.sampling = sampling
        self.engine_config = engine_config
        self.dtypes = dtypes
        self.mesh = mesh
        self.pad_id = pad_id
        self.B = engine_config.max_batch_size
        self.sync_steps = max(1, engine_config.decode_sync_steps)
        self.T = -(-engine_config.max_seq_len // 128) * 128
        # only buckets that leave decode room fit a slot; an empty ladder is
        # a config error — fail at construction, not per-request
        self.buckets = tuple(
            b for b in engine_config.prompt_buckets if b < self.T
        )
        if not self.buckets:
            raise ValueError(
                f"no prompt bucket in {engine_config.prompt_buckets} fits "
                f"max_seq_len={engine_config.max_seq_len} (slot length {self.T})"
            )
        jmesh = mesh.mesh if mesh is not None and mesh.tp > 1 else None
        if engine_config.kv_quant not in ("bf16", "int8"):
            raise ValueError(
                f"kv_quant={engine_config.kv_quant!r}: expected 'bf16' or 'int8'"
            )
        self.kv_quant = engine_config.kv_quant
        # ---- paged KV (block-pool arena; EngineConfig.kv_paged) ---------
        self.paged = bool(getattr(engine_config, "kv_paged", False))
        self.kv_pool: Optional[KVBlockPool] = None
        if self.paged:
            # tp>1 is served by the HEAD-SHARDED arena (each device holds
            # K/tp heads of every block; ops.attention.paged_partition_specs)
            # — the only constraint is that the kv-head count tiles the axis
            engine_config.validate_tp_layout(
                mesh.tp if mesh is not None else 1, config.num_kv_heads
            )
            bs = int(engine_config.kv_block_size)
            min_tile = 32 if self.kv_quant == "int8" else 16
            if bs < 1 or bs % min_tile:
                raise ValueError(
                    f"kv_block_size={bs} must be a positive multiple of the "
                    f"Mosaic {min_tile}-row tile (kv_quant={self.kv_quant!r})"
                )
            bad = [b for b in self.buckets if b % bs]
            if bad or self.T % bs:
                raise ValueError(
                    f"kv_block_size={bs} must divide every prompt bucket "
                    f"{self.buckets} and the slot length {self.T}"
                )
            # max logical blocks any row can hold (tables are [B, MB])
            self.MB = self.T // bs
            usable = int(engine_config.kv_pool_blocks) or self.B * self.MB
            if usable < self.MB:
                raise ValueError(
                    f"kv_pool_blocks={usable}: the pool must hold at least "
                    f"one full row ({self.MB} blocks of {bs})"
                )
            self.kv_pool = KVBlockPool(usable + 1, bs)  # +1: the null block
            self.block_size = bs
            self._tables_host = np.zeros((self.B, self.MB), np.int32)
            self._tables_dev = None
            self._tables_dirty = True
            self._slot_blocks: List[List[int]] = [[] for _ in range(self.B)]
            # block-granular prefix reuse: chain_key -> (full block ids,
            # covered tokens, prefix length); the pool holds one cache ref
            # per registered block so rows come and go copy-free
            self._prefix_blocks: "Dict[object, Tuple[List[int], int, int]]" = {}
            # covered tokens across registrations, maintained at every
            # register/evict site (all on the scheduler thread): the
            # fragmentation gauge's scrape-thread callback reads this ONE
            # int instead of iterating the dict the scheduler mutates
            self._registered_tokens = 0
            # admissions that mapped a registration's shared blocks since it
            # was (re-)registered — release_prestaged(only_unused=True)
            # keeps a registration live traffic has proven hot
            self._prefix_uses: Dict[object, int] = {}
            # hotness tier per registration (engine/tiering.py): admission
            # and growth pressure reclaim non-hot registrations FIRST (and
            # even while rows decode — a warm chunk's KV survives in the
            # prefix cache, one re-scatter away), so tier occupancy, not
            # raw headroom, decides backpressure
            self._prefix_tier: Dict[object, str] = {}
            # non-hot registered blocks right now — a single int the
            # admission gate's reclaimable hint reads LOCK-FREE from the
            # HTTP threads (maintained only on the scheduler thread)
            self._reclaimable_blocks = 0
            # registration GENERATION per chain key: a deferred lookahead
            # release presents the generation it staged, so it can never
            # free a registration a later admission re-created at the same
            # key (uses resets to 0 on re-registration — the counter alone
            # can't tell the two apart)
            self._prefix_reg_gen: Dict[object, int] = {}
            self._reg_seq = 0
            self._admit_seq = 0
            self._preempted: List[Tuple[int, List[int]]] = []
            self._blocks_at_retire: Dict[int, int] = {}
            # CHUNK-granular canonical registrations (reuse="chunk"):
            # seg_key -> (full block ids, canonical logical offset, segment
            # length, cache-entry creation stamp, tokens-counted flag).
            # Unlike _prefix_blocks (whole-chain sharing, copy-free), these
            # are the SOURCE blocks a per-chunk admission re-rotates into
            # freshly allocated destination blocks at arbitrary order —
            # content-safe at any position because K is position-shifted in
            # the copy. Stamp identity ties each registration to the
            # prefix-cache entry it mirrors, so a rebuilt entry silently
            # retires the stale registration (plan lookups decline on
            # mismatch). OrderedDict: plan hits move-to-end, so the cap
            # (PrefixCacheConfig.chunk_pool_regs) evicts least-recently-
            # PLANNED, not oldest-inserted.
            self._chunk_regs: "OrderedDict[str, tuple]" = OrderedDict()
            self._chunk_reg_tokens = 0
        # ---- speculative decoding (paged draft-and-verify; ISSUE 13) ----
        # Each sync window may run as ONE multi-token VERIFY step instead
        # of decode_sync_steps single-token steps: the host drafts up to
        # spec_K continuation tokens per row by prompt-lookup over the
        # row's own history (the retrieved chunks ARE the draft corpus —
        # no draft model), the device feeds last_tok + drafts through the
        # block tables in one chunked forward, and target-matching
        # acceptance keeps the longest prefix equal to what the vanilla
        # step would have sampled — greedy AND seeded streams stay
        # byte-identical by construction. docs/SPECULATIVE.md.
        self.spec_on = bool(getattr(engine_config, "spec_paged", False))
        # requests whose rows ever OFFERED drafts to a verify window — the
        # per-request approximation fingerprint's spec_verify source
        # (obs/shadow.py). Engine state, NOT the goodput ledger: turning
        # attribution accounting off must not erase audit fingerprints.
        # Popped at delivery / discard; bounded against never-delivered
        # rids by the discard sweep sharing the ledger's cleanup sites.
        self._spec_rids: set = set()
        if self.spec_on:
            if not self.paged:
                raise ValueError(
                    "spec_paged=True requires kv_paged=True — the verify "
                    "step writes drafted positions through block tables "
                    "(the dense continuous path does not speculate)"
                )
            self.spec_K = int(engine_config.spec_paged_tokens)
            if self.spec_K < 1:
                raise ValueError(
                    f"spec_paged_tokens={self.spec_K}: expected >= 1"
                )
            self.spec_ngram = max(1, int(engine_config.spec_ngram))
            self.spec_min_accept = float(engine_config.spec_paged_min_accept)
            if not 0.0 <= self.spec_min_accept <= 1.0:
                raise ValueError(
                    f"spec_paged_min_accept={self.spec_min_accept}: an "
                    "acceptance-RATE floor must lie in [0, 1]"
                )
        # ---- unified ragged sync windows (chunked prefill; ISSUE 16) ----
        # With interleave_prefill on, admission no longer prefills in one
        # phase-separated shot: admit_many RESERVES a row and queues a
        # chunked-admission record, and each mixed window feeds a budgeted
        # slice of pending prompts alongside every active decode lane
        # through ONE chunked forward (paged_chunk_attention's third
        # consumer, after prefix splicing and speculative verify). Streams
        # stay byte-identical to the phase-separated scheduler because
        # sampling is (seed, position)-keyed: the first token of a prompt
        # of length P folds fold_in(row_key, P) on its FINAL chunk exactly
        # as the one-shot admission does, and decode lanes fold wi+1
        # exactly as step_paged does — window shape cannot change draws.
        self.interleave_on = bool(
            getattr(engine_config, "interleave_prefill", False)
        )
        # in-flight chunked admissions, admission order (= scheduling
        # order; FIFO keeps TTFT fair). rid -> dict with the reserved row,
        # truncated prompt, progress frontier, UNFOLDED row key, decode
        # budget, admission stamps. Initialized unconditionally: reset(),
        # evict_requests and the planner touch it without re-checking the
        # knob.
        self._chunk_admissions: "OrderedDict[int, dict]" = OrderedDict()
        if self.interleave_on:
            engine_config.validate_interleave()  # requires kv_paged, ranges
            self.chunk_tokens = int(engine_config.prefill_chunk_tokens)
            self.window_budget = int(engine_config.window_token_budget) or (
                self.B + self.chunk_tokens
            )
        # ---- goodput ledger (obs/goodput.py; ISSUE 14) ------------------
        # every device sync window — admission prefills, decode windows,
        # verify windows — is attributed into the closed category set with
        # a per-request chip-second split; the scheduler pops each
        # request's figures at delivery (/generate timings), /metrics
        # reads the rolling totals, and each window journals ONE
        # goodput_window flight event so flightview --goodput reconstructs
        # the same report offline. Host-side dict math only; the
        # its cost to a decode step is not measured on the chip (PERF.md §7).
        self.ledger = obs_goodput.ledger_for(
            config, engine_config, device_kind=serving_device_kind(mesh)
        )
        # request ids whose NEXT admission re-feeds tokens already computed
        # once (preemption / reset resubmission) — that admission's real
        # token lanes are attributed preempt_rework, exactly once (the
        # scheduler marks before requeueing; the admission pops)
        self._rework_rids: "set" = set()
        self.params, fused = maybe_fuse_params(params, engine_config, mesh)
        self.params, quantized = maybe_quantize_params(self.params, engine_config)
        self.model = LlamaModel(
            config, dtypes, attn_impl=engine_config.attn_impl, mesh=jmesh,
            fused_qkv=fused, quantized=quantized, kv_quant=self.kv_quant,
        )
        self.model_step = self.model.copy(row_frontier=True)
        # chunked variant for prefix-cache admissions: the suffix prefills
        # over a spliced cached-prefix block with offset causality
        self.model_chunked = self.model.copy(chunked=True)
        if self.paged:
            # paged variants: same static switches + the block-table arg
            self.model_step_paged = self.model.copy(row_frontier=True, paged=True)
            self.model_chunked_paged = self.model.copy(chunked=True, paged=True)
        self._build_identity = build_identity(
            "continuous", families.of(config), config, engine_config, dtypes, sampling, mesh,
            pad_id, fused=fused, quantized=quantized)
        self._compiled: Dict[Tuple[str, int, int], jax.stages.Compiled] = {}
        # ---- persistent device state -----------------------------------
        # the cache rides as a TUPLE pytree through every executable:
        # (k, v) bf16, or (k, v, k_scale, v_scale) with kv_quant="int8" —
        # the int8 payloads and fp32 scale planes donate/rebuild together
        self._cache = self._fresh_cache()
        # per-device arena residency, captured ONCE from the freshly built
        # planes (sharding is static: reset() rebuilds identical shapes, so
        # this never goes stale). The scrape-thread gauge reads this dict —
        # touching the LIVE planes there would race a step's donation and
        # crash /metrics with "Array has been deleted"
        self._arena_device_bytes: Dict[str, float] = {}
        if self.paged:
            for plane in self._cache:
                for sh in plane.addressable_shards:
                    did = str(getattr(sh.device, "id", 0))
                    self._arena_device_bytes[did] = (
                        self._arena_device_bytes.get(did, 0.0)
                        + float(sh.data.nbytes)
                    )
        self._kv_start = self._put(jnp.zeros((self.B,), jnp.int32))
        self._kv_len = self._put(jnp.zeros((self.B,), jnp.int32))
        self._last_tok = self._put(jnp.zeros((self.B,), jnp.int32))
        self._active = self._put(jnp.zeros((self.B,), bool))
        # per-row PRNG keys: a request's draws are keyed by its own seed and
        # token position, so they do not depend on its batchmates (solo vs
        # shared-batch runs of the same seeded request sample identically)
        self._rng_keys = self._put(jnp.zeros((self.B, 2), jnp.uint32))
        self._rng = jax.random.PRNGKey(sampling.seed)  # seedless-key stream
        # ---- host-side bookkeeping -------------------------------------
        self.slots = [_Slot() for _ in range(self.B)]
        self.steps = 0  # global decode steps executed (tests/metrics)
        self.stats = EngineStats()  # /metrics parity with InferenceEngine
        # observability handles (obs/metrics.py): standalone engines report
        # into the process default registry; RagService rebinds to its own
        self.bind_metrics(obs_metrics.default_registry())

    def bind_metrics(self, registry) -> None:
        """Point this engine's metric handles at ``registry``. Unlike the
        one-shot engine, the slot engine's host loop sees real per-request
        and per-window boundaries, so TTFT and inter-token latency here are
        measured EXACTLY (admission → first token; step window / k)."""
        self._obs = registry
        self._m_ttft = registry.histogram(
            "rag_time_to_first_token_seconds",
            "submit-to-first-token (queue + coalesce + prefill + fetch)",
            buckets=obs_metrics.REQUEST_BUCKETS,
        )
        self._m_itl = registry.labeled_histogram(
            "rag_decode_inter_token_seconds",
            "per-decoded-token latency (mode label: oneshot_est is call "
            "duration over decode steps; continuous is exact per window)",
            buckets=obs_metrics.TOKEN_LATENCY_BUCKETS,
        ).labels(mode="continuous")
        # step-time breakdown (ISSUE 3 per-device telemetry): where one sync
        # window's wall clock goes — the device step + token-plane fetch
        # (phase=device_fetch), host retire bookkeeping (phase=host_drain),
        # and admission work between windows (phase=admit: prefill + insert
        # + first-token fetch for a whole admitted chunk). On a dashboard, a
        # growing device_fetch share under flat host_drain is link pressure;
        # a growing admit share is churn (short answers re-admitting).
        step_fam = registry.labeled_histogram(
            "rag_continuous_step_seconds",
            "continuous-engine step-time breakdown (phase label: "
            "device_fetch | host_drain | admit)",
            buckets=obs_metrics.LATENCY_BUCKETS,
        )
        self._m_step_device = step_fam.labels(phase="device_fetch")
        self._m_step_drain = step_fam.labels(phase="host_drain")
        self._m_step_admit = step_fam.labels(phase="admit")
        # paged KV pool occupancy (families exist in every mode so scrapes
        # and dashboards stay uniform; they read 0 under the dense cache)
        pool = self.kv_pool
        registry.labeled_gauge(
            "rag_kv_pool_blocks_total",
            "allocatable physical KV blocks (paged mode; 0 dense)",
        ).labels_callback(
            lambda: float(pool.usable_blocks()) if pool is not None else 0.0
        )
        registry.labeled_gauge(
            "rag_kv_pool_blocks_in_use",
            "physical KV blocks currently referenced (paged mode)",
        ).labels_callback(
            lambda: float(pool.blocks_in_use()) if pool is not None else 0.0
        )
        registry.labeled_gauge(
            "rag_kv_pool_fragmentation",
            "fraction of allocated KV token slots not holding live KV "
            "(internal fragmentation — pad/tail waste of the block layout)",
        ).labels_callback(
            lambda: (
                pool.fragmentation(self.pool_used_tokens())
                if pool is not None else 0.0
            )
        )
        self._m_pool_preempt = registry.counter(
            "rag_kv_pool_preemptions_total",
            "rows preempted mid-decode by pool exhaustion (resubmitted by "
            "the scheduler; callers see latency, not errors)",
        )
        # per-device arena residency (tp triage: head-sharded arenas show
        # ~total/tp per chip — a device whose share diverges is holding
        # something else). Values come from the construction-time static
        # dict, never the live planes (see __init__)
        dev_fam = registry.labeled_gauge(
            "rag_kv_pool_device_bytes",
            "paged KV arena bytes resident per device (head-sharded over "
            "tp: ~arena_total/tp per chip; 0 under the dense cache)",
        )
        for did in sorted(self._arena_device_bytes) or ["0"]:
            dev_fam.labels_callback(
                lambda did=did: self._arena_device_bytes.get(did, 0.0),
                device=did,
            )
        # (the rag_spec_tokens_total / rag_spec_acceptance_rate families
        # are registered by the SERVICE — server/app.py — off the shared
        # EngineStats fields, so they exist uniformly in every serving
        # mode; standalone engines expose the same numbers via .stats)

    def warmup(self, batch_sizes=None, buckets=None):
        """AOT-compile every executable serving will hit (readiness gating).
        ``batch_sizes`` here sizes the ADMISSION-group ladder (rounded to
        powers of two): a scheduler that admits queued requests in groups
        should warm the group sizes it will use, or the first burst pays a
        mid-serving compile. Slot geometry itself is fixed at construction."""
        sizes = {1}
        for b in batch_sizes or (1,):
            n = 1
            while n * 2 <= min(max(1, b), self.B):
                n *= 2
                sizes.add(n)  # the WHOLE pow2 ladder: admit_many splits
                # arbitrary group sizes into pow2 chunks, so every rung
                # below the cap is reachable at runtime
        for S in buckets or self.buckets:
            if S not in self.buckets:
                continue  # admit can never use a bucket without decode room
            for n in sorted(sizes):
                if self.paged:
                    self._get("prefill_paged", S, n)
                    self._get("insert_paged", S, n)
                else:
                    self._get("prefill", S, n)
                    self._get("insert", S, n)
        self._get("step_paged" if self.paged else "step", self.sync_steps)
        if self.spec_on:
            # the verify executable AND the plain window both serve under
            # speculation (windows where no row drafts fall back), so warm
            # both — the first quoting answer must not pay a compile
            self._get("verify_paged", self.spec_K)
        if self.interleave_on:
            # the mixed decode+chunk window — the first interleaved
            # admission must not pay a compile either
            self._get("mixed_step", self.chunk_tokens)
        self._warm_state_ops()

    def _warm_state_ops(self):
        """Admission and retirement touch the slot state with a few
        op-by-op device calls (row-key split and store, active-mask clear),
        and each of those compiles on first use — inside the first request
        unless it happens here. Results are thrown away: ``_rng`` does not
        advance, so streams are what they were."""
        _, row_key = jax.random.split(self._rng)
        self._rng_keys.at[0].set(self._put(row_key))
        self._active & self._put(jnp.asarray(np.ones((self.B,), bool)))

    def _put(self, x, sharding=None):
        """Place a host/device value to match a lowered aval's sharding;
        identity off-mesh."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, sharding or self.mesh.replicated)

    def _fresh_cache(self):
        """The cache-state tuple for the full [B, T] slot block (__init__).
        On a mesh the zeros are built DIRECTLY sharded (jit with
        out_shardings) — materializing the full cache on one device and
        resharding would transiently need tp× the steady per-chip footprint,
        an OOM risk at construction and at every post-failure reset."""

        def build():
            if self.paged:
                cache = make_kv_arena(
                    self.config, self.kv_pool.num_blocks, self.block_size,
                    self.dtypes.compute_dtype, quant=self.kv_quant,
                )
            else:
                cache = make_kv_cache(
                    self.config, self.B, self.T, self.dtypes.compute_dtype,
                    quant=self.kv_quant,
                )
            if self.kv_quant == "int8":
                return (cache.k, cache.v, cache.k_scale, cache.v_scale)
            return (cache.k, cache.v)

        if self.mesh is None:
            return build()
        return jax.jit(build, out_shardings=self._cache_shardings())()

    def reset(self):
        """Rebuild ALL device state after a failed step. A step that dies
        during device execution has already invalidated its DONATED inputs
        (cache, kv_len, last_tok, active) — merely deactivating slots would
        leave the next admit holding deleted arrays, bricking the engine
        while /healthz still reports ready."""
        flight.emit(
            "reset",
            in_flight=sum(1 for s in self.slots if s.active),
        )
        self.slots = [_Slot() for _ in range(self.B)]
        self._cache = self._fresh_cache()
        self._kv_start = self._put(jnp.zeros((self.B,), jnp.int32))
        self._kv_len = self._put(jnp.zeros((self.B,), jnp.int32))
        self._last_tok = self._put(jnp.zeros((self.B,), jnp.int32))
        self._active = self._put(jnp.zeros((self.B,), bool))
        self._rng_keys = self._put(jnp.zeros((self.B, 2), jnp.uint32))
        if self.paged:
            # every block back to the free list: the arena was rebuilt, so a
            # ref held across reset() would leak the pool one reset at a
            # time (make chaos asserts zero leaked blocks after recovery)
            self.kv_pool.reset()
            self._tables_host[:] = NULL_BLOCK
            self._tables_dirty = True
            self._slot_blocks = [[] for _ in range(self.B)]
            self._prefix_blocks.clear()
            self._prefix_uses.clear()
            self._prefix_reg_gen.clear()
            self._prefix_tier.clear()
            self._reclaimable_blocks = 0
            self._registered_tokens = 0
            # chunk registrations' blocks went back with kv_pool.reset()
            self._chunk_regs.clear()
            self._chunk_reg_tokens = 0
            # pending preemption records describe PRE-reset slots; the reset
            # recovery resubmits every in-flight request itself, so replaying
            # a stale record would double-submit it (duplicate tokens at the
            # stream head + a full duplicate decode)
            self._preempted.clear()
            # same story for in-flight chunked admissions: their blocks went
            # back with kv_pool.reset(), and the reset recovery resubmits
            # the requests — a stale record would re-prefill into a row the
            # resubmission also claims
            self._chunk_admissions.clear()

    # ------------------------------------------------------------------
    # executables
    # ------------------------------------------------------------------
    def _get(self, kind: str, S: int, n: int = 1):
        key = (kind, S, n)
        fn = self._compiled.get(key)
        if fn is None:
            build = {
                "step": lambda: self._build_step(S),  # S carries the sync window here
                "step_paged": lambda: self._build_step_paged(S),
                "prefill": lambda: self._build_prefill(S, n),
                "prefill_paged": lambda: self._build_prefill_paged(S, n),
                "insert": lambda: self._build_insert(S, n),
                "insert_paged": lambda: self._build_insert_paged(S, n),
                "prefill_px": lambda: self._build_prefill_prefixed(S, n),  # n: the suffix bucket
                "prefill_px_paged": lambda: self._build_prefill_px_paged(S),  # S: the suffix bucket
                "prefix_scatter": lambda: self._build_prefix_scatter(S),  # S: the buffer width
                "chunk_splice": lambda: self._build_chunk_splice(S),  # S: the block count
                "boundary_px": lambda: self._build_boundary_px_paged(S),  # S: the window
                "verify_paged": lambda: self._build_verify_paged(S),  # S: the draft count K
                "mixed_step": lambda: self._build_mixed_step(S),  # S: the chunk width
            }[kind]
            fn = tracing.build_span("continuous", key, build, identity=self._build_identity,
                                    rows=n, bucket=S)
            self._compiled[key] = fn
        return fn

    def _shardings(self):
        """(cache_payload, cache_scale, replicated) NamedShardings — or all
        None off-mesh. The cache shards its kv-head axis over tp (matching
        the attention kernels' shard_map specs) when head counts divide;
        everything host-fed is replicated. The SAME specs serve both
        layouts: the dense ``[L, B, K, T, hd]`` cache and the paged
        ``[L, N, K, bs, hd]`` arena put kv heads at dim 2 (and the scale
        planes drop the trailing hd either way), so the head-sharded arena
        is spec-identical to the dense tp cache. Executables are lowered
        with and return EXACTLY these, so state tuples round-trip between
        prefill → insert → step without 'sharding does not match'
        rejections (an unsharded lowering bricks every request on a tp>1
        mesh)."""
        if self.mesh is None:
            return None, None, None
        rep = self.mesh.replicated
        K, tp = self.config.num_kv_heads, self.mesh.tp
        if tp > 1 and K % tp == 0:
            return (
                self.mesh.sharding(None, None, "tp", None, None),
                self.mesh.sharding(None, None, "tp", None),
                rep,
            )
        return rep, rep, rep

    def _cache_shardings(self):
        """Per-plane shardings for the cache-state tuple (None off-mesh)."""
        pay, sc, _ = self._shardings()
        if self.kv_quant == "int8":
            return (pay, pay, sc, sc)
        return (pay, pay)

    def _arena_shardings(self):
        """Per-plane shardings for the PAGED arena tuple — identical to the
        dense cache's (``_shardings``: kv heads at dim 2 in both layouts),
        aliased for call-site clarity."""
        return self._cache_shardings()

    def _cache_avals(self, batch: int, length: int):
        """ShapeDtypeStructs (with shardings, on-mesh) for the cache tuple."""
        L, K, hd = self.config.num_layers, self.config.num_kv_heads, self.config.head_dim
        cdt = jnp.int8 if self.kv_quant == "int8" else self.dtypes.compute_dtype
        shardings = self._cache_shardings()
        payload = jax.ShapeDtypeStruct(
            (L, batch, K, length, hd), cdt, sharding=shardings[0]
        )
        if self.kv_quant == "int8":
            scale = jax.ShapeDtypeStruct(
                (L, batch, K, length), jnp.float32, sharding=shardings[2]
            )
            return (payload, payload, scale, scale)
        return (payload, payload)

    def _build_prefill(self, S: int, n: int = 1):
        """``n`` requests prefill together into fresh S-length row caches —
        batched admission amortizes the per-admission dispatch + first-token
        fetch (decisive on a slow host link: one round-trip per GROUP).
        Per-row pre-folded keys keep draws (seed, position)-deterministic
        regardless of the admission grouping."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model
        kv_quant = self.kv_quant

        @phase_scope("prefill")
        def prefill(params, tokens, pad_mask, rngs):
            cache = make_kv_cache(cfg, n, S, dt.compute_dtype, quant=kv_quant)
            kv_start, _ = mask_window(pad_mask)
            positions = jnp.clip(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
            logits, cache = model.apply(
                {"params": params}, tokens, positions, cache,
                kv_start, jnp.full((n,), S, jnp.int32), jnp.int32(0),
                last_logit_only=True,
            )
            tok0 = sample_token_per_row(rngs, logits[:, -1], sampling)
            rows = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            return rows, tok0, kv_start

        rep = self.mesh.replicated if self.mesh is not None else None
        # pin output shardings so the row block arrives EXACTLY as insert's
        # lowered avals expect it (unpinned propagation can pick a different
        # layout and insert would reject the mismatch at call time)
        out_shardings = (
            (self._cache_shardings(), rep, rep) if self.mesh is not None else None
        )
        return jax.jit(prefill, out_shardings=out_shardings), (
            param_avals(self.params),
            jax.ShapeDtypeStruct((n, S), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((n, S), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=rep),
        )

    def _build_prefill_prefixed(self, S: int, C: int):
        """Batch-1 PREFIXED admission (KV prefix cache): splice a
        ``CachedPrefix`` block into a fresh left-padded ``S``-slot row cache
        and prefill only the ``C``-bucketed suffix — the row block then goes
        through the ordinary ``_insert`` executable, which already accepts
        pre-populated KV rows (it splices whatever row planes it is handed).

        Slot geometry: the row's tokens end at slot ``S`` (left padding), so
        the prefix block lands at ``start = S - total`` and the suffix
        chunk-prefills at ``start + prefix_len``. Positions stay canonical
        (0-based) — RoPE is baked into the cached K by position, not slot.
        """
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        mc = self.model_chunked
        kv_quant = self.kv_quant
        P = self.engine_config.prefix_cache.max_prefix_tokens
        # the splice buffer is P wide and lands as low as slot 0, and the
        # suffix write spans [start + prefix_len, start + prefix_len + C):
        # size the build cache so neither dynamic_update_slice can clamp
        # (a clamped start silently shifts the block over valid KV)
        T_build = -(-(S + P + C) // 128) * 128
        i32 = jnp.int32
        from rag_llm_k8s_tpu.models.llama import KVCache

        @phase_scope("prefill")
        def prefill(params, suffix_tokens, suffix_len, ctx, prefix_len, rngs):
            cache = make_kv_cache(cfg, 1, T_build, dt.compute_dtype, quant=kv_quant)
            planes = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            plen = prefix_len.astype(i32)
            slen = suffix_len.astype(i32)
            total = plen + slen
            start = (S - total).astype(i32)
            planes = tuple(
                jax.lax.dynamic_update_slice(
                    c, b.astype(c.dtype),
                    (0, 0, 0, start) + ((0,) if c.ndim == 5 else ()),
                )
                for c, b in zip(planes, ctx)
            )
            positions = (plen + jnp.arange(C, dtype=i32))[None, :]
            kv_start = jnp.broadcast_to(start, (1,))
            # real tokens end exactly at slot S; right-padded suffix K/V
            # beyond lands at >= S and is dropped by the row slice below
            logits, cache = mc.apply(
                {"params": params}, suffix_tokens, positions, KVCache(*planes),
                kv_start, jnp.full((1,), S, i32), start + plen,
                logit_index=slen - 1,
            )
            tok0 = sample_token_per_row(rngs, logits[:, -1], sampling)
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            rows = tuple(c[:, :, :, :S] for c in out)
            return rows, tok0, kv_start

        rep = self.mesh.replicated if self.mesh is not None else None
        ctx_avals = tuple(
            jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
            for shape, dtype in self._prefix_plane_shapes(P)
        )
        out_shardings = (
            (self._cache_shardings(), rep, rep) if self.mesh is not None else None
        )
        return jax.jit(prefill, out_shardings=out_shardings), (
            param_avals(self.params),
            jax.ShapeDtypeStruct((1, C), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            ctx_avals,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=rep),
        )

    def _prefix_plane_shapes(self, length: int):
        """(shape, dtype) per prefix-buffer plane — mirrors the one-shot
        engine's layout so CachedPrefix descriptors are interchangeable."""
        c = self.config
        cdt = jnp.int8 if self.kv_quant == "int8" else self.dtypes.compute_dtype
        pay = ((c.num_layers, 1, c.num_kv_heads, length, c.head_dim), cdt)
        out = [pay, pay]
        if self.kv_quant == "int8":
            sc = ((c.num_layers, 1, c.num_kv_heads, length), jnp.float32)
            out += [sc, sc]
        return out

    def admit_prefixed(
        self,
        request_id: int,
        suffix: Sequence[int],
        prefix,  # CachedPrefix (engine/prefix_cache.py)
        max_new: int,
        seed: Optional[int] = None,
    ) -> Tuple[int, Optional[List[int]]]:
        """Admit one request whose prompt head is a cached prefix: only the
        suffix prefills; the prefix KV splices from the descriptor. Same
        return contract as ``admit``. Raises ValueError when the shapes
        don't fit a slot (caller falls back to a plain admission)."""
        free = self.free_slots()
        assert free, "admit_prefixed() without a free slot"
        if not suffix:
            # logit_index would clip to a PAD position — see generate_prefixed
            raise ValueError("admit_prefixed needs a non-empty suffix")
        pc = getattr(self.engine_config, "prefix_cache", None)
        if pc is None or prefix.capacity != pc.max_prefix_tokens:
            raise ValueError("prefix descriptor does not match this engine's config")
        total = prefix.length + len(suffix)
        S = bucket_len(max(total, 1), self.buckets)
        if total > S:
            raise ValueError(
                f"prefixed prompt of {total} tokens exceeds the largest "
                f"continuous bucket {S}"
            )
        if len(suffix) > max(pc.suffix_buckets):
            raise ValueError(
                f"prefixed suffix of {len(suffix)} tokens exceeds the "
                f"largest suffix bucket {max(pc.suffix_buckets)}"
            )
        C = bucket_len(max(len(suffix), 1), pc.suffix_buckets)
        max_new_c = max(1, min(max_new, self.T - S))
        if seed is not None:
            row_key = jax.random.PRNGKey(seed)
        else:
            self._rng, row_key = jax.random.split(self._rng)
        folded = jax.random.fold_in(row_key, total)[None, :]

        toks = np.full((1, C), self.pad_id, np.int32)
        toks[0, : len(suffix)] = list(suffix)
        row = free[0]
        if self.paged:
            return self._admit_prefixed_paged(
                request_id, suffix, prefix, C, max_new_c, row, row_key,
                folded, toks,
            )
        t_admit = time.perf_counter()
        row_cache, tok0s, row_starts = self._get("prefill_px", S, C)(
            self.params, self._put(toks), self._put(jnp.int32(len(suffix))),
            tuple(self._put(p) for p in prefix.planes),
            self._put(jnp.int32(prefix.length)), self._put(folded),
        )
        try:
            (self._cache, self._kv_start, self._kv_len,
             self._last_tok, self._active, self._rng_keys) = self._get("insert", S, 1)(
                self._cache, row_cache,
                self._kv_start, self._kv_len, self._last_tok, self._active,
                self._rng_keys, self._put(np.asarray([row], np.int32)),
                row_starts, tok0s, self._put(row_key[None, :]),
            )
        except BaseException as e:  # noqa: BLE001 — same contract as _admit_chunk
            self.reset()
            raise EngineStateLost("insert failed; engine state reset") from e
        tok0 = int(np.asarray(tok0s)[0])
        self.stats.generate_calls += 1
        self.stats.prefill_tokens += len(suffix)
        self.stats.prefill_tokens_skipped += int(prefix.length)
        flight.emit(
            "admit", request_id, slot=row, prompt_len=total,
            prefix_len=int(prefix.length), tok0=tok0,
            **_tenant_attr(self.ledger, request_id),
        )
        self._journal_window(self.ledger.record_prefill_px(
            time.perf_counter() - t_admit, bucket=C, rid=request_id,
            computed=len(suffix), skipped=int(prefix.length),
            rework=bool(self._take_rework((request_id,))),
        ))
        if tok0 in self.config.eos_token_ids or max_new_c <= 1:
            out = [] if tok0 in self.config.eos_token_ids else [tok0]
            self.stats.decode_tokens += len(out)
            m = np.ones(self.B, bool)
            m[row] = False
            self._active = self._active & self._put(jnp.asarray(m))
            return row, out
        self.slots[row] = _Slot(
            request_id=request_id, tokens=[tok0], remaining=max_new_c - 1,
            active=True,
        )
        self.stats.decode_tokens += 1
        return row, None

    def _admit_prefixed_paged(
        self, request_id, suffix, prefix, C, max_new_c, row, row_key,
        folded, toks,
    ):
        """Paged tail of ``admit_prefixed``: block-granular prefix reuse.

        Shared FULL blocks of a previously-seen prefix (keyed by the
        descriptor's ``chain_key`` — set only under exact-chain reuse, the
        policy whose cached KV is bit-faithful to a cold prefill) map into
        the row's table copy-free, pinned by a pool ref; only the partial
        tail block scatters from the descriptor's splice buffer, and only
        the suffix prefills — a paged chunk straight into pool blocks. A
        first sighting scatters the whole prefix and REGISTERS its full
        blocks (one cache ref each), so the next request with the same
        prompt head shares them without copying a byte."""
        t_admit = time.perf_counter()
        bs = self.block_size
        plen = int(prefix.length)
        slen = len(suffix)
        total = plen + slen
        P = int(prefix.capacity)
        if P % bs:
            raise ValueError(
                f"prefix capacity {P} not a multiple of kv_block_size {bs}"
            )
        key = getattr(prefix, "chain_key", None)
        shared_ids: List[int] = []
        if key is not None:
            entry = self._prefix_blocks.get(key)
            if entry is not None and entry[2] == plen:
                shared_ids = list(entry[0])
                self._prefix_uses[key] = self._prefix_uses.get(key, 0) + 1
        # chunk-granular assembly (reuse="chunk"): when the whole chain has
        # no shared registration but every chunk has a canonical one, the
        # block table assembles from per-chunk registrations at arbitrary
        # order — gather + RoPE-re-rotate into fresh blocks + boundary
        # re-prefill straight into pool blocks, no splice-buffer scatter
        plan = None
        if not shared_ids:
            plan = self._chunk_splice_plan(prefix)
        covered = len(shared_ids)
        need_total = self.kv_pool.blocks_for(max(total, 1))
        priv = self.kv_pool.alloc(need_total - covered)  # PoolExhausted → caller
        if shared_ids:
            self.kv_pool.ref(shared_ids)  # the row's own pin
        ids_all = shared_ids + priv
        self._assign_row_blocks(row, ids_all)
        self._device_tables()

        # scatter the un-shared prefix slabs (all of them on a miss; just
        # the partial tail block on a hit) from the splice buffer — unless
        # the per-chunk assembly path populates the blocks instead
        nbp = P // bs
        scatter_ids = np.zeros((nbp,), np.int32)
        if plan is None:
            for j in range(covered, min(self.kv_pool.blocks_for(plen), nbp)):
                scatter_ids[j] = ids_all[j]
        try:
            if plan is not None:
                self._chunk_splice_into_row(row, ids_all, plan)
            elif scatter_ids.any():
                self._cache = self._get("prefix_scatter", P, 0)(
                    self._cache, tuple(self._put(p) for p in prefix.planes),
                    self._put(jnp.asarray(scatter_ids)),
                )
            self._cache, tok0s = self._get("prefill_px_paged", C, 0)(
                self.params, self._cache,
                self._put(jnp.asarray(self._tables_host[row : row + 1])),
                self._put(toks), self._put(jnp.int32(slen)),
                self._put(jnp.int32(plen)), self._put(folded),
            )
        except BaseException as e:  # noqa: BLE001 — donated arena invalidated
            self.reset()
            raise EngineStateLost("prefixed insert failed; engine state reset") from e

        # register a first-seen prefix's full blocks for future sharing —
        # from the scatter path AND the per-chunk assembly (a repeated
        # permutation must map these blocks copy-free, not re-splice and
        # re-run its boundary prefills on every admission)
        full_n = plen // bs
        shared_tok = covered * bs  # tokens this row serves from shared blocks
        chain_registered = key is not None and not shared_ids and full_n > 0
        if chain_registered:
            reg = ids_all[:full_n]
            self.kv_pool.ref(reg)  # the cache's own ref outlives the row
            self._register_prefix(key, reg, plen)
            shared_tok = full_n * bs  # now registration-counted, not row-counted
        if plan is None and not shared_ids:
            # block-aligned exact spans become per-chunk canonical copies
            # (reuse="chunk" metadata only; no-op otherwise). Scatter
            # admissions ONLY: on a chain hit the blocks hold an EARLIER
            # admission's content — this resolve's span exactness/stamps
            # do not describe those bytes, and registering them could
            # canonicalize a re-rotated copy (compounding drift)
            self._register_chunks_from_scatter(
                prefix, ids_all, chain_registered=chain_registered
            )

        tok0 = int(np.asarray(tok0s)[0])
        self._kv_len = self._kv_len.at[row].set(total)
        self._last_tok = self._last_tok.at[row].set(tok0)
        self._rng_keys = self._rng_keys.at[row].set(self._put(row_key))
        self.stats.generate_calls += 1
        self.stats.prefill_tokens += slen
        self.stats.prefill_tokens_skipped += plen
        flight.emit(
            "admit", request_id, slot=row, prompt_len=total, prefix_len=plen,
            shared=shared_tok, tok0=tok0,
            **_tenant_attr(self.ledger, request_id),
        )
        self._journal_window(self.ledger.record_prefill_px(
            time.perf_counter() - t_admit, bucket=C, rid=request_id,
            computed=slen, skipped=plen,
            rework=bool(self._take_rework((request_id,))),
        ))
        if tok0 in self.config.eos_token_ids or max_new_c <= 1:
            out = [] if tok0 in self.config.eos_token_ids else [tok0]
            self.stats.decode_tokens += len(out)
            self._blocks_at_retire[request_id] = len(self._slot_blocks[row])
            self._release_row(row)
            return row, out
        self._active = self._active.at[row].set(True)
        self._admit_seq += 1
        self.slots[row] = _Slot(
            request_id=request_id, tokens=[tok0], remaining=max_new_c - 1,
            active=True, kv_ub=total, admit_seq=self._admit_seq,
            prompt_len=total, shared_tokens=shared_tok,
            # spec draft corpus: a prefixed admission only carries the
            # SUFFIX token ids (the prefix is a KV descriptor — its ids
            # never reach the engine), so the corpus starts there and
            # grows with the emitted stream; drafting still fires on
            # self-repeats, just without the spliced context's text
            history=(list(suffix) + [tok0]) if self.spec_on else [],
        )
        self.stats.decode_tokens += 1
        return row, None

    def prestage_prefix(self, prefix, tier: str = "hot") -> "str | bool":
        """Warm a ``CachedPrefix``'s full blocks into POOL blocks ahead of
        any admission (the lookahead pipeline's paged leg — rag/lookahead):
        allocate ``length // block_size`` blocks, scatter the prefix planes
        into them, and REGISTER them under the chain key, so the first
        admission with this prompt head maps them copy-free instead of
        scattering — exactly the sharing ``_admit_prefixed_paged`` sets up
        on a first sighting, moved off the request path.

        Must be called from the engine's owning (dispatcher) thread —
        ``ContinuousScheduler.run_on_engine`` is the safe entry. Headroom-
        gated: never takes blocks unless a full row's growth stays free, so
        pre-staging cannot starve live admissions. Returns ``"registered"``
        when THIS call created the registration (the caller owns the later
        release), ``"resident"`` when it already existed (an earlier
        admission or prestage owns it — never release someone else's), and
        False when nothing was staged."""
        if not self.paged:
            return False
        if tier == "cold":
            # a cold REGISTRATION must not exist (cold = not in the pool;
            # set_prefix_tier drops on cold for the same reason) — and the
            # prestage itself is evidence the chain is about to be used,
            # so register it reclaimable-but-resident
            tier = "warm"
        if tier not in ("hot", "warm"):
            raise ValueError(f"prestage tier={tier!r}: expected hot|warm|cold")
        key = getattr(prefix, "chain_key", None)
        if key is None:  # "slot"-mode prefixes are not content-identical
            return False
        pc = getattr(self.engine_config, "prefix_cache", None)
        if pc is None or prefix.capacity != pc.max_prefix_tokens:
            return False
        bs = self.block_size
        P = int(prefix.capacity)
        plen = int(prefix.length)
        full_n = plen // bs
        if P % bs or full_n <= 0 or full_n > P // bs:
            return False
        entry = self._prefix_blocks.get(key)
        if entry is not None and entry[2] == plen:
            return "resident"  # earlier admission or prestage owns it
        if not self.kv_pool.can_alloc(full_n + self.MB):
            return False  # headroom: live traffic keeps a full row's growth
        ids = self.kv_pool.alloc(full_n)
        try:
            # fault site "kv_swap_in": a cold chain's host→HBM re-stage
            # dying between alloc and scatter. Nothing was scattered and
            # nothing donated — free the blocks and decline, and the
            # admission path recomputes from tokens (zero leaked blocks;
            # distinct from a real scatter failure below, which invalidates
            # the donated arena and must reset)
            faults.maybe_fail("kv_swap_in")
        except faults.InjectedFault:
            self.kv_pool.free(ids)
            return False
        nbp = P // bs
        scatter_ids = np.zeros((nbp,), np.int32)
        scatter_ids[:full_n] = ids
        try:
            self._cache = self._get("prefix_scatter", P, 0)(
                self._cache, tuple(self._put(p) for p in prefix.planes),
                self._put(jnp.asarray(scatter_ids)),
            )
        except BaseException as e:  # noqa: BLE001 — donated arena invalidated
            self.reset()  # reset() reclaims ids with everything else
            raise EngineStateLost(
                "prefix prestage failed; engine state reset"
            ) from e
        # alloc()'s ref IS the registration ref (no row holds these yet) —
        # every reclaim path goes through _drop_registration, so
        # registrations free exactly once. ``tier`` (from the prefix
        # cache's hotness) decides how readily admission reclaims it.
        self._register_prefix(key, ids, plen, tier=tier)
        return "registered"

    def prestage_gen(self, chain_key):
        """The live registration generation for a chain (None when not
        registered) — a deferred release records it at staging time and
        presents it back (``release_prestaged(gen=)``), so it can never
        free a registration a later admission re-created at the same key.
        Same thread contract as ``prestage_prefix``."""
        return self._prefix_reg_gen.get(chain_key)

    def _register_prefix(self, key, ids, plen: int, tier: str = "hot") -> int:
        """Register a chain's full blocks for future copy-free sharing and
        return the registration generation; enforces the bounded-8 set.
        The caller has already taken the registration's pool ref. ``tier``
        is the chain's hotness class — non-hot registrations are the first
        blocks admission reclaims under pressure."""
        self._reg_seq += 1
        cov = len(ids) * self.block_size
        self._prefix_blocks[key] = (list(ids), cov, plen)
        self._prefix_uses[key] = 0
        self._prefix_reg_gen[key] = self._reg_seq
        self._prefix_tier[key] = tier
        self.kv_pool.account_tier(tier, len(ids))
        if tier != "hot":
            self._reclaimable_blocks += len(ids)
        self._registered_tokens += cov
        while len(self._prefix_blocks) > 8:  # bounded registration set
            self._drop_registration(next(iter(self._prefix_blocks)))
        return self._reg_seq

    def _drop_registration(self, key) -> bool:
        """The one place a registration dies: pops every side table, fixes
        the fragmentation counter, returns the blocks to the pool."""
        entry = self._prefix_blocks.pop(key, None)
        if entry is None:
            return False
        self._prefix_uses.pop(key, None)
        self._prefix_reg_gen.pop(key, None)
        ids, cov, _ = entry
        tier = self._prefix_tier.pop(key, "hot")
        self.kv_pool.account_tier(tier, -len(ids))
        if tier != "hot":
            self._reclaimable_blocks = max(
                0, self._reclaimable_blocks - len(ids)
            )
        self._registered_tokens -= cov
        self.kv_pool.free(ids)
        return True

    def set_prefix_tier(self, chain_key, tier: str) -> bool:
        """Move a registration between hotness tiers (scheduler thread —
        the service's retier maintenance arrives via ``run_on_engine``).
        ``"cold"`` DROPS the registration: a cold chain's arena blocks go
        back to the pool and its KV survives only in the prefix cache's
        host spill, one prestage re-scatter away (the pool-side spill).
        Returns True when anything changed."""
        if not self.paged:
            return False
        entry = self._prefix_blocks.get(chain_key)
        if entry is None:
            return False
        if tier == "cold":
            return self._drop_registration(chain_key)
        old = self._prefix_tier.get(chain_key, "hot")
        if old == tier:
            return False
        n = len(entry[0])
        self.kv_pool.account_tier(old, -n)
        self.kv_pool.account_tier(tier, n)
        self._prefix_tier[chain_key] = tier
        if old == "hot" and tier != "hot":
            self._reclaimable_blocks += n
        elif old != "hot" and tier == "hot":
            self._reclaimable_blocks = max(0, self._reclaimable_blocks - n)
        return True

    def retier_registrations(self, tier_fn) -> int:
        """Re-tag every registered chain with ``tier_fn(chain_key)`` — the
        cache→pool tier mirror (the service passes the prefix cache's
        ``chain_tier``; scheduler thread via ``run_on_engine``). A chain
        judged "cold" drops its registration. Returns how many
        registrations changed. Keeps the registration table behind the
        engine's API — callers never touch ``_prefix_blocks``."""
        if not self.paged:
            return 0
        changed = 0
        for key in list(self._prefix_blocks):
            if self.set_prefix_tier(key, tier_fn(key)):
                changed += 1
        return changed

    def tier_occupancy(self) -> Dict[str, int]:
        """Registered-block tier ledger + live-row blocks (the pool's
        view; empty dict dense). Reading the POOL's lock-guarded ledger is
        scrape-safe from any thread."""
        if not self.paged:
            return {}
        return self.kv_pool.tier_occupancy()

    def reclaimable_blocks(self) -> int:
        """Non-hot registered blocks the scheduler can reclaim without
        touching a live row — the admission gate's tier-occupancy signal
        (lock-free read of a scheduler-maintained int): while this is
        positive, a saturated pool is NOT a shed — the next admission
        sweep frees these and the request only queues."""
        if not self.paged:
            return 0
        return self._reclaimable_blocks

    def release_prestaged(self, chain_key, only_unused: bool = False,
                          gen=None) -> bool:
        """Stale-prefetch cancellation, pool side: drop one registered
        chain and free its blocks (ref-count-correct — rows still decoding
        over shared copies hold their own refs, so the pool only reclaims
        the registration's). ``only_unused=True`` keeps a registration an
        admission has mapped since it was staged — live traffic proved the
        speculation right, so the lookahead release must not cost future
        sharing. ``gen`` (from ``prestage_gen`` at staging time) guards the
        deferred-release race: if the staged registration was evicted and a
        later admission re-created one at this key, the generations differ
        and the admission's registration survives. Same thread contract as
        ``prestage_prefix``."""
        if not self.paged:
            return False
        if gen is not None and self._prefix_reg_gen.get(chain_key) != gen:
            return False  # a re-created registration owns this key now
        if only_unused and self._prefix_uses.get(chain_key, 0) > 0:
            return False
        return self._drop_registration(chain_key)

    def _build_insert(self, S: int, n: int = 1):
        """Splice ``n`` freshly prefilled row blocks + their per-row state
        into arbitrary slots in ONE device call (the admission group's
        counterpart to the batched prefill)."""

        @phase_scope("prefill")
        def insert(cache, row_cache, kv_start, kv_len, last_tok, active,
                   rng_keys, rows, row_starts, tok0s, row_keys):
            # each row's prompt KV occupies slots [0, S); frontiers are
            # per-row so nothing else moves. zip pairs each state plane
            # (payload or scale) with its [L, n, ...] block — same update
            # either way. The loop is static (n is compile-time).
            for i in range(n):
                blk = tuple(
                    jax.lax.dynamic_slice(
                        r, (0, i) + (0,) * (r.ndim - 2),
                        (r.shape[0], 1) + r.shape[2:],
                    )
                    for r in row_cache
                )
                cache = tuple(
                    jax.lax.dynamic_update_slice(
                        c, b, (0, rows[i]) + (0,) * (c.ndim - 2)
                    )
                    for c, b in zip(cache, blk)
                )
                kv_start = kv_start.at[rows[i]].set(row_starts[i])
                kv_len = kv_len.at[rows[i]].set(S)
                last_tok = last_tok.at[rows[i]].set(tok0s[i])
                active = active.at[rows[i]].set(True)
                rng_keys = rng_keys.at[rows[i]].set(row_keys[i])
            return cache, kv_start, kv_len, last_tok, active, rng_keys

        i32 = jnp.int32
        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            (self._cache_shardings(), rep, rep, rep, rep, rep)
            if self.mesh is not None else None
        )
        # row_cache is not donated: an [L,n,...] block cannot alias into the
        # [L,B,...] cache, so donation would only emit a warning
        return jax.jit(insert, donate_argnums=(0, 2, 3, 6), out_shardings=out_shardings), (
            self._cache_avals(self.B, self.T),
            self._cache_avals(n, S),
            jax.ShapeDtypeStruct((self.B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((self.B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((self.B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((self.B,), bool, sharding=rep),
            jax.ShapeDtypeStruct((self.B, 2), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((n,), i32, sharding=rep),
            jax.ShapeDtypeStruct((n,), i32, sharding=rep),
            jax.ShapeDtypeStruct((n,), i32, sharding=rep),
            jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=rep),
        )

    def _build_step(self, k: int = 1):
        """The decode executable: ``k`` decode steps for all ``B`` slots as
        ONE device program, returning the ``[k, B]`` token/EOS planes in a
        single host fetch. ``k == 1`` is the classic per-step sync; ``k > 1``
        (``EngineConfig.decode_sync_steps``) scans the step body on device —
        on-device EOS masking makes the blind multi-step correct (a finished
        row stops attending/advancing mid-window), the host just discards
        anything a row produced after its EOS or budget."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model_step
        eos_ids = cfg.eos_token_ids
        B, T = self.B, self.T
        kv_quant = self.kv_quant
        from rag_llm_k8s_tpu.models.llama import KVCache

        def one(params, cache_t, kv_start, kv_len, last_tok, active, rng_keys):
            wi = jnp.where(active, kv_len, 0)  # inactive rows park at slot 0
            posv = jnp.clip(wi - kv_start, 0)  # inactive rows: junk, masked
            logits, cache = model.apply(
                {"params": params}, last_tok[:, None], posv[:, None],
                KVCache(*cache_t), kv_start, wi + 1, wi,
            )
            # key = fold(row seed key, token position): draws depend only on
            # the request's own seed + position, never on batchmates — a
            # seeded request samples identically solo or mid-batch
            keys = jax.vmap(jax.random.fold_in)(rng_keys, posv + 1)
            tok = sample_token_per_row(keys, logits[:, 0], sampling)
            hit_eos = _isin(tok, eos_ids)
            # frontier advances only for rows that were active this step and
            # stays < T (the scheduler retires rows before they get close)
            kv_len = jnp.where(active, jnp.minimum(wi + 1, T - 1), kv_len)
            active = active & ~hit_eos
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            return out, kv_len, tok, hit_eos, active

        @phase_scope("decode")
        def step(params, cache_t, kv_start, kv_len, last_tok, active, rng_keys):
            if k == 1:
                cache_t, kv_len, tok, hit_eos, active = one(
                    params, cache_t, kv_start, kv_len, last_tok, active, rng_keys
                )
                return cache_t, kv_len, tok, tok[None], hit_eos[None], active

            def body(carry, _):
                cache_t, kv_len, last_tok, active = carry
                cache_t, kv_len, tok, hit_eos, active = one(
                    params, cache_t, kv_start, kv_len, last_tok, active, rng_keys
                )
                return (cache_t, kv_len, tok, active), (tok, hit_eos)

            (cache_t, kv_len, tok, active), (toks, eoss) = jax.lax.scan(
                body, (cache_t, kv_len, last_tok, active), None, length=k
            )
            return cache_t, kv_len, tok, toks, eoss, active

        i32 = jnp.int32
        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            (self._cache_shardings(), rep, rep, rep, rep, rep)
            if self.mesh is not None else None
        )
        # kv_start (2) and rng_keys (6) are NOT donated: neither is among the
        # outputs, and the host keeps using their buffers across steps
        return jax.jit(step, donate_argnums=(1, 3, 4, 5), out_shardings=out_shardings), (
            param_avals(self.params),
            self._cache_avals(B, T),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), bool, sharding=rep),
            jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=rep),
        )


    # ------------------------------------------------------------------
    # paged executables (EngineConfig.kv_paged)
    # ------------------------------------------------------------------
    def _arena_avals(self):
        """ShapeDtypeStructs for the arena plane tuple (head-sharded over
        tp on a mesh — the same ``_shardings`` specs as the dense cache,
        since kv heads sit at dim 2 in both layouts)."""
        L, K, hd = self.config.num_layers, self.config.num_kv_heads, self.config.head_dim
        N, bs = self.kv_pool.num_blocks, self.block_size
        cdt = jnp.int8 if self.kv_quant == "int8" else self.dtypes.compute_dtype
        pay_sh, sc_sh, _ = self._shardings()
        payload = jax.ShapeDtypeStruct((L, N, K, bs, hd), cdt, sharding=pay_sh)
        if self.kv_quant == "int8":
            scale = jax.ShapeDtypeStruct((L, N, K, bs), jnp.float32, sharding=sc_sh)
            return (payload, payload, scale, scale)
        return (payload, payload)

    def _build_prefill_paged(self, S: int, n: int = 1):
        """Paged admission prefill: ``n`` RIGHT-padded prompts (logical
        positions start at 0 — the layout that makes prefix blocks shareable
        and pad cost zero) prefill into a fresh dense ``[n, S]`` build cache;
        the insert executable scatters the rows into pool blocks. Per-row
        real lengths ride as a vector: the first token samples at each row's
        OWN last real position (vector ``logit_index``), so mixed-length
        admission groups still share one executable."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model
        kv_quant = self.kv_quant
        i32 = jnp.int32

        @phase_scope("prefill")
        def prefill(params, tokens, lens, rngs):
            cache = make_kv_cache(cfg, n, S, dt.compute_dtype, quant=kv_quant)
            positions = jnp.broadcast_to(jnp.arange(S, dtype=i32)[None, :], (n, S))
            logits, cache = model.apply(
                {"params": params}, tokens, positions, cache,
                jnp.zeros((n,), i32), lens.astype(i32), jnp.int32(0),
                logit_index=jnp.maximum(lens.astype(i32) - 1, 0),
            )
            tok0 = sample_token_per_row(rngs, logits[:, -1], sampling)
            rows = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            return rows, tok0

        rep = self.mesh.replicated if self.mesh is not None else None
        # pin output shardings so the row block arrives EXACTLY as
        # insert_paged's lowered avals expect it (same contract as the
        # dense prefill → insert pair)
        out_shardings = (
            (self._cache_shardings(), rep) if self.mesh is not None else None
        )
        return jax.jit(prefill, out_shardings=out_shardings), (
            param_avals(self.params),
            jax.ShapeDtypeStruct((n, S), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=rep),
        )

    def _build_insert_paged(self, S: int, n: int = 1):
        """Scatter ``n`` freshly prefilled rows into their pool blocks (ONE
        device call for the group) + splice per-row state. The block loop is
        static (``S // block_size`` slabs per row); slabs whose logical block
        a short prompt never reached carry id 0 — their junk lands in the
        reserved null block, which nothing ever reads, so lazy allocation
        costs no executable shapes."""
        bs = self.block_size
        nb = S // bs

        @phase_scope("prefill")
        def insert(arena, row_cache, kv_len, last_tok, active, rng_keys,
                   rows, block_ids, lens, tok0s, row_keys):
            # ONE scatter per plane over the block axis: reshape each row's
            # S-length planes into n*nb slabs and write them at their
            # table-assigned physical ids. An unrolled dynamic_update_slice
            # loop here multiplied the executable's HLO by S/bs (up to
            # hundreds of ops per plane) and with it the warmup compile
            # time; a scatter is fine on this PER-ADMISSION path (the
            # no-scatter rule protects the per-STEP write only). Slabs of
            # never-reached blocks carry id 0 — duplicate null-block
            # indices race, and the null block's content is don't-care.
            flat_ids = block_ids.reshape(-1)  # [n * nb]
            new = []
            for a, r in zip(arena, row_cache):
                L, K = r.shape[0], r.shape[2]
                if a.ndim == 5:
                    hd = r.shape[4]
                    slabs = r.reshape(L, n, K, nb, bs, hd).transpose(
                        0, 1, 3, 2, 4, 5
                    ).reshape(L, n * nb, K, bs, hd)
                else:
                    slabs = r.reshape(L, n, K, nb, bs).transpose(
                        0, 1, 3, 2, 4
                    ).reshape(L, n * nb, K, bs)
                new.append(a.at[:, flat_ids].set(slabs.astype(a.dtype)))
            for i in range(n):
                kv_len = kv_len.at[rows[i]].set(lens[i])
                last_tok = last_tok.at[rows[i]].set(tok0s[i])
                active = active.at[rows[i]].set(True)
                rng_keys = rng_keys.at[rows[i]].set(row_keys[i])
            return tuple(new), kv_len, last_tok, active, rng_keys

        i32 = jnp.int32
        rep = self.mesh.replicated if self.mesh is not None else None
        row_avals = self._cache_avals(n, S)
        out_shardings = (
            (self._arena_shardings(), rep, rep, rep, rep)
            if self.mesh is not None else None
        )
        return jax.jit(
            insert, donate_argnums=(0, 2, 3, 5), out_shardings=out_shardings
        ), (
            self._arena_avals(),
            row_avals,
            jax.ShapeDtypeStruct((self.B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((self.B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((self.B,), bool, sharding=rep),
            jax.ShapeDtypeStruct((self.B, 2), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((n,), i32, sharding=rep),
            jax.ShapeDtypeStruct((n, nb), i32, sharding=rep),
            jax.ShapeDtypeStruct((n,), i32, sharding=rep),
            jax.ShapeDtypeStruct((n,), i32, sharding=rep),
            jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=rep),
        )

    def _build_step_paged(self, k: int = 1):
        """The paged decode executable: identical control flow to
        ``_build_step`` — the model streams each row's LIVE blocks via its
        table instead of a dense ``T`` window, so step bandwidth scales with
        real tokens. Tables are NOT donated (host-maintained; one device
        copy serves many windows)."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model_step_paged
        eos_ids = cfg.eos_token_ids
        B = self.B
        Tmax = self.MB * self.block_size
        kv_quant = self.kv_quant
        from rag_llm_k8s_tpu.models.llama import KVCache

        def one(params, cache_t, tables, kv_len, last_tok, active, rng_keys):
            wi = jnp.where(active, kv_len, 0)  # inactive rows park at 0
            # an inactive row's junk write must land in the NULL block, not
            # table[row, 0]: a row that hit EOS mid-window still has its
            # real table mapped (the host nulls it only at drain, after the
            # window), and logical block 0 can be a REF-SHARED prefix block
            # — writing there would corrupt every sharer's KV silently
            tables_eff = jnp.where(active[:, None], tables, NULL_BLOCK)
            logits, cache = model.apply(
                {"params": params}, last_tok[:, None], wi[:, None],
                KVCache(*cache_t), jnp.zeros((B,), jnp.int32), wi + 1, wi,
                block_tables=tables_eff,
            )
            # same (seed, position) key fold as the dense step — a request
            # samples identically under either cache layout
            keys = jax.vmap(jax.random.fold_in)(rng_keys, wi + 1)
            tok = sample_token_per_row(keys, logits[:, 0], sampling)
            hit_eos = _isin(tok, eos_ids)
            kv_len = jnp.where(active, jnp.minimum(wi + 1, Tmax - 1), kv_len)
            active = active & ~hit_eos
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            return out, kv_len, tok, hit_eos, active

        @phase_scope("decode")
        def step(params, cache_t, tables, kv_len, last_tok, active, rng_keys):
            if k == 1:
                cache_t, kv_len, tok, hit_eos, active = one(
                    params, cache_t, tables, kv_len, last_tok, active, rng_keys
                )
                return cache_t, kv_len, tok, tok[None], hit_eos[None], active

            def body(carry, _):
                cache_t, kv_len, last_tok, active = carry
                cache_t, kv_len, tok, hit_eos, active = one(
                    params, cache_t, tables, kv_len, last_tok, active, rng_keys
                )
                return (cache_t, kv_len, tok, active), (tok, hit_eos)

            (cache_t, kv_len, tok, active), (toks, eoss) = jax.lax.scan(
                body, (cache_t, kv_len, last_tok, active), None, length=k
            )
            return cache_t, kv_len, tok, toks, eoss, active

        i32 = jnp.int32
        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            (self._arena_shardings(), rep, rep, rep, rep, rep)
            if self.mesh is not None else None
        )
        return jax.jit(
            step, donate_argnums=(1, 3, 4, 5), out_shardings=out_shardings
        ), (
            param_avals(self.params),
            self._arena_avals(),
            jax.ShapeDtypeStruct((B, self.MB), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), bool, sharding=rep),
            jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=rep),
        )

    def _build_verify_paged(self, K: int):
        """The speculative VERIFY executable (ISSUE 13): one device call
        feeds every active row ``last_tok`` + its ``K`` drafted tokens
        through the paged chunked model — the masked-plane scatter writes
        all ``K+1`` positions through each row's block table (per-row
        vector base + lane offsets, the same write the admission chunk
        path uses), the paged chunk kernel attends each lane with offset
        causality, and ``K+1`` logit planes come back instead of one.

        Acceptance happens ON DEVICE so the host fetch stays one
        round-trip: plane ``j``'s TARGET is what the vanilla step loop
        would have sampled at that position — argmax for greedy, the
        (seed, position)-keyed categorical draw for sampling (the fold
        sequence continues exactly, so seeded streams match bit-for-bit;
        engine/sampling.py). A row accepts the longest draft prefix equal
        to its targets and emits the target at the first mismatch (the
        correction) or the bonus target on full acceptance — the emitted
        stream is the vanilla stream BY CONSTRUCTION, speculation only
        changes how many tokens one window retires.

        Rejected lanes need no explicit retraction: their KV writes land
        beyond the advanced ``kv_len`` frontier, where no kernel window
        ever reads and the next window overwrites — the same masking
        discipline that makes blind multi-step sync windows correct.
        Lanes past a row's own ``n_drafts`` (rows draft different lengths
        in one window) write junk into mapped-but-beyond-frontier slots
        or, past the row's table, the NULL block (the llama.py scatter
        parks out-of-table positions there). Inactive rows park wholesale
        at the null block, exactly like the plain step."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model_chunked_paged
        eos_ids = cfg.eos_token_ids
        B = self.B
        S = K + 1
        Tmax = self.MB * self.block_size
        kv_quant = self.kv_quant
        i32 = jnp.int32
        from rag_llm_k8s_tpu.models.llama import KVCache

        @phase_scope("verify")
        def verify(params, cache_t, tables, kv_len, last_tok, active,
                   rng_keys, drafts, n_drafts):
            wi = jnp.where(active, kv_len, 0)  # inactive rows park at 0
            # inactive rows' junk routes to the NULL block (same rule as
            # the plain step: an EOS'd row's table is still mapped until
            # the host drains, and logical block 0 can be ref-shared)
            tables_eff = jnp.where(active[:, None], tables, NULL_BLOCK)
            nd = jnp.where(active, n_drafts, 0)
            fed = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            pos = wi[:, None] + jnp.arange(S, dtype=i32)[None, :]  # [B, S]
            # the deepest VALID lane (j = nd) attends keys <= wi + nd:
            # kv_len = wi + nd + 1 caps every row's window there; junk
            # lanes beyond see a truncated window and junk logits nobody
            # samples from
            logits, cache = model.apply(
                {"params": params}, fed, pos, KVCache(*cache_t),
                jnp.zeros((B,), i32), wi + 1 + nd, wi,
                block_tables=tables_eff,
            )
            # plane j samples the token that will sit at position
            # wi + j + 1 — fold EXACTLY the key the vanilla step would
            # have folded for it ((seed, position) discipline)
            keys = jax.vmap(
                jax.vmap(jax.random.fold_in, in_axes=(None, 0))
            )(rng_keys, pos + 1)  # [B, S, 2]
            targets = sample_targets_per_row(keys, logits, sampling)
            m, emitted = accept_drafts(drafts, targets, nd)
            jj = jnp.arange(S, dtype=i32)[None, :]
            is_eos = _isin(emitted, eos_ids)  # [B, S] elementwise
            hit_eos = jnp.any(is_eos & (jj <= m[:, None]), axis=1)
            # frontier: last_tok's KV at wi + accepted drafts' at
            # wi+1..wi+m are valid; the correction token (plane m) is the
            # new last_tok, written next window at the new frontier —
            # identical bookkeeping to m+1 vanilla steps
            kv_len = jnp.where(
                active, jnp.minimum(wi + m + 1, Tmax - 1), kv_len
            )
            new_last = jnp.take_along_axis(emitted, m[:, None], axis=1)[:, 0]
            last_tok = jnp.where(active, new_last, last_tok)
            n_emit = jnp.where(active, m + 1, 0)
            active = active & ~hit_eos
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            # [S, B] planes mirror the plain step's [k, B] fetch layout
            return (
                out, kv_len, last_tok, emitted.T, n_emit, is_eos.T,
                m, active,
            )

        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            (self._arena_shardings(), rep, rep, rep, rep, rep, rep, rep)
            if self.mesh is not None else None
        )
        # tables/rng_keys/drafts are host-fed per window, never donated
        return jax.jit(
            verify, donate_argnums=(1, 3, 4, 5), out_shardings=out_shardings
        ), (
            param_avals(self.params),
            self._arena_avals(),
            jax.ShapeDtypeStruct((B, self.MB), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), bool, sharding=rep),
            jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((B, K), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
        )

    def _build_mixed_step(self, C: int):
        """The MIXED decode+chunk window executable (ISSUE 16): one device
        call advances every active decode lane by one token AND feeds each
        scheduled admission a ``<= C``-token slice of its prompt through
        the paged chunked model — ``paged_chunk_attention``'s third
        consumer, after prefix splicing and speculative verify. Lane
        width ``C`` is static (one compile per chunk size); rows declare
        their role per window with host-fed vectors:

        - decode rows (``active`` & ``n_fed == 0``): lane 0 carries the
          device-resident ``last_tok`` at position ``kv_len`` — exactly
          the plain step's write/attend/sample, with ``C - 1`` junk lanes
          beyond the frontier (verify's masking discipline);
        - chunk rows (``n_fed > 0``): lanes ``0..n_fed-1`` carry prompt
          tokens at canonical positions ``chunk_base + j`` (``chunk_base``
          is HOST-fed — a never-inserted prefilling row's device
          ``kv_len`` is junk), written through the row's block table with
          offset causality. The FINAL chunk additionally samples the
          first token from lane ``n_fed - 1``'s plane;
        - everything else parks wholesale at the NULL block.

        Byte-identity falls out of the (seed, position) key discipline:
        every row folds ``fold_in(row_key, base + n_eff)`` — a decode row
        folds ``wi + 1`` exactly like ``_build_step_paged``, and a final
        chunk folds ``fold_in(row_key, prompt_len)`` exactly like the
        one-shot admission — so the window's shape cannot change any
        draw, and chunked prompt KV bit-equals one-shot prefill KV (same
        canonical positions, same kernel)."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model_chunked_paged
        eos_ids = cfg.eos_token_ids
        B = self.B
        Tmax = self.MB * self.block_size
        kv_quant = self.kv_quant
        i32 = jnp.int32
        from rag_llm_k8s_tpu.models.llama import KVCache

        @phase_scope("mixed")
        def mixed(params, cache_t, tables, kv_len, last_tok, active,
                  rng_keys, fed, n_fed, chunk_base, final):
            is_chunk = n_fed > 0
            is_dec = active & ~is_chunk
            # tokens each row really feeds this window: 1 for decode
            # lanes, the slice width for chunk rows, 0 for bystanders
            n_eff = jnp.where(is_dec, 1, n_fed)
            part = n_eff > 0
            # decode rows anchor at the device frontier; chunk rows at the
            # host-tracked progress frontier (their device kv_len is junk
            # until the final chunk lands)
            base = jnp.where(
                is_chunk, chunk_base, jnp.where(active, kv_len, 0)
            )
            # decode rows' lane 0 is the device-resident last_tok — the
            # host never fetches it between windows (same reason the
            # plain step keeps it on device)
            lane0 = jnp.arange(C, dtype=i32)[None, :] == 0
            fed_eff = jnp.where(is_dec[:, None] & lane0, last_tok[:, None], fed)
            # bystanders' junk routes to the NULL block (same rule as the
            # plain step: an EOS'd row's table is still mapped until the
            # host drains, and logical block 0 can be ref-shared)
            tables_eff = jnp.where(part[:, None], tables, NULL_BLOCK)
            pos = base[:, None] + jnp.arange(C, dtype=i32)[None, :]  # [B, C]
            # the deepest REAL lane (j = n_eff - 1) attends keys
            # <= base + n_eff - 1: kv_len = base + n_eff caps every row's
            # window there; junk lanes beyond see truncated windows and
            # junk logits nobody samples from
            logits, cache = model.apply(
                {"params": params}, fed_eff, pos, KVCache(*cache_t),
                jnp.zeros((B,), i32), base + n_eff, base,
                block_tables=tables_eff,
            )
            # each row samples from its last REAL lane's plane: plane 0
            # for decode (= the plain step's logits[:, 0]), plane
            # n_fed - 1 for a final chunk (= the one-shot admission's
            # logit_index = prompt_len - 1 plane)
            sel = jnp.take_along_axis(
                logits, jnp.maximum(n_eff - 1, 0)[:, None, None], axis=1
            )[:, 0]
            keys = jax.vmap(jax.random.fold_in)(rng_keys, base + n_eff)
            tok = sample_token_per_row(keys, sel, sampling)
            hit_eos = _isin(tok, eos_ids)
            # frontier: base + n_eff KV positions are now written — wi + 1
            # for decode (the plain step's update), prompt progress for
            # chunk rows (the final chunk lands kv_len = prompt_len, the
            # exact post-admission invariant: tok0's KV writes next window)
            kv_len = jnp.where(
                part, jnp.minimum(base + n_eff, Tmax - 1), kv_len
            )
            last_tok = jnp.where(is_dec | final, tok, last_tok)
            # final chunks activate their row (admission complete); decode
            # rows stay active; both retire on EOS. Mid-prompt chunk rows
            # stay device-inactive until their final chunk.
            active = (active | final) & ~hit_eos
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            return out, kv_len, last_tok, tok, hit_eos, active

        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            (self._arena_shardings(), rep, rep, rep, rep, rep)
            if self.mesh is not None else None
        )
        # tables/rng_keys/fed/n_fed/chunk_base/final are host-fed per
        # window, never donated
        return jax.jit(
            mixed, donate_argnums=(1, 3, 4, 5), out_shardings=out_shardings
        ), (
            param_avals(self.params),
            self._arena_avals(),
            jax.ShapeDtypeStruct((B, self.MB), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), bool, sharding=rep),
            jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((B, C), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), i32, sharding=rep),
            jax.ShapeDtypeStruct((B,), bool, sharding=rep),
        )

    def _build_prefix_scatter(self, P: int):
        """Scatter a ``CachedPrefix``'s splice-buffer planes into pool
        blocks: a static loop over the buffer's ``P // block_size`` slabs,
        each landing at its table-assigned physical block (id 0 = the null
        block for slabs past the real prefix — junk nothing reads). Serves
        both the miss path (all blocks private) and the hit path (shared
        blocks carry id 0 here — already populated, skip the write)."""
        bs = self.block_size
        nbp = P // bs  # admit_prefixed validates P % block_size == 0

        @phase_scope("prefill")
        def scatter(arena, planes, ids):
            # ONE scatter per plane (same shape discipline as insert_paged:
            # an unrolled loop here emitted P/bs slice/update pairs per
            # plane — hundreds of HLO ops at the 4096-token default buffer)
            new = []
            for a, p in zip(arena, planes):
                L, K = p.shape[0], p.shape[2]
                if a.ndim == 5:
                    hd = p.shape[4]
                    slabs = p[:, 0, :, : nbp * bs].reshape(
                        L, K, nbp, bs, hd
                    ).transpose(0, 2, 1, 3, 4)  # [L, nbp, K, bs, hd]
                else:
                    slabs = p[:, 0, :, : nbp * bs].reshape(
                        L, K, nbp, bs
                    ).transpose(0, 2, 1, 3)
                new.append(a.at[:, ids].set(slabs.astype(a.dtype)))
            return tuple(new)

        i32 = jnp.int32
        rep = self.mesh.replicated if self.mesh is not None else None
        plane_avals = tuple(
            jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
            for shape, dtype in self._prefix_plane_shapes(P)
        )
        out_shardings = (
            self._arena_shardings() if self.mesh is not None else None
        )
        return jax.jit(
            scatter, donate_argnums=(0,), out_shardings=out_shardings
        ), (
            self._arena_avals(),
            plane_avals,
            jax.ShapeDtypeStruct((nbp,), i32, sharding=rep),
        )

    def _build_prefill_px_paged(self, C: int):
        """Paged PREFIXED admission, batch 1: the prefix KV already sits in
        this row's pool blocks (shared copy-free via ref counts, or freshly
        scattered from the descriptor); only the ``C``-bucketed suffix
        prefills, as a paged CHUNK over the row's table (queries at logical
        ``plen + t``, offset causality). Writes go straight into pool
        blocks — no per-row ``(S,)`` cache materialization or splice."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model_chunked_paged
        kv_quant = self.kv_quant
        i32 = jnp.int32
        from rag_llm_k8s_tpu.models.llama import KVCache

        @phase_scope("prefill")
        def px(params, arena, row_table, suffix_tokens, slen, plen, rngs):
            positions = (plen + jnp.arange(C, dtype=i32))[None, :]
            total = (plen + slen).astype(i32)
            logits, cache = model.apply(
                {"params": params}, suffix_tokens, positions,
                KVCache(*arena), jnp.zeros((1,), i32),
                jnp.broadcast_to(total, (1,)), jnp.broadcast_to(plen, (1,)),
                logit_index=jnp.maximum(slen - 1, 0),
                block_tables=row_table,
            )
            tok0 = sample_token_per_row(rngs, logits[:, -1], sampling)
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )
            return out, tok0

        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            (self._arena_shardings(), rep) if self.mesh is not None else None
        )
        return jax.jit(
            px, donate_argnums=(1,), out_shardings=out_shardings
        ), (
            param_avals(self.params),
            self._arena_avals(),
            jax.ShapeDtypeStruct((1, self.MB), i32, sharding=rep),
            jax.ShapeDtypeStruct((1, C), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=rep),
        )

    def _build_chunk_splice(self, nb: int):
        """Per-chunk paged splice (chunk-granular prefix reuse): copy ``nb``
        physical blocks' K/V from a chunk's canonical registration into
        freshly allocated destination blocks, position-shifting K by the
        closed-form RoPE ``delta`` rotation in the same pass (the int8
        arena goes dequant → rotate → requant with per-vector scale
        recomputation). V is position-free and copies untouched. One
        executable per block count, like every other admission-path op."""
        from rag_llm_k8s_tpu.models.llama import rope_frequencies
        from rag_llm_k8s_tpu.ops.attention import (
            rope_rerotate,
            rope_rerotate_q8,
        )

        inv = rope_frequencies(self.config)
        kv_quant = self.kv_quant
        i32 = jnp.int32

        @phase_scope("prefill")
        def splice(arena, src, dst, delta):
            k, v = arena[0], arena[1]
            ks = jnp.take(k, src, axis=1)  # [L, nb, K, bs, hd]
            vs = jnp.take(v, src, axis=1)
            if kv_quant == "int8":
                ksc = jnp.take(arena[2], src, axis=1)  # [L, nb, K, bs]
                vsc = jnp.take(arena[3], src, axis=1)
                rk, rks = rope_rerotate_q8(ks, ksc, delta, inv)
                return (
                    k.at[:, dst].set(rk),
                    v.at[:, dst].set(vs),
                    arena[2].at[:, dst].set(rks),
                    arena[3].at[:, dst].set(vsc),
                )
            rk = rope_rerotate(ks, delta, inv)
            return (k.at[:, dst].set(rk), v.at[:, dst].set(vs))

        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            self._arena_shardings() if self.mesh is not None else None
        )
        return jax.jit(
            splice, donate_argnums=(0,), out_shardings=out_shardings
        ), (
            self._arena_avals(),
            jax.ShapeDtypeStruct((nb,), i32, sharding=rep),
            jax.ShapeDtypeStruct((nb,), i32, sharding=rep),
            jax.ShapeDtypeStruct((), i32, sharding=rep),
        )

    def _build_boundary_px_paged(self, W: int):
        """Boundary-correction re-prefill straight into pool blocks: the
        first ``W`` tokens of a spliced chunk recompute THROUGH the model
        with the true left context (offset causality over the row's table
        at logical ``woff``; ``kv_len = woff + W`` hides everything to the
        right), their fresh K/V scattering into the already-mapped
        destination blocks. No sampling, no logits consumed — exactly the
        width is written, so the spliced tail beyond the window survives
        (unlike the right-padded suffix prefill, whose pad writes land
        outside every kv window)."""
        model = self.model_chunked_paged
        kv_quant = self.kv_quant
        i32 = jnp.int32
        from rag_llm_k8s_tpu.models.llama import KVCache

        @phase_scope("prefill")
        def bfix(params, arena, row_table, toks, woff):
            positions = (woff + jnp.arange(W, dtype=i32))[None, :]
            kv_len = jnp.broadcast_to(woff + W, (1,)).astype(i32)
            _, cache = model.apply(
                {"params": params}, toks, positions, KVCache(*arena),
                jnp.zeros((1,), i32), kv_len,
                jnp.broadcast_to(woff, (1,)),
                logit_index=jnp.int32(0), block_tables=row_table,
            )
            return (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kv_quant == "int8" else (cache.k, cache.v)
            )

        rep = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            self._arena_shardings() if self.mesh is not None else None
        )
        return jax.jit(
            bfix, donate_argnums=(1,), out_shardings=out_shardings
        ), (
            param_avals(self.params),
            self._arena_avals(),
            jax.ShapeDtypeStruct((1, self.MB), i32, sharding=rep),
            jax.ShapeDtypeStruct((1, W), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        )

    # ------------------------------------------------------------------
    # chunk-granular registrations (reuse="chunk"; scheduler thread only)
    # ------------------------------------------------------------------
    def _chunk_splice_plan(self, prefix):
        """Can this prefix assemble from per-chunk canonical registrations?
        Returns ``[(span, registration), ...]`` covering the WHOLE prefix —
        every span block-aligned, every registration stamp-matched to the
        cache entry the span was resolved from — or None (the admission
        falls back to the buffer-scatter path). All-or-nothing: a partial
        assembly would still scatter the rest, paying both paths."""
        chunks = getattr(prefix, "chunks", None)
        if not chunks or not self._chunk_regs:
            return None
        bs = self.block_size
        if sum(c.length for c in chunks) != int(prefix.length):
            return None
        plan = []
        for c in chunks:
            if c.off % bs or c.length % bs or c.length == 0:
                return None
            reg = self._chunk_regs.get(c.key)
            if (
                reg is None or reg[3] != c.stamp or reg[2] != c.length
                or reg[1] % bs or len(reg[0]) != c.length // bs
            ):
                return None
            plan.append((c, reg))
        try:
            # fault site "chunk_splice": a mid-splice fault pool-side.
            # Nothing is allocated yet — decline the plan and the admission
            # recomputes via the buffer-scatter path, leaking zero blocks.
            faults.maybe_fail("chunk_splice")
        except faults.InjectedFault:
            return None
        for c, _ in plan:
            # planned = in use: the cap evicts least-recently-PLANNED
            self._chunk_regs.move_to_end(c.key)
        return plan

    def _chunk_splice_into_row(self, row: int, ids_all: List[int], plan):
        """Assemble a row's prefix from per-chunk canonical registrations:
        gather each span's source blocks, re-rotate K by the span's
        position delta into the row's destination blocks, then run the
        bounded boundary-correction prefills in ascending offset order
        (each sees the corrected chunks to its left; ``kv_len`` caps its
        view below everything to the right). Device work only — the caller
        owns alloc/assign and the EngineStateLost contract."""
        bs = self.block_size
        for c, reg in plan:
            src_ids, canon_off = reg[0], reg[1]
            nb = len(src_ids)
            dst = ids_all[c.off // bs : c.off // bs + nb]
            delta = c.off - canon_off
            self._cache = self._get("chunk_splice", nb)(
                self._cache,
                self._put(jnp.asarray(np.asarray(src_ids, np.int32))),
                self._put(jnp.asarray(np.asarray(dst, np.int32))),
                self._put(jnp.int32(delta)),
            )
            if delta:
                flight.emit("rerotate", tokens=c.length, delta=delta)
            flight.emit("chunk_splice", tokens=c.length, delta=delta, pool=1)
        row_table = None
        for c, reg in plan:
            delta = c.off - reg[1]
            if (c.exact and delta == 0) or not c.fixup_ids:
                continue  # canonical placement: content already faithful
            W = len(c.fixup_ids)
            if row_table is None:
                row_table = self._put(
                    jnp.asarray(self._tables_host[row : row + 1])
                )
            toks = np.asarray([list(c.fixup_ids)], np.int32)
            self._cache = self._get("boundary_px", W)(
                self.params, self._cache, row_table,
                self._put(jnp.asarray(toks)), self._put(jnp.int32(c.off)),
            )
            flight.emit("boundary_fixup", tokens=W)

    def _register_chunks_from_scatter(self, prefix, ids_all: List[int],
                                      chain_registered: bool = False):
        """After a buffer-scatter admission, register each block-aligned
        EXACT span's freshly scattered blocks as the chunk's canonical pool
        copy (one pool ref each — they outlive the row). Only exact spans
        qualify: registering a re-rotated copy would compound drift when a
        later splice rotates it again. Stamp identity ties the
        registration to the prefix-cache entry, so a rebuilt entry's stale
        registration simply stops matching. Only call this from the
        admission that actually SCATTERED the blocks — on a chain hit the
        block content was written by an earlier admission and this
        resolve's spans do not describe it. ``chain_registered``: this
        admission's full blocks are ALSO chain-registered — the chunk
        registrations then carry ``counted=False``, which gates ALL THREE
        accountings (fragmentation tokens, the reclaimable-blocks hint,
        and the pool's warm-tier ledger): a chain-covered chunk reg's
        drop frees no blocks while the chain ref lives, so advertising it
        reclaimable would make the gate queue a request no sweep can
        place (gauge-grade: once the chain registration drops, its chunk
        regs under-report until they too are swept)."""
        chunks = getattr(prefix, "chunks", None)
        if not chunks:
            return
        bs = self.block_size
        pc = getattr(self.engine_config, "prefix_cache", None)
        cap = max(1, int(getattr(pc, "chunk_pool_regs", 32) or 32))
        full_tokens = (int(prefix.length) // bs) * bs
        for c in chunks:
            if (
                not c.exact or c.length == 0
                or c.off % bs or c.length % bs
                or c.off + c.length > full_tokens
            ):
                continue
            old = self._chunk_regs.get(c.key)
            if old is not None and old[3] == c.stamp:
                continue  # this entry generation is already registered
            nb = c.length // bs
            span_ids = ids_all[c.off // bs : c.off // bs + nb]
            self.kv_pool.ref(span_ids)  # the registration's own ref
            if old is not None:
                self._drop_chunk_reg(c.key)
            counted = not chain_registered
            self._chunk_regs[c.key] = (
                list(span_ids), c.off, c.length, c.stamp, counted
            )
            if counted:
                self._chunk_reg_tokens += c.length
                self._reclaimable_blocks += len(span_ids)
                self.kv_pool.account_tier("warm", len(span_ids))
            while len(self._chunk_regs) > cap:  # bounded registration set
                self._drop_chunk_reg(next(iter(self._chunk_regs)))

    def _drop_chunk_reg(self, key) -> bool:
        """The one place a chunk registration dies: pops the entry, fixes
        the fragmentation counter, returns the blocks to the pool."""
        reg = self._chunk_regs.pop(key, None)
        if reg is None:
            return False
        if reg[4]:
            n = len(reg[0])
            self._chunk_reg_tokens -= reg[2]
            self._reclaimable_blocks = max(0, self._reclaimable_blocks - n)
            self.kv_pool.account_tier("warm", -n)
        self.kv_pool.free(reg[0])
        return True

    # ------------------------------------------------------------------
    # paged host bookkeeping (scheduler thread only, like the operations)
    # ------------------------------------------------------------------
    def _device_tables(self):
        """The device copy of the block tables, refreshed only when the host
        tables changed (admission, growth, retire) — a [B, MB] int32 put,
        tiny next to any step."""
        if self._tables_dirty or self._tables_dev is None:
            self._tables_dev = self._put(jnp.asarray(self._tables_host))
            self._tables_dirty = False
        return self._tables_dev

    def _assign_row_blocks(self, row: int, ids: List[int], start_block: int = 0):
        """Map ``ids`` into the row's table at logical blocks
        ``[start_block, ...)`` and record ownership."""
        for j, b in enumerate(ids):
            self._tables_host[row, start_block + j] = b
        self._slot_blocks[row].extend(ids)
        self._tables_dirty = True

    def _release_row(self, row: int) -> None:
        """Return the row's blocks to the pool and null its table — MUST
        happen before the next step: an inactive row still writes its junk
        token at table[row, 0], and a stale entry would corrupt whoever the
        freed block is reallocated to."""
        if self._slot_blocks[row]:
            self.kv_pool.free(self._slot_blocks[row])
            self._slot_blocks[row] = []
        if self._tables_host[row].any():
            self._tables_host[row, :] = NULL_BLOCK
            self._tables_dirty = True

    def _retire_rows(self, rows: List[int]) -> None:
        """Paged-mode retire hook (budget/EOS/evict): record the per-request
        block footprint, then free."""
        if not self.paged:
            return
        if len(self._blocks_at_retire) > 8192:
            # raw-engine callers (tests, scripts) never pop; don't let the
            # footprint map grow without bound under them
            self._blocks_at_retire.clear()
        for r in rows:
            rid = self.slots[r].request_id
            if rid >= 0:
                self._blocks_at_retire[rid] = len(self._slot_blocks[r])
            self._release_row(r)

    def pop_blocks_allocated(self, request_id: int) -> Optional[int]:
        """Blocks the request held at retirement (paged; None otherwise) —
        the scheduler forwards it into the response timings."""
        if not self.paged:
            return None
        return self._blocks_at_retire.pop(request_id, None)

    # ------------------------------------------------------------------
    # goodput ledger plumbing (obs/goodput.py; scheduler thread only)
    # ------------------------------------------------------------------
    def mark_rework(self, request_id: int) -> None:
        """The next admission of ``request_id`` re-feeds tokens already
        computed once (preemption resume / reset resubmission): its real
        token lanes attribute to ``preempt_rework``, not fresh prefill.
        The mark is consumed by exactly one admission — rework is never
        double-counted."""
        if len(self._rework_rids) > 4096:  # stale marks of failed retries
            # sweep BEFORE adding: the fresh mark (and only accreted stale
            # ones) must survive the overflow, or the very resubmission
            # that tripped it loses its rework attribution
            self._rework_rids.clear()
        self._rework_rids.add(request_id)

    def _take_rework(self, rids) -> "set":
        taken = {r for r in rids if r in self._rework_rids}
        self._rework_rids -= taken
        return taken

    def pop_request_goodput(self, request_id: int,
                            tokens: float = 0.0) -> Optional[Dict]:
        """One completed request's attributed chip-time figures (chip_ms,
        goodput_frac, cost_usd, speculation stats) — the scheduler
        forwards them into the response timings at delivery. ``tokens``
        (the delivered count) feeds the ledger's per-tenant rollup."""
        return self.ledger.pop_request(request_id, tokens=tokens)

    def pop_spec_seen(self, request_id: int) -> bool:
        """True iff any verify window ever judged drafts for this request
        — the spec_verify half of the per-request approximation
        fingerprint (obs/shadow.py), independent of the goodput ledger.
        Popping keeps the set bounded by in-flight requests."""
        try:
            self._spec_rids.remove(request_id)
            return True
        except KeyError:
            return False

    def discard_request_goodput(self, request_id: int) -> None:
        """Reclaim a never-delivered request's ledger entry (gave up /
        deadline eviction / shutdown) — without this, failed requests
        accrete until the bounded map evicts in-flight entries with them.
        The spec-fingerprint set shares the cleanup (same lifetime)."""
        self.ledger.discard_request(request_id)
        self._spec_rids.discard(request_id)

    def _journal_window(self, summary) -> None:
        if summary is not None:
            flight.emit("goodput_window", **summary)

    def _journal_emitted(self) -> None:
        """Flight-WAL watermark pass (every sync-window drain): journal
        each live row's emitted-token delta as one ``token_emit`` event,
        so concatenating a request's token_emit events rebuilds its full
        emitted stream — the state a warm restart folds back in. Gated on
        an attached WAL: without one this is a no-op (the ring needs no
        per-window token copies; greedy resume recomputes). Tokens
        appended after the last window before a SIGKILL are simply
        recomputed on resume — deterministic decode makes the tail safe
        to lose."""
        if not flight.wal_enabled():
            return
        for slot in self.slots:
            if slot.active and len(slot.tokens) > slot.wal_mark:
                flight.emit("token_emit", slot.request_id,
                            toks=slot.tokens[slot.wal_mark:])
                slot.wal_mark = len(slot.tokens)

    def blocks_needed(self, prompt_len: int) -> int:
        """Admission-time block cost of a prompt (0 in dense mode)."""
        if not self.paged:
            return 0
        return self.kv_pool.blocks_for(max(int(prompt_len), 1))

    def admission_state(self, prompt_len: int) -> str:
        """'ok' — admissible now; 'wait' — pool pressure, decode will free
        blocks; 'never' — the prompt alone outsizes the whole pool."""
        if not self.paged:
            return "ok"
        # the verdict arithmetic (never / incremental-ok / +1-headroom
        # want) is the decision core's; only the stateful reclaim loop
        # below stays here
        verdict, want = sim_policy.admission_verdict(
            self.blocks_needed(prompt_len), self.kv_pool.usable_blocks(),
            self.interleave_on, self.MB,
        )
        if verdict != "check":
            return verdict
        if self.kv_pool.can_alloc(want):
            return "ok"
        if self._prefix_blocks or self._chunk_regs:
            # tier occupancy, not raw headroom: WARM registrations give
            # their blocks to a live admission even while rows decode —
            # the chunk KV survives (int8) in the prefix cache, one
            # re-scatter away, so reclaiming them costs a future re-stage,
            # never a re-prefill. HOT registrations are proven-shared
            # working set and are only sacrificed when nothing decodes
            # (the idle branch below).
            if self._chunk_regs:
                # chunk-canonical copies go FIRST (same order as the
                # growth-pressure path): pure prefill avoidance, rebuilt
                # from the prefix cache on the next exact scatter —
                # cheaper to restore than a whole warm chain's re-stage
                for key in list(self._chunk_regs):
                    self._drop_chunk_reg(key)
                    if self.kv_pool.can_alloc(want):
                        return "ok"
            for key in [
                k for k, t in list(self._prefix_tier.items()) if t != "hot"
            ]:
                self._drop_registration(key)
                if self.kv_pool.can_alloc(want):
                    return "ok"
        if self._prefix_blocks and not self.has_active():
            # nothing is decoding, yet the pool can't take one prompt: the
            # registered prefix blocks are the only other holder — drop the
            # oldest registrations until the admission fits (cache refs are
            # re-buildable; a wedged queue is not)
            for key in list(self._prefix_blocks):
                self._drop_registration(key)
                if self.kv_pool.can_alloc(want):
                    return "ok"
        return "wait" if self.has_active() else (
            "ok" if self.kv_pool.can_alloc(want) else "never"
        )

    def _ensure_decode_blocks(
        self, horizon: "Optional[Dict[int, int]]" = None
    ) -> None:
        """Grow every active row's table to cover the next sync window
        (positions up to ``kv_ub + k``) BEFORE the device call — a write
        landing in an unmapped block would vanish into the null block and
        corrupt the stream one step later. ``horizon`` overrides the
        per-row token horizon (speculative verify windows write
        ``n_drafts + 1`` positions per row, not ``sync_steps`` — rows
        draft different lengths, so the map is per-row). Exhaustion
        preempts the NEWEST-admitted rows (their emitted tokens return to
        the scheduler, which resubmits once blocks free — vLLM-style
        recompute preemption) until the remaining rows fit."""
        k = self.sync_steps
        while True:
            # mapped logical blocks are contiguous from 0, so the
            # ownership list IS the count — no B x MB table rescan on
            # the hot per-window path
            short = sim_policy.grow_shortfall(
                (
                    (slot.admit_seq, row, slot.kv_ub,
                     len(self._slot_blocks[row]))
                    for row, slot in enumerate(self.slots) if slot.active
                ),
                k, horizon, self.block_size, self.MB,
            )  # (admit_seq, row, missing, have), oldest admissions first
            if not short:
                return
            ok = True
            for _, row, missing, have in short:
                try:
                    ids = self.kv_pool.alloc(missing)
                except PoolExhausted:
                    ok = False
                    break
                self._assign_row_blocks(row, ids, start_block=have)
                flight.emit(
                    "block_grow", self.slots[row].request_id,
                    blocks=missing, total=have + missing,
                )
            if ok:
                return
            # growth blocked: drop registered prefix blocks first (cache
            # refs are re-buildable; without this a lone active row whose
            # growth the registrations crowd out would preempt ITSELF in a
            # loop), then preempt the newest active row and retry.
            # Non-hot registrations go first — a warm chunk costs one
            # re-scatter to bring back, a hot one a proven-shared re-stage
            if self._chunk_regs:
                # chunk-canonical copies go before chain registrations:
                # they are rebuilt from the cache by any exact scatter
                self._drop_chunk_reg(next(iter(self._chunk_regs)))
                continue
            if self._prefix_blocks:
                self._drop_registration(sim_policy.reclaim_registration(
                    self._prefix_blocks, self._prefix_tier,
                    self._prefix_reg_gen,
                ))
                continue
            if self._chunk_admissions:
                # pending chunked admissions are the cheapest preemption
                # victims: ZERO emitted tokens to replay — the scheduler
                # resubmits them wholesale, and recompute is exactly the
                # prefill that hadn't happened yet. Newest-queued first,
                # matching the active-row discipline below.
                rid, rec = self._chunk_admissions.popitem()
                self._preempt_chunk_admission(rid, rec)
                continue
            _, victim = sim_policy.preempt_victim(
                (s.admit_seq, r) for r, s in enumerate(self.slots) if s.active
            )
            vslot = self.slots[victim]
            logger.warning(
                "kv pool exhausted mid-decode; preempting request %d "
                "(%d blocks back to the pool)",
                vslot.request_id, len(self._slot_blocks[victim]),
            )
            self._preempted.append((vslot.request_id, list(vslot.tokens)))
            self._m_pool_preempt.inc()
            flight.emit(
                "preempt", vslot.request_id,
                blocks=len(self._slot_blocks[victim]),
                n_tokens=len(vslot.tokens),
            )
            m = np.ones(self.B, bool)
            m[victim] = False
            self._active = self._active & self._put(jnp.asarray(m))
            self._release_row(victim)
            self.slots[victim] = _Slot()

    def _preempt_chunk_admission(self, rid: int, rec: dict) -> None:
        """Cancel an in-flight chunked admission under pool pressure: its
        partially-written blocks return to the pool and the request joins
        the preempted list with ZERO emitted tokens — the scheduler
        resubmits it (``_fold_emitted`` no-ops on the empty record), so
        the only cost is re-prefilling what this row had staged."""
        row = rec["row"]
        logger.warning(
            "kv pool exhausted; preempting chunked admission %d "
            "(%d blocks back to the pool, %d/%d prompt tokens staged)",
            rid, len(self._slot_blocks[row]), rec["progress"],
            len(rec["prompt"]),
        )
        self._preempted.append((rid, []))
        self._m_pool_preempt.inc()
        flight.emit(
            "preempt", rid,
            blocks=len(self._slot_blocks[row]), n_tokens=0,
        )
        self._release_row(row)
        self.slots[row] = _Slot()

    def drain_preempted(self) -> List[Tuple[int, List[int]]]:
        """Requests preempted by pool exhaustion since the last drain, as
        ``(request_id, emitted_tokens)`` — the scheduler resubmits them
        (prompt + emitted, budget reduced), so preemption is invisible to
        callers beyond latency."""
        if not self.paged or not self._preempted:
            return []
        out, self._preempted = self._preempted, []
        return out

    def pool_used_tokens(self) -> int:
        """Live logical tokens across UNIQUE pool blocks (host mirrors) —
        the numerator of the fragmentation gauge. Ref-shared prefix blocks
        count once, via their registration: each sharing row subtracts the
        tokens its table serves from shared blocks (a row whose
        registration was since dropped briefly over-reports fragmentation —
        a gauge-grade approximation, clamped by the pool)."""
        if not self.paged:
            return 0
        rows = sum(
            max(s.kv_ub - s.shared_tokens, 0) for s in self.slots if s.active
        )
        # the registration totals are single ints maintained on the
        # scheduler thread — iterating _prefix_blocks here would race the
        # scheduler's register/evict and crash a /metrics scrape with
        # "dictionary changed size during iteration"
        return rows + self._registered_tokens + self._chunk_reg_tokens

    # ------------------------------------------------------------------
    # operations (called by the scheduler thread only)
    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        # a prefilling row is reserved by an in-flight chunked admission:
        # not decoding yet, but not admissible either
        return [
            i for i, s in enumerate(self.slots)
            if not s.active and not s.prefilling
        ]

    def has_active(self) -> bool:
        # prefilling rows count: the scheduler must keep stepping (mixed
        # windows are what advances them), and admission_state must say
        # "wait", not "never", while they hold pool blocks
        return any(s.active or s.prefilling for s in self.slots)

    def evict_requests(self, request_ids: Sequence[int]) -> List[int]:
        """Deactivate the slots serving ``request_ids`` (deadline eviction):
        the rows stop attending/advancing on the very next step and their
        slots are immediately admissible again. Reuses the same deactivate
        mask the budget-retire path applies after ``step()`` — eviction is
        a retire without a result. Returns the freed row indices."""
        wanted = set(request_ids)
        rows = [
            i for i, s in enumerate(self.slots)
            if s.active and s.request_id in wanted
        ]
        if rows:
            m = np.ones(self.B, bool)
            m[rows] = False
            self._active = self._active & self._put(jnp.asarray(m))
            for r in rows:
                flight.emit(
                    "evict", self.slots[r].request_id,
                    n_tokens=len(self.slots[r].tokens),
                )
            self._retire_rows(rows)  # paged: blocks back to the free list
            for r in rows:
                self.slots[r] = _Slot()
        # in-flight chunked admissions are evictable too — the deadline
        # sweep sees them in `waiting` like any decoding request, and their
        # partially-written blocks must go back or eviction leaks the pool
        for rid in [r for r in self._chunk_admissions if r in wanted]:
            rec = self._chunk_admissions.pop(rid)
            row = rec["row"]
            flight.emit("evict", rid, n_tokens=0)
            self._blocks_at_retire[rid] = len(self._slot_blocks[row])
            self._release_row(row)
            self.slots[row] = _Slot()
            rows.append(row)
        return rows

    def admit(
        self,
        request_id: int,
        prompt: Sequence[int],
        max_new: int,
        seed: Optional[int] = None,
    ) -> Tuple[int, Optional[List[int]]]:
        """Prefill + insert into a free slot. Returns ``(slot, finished)``;
        ``finished`` is the complete token list when the request ends at its
        very first token (EOS or max_new=1) without keeping the slot."""
        res = self.admit_many([(request_id, prompt, max_new, seed)])[0]
        if isinstance(res, BaseException):
            raise res
        return res

    def admit_many(
        self, items: Sequence[Tuple[int, Sequence[int], int, Optional[int]]]
    ) -> List[Tuple[int, Optional[List[int]]]]:
        """Admit a GROUP of requests: same-bucket requests prefill together
        (one batched forward), splice into their slots in one insert call,
        and their first tokens return in ONE device→host fetch — the
        per-admission round-trip (the continuous engine's biggest cost on a
        slow host link) amortizes over the group. Returns ``(slot,
        finished)`` per item, input order.

        Per item: the prompt is bucketed over the FULL bucket ladder and
        ``max_new`` is clamped to the remaining cache room (mirroring
        ``InferenceEngine._clamp_max_new``) — the prompt is never cut to
        make room for generation. Only a prompt over the largest bucket
        truncates, loudly (continuous slots are fixed-length; route such
        prompts through ``InferenceEngine``'s chunked prefill instead).
        Draws stay (seed, position)-keyed per row, so admission grouping
        never changes what a request samples.

        Failure isolation: a failed CHUNK fails only its own items — their
        result entries are the exception instance (callers re-raise or
        deliver per item); earlier chunks' admissions stand.
        ``EngineStateLost`` is the exception to that: the reset wiped every
        slot, so it propagates out of the whole call."""
        free = self.free_slots()
        assert len(items) <= len(free), "admit_many() without enough free slots"
        # the FIRST chunk's ledger window absorbs this call's prep (per-item
        # key derivation is device work too) — without it, the per-request
        # chip-second sums drift below the scheduler's measured busy time
        # and the conservation invariant frays at small window counts
        self._admit_lead = time.perf_counter()

        prepared = []  # (item_idx, rid, S, p, max_new_c, row_key)
        for i, (rid, prompt, max_new, seed) in enumerate(items):
            S = sim_policy.bucket_len(max(len(prompt), 1), self.buckets)
            max_new_c = sim_policy.clamp_max_new(max_new, S, self.T)
            p = list(prompt)[-S:]
            if len(prompt) > S:
                logger.warning(
                    "continuous-batch prompt of %d tokens exceeds the largest "
                    "bucket %d; left-truncating", len(prompt), S,
                )
            if seed is not None:
                row_key = jax.random.PRNGKey(seed)
            else:
                self._rng, row_key = jax.random.split(self._rng)
            prepared.append((i, rid, S, p, max_new_c, row_key))

        if self.interleave_on and self.paged:
            # unified ragged windows (ISSUE 16): admission is INCREMENTAL —
            # reserve a row and queue the prompt; mixed windows feed it in
            # budgeted chunks alongside decode. No prefill forward, no
            # up-front block allocation (the planner allocates per chunk),
            # so this path cannot raise PoolExhausted. The prep above ran
            # UNCHANGED — same bucketing/truncation/clamp and the same
            # ``self._rng`` split order, so streams bit-match the
            # phase-separated scheduler.
            results = [None] * len(items)
            free_iter = iter(free)
            for i, rid, S, p, max_new_c, row_key in prepared:
                self._queue_chunk_admission(
                    i, rid, S, p, max_new_c, row_key,
                    next(free_iter), results,
                )
            return results

        results: List = [None] * len(items)
        free_iter = iter(free)
        # same-bucket grouping in pow2 chunks (warmup-friendly executable
        # ladder), arrival order preserved — the decision core plans it
        for S, member_idx in sim_policy.admission_chunks(
            [(j, entry[2]) for j, entry in enumerate(prepared)], self.B
        ):
            chunk = [prepared[j] for j in member_idx]
            rows = [next(free_iter) for _ in chunk]
            try:
                self._admit_chunk(S, chunk, rows, results)
            except EngineStateLost:
                raise  # slots are gone for EVERYONE; callers must fail
            except BaseException as e:  # noqa: BLE001 — per-chunk isolation
                for i, _, _, _, _, _ in chunk:
                    results[i] = e
        return results

    def _admit_chunk_t0(self) -> float:
        """This chunk's ledger-window start: the admit_many call's entry
        stamp for the first chunk (prep absorbed), now for the rest."""
        lead = getattr(self, "_admit_lead", None)
        if lead is not None:
            self._admit_lead = None
            return lead
        return time.perf_counter()

    def _admit_chunk(self, S: int, chunk, rows: List[int], results: List):
        """One batched prefill + insert + first-token fetch for ``chunk``."""
        if self.paged:
            return self._admit_chunk_paged(S, chunk, rows, results)
        t_led = self._admit_chunk_t0()  # ledger window (prep absorbed)
        t_admit = time.perf_counter()  # _m_step_admit keeps chunk-only
        n = len(chunk)
        tokens = np.full((n, S), self.pad_id, np.int32)
        mask = np.zeros((n, S), np.int32)
        folded_keys, base_keys = [], []
        for r, (_, _, _, p, _, row_key) in enumerate(chunk):
            tokens[r, S - len(p):] = p
            mask[r, S - len(p):] = 1
            # position-indexed draw: the first sampled token sits at position
            # len(p); decode steps continue the same fold sequence. Keys STAY
            # on device — fetching them here would put one host round-trip
            # per request back on the admission path the batching removed
            folded_keys.append(jax.random.fold_in(row_key, len(p)))
            base_keys.append(row_key)
        folded = jnp.stack(folded_keys)
        row_keys = jnp.stack(base_keys)

        row_cache, tok0s, row_starts = self._get("prefill", S, n)(
            self.params, self._put(tokens), self._put(mask), self._put(folded)
        )
        try:
            # fault site "insert": models a device fault inside the donated
            # splice — the handler below must reset and raise EngineStateLost
            faults.maybe_fail("insert")
            # insert dispatches BEFORE the tok0 fetch: the splice runs on
            # device while the first tokens cross the host link
            (self._cache, self._kv_start, self._kv_len,
             self._last_tok, self._active, self._rng_keys) = self._get("insert", S, n)(
                self._cache, row_cache,
                self._kv_start, self._kv_len, self._last_tok, self._active,
                self._rng_keys, self._put(np.asarray(rows, np.int32)),
                row_starts, tok0s, self._put(row_keys),
            )
        except BaseException as e:  # noqa: BLE001
            # insert donates the engine's cache/state buffers: a failure
            # mid-execution has invalidated them even though nothing was
            # reassigned — rebuild now, or every later admit serves
            # "Array has been deleted" while /healthz stays green
            self.reset()
            raise EngineStateLost("insert failed; engine state reset") from e

        try:
            tok0_h = np.asarray(tok0s)  # ONE fetch for the whole chunk
            self._m_step_admit.observe(time.perf_counter() - t_admit)
            deactivate = []
            for r, (i, rid, _, p, max_new_c, _) in enumerate(chunk):
                tok0 = int(tok0_h[r])
                row = rows[r]
                self.stats.generate_calls += 1
                self.stats.prefill_tokens += len(p)
                flight.emit(
                    "admit", rid, slot=row, prompt_len=len(p), bucket=S,
                    tok0=tok0, **_tenant_attr(self.ledger, rid),
                )
                if tok0 in self.config.eos_token_ids or max_new_c <= 1:
                    # finished at its very first token: the slot was spliced
                    # active by the batched insert — release it on device too
                    out = [] if tok0 in self.config.eos_token_ids else [tok0]
                    self.stats.decode_tokens += len(out)
                    deactivate.append(row)
                    results[i] = (row, out)
                    continue
                self.slots[row] = _Slot(
                    request_id=rid, tokens=[tok0], remaining=max_new_c - 1,
                    active=True,
                )
                self.stats.decode_tokens += 1  # tok0, sampled at prefill
                results[i] = (row, None)
            if deactivate:
                m = np.ones(self.B, bool)
                m[deactivate] = False
                self._active = self._active & self._put(jnp.asarray(m))
            led_rows = {rid: len(p) for _, rid, _, p, _, _ in chunk}
            self._journal_window(self.ledger.record_prefill(
                time.perf_counter() - t_led, bucket=S, rows=led_rows,
                rework=self._take_rework(led_rows),
            ))
        except BaseException:  # noqa: BLE001 — release before isolation
            # the insert already spliced these rows device-active; failing
            # here (e.g. the tok0 fetch) would otherwise leave them decoding
            # garbage every step with no host _Slot to ever retire them —
            # deactivate the whole chunk's rows and drop any _Slot entries
            # made above, THEN let admit_many's per-chunk isolation handle it
            m = np.ones(self.B, bool)
            m[rows] = False
            self._active = self._active & self._put(jnp.asarray(m))
            for row in rows:
                self.slots[row] = _Slot()  # fresh inactive slot
            raise

    def _admit_chunk_paged(self, S: int, chunk, rows: List[int], results: List):
        """Paged twin of ``_admit_chunk``: allocate each row's blocks, one
        RIGHT-padded batched prefill, one scatter-insert into the arena —
        no per-row ``(S,)`` cache splice survives past the insert call.
        ``PoolExhausted`` during allocation is backpressure, not failure:
        already-taken blocks return and the exception propagates so the
        scheduler can requeue the chunk's items."""
        t_led = self._admit_chunk_t0()  # ledger window (prep absorbed)
        t_admit = time.perf_counter()  # _m_step_admit keeps chunk-only
        n = len(chunk)
        bs = self.block_size
        nb = S // bs
        taken: List[Tuple[int, List[int]]] = []  # (row, ids)
        block_ids = np.zeros((n, nb), np.int32)  # NULL beyond a row's need
        lens = np.zeros((n,), np.int32)
        try:
            for r, (_, _, _, p, _, _) in enumerate(chunk):
                need = self.kv_pool.blocks_for(max(len(p), 1))
                ids = self.kv_pool.alloc(need)
                taken.append((rows[r], ids))
                block_ids[r, : len(ids)] = ids
                lens[r] = len(p)
        except PoolExhausted:
            for _, ids in taken:
                self.kv_pool.free(ids)
            # the bounced chunk cost real scheduler time (per-item key
            # prep is device work): attribute the failed attempt to its
            # requests — they requeue, and without this the conservation
            # invariant frays under sustained pool pressure
            self._journal_window(self.ledger.record_preempt_stall(
                time.perf_counter() - t_led,
                [c[1] for c in chunk], kind="prefill",
            ))
            raise
        tokens = np.full((n, S), self.pad_id, np.int32)
        folded_keys, base_keys = [], []
        for r, (_, _, _, p, _, row_key) in enumerate(chunk):
            tokens[r, : len(p)] = p  # RIGHT-padded: logical positions 0..len
            # same (seed, position) fold as the dense path: the first
            # sampled token sits at canonical position len(p) either way
            folded_keys.append(jax.random.fold_in(row_key, len(p)))
            base_keys.append(row_key)
        folded = jnp.stack(folded_keys)
        row_keys = jnp.stack(base_keys)

        for row, ids in taken:
            self._assign_row_blocks(row, ids)
        self._device_tables()  # refresh before anything can step

        try:
            row_cache, tok0s = self._get("prefill_paged", S, n)(
                self.params, self._put(tokens), self._put(jnp.asarray(lens)),
                self._put(folded),
            )
        except BaseException:  # noqa: BLE001 — nothing donated yet
            # the prefill touches none of the engine's donated state, so
            # per-chunk isolation is enough — but the blocks taken above
            # must go back and the tables re-null, or a one-off device
            # error becomes a permanent pool leak on inactive rows
            for row, _ in taken:
                self._release_row(row)
            raise
        try:
            faults.maybe_fail("insert")
            (self._cache, self._kv_len, self._last_tok,
             self._active, self._rng_keys) = self._get("insert_paged", S, n)(
                self._cache, row_cache,
                self._kv_len, self._last_tok, self._active, self._rng_keys,
                self._put(np.asarray(rows, np.int32)),
                self._put(jnp.asarray(block_ids)),
                self._put(jnp.asarray(lens)), tok0s, self._put(row_keys),
            )
        except BaseException as e:  # noqa: BLE001 — donated arena invalidated
            self.reset()
            raise EngineStateLost("insert failed; engine state reset") from e

        try:
            tok0_h = np.asarray(tok0s)  # ONE fetch for the whole chunk
            self._m_step_admit.observe(time.perf_counter() - t_admit)
            deactivate = []
            for r, (i, rid, _, p, max_new_c, _) in enumerate(chunk):
                tok0 = int(tok0_h[r])
                row = rows[r]
                self.stats.generate_calls += 1
                self.stats.prefill_tokens += len(p)
                flight.emit(
                    "admit", rid, slot=row, prompt_len=len(p), bucket=S,
                    tok0=tok0, **_tenant_attr(self.ledger, rid),
                )
                if tok0 in self.config.eos_token_ids or max_new_c <= 1:
                    out = [] if tok0 in self.config.eos_token_ids else [tok0]
                    self.stats.decode_tokens += len(out)
                    deactivate.append(row)
                    self._blocks_at_retire[rid] = len(self._slot_blocks[row])
                    self._release_row(row)
                    results[i] = (row, out)
                    continue
                self._admit_seq += 1
                self.slots[row] = _Slot(
                    request_id=rid, tokens=[tok0], remaining=max_new_c - 1,
                    active=True, kv_ub=len(p), admit_seq=self._admit_seq,
                    prompt_len=len(p),
                    # spec draft corpus: the full assembled prompt (head +
                    # retrieved chunks arrive through the scheduler as one
                    # token list) + the first sampled token
                    history=(list(p) + [tok0]) if self.spec_on else [],
                )
                self.stats.decode_tokens += 1
                results[i] = (row, None)
            if deactivate:
                m = np.ones(self.B, bool)
                m[deactivate] = False
                self._active = self._active & self._put(jnp.asarray(m))
            led_rows = {rid: len(p) for _, rid, _, p, _, _ in chunk}
            self._journal_window(self.ledger.record_prefill(
                time.perf_counter() - t_led, bucket=S, rows=led_rows,
                rework=self._take_rework(led_rows),
            ))
        except BaseException:  # noqa: BLE001 — release before isolation
            m = np.ones(self.B, bool)
            m[rows] = False
            self._active = self._active & self._put(jnp.asarray(m))
            for row in rows:
                self._release_row(row)
                self.slots[row] = _Slot()
            raise

    def _queue_chunk_admission(
        self, i: int, rid: int, S: int, p: List[int], max_new_c: int,
        row_key, row: int, results: List,
    ) -> None:
        """Reserve ``row`` for an incremental admission and queue its
        record — zero device work. The row's UNFOLDED key is staged now
        (the ``insert_paged`` idiom): the final chunk's executable folds
        ``(row_key, len(p))`` from it, and decode continues the same fold
        sequence once the row activates."""
        self._admit_seq += 1
        self._rng_keys = self._rng_keys.at[row].set(self._put(row_key))
        self.slots[row] = _Slot(
            request_id=rid, prefilling=True, admit_seq=self._admit_seq,
            prompt_len=len(p),
        )
        self._chunk_admissions[rid] = {
            "row": row, "prompt": p, "progress": 0, "row_key": row_key,
            "max_new": max_new_c, "bucket": S, "admit_seq": self._admit_seq,
            # TTFT anchors: the scheduler overwrites t_submit with the
            # request's real submit stamp (or None for retries/resumes,
            # which never observe TTFT — phase-separated parity); raw
            # engine callers fall back to the queue stamp
            "t_admit": time.monotonic(),
        }
        results[i] = (row, None)

    def _alloc_chunk_blocks(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks for a scheduled prefill chunk, reclaiming
        re-buildable registrations under pressure in ``admission_state``'s
        order: chunk-canonical copies first, then non-hot prefix chains,
        then — only when nothing decodes — hot chains. ``None`` = the pool
        really is full; the planner idles the admission this window."""
        while True:
            try:
                return self.kv_pool.alloc(n)
            except PoolExhausted:
                if self._chunk_regs:
                    self._drop_chunk_reg(next(iter(self._chunk_regs)))
                    continue
                non_hot = [
                    k for k, t in list(self._prefix_tier.items())
                    if t != "hot"
                ]
                if non_hot:
                    self._drop_registration(non_hot[0])
                    continue
                if self._prefix_blocks and not self.has_active():
                    self._drop_registration(next(iter(self._prefix_blocks)))
                    continue
                return None

    def _step_mixed(self) -> List[Tuple[int, List[int]]]:
        """One UNIFIED ragged sync window (ISSUE 16): every active decode
        lane advances one token while a budgeted slice of each pending
        chunked admission prefills through the SAME device call — decode
        never stops for admission, and a long prompt spreads its prefill
        across as many windows as its chunks.

        Budget split: active decode lanes cost one token each; the
        remainder slices pending admissions FIFO (oldest first — the
        request closest to its first token wins the window's leftover).
        Each scheduled chunk allocates only ITS blocks (incremental — the
        one-shot path pays the whole prompt up front); pool pressure
        reclaims re-buildable registrations, then idles the youngest
        admissions for the window.

        The drain mirrors the two phase-separated paths exactly: decode
        rows drain like a ``k=1`` plain window, final chunks run
        ``_admit_chunk_paged``'s tail (admit event, EOS/max_new<=1
        immediate retire, fresh active ``_Slot`` otherwise) — so streams,
        events and block accounting are indistinguishable downstream."""
        C = self.chunk_tokens
        t_w = time.perf_counter()  # ledger window: planning + growth included
        Tmax = self.MB * self.block_size
        # map decode lanes' one write each BEFORE dispatch; exhaustion here
        # preempts pending chunked admissions before any decoding row
        self._ensure_decode_blocks(horizon={})
        n_dec = sum(1 for s in self.slots if s.active)
        # the budget split (decode lanes first, remainder FIFO over the
        # pending admissions in chunk_tokens slices) is the decision
        # core's; this loop only stages each slice's blocks, idling the
        # younger admissions at the first slice the pool cannot take
        sched = []  # (rid, rec, offset, take, final)
        for rid, off, take, final in sim_policy.plan_mixed_window(
            [(rid, len(rec["prompt"]), rec["progress"])
             for rid, rec in self._chunk_admissions.items()],
            self.window_budget, n_dec, C,
        ):
            rec = self._chunk_admissions[rid]
            row = rec["row"]
            need = self.kv_pool.blocks_for(off + take)
            have = len(self._slot_blocks[row])
            if need > have:
                ids = self._alloc_chunk_blocks(need - have)
                if ids is None:
                    break  # pool pressure: idle the rest this window
                self._assign_row_blocks(row, ids, start_block=have)
            sched.append((rid, rec, off, take, final))
        flight.emit(
            "window_budget", budget=self.window_budget, decode_lanes=n_dec,
            chunk_tokens=sum(t for _, _, _, t, _ in sched),
            chunks=len(sched), queued=len(self._chunk_admissions),
        )
        for rid, rec, off, take, final in sched:
            flight.emit(
                "prefill_chunk_sched", rid, offset=off, tokens=take,
                remaining=len(rec["prompt"]) - off - take, final=int(final),
            )
        if not sched and n_dec == 0:
            # the pool can't stage even the oldest admission and nothing
            # decodes: make room by preempting the newest (the scheduler
            # resubmits; the admission_state gate re-screens impossible
            # prompts) instead of spinning an empty window
            if self._chunk_admissions:
                vrid, vrec = self._chunk_admissions.popitem()
                self._preempt_chunk_admission(vrid, vrec)
            self._journal_window(self.ledger.record_preempt_stall(
                time.perf_counter() - t_w,
                [r for r, _ in self._preempted], kind="prefill",
            ))
            return []
        flight.emit(
            "sync_window_open", steps=1, active=n_dec + len(sched),
        )
        fed = np.full((self.B, C), self.pad_id, np.int32)
        n_fed = np.zeros((self.B,), np.int32)
        chunk_base = np.zeros((self.B,), np.int32)
        final_v = np.zeros((self.B,), bool)
        for rid, rec, off, take, final in sched:
            row = rec["row"]
            fed[row, :take] = rec["prompt"][off : off + take]
            n_fed[row] = take
            chunk_base[row] = off
            final_v[row] = final
        # context tokens resident at dispatch: decode rows' frontiers plus
        # each chunk's attended prefix (its own slice included)
        ctx = sum(s.kv_ub for s in self.slots if s.active) + sum(
            off + take for _, _, off, take, _ in sched
        )
        t0 = time.perf_counter()
        (self._cache, self._kv_len, self._last_tok, toks, eoss,
         self._active) = self._get("mixed_step", C)(
            self.params, self._cache, self._device_tables(),
            self._kv_len, self._last_tok, self._active, self._rng_keys,
            self._put(jnp.asarray(fed)), self._put(jnp.asarray(n_fed)),
            self._put(jnp.asarray(chunk_base)),
            self._put(jnp.asarray(final_v)),
        )
        self.steps += 1
        tok_h = np.asarray(toks)  # [B] — ONE fetch for decode AND admissions
        t_fetch = time.perf_counter()
        self._m_itl.observe(t_fetch - t0)
        self._m_step_device.observe(t_fetch - t0)
        eos_h = np.asarray(eoss)
        for slot in self.slots:
            if slot.active:
                slot.kv_ub = min(slot.kv_ub + 1, Tmax - 1)
        done: List[Tuple[int, List[int]]] = []
        deactivate = []
        kept: Dict[int, int] = {}  # rid -> decode tokens kept (ledger)
        # ---- decode lanes: exactly a k=1 plain-window drain --------------
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            finished = False
            kept[slot.request_id] = 0
            if eos_h[i]:
                finished = True  # EOS token itself is not emitted
            else:
                slot.tokens.append(int(tok_h[i]))
                if self.spec_on:
                    slot.history.append(int(tok_h[i]))
                slot.remaining -= 1
                self.stats.decode_tokens += 1
                kept[slot.request_id] += 1
                if slot.remaining <= 0:
                    finished = True
            if finished:
                done.append((slot.request_id, slot.tokens))
                flight.emit(
                    "eos", slot.request_id,
                    reason="budget" if slot.remaining <= 0 else "eos",
                    n_tokens=len(slot.tokens),
                )
                slot.active = False
                deactivate.append(i)
        # ---- chunk rows: progress, and _admit_chunk_paged's tail on the
        # final chunk --------------------------------------------------
        chunk_led: Dict[int, int] = {}  # rid -> real prefill lanes (ledger)
        finished_rows: List[int] = []
        for rid, rec, off, take, final in sched:
            row = rec["row"]
            rec["progress"] = off + take
            chunk_led[rid] = take
            if not final:
                continue
            tok0 = int(tok_h[row])
            p = rec["prompt"]
            max_new_c = rec["max_new"]
            del self._chunk_admissions[rid]
            self.stats.generate_calls += 1
            self.stats.prefill_tokens += len(p)
            flight.emit(
                "admit", rid, slot=row, prompt_len=len(p),
                bucket=rec["bucket"], tok0=tok0,
                **_tenant_attr(self.ledger, rid),
            )
            ts = rec.get("t_submit", rec["t_admit"])
            if ts is not None:
                self._m_ttft.observe(time.monotonic() - ts)
            if tok0 in self.config.eos_token_ids or max_new_c <= 1:
                out = [] if tok0 in self.config.eos_token_ids else [tok0]
                self.stats.decode_tokens += len(out)
                done.append((rid, out))
                # the executable left an EOS'd final inactive; the budget
                # case it activated — mask either way, and retire via the
                # common tail (the slot still carries rid for the footprint)
                deactivate.append(row)
                finished_rows.append(row)
                continue
            self.slots[row] = _Slot(
                request_id=rid, tokens=[tok0], remaining=max_new_c - 1,
                active=True, kv_ub=len(p), admit_seq=rec["admit_seq"],
                prompt_len=len(p),
                history=(list(p) + [tok0]) if self.spec_on else [],
            )
            self.stats.decode_tokens += 1
        if deactivate:
            mask = np.ones(self.B, bool)
            mask[deactivate] = False
            self._active = self._active & self._put(jnp.asarray(mask))
            self._retire_rows(deactivate)  # blocks back + footprint record
        for row in finished_rows:
            self.slots[row] = _Slot()  # clear the prefilling reservation
        self._m_step_drain.observe(time.perf_counter() - t_fetch)
        self._journal_window(self.ledger.record_mixed(
            time.perf_counter() - t_w, batch=self.B, lanes=C,
            decode_kept=kept, chunk_rows=chunk_led,
            rework=self._take_rework(chunk_led), ctx_tokens=ctx,
        ))
        self._journal_emitted()
        flight.emit(
            "sync_window_close", steps=1, done=len(done),
            duration_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )
        return done

    def step(self) -> List[Tuple[int, List[int]]]:
        """``decode_sync_steps`` decode steps for every active slot in one
        device call + one host fetch. Returns completed requests as
        ``(request_id, tokens)`` and frees their slots.

        With ``spec_paged`` enabled, a window where drafting is expected
        to WIN runs as ONE multi-token verify step instead
        (``_step_verify`` — up to ``spec_K + 1`` tokens retired per row
        per fetch). The routing is throughput-gated, not draft-gated: a
        verify call retires ``1 + accepted`` tokens per row while a plain
        window retires ``sync_steps`` per row, so one persistently-
        quoting row in a large batch must not collapse the k-step
        amortization for every non-drafting batchmate
        (``_verify_worthwhile``). Windows that don't clear the bar (and
        all no-draft windows) keep the plain path untouched."""
        faults.maybe_fail("decode_step")
        if self.interleave_on and self.paged and self._chunk_admissions:
            # unified ragged window: pending chunked admissions ride along
            # with decode; speculation resumes once the queue drains (both
            # window shapes are draw-invariant, so streams never notice
            # the handoff)
            return self._step_mixed()
        if self.spec_on and self.paged:
            drafts = self._draft_for_slots()
            if any(drafts.values()) and self._verify_worthwhile(drafts):
                return self._step_verify(drafts)
        k = self.sync_steps
        t_w = time.perf_counter()  # ledger window: block growth included
        if self.paged:
            # map the blocks this window will write BEFORE dispatch (an
            # unmapped write vanishes into the null block and corrupts the
            # stream one step later); exhaustion preempts the newest rows
            self._ensure_decode_blocks()
            if not self.has_active():
                # everything was preempted: nothing to step — but the
                # scheduler WAS busy preempting; attribute the stall to
                # the preempted requests or conservation frays in storms
                self._journal_window(self.ledger.record_preempt_stall(
                    time.perf_counter() - t_w,
                    [rid for rid, _ in self._preempted],
                ))
                return []
        flight.emit(
            "sync_window_open", steps=k,
            active=sum(1 for s in self.slots if s.active),
        )
        # context tokens resident at dispatch (paged host mirror) — the
        # decode window's KV-read bytes in the roofline estimate
        ctx = sum(s.kv_ub for s in self.slots if s.active) if self.paged else 0
        t0 = time.perf_counter()
        if self.paged:
            (self._cache, self._kv_len, self._last_tok, toks, eoss,
             self._active) = self._get("step_paged", k)(
                self.params, self._cache, self._device_tables(),
                self._kv_len, self._last_tok, self._active, self._rng_keys,
            )
            Tmax = self.MB * self.block_size
            for slot in self.slots:
                if slot.active:
                    slot.kv_ub = min(slot.kv_ub + k, Tmax - 1)
        else:
            (self._cache, self._kv_len, self._last_tok, toks, eoss,
             self._active) = self._get("step", k)(
                self.params, self._cache, self._kv_start,
                self._kv_len, self._last_tok, self._active, self._rng_keys,
            )
        self.steps += k
        tok_h = np.asarray(toks)  # [k, B]
        # EXACT inter-token latency: one sync window (device step + the
        # token-plane fetch) amortized over its k steps — every active row
        # advanced k tokens in this wall-clock interval
        t_fetch = time.perf_counter()
        self._m_itl.observe((t_fetch - t0) / k)
        self._m_step_device.observe(t_fetch - t0)
        eos_h = np.asarray(eoss)
        done: List[Tuple[int, List[int]]] = []
        deactivate = []
        kept: Dict[int, int] = {}  # rid -> tokens this window kept (ledger)
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            finished = False
            kept[slot.request_id] = 0
            for j in range(k):
                if eos_h[j, i]:
                    finished = True  # EOS token itself is not emitted
                    break
                slot.tokens.append(int(tok_h[j, i]))
                if self.spec_on:
                    slot.history.append(int(tok_h[j, i]))
                slot.remaining -= 1
                self.stats.decode_tokens += 1
                kept[slot.request_id] += 1
                if slot.remaining <= 0:
                    finished = True  # later window tokens (if any) discarded
                    break
            if finished:
                done.append((slot.request_id, slot.tokens))
                flight.emit(
                    "eos", slot.request_id,
                    reason="budget" if slot.remaining <= 0 else "eos",
                    n_tokens=len(slot.tokens),
                )
                slot.active = False
                deactivate.append(i)
        if deactivate:
            # rows that hit their budget (not EOS) must stop decoding on
            # device too; EOS rows were already deactivated in-step
            mask = np.ones(self.B, bool)
            mask[deactivate] = False
            self._active = self._active & self._put(jnp.asarray(mask))
            self._retire_rows(deactivate)  # paged: blocks back to the pool
        self._m_step_drain.observe(time.perf_counter() - t_fetch)
        self._journal_window(self.ledger.record_decode(
            time.perf_counter() - t_w, batch=self.B, steps=k,
            kept=kept, ctx_tokens=ctx,
        ))
        self._journal_emitted()
        flight.emit(
            "sync_window_close", steps=k, done=len(done),
            duration_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )
        return done

    # ------------------------------------------------------------------
    # speculative decoding (spec_paged; docs/SPECULATIVE.md)
    # ------------------------------------------------------------------
    def _draft_for_slots(self) -> Dict[int, List[int]]:
        """This window's draft per active row: prompt-lookup over the
        row's own history (assembled prompt + emitted — the retrieved
        chunks ARE the corpus), length-capped by the row's decayed
        acceptance EMA (low-acceptance rows degrade to K=1;
        engine/speculative.py), its remaining token budget (tokens past
        it are discarded anyway) and the slot ladder's top (a draft whose
        accepted frontier would overrun ``Tmax`` can't be mapped). An
        empty list means the row takes a plain decode step — inside the
        verify window when batchmates drafted, on the ordinary sync-step
        path when nobody did."""
        Tmax = self.MB * self.block_size
        out: Dict[int, List[int]] = {}
        for row, slot in enumerate(self.slots):
            if not slot.active:
                continue
            k_row = adaptive_draft_len(
                slot.spec_ema, self.spec_K, self.spec_min_accept
            )
            k_row = min(k_row, slot.remaining - 1, Tmax - 2 - slot.kv_ub)
            if k_row < 1:
                out[row] = []
                continue
            out[row] = prompt_lookup_draft(
                slot.history, self.spec_ngram, k_row
            )
        return out

    def _verify_worthwhile(self, drafts: Dict[int, List[int]]) -> bool:
        """Should this window verify instead of running the plain path?
        A verify window is ONE device call retiring ``1 + accepted``
        tokens per row; a plain window retires ``sync_steps`` per row per
        call. Compare the EMA-expected verify yield against the plain
        window's certain ``k × active`` — under ``sync_steps == 1`` any
        draft wins (the verify can only add tokens), but at ``k > 1`` a
        lone quoting row must not cost every batchmate ``k - 1`` tokens
        per fetch. Fresh rows (no EMA) count optimistically — the first
        verify measures them."""
        k = self.sync_steps
        if k <= 1:
            return True
        n_active = 0
        expected = 0.0
        for row, slot in enumerate(self.slots):
            if not slot.active:
                continue
            n_active += 1
            d = drafts.get(row)
            if d:
                ema = 1.0 if slot.spec_ema is None else slot.spec_ema
                expected += 1.0 + ema * len(d)
            else:
                expected += 1.0
        return expected >= n_active * k

    def _step_verify(
        self, drafts: Dict[int, List[int]]
    ) -> List[Tuple[int, List[int]]]:
        """One speculative sync window: grow tables for each row's OWN
        horizon (``n_drafts + 1`` writes — exhaustion preempts newest
        rows exactly like a plain window; a preempted row's drafts die
        with its slot), run the verify executable, then drain up to
        ``n_emit`` tokens per row from the fetched planes. The drain is
        the plain window's loop with the window bound per-row instead of
        ``k`` — EOS/budget retirement, block release and preemption
        resume are shared, so every recovery path sees one shape of
        state."""
        K = self.spec_K
        t_w = time.perf_counter()  # ledger window: block growth included
        self._ensure_decode_blocks(
            {row: len(d) + 1 for row, d in drafts.items()}
        )
        if not self.has_active():
            # everything was preempted: same stall attribution as the
            # plain window's early return
            self._journal_window(self.ledger.record_preempt_stall(
                time.perf_counter() - t_w,
                [rid for rid, _ in self._preempted],
            ))
            return []
        d_arr = np.zeros((self.B, K), np.int32)
        nd = np.zeros((self.B,), np.int32)
        for row, d in drafts.items():
            if d and self.slots[row].active:
                d_arr[row, : len(d)] = d
                nd[row] = len(d)
        n_active = sum(1 for s in self.slots if s.active)
        flight.emit(
            "spec_draft", rows=int((nd > 0).sum()), active=n_active,
            drafted=int(nd.sum()),
        )
        flight.emit("sync_window_open", steps=1, active=n_active, spec=1)
        t0 = time.perf_counter()
        (self._cache, self._kv_len, self._last_tok, toks, n_emit, eoss,
         acc, self._active) = self._get("verify_paged", K)(
            self.params, self._cache, self._device_tables(),
            self._kv_len, self._last_tok, self._active, self._rng_keys,
            self._put(jnp.asarray(d_arr)), self._put(jnp.asarray(nd)),
        )
        self.steps += 1
        tok_h = np.asarray(toks)  # [K+1, B] emitted planes
        ne_h = np.asarray(n_emit)  # [B] valid planes per row (m + 1)
        t_fetch = time.perf_counter()
        eos_h = np.asarray(eoss)
        acc_h = np.asarray(acc)  # [B] accepted prefix lengths
        emitted_total = int(ne_h.sum())
        # per-ROW per-token latency, like the plain window's window/k:
        # the mean row advanced emitted_total / n_active tokens in this
        # wall-clock interval
        self._m_itl.observe(
            (t_fetch - t0) * n_active / max(emitted_total, 1)
        )
        self._m_step_device.observe(t_fetch - t0)
        Tmax = self.MB * self.block_size
        done: List[Tuple[int, List[int]]] = []
        deactivate = []
        drafted_total = int(nd.sum())
        accepted_total = 0
        # ledger + per-request spec stats: rid -> (kept, offered, accepted)
        led_rows: Dict[int, Tuple[int, int, int]] = {}
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            offered, m = int(nd[i]), int(acc_h[i])
            accepted_total += m
            if offered:
                self._spec_rids.add(slot.request_id)
            slot.spec_ema = fold_acceptance(slot.spec_ema, offered, m)
            # the exact new frontier (not an upper bound): the device
            # advanced kv_len by exactly n_emit valid positions
            slot.kv_ub = min(slot.kv_ub + int(ne_h[i]), Tmax - 1)
            finished = False
            n_kept = 0
            for j in range(int(ne_h[i])):
                if eos_h[j, i]:
                    finished = True  # EOS token itself is not emitted
                    break
                slot.tokens.append(int(tok_h[j, i]))
                slot.history.append(int(tok_h[j, i]))
                slot.remaining -= 1
                self.stats.decode_tokens += 1
                n_kept += 1
                if slot.remaining <= 0:
                    finished = True  # tokens past the budget discarded
                    break
            led_rows[slot.request_id] = (n_kept, offered, m)
            if finished:
                done.append((slot.request_id, slot.tokens))
                flight.emit(
                    "eos", slot.request_id,
                    reason="budget" if slot.remaining <= 0 else "eos",
                    n_tokens=len(slot.tokens),
                )
                slot.active = False
                deactivate.append(i)
        self.stats.spec_verify_steps += 1
        self.stats.spec_drafted_rows += int((nd > 0).sum())
        self.stats.spec_drafted_tokens += drafted_total
        self.stats.spec_accepted_tokens += accepted_total
        self.stats.spec_emitted_tokens += emitted_total
        flight.emit(
            "spec_verify", drafted=drafted_total, accepted=accepted_total,
            rejected=drafted_total - accepted_total, emitted=emitted_total,
        )
        if deactivate:
            mask = np.ones(self.B, bool)
            mask[deactivate] = False
            self._active = self._active & self._put(jnp.asarray(mask))
            self._retire_rows(deactivate)  # paged: blocks back to the pool
        self._m_step_drain.observe(time.perf_counter() - t_fetch)
        self._journal_window(self.ledger.record_verify(
            time.perf_counter() - t_w, batch=self.B, lanes_per_row=K + 1,
            rows=led_rows,
            ctx_tokens=sum(s.kv_ub for s in self.slots if s.active),
        ))
        self._journal_emitted()
        flight.emit(
            "sync_window_close", steps=1, done=len(done),
            duration_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )
        return done


class ContinuousScheduler:
    """Thread-safe facade: ``submit()`` blocks the caller; a dispatcher
    thread owns the engine, admitting between decode steps.

    Resilience behavior (ISSUE 4):

    - **deadline eviction**: a submit carrying a :class:`Deadline` that
      expires mid-decode has its slot EVICTED within one scheduler
      iteration (``engine.evict_requests``) — the abandoned request stops
      burning a decode slot the moment its client has given up;
    - **reset recovery**: an :class:`EngineStateLost` (the reset wiped every
      slot) RESUBMITS the in-flight prompts once, after a jittered backoff,
      with each request's token budget reduced by what it already emitted
      (the emitted tokens are appended to the resubmitted prompt, so the
      client still receives one seamless continuation). A single transient
      device fault is therefore invisible to callers; a second fault on the
      same request fails it (``rag_inflight_retries_total{outcome}``);
    - **breaker feed**: every reset is reported to the attached
      :class:`~rag_llm_k8s_tpu.resilience.breaker.CircuitBreaker` (set by
      the service) — a reset storm flips readiness, Kubernetes drains the
      pod, and admission sheds with 503 in the meantime.
    """

    def __init__(
        self,
        engine: ContinuousEngine,
        retries: int = 1,
        retry_backoff_s: float = 0.05,
    ):
        self.engine = engine
        self.retries = max(0, retries)
        self.retry_backoff_s = max(0.0, retry_backoff_s)
        # set by the service: engine resets feed the readiness breaker
        self.breaker = None
        # measured busy wall-clock: time the dispatcher spent INSIDE
        # engine.step()/admit_many() — the goodput conservation anchor
        # (per-request attributed chip-seconds must sum to this within
        # tolerance; tests/test_goodput.py pins 5%). Written only by the
        # dispatcher thread; reads are gauge-grade.
        self._busy_s = 0.0
        self._queue: "queue.Queue[Optional[_Pending]]" = queue.Queue()
        self._stop = threading.Event()
        # serializes the stop-check+enqueue in submit() against shutdown()'s
        # final drain — without it an item can land in the queue after the
        # drain and block its caller forever
        self._lifecycle_lock = threading.Lock()
        self.bind_metrics(obs_metrics.default_registry())
        self._worker = threading.Thread(
            target=self._run, daemon=True, name="continuous-scheduler"
        )
        self._worker.start()

    def bind_metrics(self, registry) -> None:
        """Resilience accounting (service rebinds, like the engines)."""
        self._m_resets = registry.counter(
            "rag_engine_resets_total",
            "engine state resets (EngineStateLost / failed decode steps)",
        )
        self._m_retries = registry.labeled_counter(
            "rag_inflight_retries_total",
            "in-flight requests resubmitted after an engine reset "
            "(outcome: resubmitted | succeeded | gave_up)",
        )
        for o in ("resubmitted", "succeeded", "gave_up"):
            self._m_retries.labels(outcome=o)
        dl_fam = registry.labeled_counter(
            "rag_deadline_exceeded_total",
            "requests failed by their end-to-end deadline (stage label)",
        )
        self._m_deadline_queue = dl_fam.labels(stage="queue")
        self._m_deadline_decode = dl_fam.labels(stage="decode")
        self._m_join_timeout = registry.counter(
            "rag_scheduler_join_timeouts_total",
            "scheduler shutdowns whose worker thread outlived join(timeout)",
        )

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        seed: Optional[int] = None,  # honored per-row: draws are seed+position keyed
        timeout: Optional[float] = None,
        deadline: Optional[Deadline] = None,
        info: Optional[Dict] = None,  # out-param: per-request engine facts
        tenant: Optional[str] = None,  # edge-interned tenant (bounded set)
        resume_emitted: Optional[Sequence[int]] = None,  # warm restart: prior tokens
    ) -> List[int]:
        if self._stop.is_set():
            raise RuntimeError("scheduler is shut down")
        max_new = (
            self.engine.sampling.max_new_tokens
            if max_new_tokens is None else max_new_tokens
        )
        if max_new <= 0:
            return []
        rid = next(_REQUEST_IDS)  # process-global: flight-journal identity
        if info is not None:
            # out-param: the flight journal keys this request's lifecycle
            # timeline on the id (GET /debug/timeline/<id>)
            info["request_id"] = rid
        item = _Pending(
            request_id=rid, prompt=list(prompt), max_new=max_new, seed=seed,
            deadline=deadline, retries_left=self.retries, tenant=tenant,
        )
        # the replay trace record (sim/replay.py): everything a re-drive
        # needs to reproduce this request — the prompt token ids ride
        # along only while the arrival_ids knob is on (they dominate the
        # ring's memory at long prompts)
        arr = {"prompt_len": len(item.prompt), "max_new": max_new}
        if seed is not None:
            arr["seed"] = seed
        if deadline is not None:
            arr["deadline_ms"] = deadline.budget_ms
        if tenant is not None:
            # rides the trace record too: a re-driven journal re-prices
            # per tenant (sim/replay.py forwards it into its submits)
            arr["tenant"] = tenant
            self.engine.ledger.note_tenant(rid, tenant)
        if flight.arrival_ids():
            arr["ids"] = list(item.prompt)
        flight.emit("arrival", rid, **arr)
        if resume_emitted:
            # warm restart (server/main.py): tokens a dead incarnation's
            # WAL proved emitted fold in through the SAME path a preempt
            # resume uses — the prompt grows, the budget shrinks, and the
            # delivered stream stays byte-identical to an uninterrupted
            # run. The arrival above recorded the ORIGINAL prompt; the
            # token_emit re-journals the folded tokens into THIS
            # incarnation's WAL so a second crash still reconstructs the
            # full stream from one epoch.
            self._fold_emitted(item, list(resume_emitted))
            if item.emitted:
                item.resumed = True
                flight.emit("token_emit", rid, toks=list(item.emitted))
            flight.emit(
                "resubmit", rid, outcome="restored",
                n_emitted=len(item.emitted),
            )
        with self._lifecycle_lock:  # stop-check + enqueue must be atomic
            if self._stop.is_set():
                raise RuntimeError("scheduler is shut down")
            self._queue.put(item)
        wait_t = timeout
        if wait_t is None and deadline is not None:
            # small grace past the deadline: the worker evicts the row and
            # delivers a stage-precise error within one iteration — prefer
            # that over racing it with a caller-side raise
            wait_t = deadline.wait_timeout() + 0.25
        if not item.done.wait(wait_t):
            if deadline is not None and deadline.expired():
                # the worker's eviction sweep frees the slot; the caller
                # need not (and must not) block on it. Mark the item so the
                # sweep skips ITS deadline-counter increment — this expiry
                # is counted once, at the caller's stage="generate"
                item.abandoned = True
                raise DeadlineExceeded("generate", deadline.budget_ms)
            raise TimeoutError("generation timed out")
        if item.error is not None:
            raise item.error
        if info is not None and item.blocks_allocated is not None:
            # paged mode: the row's peak block footprint (per-row
            # blocks_allocated in the /generate timings block)
            info["kv_blocks_allocated"] = item.blocks_allocated
        if info is not None and item.goodput is not None:
            # goodput ledger: this request's attributed chip-time figures
            # (chip_ms / goodput_frac / cost_usd / speculation stats) —
            # the service folds them into the /generate timings block
            info["goodput"] = item.goodput
        if info is not None and item.spec_seen:
            # approximation fingerprint (obs/shadow.py): verify windows
            # judged drafts for this request — stamped from ENGINE state
            # (pop_spec_seen), never the goodput ledger, so
            # TPU_RAG_GOODPUT=0 cannot erase speculation attribution
            # from shadow audits
            ap = info.setdefault("approx", [])
            if "spec_verify" not in ap:
                ap.append("spec_verify")
        return item.result

    def busy_seconds(self) -> float:
        """Wall-clock the dispatcher spent inside engine device work
        (step + admissions) — the independent measurement the goodput
        conservation invariant is checked against."""
        return self._busy_s

    def run_on_engine(self, fn) -> bool:
        """Enqueue a host-side engine task — ``fn(engine)`` — executed by
        the dispatcher thread between admissions and steps. The engine is
        single-owner (its step executables DONATE the device state), so
        this is the only safe way for another thread (the lookahead
        executor's KV pre-staging, rag/lookahead.py) to touch it. Fire and
        forget; a task failure is contained exactly like a step failure
        (EngineStateLost recovery resubmits the in-flight requests).
        Returns False when the scheduler is shutting down."""
        if not callable(fn):
            raise TypeError("run_on_engine expects a callable(engine)")
        with self._lifecycle_lock:
            if self._stop.is_set():
                return False
            self._queue.put(fn)
        return True

    def shutdown(self):
        from rag_llm_k8s_tpu.engine.batching import _join_worker

        self._stop.set()
        with self._lifecycle_lock:
            self._queue.put(None)
        _join_worker(self._worker, self._m_join_timeout, "continuous-scheduler")
        # the worker's own drain ran before join returned; under the lock no
        # new item can have been enqueued since — sweep anything that raced
        # in between the worker's drain and _stop becoming visible
        with self._lifecycle_lock:
            while True:
                try:
                    it = self._queue.get_nowait()
                except queue.Empty:
                    break
                if it is not None and not callable(it):
                    it.error = RuntimeError("scheduler is shut down")
                    it.done.set()

    # ------------------------------------------------------------------
    def _run(self):
        waiting: Dict[int, _Pending] = {}
        item: Optional[_Pending] = None
        try:
            item = self._run_loop(waiting)
        finally:
            # the worker is exiting for WHATEVER reason (shutdown() or an
            # unguarded exception): close the door FIRST so post-mortem
            # submits fail fast instead of enqueueing into a drained queue
            # and blocking their caller forever
            self._stop.set()
            # fail everything still in flight or queued so no caller blocks
            # forever on a scheduler that has stopped (answer() submits with
            # timeout=None)
            err = RuntimeError("scheduler is shut down")
            leftovers = list(waiting.values())
            waiting.clear()
            if item is not None:
                leftovers.append(item)
            with self._lifecycle_lock:  # no submit can race this drain
                while True:
                    try:
                        queued = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if queued is not None and not callable(queued):
                        leftovers.append(queued)
            for it in leftovers:
                self.engine.discard_request_goodput(it.request_id)
                it.error = err
                it.done.set()

    def _run_loop(self, waiting: Dict[int, "_Pending"]) -> Optional["_Pending"]:
        """Returns the un-acked in-hand item (if any) when stopping."""
        eng = self.engine
        while not self._stop.is_set():
            # deadline sweep once per iteration: an expired in-flight request
            # frees its decode slot within ONE scheduler step
            self._evict_expired(waiting)
            if eng.has_active():
                # decode never waits on arrivals: peek, admit, step
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    item = None
            else:
                item = self._queue.get()  # idle: block until work arrives
            while item is not None:  # admit everything that fits right now
                if self._stop.is_set():
                    return item if not callable(item) else None
                if callable(item):
                    # engine task (lookahead pre-staging): host+one small
                    # device call, run in arrival order between admissions
                    self._run_engine_task(item, waiting)
                    item = self._next_nowait()
                    continue
                if self._expire_queued(item):
                    # expired while queued: fail fast, never admit — under
                    # overload this is what keeps dead work off the device
                    item = self._next_nowait()
                    continue
                # paged backpressure: a pool that can't take this prompt NOW
                # keeps it QUEUED (decode frees blocks every window; the
                # growing queue is what trips the PR-4 admission gate's 429s
                # upstream) — only a prompt the whole pool couldn't hold
                # fails outright
                state = eng.admission_state(len(item.prompt))
                if state == "never":
                    item.error = PoolExhausted(
                        eng.blocks_needed(len(item.prompt)),
                        eng.kv_pool.usable_blocks() if eng.kv_pool else 0,
                    )
                    item.done.set()
                    item = self._next_nowait()
                    continue
                if state == "wait":
                    self._safe_step(waiting)
                    self._evict_expired(waiting)
                    continue
                free = eng.free_slots()
                if not free:
                    # no room: decode until a slot frees, then admit
                    self._safe_step(waiting)
                    self._evict_expired(waiting)
                    continue
                # GROUP admission: drain whatever else is already queued up
                # to the free-slot count — the engine batches same-bucket
                # prefills and fetches all first tokens in one round-trip
                batch = [item]
                while len(batch) < len(free):
                    try:
                        nxt = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None:
                        break
                    if callable(nxt):
                        self._run_engine_task(nxt, waiting)
                        continue
                    if self._expire_queued(nxt):
                        continue  # dead on arrival: no prefill for it
                    batch.append(nxt)
                try:
                    t_busy = time.perf_counter()
                    try:
                        admitted = eng.admit_many(
                            [(b.request_id, b.prompt, b.max_new, b.seed) for b in batch]
                        )
                    finally:
                        self._busy_s += time.perf_counter() - t_busy
                    for b, res in zip(batch, admitted):
                        if isinstance(res, PoolExhausted):
                            # the chunk raced the pool (another chunk of
                            # this very group took the blocks): requeue —
                            # this is backpressure, not a failure
                            self._queue.put(b)
                            continue
                        if isinstance(res, BaseException):
                            # per-chunk failure: only ITS items fail; other
                            # chunks' admissions stand and keep decoding
                            b.error = res
                            b.done.set()
                            continue
                        _, finished = res
                        # the first token exists the moment admission
                        # returns (sampled at prefill): submit → here IS
                        # the request's exact TTFT, queue wait included.
                        # A resubmitted request already observed its real
                        # TTFT on the first attempt — a second sample would
                        # double-count it and fold the reset backoff into
                        # the histogram the SLO layer alerts on (same for a
                        # pool-preemption resume)
                        chunk_rec = eng._chunk_admissions.get(b.request_id)
                        if chunk_rec is not None:
                            # interleaved admission: no first token yet —
                            # hand the engine the real submit stamp so the
                            # mixed window that samples tok0 observes the
                            # exact TTFT (None keeps the retry/resume
                            # no-double-count rule above)
                            chunk_rec["t_submit"] = (
                                b.t_submit
                                if not b.retried and not b.resumed else None
                            )
                        elif not b.retried and not b.resumed:
                            eng._m_ttft.observe(time.monotonic() - b.t_submit)
                        if finished is not None:
                            self._deliver(b, finished)
                        else:
                            waiting[b.request_id] = b
                except EngineStateLost as e:
                    # the reset (inside the engine) wiped every slot: recover
                    # by resubmitting this batch AND the in-flight requests —
                    # their emitted tokens were lost with the slots, so they
                    # restart from their original prompts
                    self._handle_reset(e, waiting, extra=batch, emitted={})
                except BaseException as e:  # noqa: BLE001 — deliver to waiters
                    for b in batch:
                        b.error = e
                        b.done.set()
                item = self._next_nowait()
            if eng.has_active():
                self._safe_step(waiting)
        return None

    def _next_nowait(self) -> Optional["_Pending"]:
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _evict_expired(self, waiting: Dict[int, "_Pending"]):
        """Evict in-flight requests whose deadline has passed: free their
        device slots and deliver the stage-precise error."""
        expired = [
            rid for rid, it in waiting.items()
            if it.deadline is not None and it.deadline.expired()
        ]
        if not expired:
            return
        self.engine.evict_requests(expired)
        for rid in expired:
            it = waiting.pop(rid)
            if not it.abandoned:  # the caller already counted its expiry
                self._m_deadline_decode.inc()
            self.engine.discard_request_goodput(rid)  # never delivered
            it.error = DeadlineExceeded("decode", it.deadline.budget_ms)
            it.done.set()

    def _expire_queued(self, item: "_Pending") -> bool:
        """Fail an expired item straight out of the queue (stage=queue) —
        dead work must never reach the device. True when it was expired."""
        if item.deadline is None or not item.deadline.expired():
            return False
        if not item.abandoned:
            self._m_deadline_queue.inc()
        item.error = DeadlineExceeded("queue", item.deadline.budget_ms)
        item.done.set()
        return True

    def _deliver(self, item: "_Pending", tokens: List[int]):
        """Complete one request: tokens emitted before a recovered reset
        (if any) prepend the continuation — the client sees one stream."""
        if item.retried:
            self._m_retries.labels(outcome="succeeded").inc()
        item.blocks_allocated = self.engine.pop_blocks_allocated(item.request_id)
        item.result = item.emitted + tokens
        item.goodput = self.engine.pop_request_goodput(
            item.request_id, tokens=len(item.result)
        )
        pop_spec = getattr(self.engine, "pop_spec_seen", None)
        item.spec_seen = bool(pop_spec(item.request_id)) if pop_spec else False
        # stream_fnv anchors the timeline to the BYTES the client received:
        # a reconstructed lifecycle (admit → reset → resubmit → complete)
        # is provably consistent with the delivered stream. The goodput
        # attribution rides along so an offline journal can compute
        # cost-per-query percentiles with no live pod; the tenant stamp is
        # what lets obs/tenants.py price the journal per tenant.
        extra = {}
        if item.goodput is not None:
            extra["chip_ms"] = item.goodput["chip_ms"]
            if "cost_usd" in item.goodput:
                extra["cost_usd"] = round(item.goodput["cost_usd"], 8)
        if item.tenant is not None:
            extra["tenant"] = item.tenant
        flight.emit(
            "complete", item.request_id, n_tokens=len(item.result),
            stream_fnv=flight.stream_hash(item.result), **extra,
        )
        item.done.set()

    def _fold_emitted(self, it: "_Pending", toks: List[int]) -> None:
        """Fold already-emitted tokens into a request about to resubmit:
        resume only when prompt+emitted still fits a slot — past the
        largest bucket admit_many would silently left-truncate the context
        and the "seamless continuation" would be conditioned on a different
        prompt; restarting from scratch is exact. Shared by reset recovery
        and pool-preemption resume."""
        if sim_policy.resume_fits(len(it.prompt), len(toks),
                                  max(self.engine.buckets)):
            it.emitted.extend(toks)
            it.prompt = list(it.prompt) + toks
            it.max_new = max(1, it.max_new - len(toks))

    def _resume_preempted(self, waiting: Dict[int, "_Pending"]):
        """Requeue requests the paged engine preempted on pool exhaustion:
        prompt + emitted resubmits (greedy streams provably identical), the
        budget shrinks by what was already produced. Unlike reset recovery
        this burns no retry — preemption is scheduled backpressure, not a
        fault — and the TTFT histogram is not re-fed."""
        for rid, toks in self.engine.drain_preempted():
            it = waiting.pop(rid, None)
            if it is None:
                continue
            self._fold_emitted(it, toks)
            it.resumed = True
            # the resumed admission re-feeds prompt+emitted — tokens the
            # chip already computed once: attribute that admission's lanes
            # to preempt_rework (the ledger's goodput cost of preemption)
            self.engine.mark_rework(rid)
            flight.emit(
                "resubmit", rid, outcome="preempt_resume",
                n_emitted=len(toks),
            )
            self._queue.put(it)

    def _handle_reset(self, cause, waiting, extra, emitted):
        """After an engine reset: resubmit what can still be served, fail
        the rest. ``emitted`` maps request_id → tokens produced before the
        reset (captured from the host slots when the failure site allows);
        resubmitted prompts carry them so decode resumes where it stopped
        and the budget shrinks by what was already produced."""
        self._m_resets.inc()
        if self.breaker is not None:
            self.breaker.record_reset()
        items = list(waiting.values()) + list(extra)
        waiting.clear()
        retry = []
        for it in items:
            expired = it.deadline is not None and it.deadline.expired()
            if it.retries_left > 0 and not expired and not self._stop.is_set():
                retry.append(it)
            else:
                self._m_retries.labels(outcome="gave_up").inc()
                flight.emit("resubmit", it.request_id, outcome="gave_up")
                self.engine.discard_request_goodput(it.request_id)
                it.error = cause
                it.done.set()
        if not retry:
            return
        logger.warning(
            "engine reset (%s); resubmitting %d in-flight request(s)",
            cause, len(retry),
        )
        if self.retry_backoff_s > 0:
            # jittered: a device that just faulted gets a beat before the
            # retries' prefills land on it again
            time.sleep(random.uniform(0.5, 1.0) * self.retry_backoff_s)
        for it in retry:
            toks = emitted.get(it.request_id, [])
            self._fold_emitted(it, toks)
            it.retries_left -= 1
            it.retried = True
            # reset recovery re-prefills the whole prompt (+ emitted):
            # rework lanes, not fresh prefill, in the goodput ledger
            self.engine.mark_rework(it.request_id)
            self._m_retries.labels(outcome="resubmitted").inc()
            flight.emit(
                "resubmit", it.request_id, outcome="resubmitted",
                n_emitted=len(toks),
            )
            self._queue.put(it)

    def _run_engine_task(self, task, waiting: Dict[int, "_Pending"]):
        """Execute one enqueued engine task with step-grade containment: a
        task that invalidates the donated device state (EngineStateLost
        from a failed prestage scatter) recovers exactly like a failed
        step — reset already happened inside the engine, the in-flight
        requests resubmit from their prompts."""
        try:
            task(self.engine)
        except EngineStateLost as e:
            # the engine reset itself before raising: slots (and any
            # emitted tokens) are gone — resubmit from the prompts
            logger.exception(
                "engine task reset the engine; recovering %d in-flight "
                "request(s)", len(waiting),
            )
            self._handle_reset(e, waiting, extra=[], emitted={})
        except BaseException:  # noqa: BLE001 — tasks must never kill the loop
            logger.exception("engine task failed (engine state intact)")

    def _safe_step(self, waiting: Dict[int, "_Pending"]):
        """One decode step that can never kill the dispatcher: a device
        error resets the slots and RESUBMITS the in-flight requests (once
        each) so a transient fault stays invisible to callers; requests out
        of retries (or past deadline) get the error instead of a hang."""
        try:
            t_busy = time.perf_counter()
            try:
                done = self.engine.step()
            finally:
                self._busy_s += time.perf_counter() - t_busy
            self._drain_done(done, waiting)
            self._resume_preempted(waiting)
        except BaseException as e:  # noqa: BLE001 — recover, don't die
            logger.exception(
                "decode step failed; recovering %d in-flight request(s)",
                len(waiting),
            )
            # capture what each in-flight request already produced BEFORE
            # reset() wipes the host slots — the resubmission resumes from
            # the original prompt + these tokens
            emitted = {
                s.request_id: list(s.tokens)
                for s in self.engine.slots if s.active
            }
            try:
                self.engine.reset()
            except BaseException:  # noqa: BLE001 — a failed reset must not kill the loop
                logger.exception("engine reset failed after step failure")
            self._handle_reset(e, waiting, extra=[], emitted=emitted)

    def _drain_done(self, done, waiting):
        for rid, tokens in done:
            item = waiting.pop(rid, None)
            if item is not None:
                self._deliver(item, tokens)


@dataclass
class _Pending:
    request_id: int
    prompt: List[int]
    max_new: int
    seed: Optional[int] = None
    done: threading.Event = field(default_factory=threading.Event)
    result: Optional[List[int]] = None
    error: Optional[BaseException] = None
    t_submit: float = field(default_factory=time.monotonic)  # TTFT anchor
    deadline: Optional[Deadline] = None
    retries_left: int = 0  # reset-recovery resubmissions remaining
    retried: bool = False  # ever resubmitted (success/failure accounting)
    emitted: List[int] = field(default_factory=list)  # pre-reset tokens
    abandoned: bool = False  # caller gave up (it counted the expiry)
    resumed: bool = False  # requeued after a paged pool preemption
    blocks_allocated: Optional[int] = None  # paged: peak block footprint
    goodput: Optional[Dict] = None  # ledger attribution (chip_ms/cost/spec)
    spec_seen: bool = False  # verify windows judged drafts for this request
    tenant: Optional[str] = None  # edge-interned tenant (complete stamp)

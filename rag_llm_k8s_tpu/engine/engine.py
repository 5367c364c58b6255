"""The inference engine: bucketed prefill + while-loop decode, compiled once
per (batch, bucket) shape.

Replaces the reference's per-request ``model.generate`` on CPU torch
(/root/reference/llm/rag.py:172). Design, TPU-first:

- **Static shapes, bucketed prompts**: a prompt pads LEFT to the next bucket
  (``EngineConfig.prompt_buckets``); XLA compiles one executable per
  (batch_bucket, prompt_bucket, max_new) triple and reuses it for every
  request — no per-request recompiles, no dynamic shapes.
- **Left padding** keeps every sequence's write frontier at the same cache
  index, so cache appends stay ``dynamic_update_slice`` (survey §7 hard part
  (b): KV layout under pjit without per-request recompiles).
- **The whole generate call is ONE compiled function**: prefill (last-token
  logits only), the ``lax.while_loop`` over decode steps, sampling, and EOS
  tracking all live on device; the host sees only final token ids. With
  params placed via NamedSharding, XLA propagates TP shardings through the
  loop and inserts ICI collectives.
- **AOT compilation**: executables are traced, lowered and compiled from
  abstract shapes (``obs/tracing.py build_span``), so ``warmup()`` pays
  compile time only — no throwaway generations (readiness gating).
- **Early exit**: the while_loop stops when every row has emitted EOS —
  short answers don't pay for ``max_new_tokens`` steps (the reference always
  runs the full HF sequential loop per request).
"""

from __future__ import annotations

import logging
import threading
import time
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
from rag_llm_k8s_tpu.engine.sampling import NEG_INF, _prepared_logits, sample_token
from rag_llm_k8s_tpu.models import families
from rag_llm_k8s_tpu.models.llama import (
    KVCache,
    make_kv_cache,
    mask_window,
)
from rag_llm_k8s_tpu.obs import flight
from rag_llm_k8s_tpu.obs import goodput as obs_goodput
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.obs.tracing import phase_scope
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.utils.buckets import bucket_len, next_pow2

logger = logging.getLogger(__name__)


@jax.jit
@phase_scope("prefill")
def _splice_prefix_planes(dst, block, offset):
    """Write a segment KV block into a prefix buffer at slot ``offset``.

    Both are plane tuples — payloads ``[L, 1, K, T, hd]`` and (int8-KV)
    scale planes ``[L, 1, K, T]``; the slot axis is 3 in both layouts.
    jit-cached per (buffer, block-bucket) shape pair, so splicing stays a
    bounded set of tiny executables regardless of how many distinct prefixes
    ever assemble.
    """
    out = []
    for c, b in zip(dst, block):
        starts = (0, 0, 0, offset) + ((0,) if c.ndim == 5 else ())
        out.append(jax.lax.dynamic_update_slice(c, b.astype(c.dtype), starts))
    return tuple(out)


def _isin(tokens: jax.Array, ids: Tuple[int, ...]) -> jax.Array:
    hit = jnp.zeros(tokens.shape, dtype=bool)
    for i in ids:
        hit = hit | (tokens == i)
    return hit


def param_avals(params):
    """Abstract (shape, dtype, sharding) tree for AOT builds —
    shared by the one-shot and continuous engines."""
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=leaf.sharding)
        if isinstance(leaf, jax.Array)
        else jax.ShapeDtypeStruct(np.shape(leaf), np.asarray(leaf).dtype),
        params,
    )


def build_identity(engine: str, family, config, engine_config: EngineConfig, dtypes: DTypePolicy,
                   sampling: SamplingConfig, mesh: Optional[MeshContext], pad_id: int, **flags):
    """What an engine's traced programs close over, for ``tracing.build_span``
    to key a build on without tracing it (``core/compile_cache.py``): every
    array they use is an argument, so these and the abstract arguments are
    the whole of an executable's inputs. Shared by both engines."""
    return (
        engine, family.name, config, engine_config, dtypes, sampling, int(pad_id),
        # what ``attn_impl="auto"`` resolves by, where the models are traced
        jax.default_backend(), sorted(flags.items()),
        None if mesh is None else dict(mesh.mesh.shape),
    )


def maybe_fuse_params(params, engine_config: EngineConfig, mesh):
    """Fuse q/k/v and gate/up projection weights once at engine construction
    when the config allows it and tp == 1 (the fused concat layout cannot be
    tp-sharded — see ``models.llama.fuse_llama_params``). Returns
    ``(params, fused?)``; already-fused or sharded trees pass through."""
    from rag_llm_k8s_tpu.models.llama import fuse_llama_params

    tp = mesh.tp if mesh is not None else 1
    attn = params.get("layers", {}).get("attn", {}) if isinstance(params, dict) else {}
    if "wqkv" in attn and tp > 1:
        raise ValueError(
            "params are in the fused wqkv layout, which cannot be tp-sharded "
            "— pass the canonical (unfused) tree when tp > 1"
        )
    # int8 trees from the streaming loader carry kernel_q, not kernel — they
    # skip fusion (concat of already-quantized kernels is possible but the
    # loader path targets 8B, where tp>1 or memory-tightness rules fusion out)
    if (
        not engine_config.fuse_matmuls
        or tp > 1
        or "wq" not in attn
        or "kernel" not in attn["wq"]
    ):
        return params, "wqkv" in attn
    return fuse_llama_params(params), True


def maybe_quantize_params(params, engine_config: EngineConfig):
    """Apply weight-only int8 quantization at engine construction when
    ``EngineConfig.weight_quant == "int8"``. Already-quantized trees (any
    ``kernel_q`` leaf — e.g. streamed in int8 by the loader) pass through.
    Returns ``(params, quantized?)``. The caller-passed bf16 tree is NOT
    donated — callers legitimately share one tree across engines — so both
    trees coexist transiently; at 8B scale quantize during the streaming
    load instead (``load_safetensors_params(..., quant="int8")``) and this
    becomes the pass-through case."""
    from rag_llm_k8s_tpu.models.llama import quantize_llama_params

    attn = params.get("layers", {}).get("attn", {}) if isinstance(params, dict) else {}
    already = any("kernel_q" in sub for sub in attn.values() if isinstance(sub, dict))
    if engine_config.weight_quant not in ("bf16", "int8"):
        raise ValueError(
            f"weight_quant={engine_config.weight_quant!r}: expected 'bf16' or 'int8'"
        )
    if engine_config.weight_quant != "int8" or already:
        return params, already
    return quantize_llama_params(params), True


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    generate_calls: int = 0
    # speculative decoding: verify forwards run (each emits >= 1 token) and
    # tokens emitted by them; emitted / verify_steps = measured acceptance
    # (tokens per verify forward, >= 1.0 — the counter VERDICT r4 asked the
    # end-to-end run to report; the benchmark's spec_tokens_per_verify)
    spec_verify_steps: int = 0
    spec_emitted_tokens: int = 0
    # paged continuous draft-and-verify (engine/speculative.py): draft
    # tokens OFFERED to verify steps and the subset ACCEPTED (emitted as
    # drafted); rejected = drafted - accepted. The one-shot path cannot
    # split these (its matcher runs on device, acceptance is folded into
    # emitted/verify_steps), so they move only under spec_paged.
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0
    # (row, verify-window) pairs that OFFERED drafts — the denominator of
    # the honest mean-accepted-length read: accepted_tokens/drafted_rows
    # (emitted/verify_steps is batch-summed and counts corrections, so it
    # floors at the active-row count even when acceptance is zero)
    spec_drafted_rows: int = 0
    # KV prefix cache: prompt tokens whose prefill was SKIPPED because their
    # KV was spliced from a cached block (prefill_tokens counts only tokens
    # actually computed — the two sum to the logical prompt-token total)
    prefill_tokens_skipped: int = 0
    # what the family's cache counted on the device (models/families.py
    # ``Family.counter_names``; none for per-head K/V), folded in with every
    # fetched answer and exported at /metrics as ``engine_<name>``
    family_counters: Dict[str, int] = field(default_factory=dict)


class InferenceEngine:
    """Owns params + compiled executables; thread-safe ``generate``."""

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
        self.config = config
        self.sampling = sampling
        self.engine_config = engine_config
        self.dtypes = dtypes
        self.mesh = mesh
        self.pad_id = pad_id
        if engine_config.kv_quant not in ("bf16", "int8"):
            raise ValueError(
                f"kv_quant={engine_config.kv_quant!r}: expected 'bf16' or 'int8'"
            )
        if engine_config.speculative not in ("off", "prompt_lookup", "auto"):
            raise ValueError(
                f"speculative={engine_config.speculative!r}: expected "
                "'off', 'prompt_lookup' or 'auto'"
            )
        # adaptive speculation ("auto"): EMA of measured tokens-per-verify;
        # when the workload/model gives ~1.0 (lookup never hits), stop paying
        # the verify overhead, re-probing every _SPEC_REPROBE-th call
        self._spec_ema: Optional[float] = None
        self._spec_skips = 0
        # the model and its cache come from the configuration's TYPE
        # (models/families.py); what a family cannot be served with refuses
        # here, by mechanism
        families.refuse_unsupported(config, engine_config, mesh)
        self.family = families.of(config)
        self.params, fused = maybe_fuse_params(params, engine_config, mesh)
        self.params, quantized = maybe_quantize_params(self.params, engine_config)
        self.model = self.family.build_model(
            config, dtypes, engine_config, mesh, fused=fused, quantized=quantized
        )
        # int32 counters the family's cache carries (0 for per-head K/V): the
        # generate programs append them to the one array the host fetches
        self._n_counters = self.family.counters_width
        # same params, STATIC chunked=True: prompts longer than the largest
        # bucket prefill through the cache chunk by chunk (offset-causal
        # chunk_prefill_attention) instead of being silently truncated
        self.model_chunked = self.model.copy(chunked=True)
        self._build_identity = build_identity(
            "one-shot", self.family, config, engine_config, dtypes, sampling, mesh, pad_id,
            fused=fused, quantized=quantized)
        self._compiled: Dict[Tuple[int, int, int, Optional[int]], jax.stages.Compiled] = {}
        # mesh-replicated chunk-token sidecar copies (see _placed_sidecar)
        self._sidecar_placed: Dict[Tuple[int, int], tuple] = {}
        self._lock = threading.Lock()
        self._gates: Dict[tuple, threading.Lock] = {}  # _get_or_build: a build lock a key
        self._rng_counter = 0
        self.stats = EngineStats(family_counters=dict.fromkeys(self.family.counter_names, 0))
        # goodput ledger (obs/goodput.py; ISSUE 14): generate is ONE device
        # program here, so the roofline model splits each call's measured
        # duration into prefill/decode shares analytically ("oneshot" windows; the
        # continuous engine measures its own). A goodput_window flight event a call.
        self.ledger = obs_goodput.ledger_for(
            config, engine_config, device_kind=serving_device_kind(mesh)
        )
        # observability handles (obs/metrics.py): standalone engines report
        # into the process default registry; RagService rebinds to its own
        self.bind_metrics(obs_metrics.default_registry())
        # cross-request KV prefix cache (engine/prefix_cache.py): owns the
        # HBM-budgeted LRU of segment blocks; this engine provides the
        # build/splice/generate executables it drives
        self.prefix_cache = None
        self._prefix_zero = None  # lazily built all-zeros splice buffer
        if getattr(engine_config, "prefix_cache", None) is not None and \
                engine_config.prefix_cache.enabled:
            from rag_llm_k8s_tpu.engine.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(engine_config.prefix_cache, self)

    # ------------------------------------------------------------------
    # observability (obs/metrics.py)
    # ------------------------------------------------------------------
    def bind_metrics(self, registry) -> None:
        """Point this engine's metric handles at ``registry`` — called at
        construction with the process default and again by RagService with
        the service's own registry, so one scrape carries the engine's
        inter-token histogram (what it builds is counted process-wide:
        obs/tracing.py ``build_span``)."""
        self._obs = registry
        # a generate call's own duration (the enqueue to the end of the
        # fetch) is not observed here: the dispatch that wraps the call
        # (obs/tracing.py ``dispatch_record``) reads it from the ``launch``
        # and ``fetch`` spans below and files it by the path that launched it,
        # ``rag_generate_dispatch_stage_seconds{path, stage="device"}``.
        # the one-shot engine's whole generate is ONE device program, so
        # its per-token figure is an ESTIMATE (call duration / decode
        # steps, prefill share included) — labeled to distinguish it from
        # the continuous engine's exact per-window measurement
        self._m_itl = registry.labeled_histogram(
            "rag_decode_inter_token_seconds",
            "per-decoded-token latency (mode label: oneshot_est is call "
            "duration over decode steps; continuous is exact per window)",
            buckets=obs_metrics.TOKEN_LATENCY_BUCKETS,
        ).labels(mode="oneshot_est")

    def _observe_generate(self, seconds: float, decode_steps: int) -> None:
        """The per-token estimate of one generate call, and nothing else."""
        self._m_itl.observe(seconds / max(decode_steps, 1))

    def _record_oneshot(
        self, call_s: float, bucket: int, batch: int, computed: int,
        decode_tokens: int, decode_steps: int, skipped: int = 0,
        info: Optional[Dict] = None,
    ) -> None:
        """Fold one generate call into the goodput ledger, journal its
        ``goodput_window`` event, and (when the caller passed an ``info``
        out-param) surface the per-request share for the /generate
        timings block."""
        w = self.ledger.record_oneshot(
            call_s, bucket=bucket, batch=batch, computed_tokens=computed,
            decode_tokens=decode_tokens, decode_steps=decode_steps,
            skipped=skipped,
        )
        if w is None:
            return
        per_row = w.pop("chip_ms_per_row")
        frac = w.pop("goodput_frac")
        flight.emit("goodput_window", **w)
        if info is not None:
            gp = {"chip_ms": per_row, "goodput_frac": frac}
            if self.ledger.chip_hour_usd > 0:
                gp["cost_usd"] = (
                    per_row / 1e3 / 3600.0 * self.ledger.chip_hour_usd
                )
            prev = info.get("goodput")
            if prev and prev.get("chip_ms"):
                # a chunked generate() calls this once per sub-batch with
                # ONE info dict: accumulate — overwriting would report
                # only the last chunk's share and under-bill the caller
                chip = prev["chip_ms"] + gp["chip_ms"]
                gp["goodput_frac"] = round(
                    (prev["chip_ms"] * prev.get("goodput_frac", 0.0)
                     + gp["chip_ms"] * frac) / chip, 6,
                )
                gp["chip_ms"] = round(chip, 4)
                if "cost_usd" in gp or "cost_usd" in prev:
                    gp["cost_usd"] = (
                        prev.get("cost_usd", 0.0) + gp.get("cost_usd", 0.0)
                    )
            info["goodput"] = gp

    # ------------------------------------------------------------------
    # compiled generate graph (one per (B, S, max_new))
    # ------------------------------------------------------------------
    def _build_generate(self, B: int, S: int, max_new: int, chunk: Optional[int] = None):
        """One generate program and its abstract arguments (``_get_or_build``
        builds it).

        ``chunk=None``: single-shot prefill at bucket ``S``. ``chunk=C``:
        ``S`` is a multiple of ``C`` and the prompt prefills through the
        cache in ``C``-sized chunks (long prompts — no silent truncation).
        """
        gen = self._make_gen(B, S, max_new, chunk)
        # abstract shapes: the build executes nothing
        avals = param_avals(self.params)
        data_sharding = self.mesh.replicated if self.mesh is not None else None
        tok_aval = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=data_sharding)
        rng_aval = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=data_sharding)
        return jax.jit(gen), (avals, tok_aval, tok_aval, rng_aval)

    def _make_gen(self, B: int, S: int, max_new: int, chunk: Optional[int] = None):
        """The generate graph body ``gen(params, tokens, pad_mask, rng)`` —
        shared by the direct executable (`_build_generate`) and the
        device-assembled RAG variant (`_build_generate_rag`), which prepends
        on-device prompt assembly to the same body."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model
        # cache length rounds up to a 128 multiple so the fused decode kernel
        # tiles it exactly AND the bf16 [.., T, hd] blocks meet Mosaic's
        # second-to-minor tile height even for tiny buckets; slots past
        # S + max_new never enter any kv window
        T = -(-(S + max_new) // 128) * 128
        eos_ids = cfg.eos_token_ids
        cache_dtype = dt.compute_dtype
        pad_id = self.pad_id

        def prefill(params, tokens, positions, cache, kv_start):
            if chunk is None:
                return model.apply(
                    {"params": params}, tokens, positions, cache,
                    kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0),
                    last_logit_only=True,
                )
            n_chunks = S // chunk
            mc = self.model_chunked

            def body(cache, ci):
                wi = ci * chunk
                tok_c = jax.lax.dynamic_slice(tokens, (0, wi), (B, chunk))
                pos_c = jax.lax.dynamic_slice(positions, (0, wi), (B, chunk))
                # last_logit_only also for interior chunks: their logits are
                # discarded, so never materialize a [B, C, V] projection
                _, cache = mc.apply(
                    {"params": params}, tok_c, pos_c, cache,
                    kv_start, jnp.broadcast_to(wi + chunk, (B,)).astype(jnp.int32),
                    wi.astype(jnp.int32), last_logit_only=True,
                )
                return cache, None

            if n_chunks > 1:
                cache, _ = jax.lax.scan(
                    body, cache, jnp.arange(n_chunks - 1, dtype=jnp.int32)
                )
            wi = (n_chunks - 1) * chunk
            return mc.apply(
                {"params": params}, tokens[:, wi:], positions[:, wi:], cache,
                kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(wi),
                last_logit_only=True,
            )

        def gen(params, tokens, pad_mask, rng):
            with phase_scope("prefill", rows=B):
                cache = families.make_cache(
                    cfg, B, T, cache_dtype, quant=self.engine_config.kv_quant
                )
                kv_start, _ = mask_window(pad_mask)  # left-pad: [S - real_len, S)
                real_len = jnp.sum(pad_mask, axis=-1)  # [B]
                positions = jnp.clip(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
                logits, cache = prefill(params, tokens, positions, cache, kv_start)
                rng, k0 = jax.random.split(rng)
                with phase_scope("sample"):
                    tok0 = sample_token(k0, logits[:, -1], sampling)
                done0 = _isin(tok0, eos_ids)
                out0 = jnp.full((B, max_new), pad_id, jnp.int32).at[:, 0].set(tok0)

            def cond(c):
                step, _, _, done, _, _ = c
                return (step < max_new) & ~jnp.all(done)

            def body(c):
                step, cache, last_tok, done, out, rng = c
                # feed token sampled at step-1: cache slot S+step-1, position real_len+step-1
                write_index = (S + step - 1).astype(jnp.int32)
                pos = (real_len + step - 1)[:, None].astype(jnp.int32)
                # the fed token's slot is written this call, so the valid
                # window runs through it: [kv_start, write_index + 1)
                kv_len = jnp.broadcast_to((write_index + 1).astype(jnp.int32), (B,))
                logits, cache = model.apply(
                    {"params": params},
                    last_tok[:, None],
                    pos,
                    cache,
                    kv_start,
                    kv_len,
                    write_index,
                )
                rng, k = jax.random.split(rng)
                with phase_scope("sample"):
                    tok = sample_token(k, logits[:, 0], sampling)
                tok = jnp.where(done, jnp.int32(eos_ids[0]), tok)
                done = done | _isin(tok, eos_ids)
                out = out.at[:, step].set(tok)
                return (step + 1, cache, tok, done, out, rng)

            # the scope is opened around the loop, not in its body: the
            # loop's condition and carried copies are decode work too
            with phase_scope("decode"):
                init = (jnp.int32(1), cache, tok0, done0, out0, rng)
                _, cache, _, _, out, _ = jax.lax.while_loop(cond, body, init)
                return self._with_counters(out, cache)

        return gen

    def _with_counters(self, out, cache):
        """Append the cache's counters (a family that carries any) to row 0
        of the output, as further columns: they come back in the ONE fetch."""
        if not self._n_counters:
            return out
        tail = jnp.zeros((out.shape[0], self._n_counters), out.dtype).at[0].set(
            cache.counters.astype(out.dtype))
        return jnp.concatenate([out, tail], axis=1)

    def _split_counters(self, out: np.ndarray) -> np.ndarray:
        """Host side of ``_with_counters``: fold the counters into the
        stats, return the output without them."""
        if not self._n_counters:
            return out
        with self._lock:
            for name, n in self.family.fold_counters(out[0, -self._n_counters:]).items():
                self.stats.family_counters[name] += n
        return out[:, :-self._n_counters]

    def _build_generate_spec(self, S: int, max_new: int):
        """The SPECULATIVE batch-1 generate program (and its abstract arguments)
        (``EngineConfig.speculative`` = "prompt_lookup"/"auto").

        Each loop iteration feeds ``k+1`` tokens — the pending last token
        plus the ``k`` tokens that followed the most recent in-context
        repeat of the trailing ``n``-gram — through the offset-causal
        chunked model (ONE forward ≈ one decode step's weight traffic),
        then keeps the longest accepted proposal prefix plus one correction
        token. Rejected proposals cost nothing to undo where the cache is by
        position: the KV frontier simply doesn't advance over their slots,
        and later iterations overwrite them (the same windowed-mask machinery
        chunked prefill already relies on). A family whose cache holds state
        that is overwritten in place gives ``Family.commit``: its verify
        model leaves every fed position's state, and the loop tells the cache
        how many positions it kept.

        Acceptance rule per position ``j`` with proposal ``x``:
        - **greedy** (``do_sample=False``): accept iff ``x`` equals the
          model's own argmax — output token-identical to the vanilla loop.
        - **sampled** (``do_sample=True``): REJECTION SAMPLING against the
          deterministic draft: accept with probability ``p_j(x)`` under the
          temperature/top-p-filtered target distribution; on rejection emit
          a draw from the residual (``p_j`` with ``x`` masked, renormalized
          — for a point-mass draft the residual of ``max(p-q, 0)`` is
          exactly that); on full acceptance emit a bonus draw from ``p_k``.
          Marginally each emitted token is distributed exactly as one
          vanilla sampling step given its prefix: ``P(x) = p(x)`` (accept)
          and ``P(y≠x) = (1-p(x))·p(y)/(1-p(x)) = p(y)`` (reject) — the
          emitted DISTRIBUTION equals vanilla 0.7/0.9 sampling
          (tests/test_speculative.py::TestSampledDistribution), though the
          stream for a pinned seed differs (different rng consumption).
        """
        gen = self._make_gen_spec(S, max_new)
        avals = param_avals(self.params)
        data_sharding = self.mesh.replicated if self.mesh is not None else None
        tok_aval = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=data_sharding)
        rng_aval = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=data_sharding)
        return jax.jit(gen), (avals, tok_aval, tok_aval, rng_aval)

    def _make_gen_spec(self, S: int, max_new: int):
        """The speculative batch-1 graph body (see ``_build_generate_spec``)
        — shared with the device-assembled RAG variant."""
        cfg, dt = self.config, self.dtypes
        model = self.model
        commit = self.family.commit
        mc = self.model_chunked if commit is None else self.model_chunked.copy(keep_steps=True)
        sampling = self.sampling
        sampled = sampling.do_sample and sampling.temperature > 0.0
        n = max(1, self.engine_config.spec_ngram)
        k = max(1, self.engine_config.spec_tokens)
        # k extra cache slots: the LAST verify forward can start as late as
        # slot S+max_new-2 and still writes k+1 slots. Without the slack,
        # dynamic_update_slice CLAMPS the out-of-range write start, silently
        # shifting the whole block left over valid accepted-token KV — the
        # exactness contract would break precisely near the token budget.
        T = -(-(S + max_new + k) // 128) * 128
        eos_ids = cfg.eos_token_ids
        cache_dtype = dt.compute_dtype
        pad_id = self.pad_id
        i32 = jnp.int32

        def gen(params, tokens, pad_mask, rng):
            with phase_scope("prefill", rows=1):
                cache = families.make_cache(
                    cfg, 1, T, cache_dtype, quant=self.engine_config.kv_quant
                )
                kv_start, _ = mask_window(pad_mask)
                real_len = jnp.sum(pad_mask, axis=-1)  # [1]
                positions = jnp.clip(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
                logits, cache = model.apply(
                    {"params": params}, tokens, positions, cache,
                    kv_start, jnp.full((1,), S, i32), i32(0),
                    last_logit_only=True,
                )
                rng, k0 = jax.random.split(rng)
                with phase_scope("sample"):
                    tok0 = sample_token(k0, logits[:, -1], sampling)  # [1]
                done0 = _isin(tok0, eos_ids)[0]
                # out and hist carry k+1 slack slots: every scatter below then
                # uses UNIQUE per-lane indices (e + j / wi + 1 + j) — clipping
                # into the last slot instead would create duplicate indices,
                # and XLA scatter picks an arbitrary winner among duplicates
                out0 = jnp.full((1, max_new + k + 1), pad_id, i32).at[:, 0].set(tok0)
                # token history mirrors cache slots: prompt at [0, S) (left-
                # padded exactly like the cache), emitted token j at S + j
                hist0 = jnp.full((1, T + k + 1), pad_id, i32)
                hist0 = jax.lax.dynamic_update_slice(hist0, tokens, (0, 0))
                hist0 = hist0.at[:, S].set(tok0)

            def cond(c):
                e, _, _, done, _, _, _ = c
                return (e < max_new) & ~done

            def body(c):
                e, cache, hist, done, out, rng, iters = c
                wi = (S + e - 1).astype(i32)  # slot of the pending token
                row = hist[0]
                last_tok = jax.lax.dynamic_slice(row, (wi,), (1,))  # [1]
                # ---- propose: last occurrence of the trailing n-gram ----
                match = jnp.ones((T + k + 1,), bool)
                for j in range(n):
                    tj = jax.lax.dynamic_slice(row, (wi - j,), (1,))[0]
                    # candidate c matches iff hist[c - j] == hist[wi - j];
                    # roll wraps but candidates below kv_start+n-1 are
                    # masked out, so wrapped lanes never survive
                    match = match & (jnp.roll(row, j) == tj)
                idx = jnp.arange(T + k + 1, dtype=i32)
                # only occurrences whose k-token continuation is already
                # WRITTEN (idx + k <= wi) may propose: the frontier's own
                # trailing gram always matches itself but continues into
                # unwritten pad history — measured on the chain-head 8B
                # it capped acceptance at ~2 tokens/verify (accept one,
                # reject at the first pad, every verify)
                match = match & (idx >= kv_start[0] + n - 1) & (idx + k <= wi)
                c_star = jnp.max(jnp.where(match, idx, -1))
                src = jnp.where(c_star >= 0, c_star + 1, 0).astype(i32)
                props = jax.lax.dynamic_slice(row, (src,), (k,))  # [k]
                # (no-match proposals are arbitrary history — harmless:
                # acceptance only ever keeps tokens equal to the greedy
                # choice, so garbage proposals just mean m = 0)
                fed = jnp.concatenate([last_tok, props])[None, :]  # [1, k+1]
                pos = (real_len[0] - 1 + e + jnp.arange(k + 1, dtype=i32))[None, :]
                # how many of the fed positions may write the cache (a family
                # whose cache cannot take a rejected write back bounds it)
                span = self.family.verify_span
                writes = k + 1 if span is None else span(cfg, pos[0, 0], k + 1)
                kv_len = jnp.full((1,), wi + writes, i32)
                logits, cache = mc.apply(
                    {"params": params}, fed, pos, cache, kv_start, kv_len, wi
                )
                with phase_scope("sample"):
                    j_idx = jnp.arange(k + 1, dtype=i32)
                    if not sampled:
                        # greedy: accept iff the proposal IS the argmax; position
                        # m then carries the correction argmax — token-identical
                        # to the vanilla greedy loop by construction
                        g = jnp.argmax(logits[0], axis=-1).astype(i32)  # [k+1]
                        acc = jnp.cumprod((props == g[:k]).astype(i32))
                        m = jnp.sum(acc)
                    else:
                        # rejection sampling vs the point-mass draft (docstring):
                        # accept proposal x_j w.p. p_j(x_j); on rejection draw
                        # from p_j with x_j masked (the normalized residual of
                        # max(p - q, 0) for q = δ_x); on full acceptance draw the
                        # bonus token from p_k. Emitted marginal == vanilla
                        # sampling exactly, per position given its prefix.
                        prepared = _prepared_logits(logits[0], sampling)  # [k+1, V]
                        probs = jax.nn.softmax(prepared, axis=-1)
                        rng, it_key = jax.random.split(rng)
                        ku, kr = jax.random.split(it_key)
                        p_prop = jnp.take_along_axis(
                            probs[:k], props[:, None], axis=-1
                        )[:, 0]  # [k]
                        accept = jax.random.uniform(ku, (k,)) < p_prop
                        acc = jnp.cumprod(accept.astype(i32))
                        m = jnp.sum(acc)
                        res = prepared[:k].at[jnp.arange(k), props].set(NEG_INF)
                        rkeys = jax.random.split(kr, k + 1)
                        r = jax.vmap(jax.random.categorical)(rkeys[:k], res)
                        bonus = jax.random.categorical(rkeys[k], prepared[k])
                        corr = jnp.where(
                            m < k, r[jnp.minimum(m, k - 1)], bonus
                        ).astype(i32)
                        # accepted positions emit their proposal; position m the
                        # correction/bonus draw (slots past m are never emitted)
                        g = jnp.concatenate([props, bonus[None].astype(i32)])
                        g = jnp.where(j_idx == m, corr, g)
                is_eos = _isin(g, eos_ids)
                eos_pos = jnp.min(jnp.where(is_eos & (j_idx <= m), j_idx, k + 1))
                m_eff = jnp.minimum(jnp.minimum(m, eos_pos), max_new - e - 1)
                if span is not None:  # nothing is kept past what was written
                    m_eff = jnp.minimum(m_eff, writes - 1)
                if commit is not None:  # the pending token and m_eff proposals were kept
                    cache = commit(cache, m_eff + 1)
                emit = j_idx <= m_eff
                out_idx = e + j_idx  # unique lanes (slack-padded buffer)
                out_row = out[0].at[out_idx].set(
                    jnp.where(emit, g, out[0][out_idx])
                )
                hist_idx = wi + 1 + j_idx
                hist_row = row.at[hist_idx].set(jnp.where(emit, g, row[hist_idx]))
                done = done | (eos_pos <= m_eff)
                return (
                    e + m_eff + 1, cache, hist_row[None], done, out_row[None],
                    rng, iters + 1,
                )

            with phase_scope("verify"):
                init = (i32(1), cache, hist0, done0, out0, rng, i32(0))
                _, cache, _, _, out, _, iters = jax.lax.while_loop(cond, body, init)
                # iters = verify forwards run; the emitted-token count over it
                # is the measured acceptance rate (EngineStats.spec_verify_steps).
                # Packed into the out buffer's first slack slot (never an
                # emission target): returning it as a second array would cost a
                # SECOND device->host round trip per generate on a slow link.
                return self._with_counters(
                    out[:, :max_new + 1].at[:, max_new].set(iters), cache)

        return gen

    def _build_generate_rag(
        self, S: int, max_new: int, cap: int, Lc: int, LA: int, LB: int,
        n: int, kk: int, spec: bool,
    ):
        """The SINGLE-FETCH RAG program (and its abstract arguments): device-side prompt
        assembly fused in front of the (vanilla or speculative) batch-1
        generate body.

        The retrieved top-k never leaves HBM before generation: inputs are
        the fused retrieve's packed ``[1, 2k]`` output (dists ‖ ids, fp32),
        the store's chunk-token sidecar ``[cap, Lc]``/``[cap]``, the fixed
        prompt head ``a_ids`` (BOS + system message + "\\n\\nContext: ") and
        per-query tail ``b_ids`` ("\\n\\nUser: {q}\\n\\nChatbot:", padded to
        ``LB``). Assembly gathers the top-``n`` chunk rows, keeps the
        longest prefix of chunks that fits ``S - LA - b_len`` (token-level
        truncation of the first chunk if even it alone overflows — the
        device mirror of the host path's budget shrinking), writes the
        segments left-packed against the right edge, and hands the
        assembled ``(tokens, pad_mask)`` to the shared generate body. The
        host sees ONE fetch per query: the output tokens (the retrieve ids
        fetch for the response's context text overlaps generation).
        """
        inner = (
            self._make_gen_spec(S, max_new) if spec
            else self._make_gen(1, S, max_new, None)
        )
        pad_id = self.pad_id
        i32 = jnp.int32

        def gen_rag(params, a_ids, b_ids, b_len, packed, store_toks, store_lens, rng):
            with phase_scope("retrieve"):
                idx = packed[0, kk : kk + n].astype(i32)  # top-n rows, rank order
                safe = jnp.clip(idx, 0, cap - 1)
                rows = store_toks[safe]  # [n, Lc] gather
                lens = store_lens[safe]  # [n]
                avail = jnp.maximum(S - LA - b_len, 0)
                keep = jnp.cumsum(lens) <= avail  # monotone: a kept prefix
                eff = jnp.where(keep, lens, 0)
                # never drop ALL context: chunk 0 truncates to the budget instead
                eff = eff.at[0].set(
                    jnp.where(keep[0], lens[0], jnp.minimum(lens[0], avail))
                )
                total = (LA + jnp.sum(eff) + b_len).astype(i32)
                start = S - total
                # one slack slot at S + Lc - 1 absorbs every masked-out lane:
                # real writes always land < S (proved by total <= S), so the
                # junk slot never collides with a real token
                buf = jnp.full((S + Lc,), pad_id, i32)
                buf = jax.lax.dynamic_update_slice(buf, a_ids, (start,))
                off = start + LA + jnp.concatenate(
                    [jnp.zeros((1,), i32), jnp.cumsum(eff)[:-1].astype(i32)]
                )
                lane = jnp.arange(Lc, dtype=i32)
                for i in range(n):  # static unroll over the top-n chunks
                    valid = lane < eff[i]
                    tgt = jnp.where(valid, off[i] + lane, S + Lc - 1)
                    buf = buf.at[tgt].set(jnp.where(valid, rows[i], buf[tgt]))
                laneb = jnp.arange(LB, dtype=i32)
                validb = laneb < b_len
                tgtb = jnp.where(validb, S - b_len + laneb, S + Lc - 1)
                buf = buf.at[tgtb].set(jnp.where(validb, b_ids, buf[tgtb]))
                tokens = buf[:S][None, :]
                pad_mask = (jnp.arange(S) >= start).astype(i32)[None, :]
            return inner(params, tokens, pad_mask, rng)

        avals = param_avals(self.params)
        ds = self.mesh.replicated if self.mesh is not None else None
        mk = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=ds)  # noqa: E731
        return jax.jit(gen_rag), (
            avals,
            mk((LA,), jnp.int32),
            mk((LB,), jnp.int32),
            mk((), jnp.int32),
            mk((1, 2 * kk), jnp.float32),
            mk((cap, Lc), jnp.int32),
            mk((cap,), jnp.int32),
            mk((2,), jnp.uint32),
        )

    def generate_rag(
        self,
        a_ids: np.ndarray,
        b_ids: np.ndarray,
        packed,
        store_toks,
        store_lens,
        n_chunks: int,
        max_new_tokens: Optional[int] = None,
        seed: Optional[int] = None,
        info: Optional[Dict] = None,  # out-param: per-request goodput share
    ) -> List[int]:
        """Single-fetch RAG generate (see ``_build_generate_rag``): the
        caller hands DEVICE arrays for the packed retrieve output and the
        chunk-token sidecar; only the final output tokens cross to the host.
        Always serves at the LARGEST prompt bucket (full-context RAG prompts
        land there; the caller guards that head + tail fit it)."""
        S = max(self.engine_config.prompt_buckets)
        max_new = (
            self.sampling.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        max_new = self._clamp_max_new(S, max_new)
        with tracing.span("launch"):  # host preparation up to the enqueue
            a = np.asarray(a_ids, np.int32)
            b = np.asarray(b_ids, np.int32)
            LA = int(a.shape[0])
            # FIXED tail bucket: one executable per store shape instead of a
            # per-question-length ladder (warmup can then cover every solo
            # query exactly; 128 scatter lanes are free next to the model).
            # Tails beyond it are the caller's fallback (host path).
            LB = self.RAG_TAIL_BUCKET
            if b.shape[0] > LB:
                raise ValueError(
                    f"prompt tail of {b.shape[0]} tokens exceeds the fused "
                    f"bucket ({LB}) — route this query through the host path"
                )
            b_pad = np.full((LB,), self.pad_id, np.int32)
            b_pad[: b.shape[0]] = b
            cap, Lc = int(store_toks.shape[0]), int(store_toks.shape[1])
            kk = int(packed.shape[1]) // 2
            n = min(n_chunks, kk)
            spec = self._spec_applicable(1, None)
            fn = self._get_rag_compiled(S, max_new, cap, Lc, LA, LB, n, kk, spec)
            rng = self._next_rng(seed)
            a_j, b_j = jnp.asarray(a), jnp.asarray(b_pad)
            blen_j, rng_j = jnp.int32(b.shape[0]), rng
            if self.mesh is not None:
                # the executable was lowered with replicated data shardings:
                # place the small per-query inputs each call, and the store
                # sidecar ONCE per snapshot (broadcasting [cap, Lc] per query
                # would be a full-sidecar transfer at corpus scale — the pair
                # is immutable, so cache the placed copy keyed by identity)
                rep = self.mesh.replicated
                a_j, b_j, blen_j, packed, rng_j = (
                    jax.device_put(x, rep) for x in (a_j, b_j, blen_j, packed, rng)
                )
                store_toks, store_lens = self._placed_sidecar(store_toks, store_lens)
            t_call = time.perf_counter()
            out_dev = fn(
                self.params, a_j, b_j, blen_j, packed, store_toks, store_lens,
                rng_j,
            )
        with tracing.span("fetch"):
            out = self._split_counters(np.asarray(out_dev))  # the ONE per-query fetch
        call_s = time.perf_counter() - t_call
        with tracing.span("deliver"):  # the trim, the stats and the goodput folds
            iters = 0
            if spec:
                iters = int(out[0, max_new])
                out = out[:, :max_new]
            eos = set(self.config.eos_token_ids)
            row: List[int] = []
            for t in out[0]:
                if int(t) in eos:
                    break
                row.append(int(t))
            spec_accept = None
            if spec and iters > 0:
                emitted = len(row) + (1 if len(row) < max_new else 0) - 1
                self._spec_record(max(emitted, 0), iters)
                spec_accept = round(max(emitted, 0) / iters, 4)
            self._observe_generate(call_s, len(row))
            with self._lock:
                self.stats.generate_calls += 1
                self.stats.decode_tokens += len(row)
                # prompt length is decided on device; the head + tail are the
                # host-known share (the service adds the gathered chunk share
                # post-hoc once the ids fetch lands — record_prefill)
                self.stats.prefill_tokens += LA + int(b.shape[0])
            # goodput ledger: the assembled prompt length is decided ON DEVICE
            # (fetching it would put a round-trip back on the path this mode
            # exists to remove), so the computed-token figure is the host-known
            # head + tail plus an n-chunks × max-segment ESTIMATE of the
            # gathered share, clamped to the bucket — category split and MFU
            # for this kind are estimates by construction (docs/GOODPUT.md)
            self._record_oneshot(
                call_s, bucket=S, batch=1,
                computed=min(LA + int(b.shape[0]) + n * Lc, S),
                decode_tokens=len(row), decode_steps=max(len(row), 1), info=info,
            )
            if info is not None and spec_accept is not None and self.ledger.enabled:
                info.setdefault("goodput", {})["spec_accept_len_mean"] = spec_accept
            if info is not None and spec and iters > 0:
                # approximation fingerprint (obs/shadow.py): see generate()
                ap = info.setdefault("approx", [])
                if "spec_verify" not in ap:
                    ap.append("spec_verify")
        return row

    def _get_rag_compiled(
        self, S: int, max_new: int, cap: int, Lc: int, LA: int, LB: int,
        n: int, kk: int, spec: bool,
    ):
        """Get-or-build the single-fetch RAG executable. Under ``speculative="auto"`` BOTH the
        spec and vanilla variants build (the EMA can flip between them
        mid-serving — a flip must never compile inside a timed request)."""
        fns = []  # the variant asked for first
        for v in ([spec, not spec] if self.engine_config.speculative == "auto" else [spec]):
            key = (1, S, max_new, ("rag", cap, Lc, LA, LB, n, kk, v))
            fns.append(self._get_or_build(
                key, "generate_rag",
                lambda v=v: self._build_generate_rag(S, max_new, cap, Lc, LA, LB, n, kk, v),
            ))
        return fns[0]

    def warm_rag(
        self, a_len: int, cap: int, Lc: int, kk: int, n: int,
        max_new_tokens: Optional[int] = None,
    ) -> None:
        """AOT-compile the single-fetch RAG executables for the given store
        shapes (compile only, nothing executes) — called by the service's
        warmup and its post-ingest hook so production queries never pay the
        compile. The tail bucket is FIXED (``RAG_TAIL_BUCKET``), so this
        covers every solo query the fused path will serve."""
        S = max(self.engine_config.prompt_buckets)
        max_new = (
            self.sampling.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        max_new = self._clamp_max_new(S, max_new)
        spec = self.engine_config.speculative in ("prompt_lookup", "auto")
        self._get_rag_compiled(
            S, max_new, cap, Lc, a_len, self.RAG_TAIL_BUCKET, n, kk, spec
        )

    def _placed_sidecar(self, store_toks, store_lens):
        """Mesh-replicated copy of the (immutable) chunk-token sidecar,
        broadcast once per snapshot identity instead of per query. Holds a
        reference to the source pair so its id() cannot be recycled. ONE
        entry only: at the 64k-row cap a generation is ~0.5 GB (source +
        replicated), so keeping superseded generations would pin real HBM —
        a snapshot swap pays one re-broadcast and frees the old pair."""
        key = (id(store_toks), id(store_lens))
        with self._lock:
            cached = self._sidecar_placed.get(key)
        if cached is not None:
            return cached[1]
        rep = self.mesh.replicated
        placed = (
            jax.device_put(store_toks, rep), jax.device_put(store_lens, rep)
        )
        with self._lock:
            self._sidecar_placed.clear()
            self._sidecar_placed[key] = ((store_toks, store_lens), placed)
        return placed

    def drop_placed_sidecar(self) -> None:
        """Release the mesh-replicated sidecar copy (service shutdown —
        ``VectorStore.release_token_device`` cannot reach this cache)."""
        with self._lock:
            self._sidecar_placed.clear()

    def record_prefill(self, n_tokens: int) -> None:
        """Post-hoc prefill-token accounting for device-assembled prompts
        (the chunk share is only known once the ids fetch lands)."""
        with self._lock:
            self.stats.prefill_tokens += int(n_tokens)

    # ------------------------------------------------------------------
    # KV prefix cache (engine/prefix_cache.py drives these)
    # ------------------------------------------------------------------
    def _prefix_capacity(self) -> int:
        return self.engine_config.prefix_cache.max_prefix_tokens

    def _prefix_plane_shapes(self, length: int):
        """(shape, dtype) per cache plane for a ``length``-slot KV block —
        payloads first, then (int8-KV) the fp32 scale planes."""
        c = self.config
        cdt = (
            jnp.int8 if self.engine_config.kv_quant == "int8"
            else self.dtypes.compute_dtype
        )
        pay = ((c.num_layers, 1, c.num_kv_heads, length, c.head_dim), cdt)
        out = [pay, pay]
        if self.engine_config.kv_quant == "int8":
            sc = ((c.num_layers, 1, c.num_kv_heads, length), jnp.float32)
            out += [sc, sc]
        return out

    def _prefix_plane_avals(self, length: int):
        ds = self.mesh.replicated if self.mesh is not None else None
        return tuple(
            jax.ShapeDtypeStruct(s, d, sharding=ds)
            for s, d in self._prefix_plane_shapes(length)
        )

    def prefix_buffer_zero(self):
        """The shared all-zeros ``[L, 1, K, P, hd]`` splice buffer every
        prefix assembly starts from (immutable — splices produce new
        buffers, so one instance serves all threads). Built OUTSIDE the
        lock: the multi-MiB device transfer must not serialize concurrent
        resolves behind first-touch init (two racing builders waste one
        allocation of an immutable buffer; first install wins)."""
        with self._lock:
            cached = self._prefix_zero
        if cached is not None:
            return cached
        planes = tuple(
            jnp.zeros(s, d)
            for s, d in self._prefix_plane_shapes(self._prefix_capacity())
        )
        if self.mesh is not None:
            planes = tuple(
                jax.device_put(p, self.mesh.replicated) for p in planes
            )
        with self._lock:
            if self._prefix_zero is None:
                self._prefix_zero = planes
            return self._prefix_zero

    def splice_prefix(self, buf, block, offset: int):
        """Splice a segment block into a prefix buffer at slot ``offset``."""
        return _splice_prefix_planes(buf, block, jnp.int32(offset))

    def rerotate_segment_kv(self, planes, delta: int):
        """Position-shift a cached segment block by ``delta`` tokens: the
        chunk-granular reuse primitive (closed-form RoPE re-rotation of the
        K planes; V passes through). Handles both the native bf16 pair and
        the int8 4-tuple layout (dequant → rotate → requant)."""
        from rag_llm_k8s_tpu.models.llama import rerotate_prefix_planes

        return rerotate_prefix_planes(self.config, planes, delta)

    @staticmethod
    def slice_prefix_block(block, width: int):
        """The first ``width`` slots of a segment block (payloads
        ``[L, 1, K, Sb, hd]``, scales ``[L, 1, K, Sb]`` — the slot axis is
        3 in both): the boundary-correction pass builds a bucket-padded
        block but must overwrite ONLY its corrected window, or the splice
        would clobber the chunk's re-rotated tail with builder padding."""
        return tuple(
            p[:, :, :, :width] if p.ndim == 4 else p[:, :, :, :width, :]
            for p in block
        )

    def build_segment_kv(self, ids: Sequence[int], ctx_planes, ctx_len: int):
        """Prefill ONE prompt segment with ``ctx_planes[:ctx_len]`` as its
        left context and return its KV block padded to the segment bucket —
        the prefix cache's miss-path builder. Counts as real prefill work
        in the stats (the tokens ARE computed, once)."""
        pc = self.engine_config.prefix_cache
        Sb = bucket_len(max(len(ids), 1), pc.segment_buckets)
        toks = np.full((1, Sb), self.pad_id, np.int32)
        toks[0, : len(ids)] = ids
        fn = self._get_segment_kv(Sb)
        toks_j = jnp.asarray(toks)
        slen_j, clen_j = jnp.int32(len(ids)), jnp.int32(ctx_len)
        if self.mesh is not None:
            rep = self.mesh.replicated
            toks_j, slen_j, clen_j = (
                jax.device_put(x, rep) for x in (toks_j, slen_j, clen_j)
            )
            ctx_planes = tuple(jax.device_put(p, rep) for p in ctx_planes)
        block = fn(self.params, toks_j, slen_j, ctx_planes, clen_j)
        with self._lock:
            self.stats.prefill_tokens += len(ids)
        return block

    def _get_segment_kv(self, Sb: int):
        key = (1, Sb, 0, ("segkv", self._prefix_capacity()))
        return self._get_or_build(key, "segment_kv", lambda: self._build_segment_kv(Sb))

    def _build_segment_kv(self, Sb: int):
        """The segment-KV builder's program: chunked prefill of up to
        ``Sb`` fresh tokens at a dynamic offset over a spliced context
        prefix, returning the fresh slots' KV block. One executable per
        segment bucket — never per (segment, offset) pair (both the offset
        and the real length are dynamic scalars)."""
        cfg, dt = self.config, self.dtypes
        mc = self.model_chunked
        P = self._prefix_capacity()
        T = -(-(P + Sb) // 128) * 128
        kvq = self.engine_config.kv_quant
        i32 = jnp.int32

        @phase_scope("prefill")
        def seg(params, tokens, seg_len, ctx, ctx_len):
            cache = make_kv_cache(cfg, 1, T, dt.compute_dtype, quant=kvq)
            planes = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kvq == "int8" else (cache.k, cache.v)
            )
            # context splices at slot 0; its garbage tail (>= ctx_len) is
            # overwritten by this segment's own K/V write below
            planes = tuple(
                jax.lax.dynamic_update_slice(c, b.astype(c.dtype), (0,) * c.ndim)
                for c, b in zip(planes, ctx)
            )
            clen = ctx_len.astype(i32)
            positions = (clen + jnp.arange(Sb, dtype=i32))[None, :]
            kv_len = jnp.broadcast_to(clen + seg_len, (1,)).astype(i32)
            _, cache = mc.apply(
                {"params": params}, tokens, positions, KVCache(*planes),
                jnp.zeros((1,), i32), kv_len, clen, last_logit_only=True,
            )
            out = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if kvq == "int8" else (cache.k, cache.v)
            )
            return tuple(
                jax.lax.dynamic_slice(
                    c,
                    (0, 0, 0, clen) + ((0,) if c.ndim == 5 else ()),
                    c.shape[:3] + (Sb,) + c.shape[4:],
                )
                for c in out
            )

        ds = self.mesh.replicated if self.mesh is not None else None
        out_shardings = (
            tuple(ds for _ in self._prefix_plane_shapes(Sb))
            if self.mesh is not None else None
        )
        return jax.jit(seg, out_shardings=out_shardings), (
            param_avals(self.params),
            jax.ShapeDtypeStruct((1, Sb), jnp.int32, sharding=ds),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=ds),
            self._prefix_plane_avals(P),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=ds),
        )

    # ------------------------------------------------------------------
    # exact-path shadow scoring (obs/shadow.py drives this)
    # ------------------------------------------------------------------
    # chunk width for the teacher-forced scorer: bounds the materialized
    # [1, C, V] logit plane (the scorer needs EVERY position's logits,
    # unlike serving prefill) — 256 × a 128k vocab is ~130 MB fp32
    _SCORE_CHUNK = 256

    def score_exact(self, prompt_ids: Sequence[int],
                    emitted_ids: Sequence[int]) -> Dict[str, object]:
        """Teacher-forced EXACT-PATH scoring for the shadow quality
        auditor: ONE chunked forward over ``prompt + emitted`` with no
        prefix reuse, no speculation, and the engine's native KV dtype —
        the reference every serving-path approximation is judged against.

        Returns per-emitted-position arrays (length ``len(emitted_ids)``):
        ``argmax`` — the exact path's greedy choice given the DELIVERED
        prefix, ``max_logit`` / ``chosen_logit`` — the exact logit of that
        choice and of the delivered token (their gap is the divergence
        evidence obs/shadow.py folds into ``logit_err``). Raises
        ValueError on shapes past the chunked-prefill cap (the auditor
        skips those as "oversize").

        Argmax equivalence between this one forward and the step-by-step
        decode loop is the property the speculative verify paths already
        pin (their multi-token forwards must emit the vanilla loop's
        tokens byte-identically), so a greedy byte-identity contract
        audits clean here by construction.
        """
        x = [int(t) for t in prompt_ids] + [int(t) for t in emitted_ids]
        W = len(emitted_ids)
        if W == 0 or len(x) < 2:
            raise ValueError("score_exact needs a prompt and >= 1 emitted token")
        cap = self.engine_config.max_chunked_prompt
        if len(x) > cap:
            raise ValueError(
                f"score_exact sequence of {len(x)} tokens exceeds "
                f"max_chunked_prompt={cap}"
            )
        chunk = min(self._SCORE_CHUNK, max(self.engine_config.prompt_buckets))
        S = -(-len(x) // chunk) * chunk
        off = S - len(x)
        tokens = np.full((1, S), self.pad_id, np.int32)
        tokens[0, off:] = x
        mask = np.zeros((1, S), np.int32)
        mask[0, off:] = 1
        nxt = np.zeros((1, S), np.int32)
        nxt[0, : S - 1] = tokens[0, 1:]
        fn = self._get_score_exact(S, chunk)
        tokens_j, mask_j = jnp.asarray(tokens), jnp.asarray(mask)
        nxt_j = jnp.asarray(nxt)
        if self.mesh is not None:
            rep = self.mesh.replicated
            tokens_j, mask_j, nxt_j = (
                jax.device_put(v, rep) for v in (tokens_j, mask_j, nxt_j)
            )
        stats = np.asarray(fn(self.params, tokens_j, mask_j, nxt_j))
        lo = off + len(x) - W - 1  # slot whose logits predict emitted[0]
        sl = slice(lo, lo + W)
        return {
            "argmax": stats[sl, 0].astype(np.int64),
            "max_logit": stats[sl, 1].astype(np.float64),
            "chosen_logit": stats[sl, 2].astype(np.float64),
        }

    def warm_score_exact(self, buckets: Sequence[int]) -> None:
        """AOT-compile the exact scorer for every padded length a prompt
        landing in one of the prompt ``buckets`` plus its token budget can
        reach — one executable per multiple of the score chunk.
        Without this the first audit at each length compiles on the audit
        thread while requests are being served."""
        ladder = sorted(self.engine_config.prompt_buckets)
        chunk = min(self._SCORE_CHUNK, max(ladder))
        for b in buckets:
            below = [x for x in ladder if x < b]
            # shortest prompt the bucket takes + one emitted token ... a
            # full bucket + the whole budget
            lo = (below[-1] + 1 if below else 1) + 1
            hi = b + self._clamp_max_new(b, self.sampling.max_new_tokens)
            for S in range(-(-lo // chunk) * chunk, -(-hi // chunk) * chunk + 1, chunk):
                self._get_score_exact(S, chunk)

    def _get_score_exact(self, S: int, chunk: int):
        key = (1, S, 0, ("shadow", chunk))
        return self._get_or_build(key, "score_exact", lambda: self._build_score_exact(S, chunk))

    def _build_score_exact(self, S: int, chunk: int):
        """The teacher-forced scorer's program: left-padded chunked
        prefill over the full sequence, reducing each chunk's [1, C, V]
        logit plane on device to per-position (argmax, max logit, logit of
        the next delivered token) — the host fetches one [S, 3] array,
        never a logit plane."""
        cfg, dt = self.config, self.dtypes
        mc = self.model_chunked
        T = -(-S // 128) * 128
        kvq = self.engine_config.kv_quant
        i32 = jnp.int32

        @phase_scope("score")  # audit work, never filed under serving
        def score(params, tokens, pad_mask, next_tokens):
            cache = families.make_cache(cfg, 1, T, dt.compute_dtype, quant=kvq)
            kv_start, _ = mask_window(pad_mask)
            positions = jnp.clip(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
            n_chunks = S // chunk

            def body(carry, ci):
                cache, stats = carry
                wi = (ci * chunk).astype(i32)
                tok_c = jax.lax.dynamic_slice(tokens, (0, wi), (1, chunk))
                pos_c = jax.lax.dynamic_slice(positions, (0, wi), (1, chunk))
                nxt_c = jax.lax.dynamic_slice(next_tokens, (0, wi), (1, chunk))
                logits, cache = mc.apply(
                    {"params": params}, tok_c, pos_c, cache,
                    kv_start, jnp.broadcast_to(wi + chunk, (1,)).astype(i32),
                    wi,
                )
                row = logits[0].astype(jnp.float32)  # [chunk, V]
                amax = jnp.argmax(row, axis=-1)
                mx = jnp.max(row, axis=-1)
                chosen = jnp.take_along_axis(
                    row, nxt_c[0][:, None], axis=-1
                )[:, 0]
                stats = jax.lax.dynamic_update_slice(
                    stats,
                    jnp.stack(
                        [amax.astype(jnp.float32), mx, chosen], axis=-1
                    ),
                    (wi, jnp.int32(0)),
                )
                return (cache, stats), None

            init = (cache, jnp.zeros((S, 3), jnp.float32))
            (_, stats), _ = jax.lax.scan(
                body, init, jnp.arange(n_chunks, dtype=i32)
            )
            return stats

        ds = self.mesh.replicated if self.mesh is not None else None
        return jax.jit(score, out_shardings=ds), (
            param_avals(self.params),
            jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=ds),
            jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=ds),
            jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=ds),
        )

    def _make_gen_prefixed(self, S_suf: int, max_new: int):
        """The prefixed generate body: splice a CachedPrefix buffer into a
        fresh cache, chunk-prefill only the (right-padded) suffix at the
        dynamic prefix frontier, then run the standard decode loop. Prefix
        and suffix lengths are DYNAMIC scalars — every hit pattern reuses
        the one ``(P, S_suf, max_new)`` executable."""
        cfg, dt, sampling = self.config, self.dtypes, self.sampling
        model = self.model
        mc = self.model_chunked
        P = self._prefix_capacity()
        T = -(-(P + S_suf + max_new) // 128) * 128
        eos_ids = cfg.eos_token_ids
        kvq = self.engine_config.kv_quant
        pad_id = self.pad_id
        i32 = jnp.int32

        def gen(params, prefix_kv, prefix_len, tokens, suffix_len, rng):
            with phase_scope("prefill", rows=1):
                cache = make_kv_cache(cfg, 1, T, dt.compute_dtype, quant=kvq)
                planes = (
                    (cache.k, cache.v, cache.k_scale, cache.v_scale)
                    if kvq == "int8" else (cache.k, cache.v)
                )
                planes = tuple(
                    jax.lax.dynamic_update_slice(c, b.astype(c.dtype), (0,) * c.ndim)
                    for c, b in zip(planes, prefix_kv)
                )
                cache = KVCache(*planes)
                plen = prefix_len.astype(i32)
                slen = suffix_len.astype(i32)
                total = plen + slen
                kv_start = jnp.zeros((1,), i32)  # left-ALIGNED batch-1 layout
                # suffix is right-padded: pad K/V land in [total, plen + S_suf),
                # outside every kv window until decode overwrites them in order
                positions = (plen + jnp.arange(S_suf, dtype=i32))[None, :]
                logits, cache = mc.apply(
                    {"params": params}, tokens, positions, cache,
                    kv_start, jnp.broadcast_to(total, (1,)), plen,
                    logit_index=slen - 1,
                )
                rng, k0 = jax.random.split(rng)
                with phase_scope("sample"):
                    tok0 = sample_token(k0, logits[:, -1], sampling)
                done0 = _isin(tok0, eos_ids)
                out0 = jnp.full((1, max_new), pad_id, i32).at[:, 0].set(tok0)

            def cond(c):
                step, _, _, done, _, _ = c
                return (step < max_new) & ~jnp.all(done)

            def body(c):
                step, cache, last_tok, done, out, rng = c
                # left-aligned: cache slot == sequence position
                write_index = (total + step - 1).astype(i32)
                pos = jnp.broadcast_to(write_index, (1,))[:, None]
                kv_len = jnp.broadcast_to(write_index + 1, (1,))
                logits, cache = model.apply(
                    {"params": params}, last_tok[:, None], pos, cache,
                    kv_start, kv_len, write_index,
                )
                rng, k = jax.random.split(rng)
                with phase_scope("sample"):
                    tok = sample_token(k, logits[:, 0], sampling)
                tok = jnp.where(done, jnp.int32(eos_ids[0]), tok)
                done = done | _isin(tok, eos_ids)
                out = out.at[:, step].set(tok)
                return (step + 1, cache, tok, done, out, rng)

            with phase_scope("decode"):
                init = (jnp.int32(1), cache, tok0, done0, out0, rng)
                _, _, _, _, out, _ = jax.lax.while_loop(cond, body, init)
            return out

        return gen

    def _build_generate_prefixed(self, S_suf: int, max_new: int):
        ds = self.mesh.replicated if self.mesh is not None else None
        return jax.jit(self._make_gen_prefixed(S_suf, max_new)), (
            param_avals(self.params),
            self._prefix_plane_avals(self._prefix_capacity()),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=ds),
            jax.ShapeDtypeStruct((1, S_suf), jnp.int32, sharding=ds),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=ds),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=ds),
        )

    def _get_prefixed(self, S_suf: int, max_new: int):
        key = (1, S_suf, max_new, ("prefix", self._prefix_capacity()))
        return self._get_or_build(
            key, "generate_prefixed", lambda: self._build_generate_prefixed(S_suf, max_new))

    def generate_prefixed(
        self,
        suffix_ids: Sequence[int],
        prefix,  # CachedPrefix
        max_new_tokens: Optional[int] = None,
        seed: Optional[int] = None,
        info: Optional[Dict] = None,  # out-param: per-request goodput share
    ) -> List[int]:
        """Generate with a device-resident cached prefix: prefill touches
        only ``suffix_ids`` (the un-cached prompt tail); the prefix KV is
        spliced from ``prefix.planes``. Raises ValueError when the suffix
        exceeds the bucket ladder (caller falls back to the cold path)."""
        pc = self.engine_config.prefix_cache
        if not suffix_ids:
            # an empty suffix would sample tok0 from a PAD token's logits
            # (logit_index clips to 0) — a silently wrong first token; every
            # real prompt has at least the per-query tail
            raise ValueError("generate_prefixed needs a non-empty suffix")
        n_suf = len(suffix_ids)
        if n_suf > max(pc.suffix_buckets):
            raise ValueError(
                f"prefixed suffix of {n_suf} tokens exceeds the largest "
                f"suffix bucket ({max(pc.suffix_buckets)})"
            )
        S_suf = bucket_len(n_suf, pc.suffix_buckets)
        max_new = (
            self.sampling.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        max_new = max(
            1, min(max_new, self.engine_config.max_seq_len
                   - max(self.engine_config.prompt_buckets))
        )
        fn = self._get_prefixed(S_suf, max_new)
        with tracing.span("launch"):
            toks = np.full((1, S_suf), self.pad_id, np.int32)
            toks[0, : len(suffix_ids)] = list(suffix_ids)
            rng = self._next_rng(seed)
            toks_j = jnp.asarray(toks)
            plen_j = jnp.int32(prefix.length)
            slen_j = jnp.int32(len(suffix_ids))
            planes = prefix.planes
            if self.mesh is not None:
                rep = self.mesh.replicated
                toks_j, plen_j, slen_j, rng = (
                    jax.device_put(x, rep) for x in (toks_j, plen_j, slen_j, rng)
                )
                planes = tuple(jax.device_put(p, rep) for p in planes)
            t_call = time.perf_counter()
            out_dev = fn(self.params, planes, plen_j, toks_j, slen_j, rng)
        with tracing.span("fetch"):
            out = np.asarray(out_dev)
        call_s = time.perf_counter() - t_call
        with tracing.span("deliver"):  # the trim, the stats and the goodput folds
            eos = set(self.config.eos_token_ids)
            row: List[int] = []
            for t in out[0]:
                if int(t) in eos:
                    break
                row.append(int(t))
            self._observe_generate(call_s, len(row))
            with self._lock:
                self.stats.generate_calls += 1
                self.stats.prefill_tokens += len(suffix_ids)
                self.stats.prefill_tokens_skipped += int(prefix.reused_tokens)
                self.stats.decode_tokens += len(row)
            self._record_oneshot(
                call_s, bucket=S_suf, batch=1, computed=len(suffix_ids),
                decode_tokens=len(row), decode_steps=max(len(row), 1),
                skipped=int(prefix.reused_tokens), info=info,
            )
        return row

    def warm_prefixed(
        self,
        suffix_lens: Sequence[int] = (),
        max_new_tokens: Optional[int] = None,
    ) -> None:
        """AOT-compile the prefixed generate executables for the suffix
        buckets serving will hit (compile only — the service's warmup and
        post-ingest hook call this so a cache hit never pays a compile)."""
        if self.prefix_cache is None:
            return
        pc = self.engine_config.prefix_cache
        max_new = (
            self.sampling.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        max_new = max(
            1, min(max_new, self.engine_config.max_seq_len
                   - max(self.engine_config.prompt_buckets))
        )
        buckets = {
            bucket_len(min(max(n, 1), max(pc.suffix_buckets)), pc.suffix_buckets)
            for n in (suffix_lens or (self.RAG_TAIL_BUCKET,))
        }
        for S_suf in sorted(buckets):
            self._get_prefixed(S_suf, max_new)

    def _get_compiled(
        self, B: int, S: int, max_new: int, chunk: Optional[int] = None
    ) -> jax.stages.Compiled:
        if chunk == "spec":
            return self._get_or_build(
                (B, S, max_new, chunk), "generate_spec", lambda: self._build_generate_spec(S, max_new))
        return self._get_or_build(
            (B, S, max_new, chunk), "generate", lambda: self._build_generate(B, S, max_new, chunk))

    def _get_or_build(self, key, program: str, build):
        """The executable cached under ``key``, built ONCE however many threads
        miss it together: ``build()`` gives ``(jitted, avals)``, and
        ``tracing.build_span`` traces, lowers, compiles and counts it."""
        with self._lock:
            fn = self._compiled.get(key)
            gate = fn is None and self._gates.setdefault(key, threading.Lock())
        if fn is None:
            with gate:  # one builder a key; a build that raises frees it for the next
                with self._lock:
                    fn = self._compiled.get(key)
                if fn is None:
                    fn = tracing.build_span(
                        program, key, build, identity=self._build_identity,
                        rows=key[0], bucket=key[1], max_new=key[2])
                    with self._lock:
                        self._compiled[key] = fn
        return fn

    _SPEC_EMA_DECAY = 0.7
    _SPEC_REPROBE = 32
    # single-fetch RAG prompt-tail bucket ("\n\nUser: {q}\n\nChatbot:"
    # padded) — fixed so the executable set is one per store shape
    RAG_TAIL_BUCKET = 128

    def _spec_applicable(self, n_prompts: int, chunk) -> bool:
        """Prompt-lookup speculation serves the batch-1 single-shot case —
        greedy (token-identical) and sampled (distribution-identical via
        rejection sampling); batch > 1 and chunked prompts fall back to the
        vanilla loop. Under ``speculative="auto"`` the engine additionally
        disables itself when MEASURED acceptance stays below
        ``spec_min_accept`` tokens/verify (a k+1-wide verify forward costs
        ~1.4 decode steps measured at the 8B int8 flagship point — below
        that, lookup is not paying for itself), re-probing every
        ``_SPEC_REPROBE``-th eligible call in case the workload changed."""
        mode = self.engine_config.speculative
        if mode not in ("prompt_lookup", "auto") or n_prompts != 1 or chunk is not None:
            return False
        if mode == "auto":
            with self._lock:
                ema, skips = self._spec_ema, self._spec_skips
                low = ema is not None and ema < self.engine_config.spec_min_accept
                if low:
                    self._spec_skips += 1
            if low and (skips + 1) % self._SPEC_REPROBE != 0:
                return False
        return True

    def _spec_record(self, emitted: int, iters: int):
        """Fold one speculative call's measured acceptance into the EMA."""
        acc = emitted / max(iters, 1)
        with self._lock:
            self.stats.spec_verify_steps += iters
            self.stats.spec_emitted_tokens += emitted
            d = self._SPEC_EMA_DECAY
            self._spec_ema = acc if self._spec_ema is None else d * self._spec_ema + (1 - d) * acc

    # ------------------------------------------------------------------
    # host-side API
    # ------------------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        return bucket_len(n, self.engine_config.prompt_buckets)

    @staticmethod
    def _bucket_batch(n: int) -> int:
        return next_pow2(n)

    def _clamp_max_new(self, S: int, max_new: int) -> int:
        """Keep S + max_new within the engine's cache budget."""
        budget = self.engine_config.max_seq_len - S
        return max(1, min(max_new, budget))

    def _next_rng(self, seed: Optional[int]) -> jax.Array:
        """Fresh randomness per call unless the caller pins a seed."""
        if seed is not None:
            return jax.random.PRNGKey(seed)
        with self._lock:
            self._rng_counter += 1
            counter = self._rng_counter
        return jax.random.fold_in(jax.random.PRNGKey(self.sampling.seed), counter)

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
        seed: Optional[int] = None,
        info: Optional[Dict] = None,  # out-param: per-request goodput share
    ) -> List[List[int]]:
        """Generate continuations for a batch of token-id prompts.

        Returns one token list per prompt, truncated at (and excluding) EOS.
        Batches larger than ``EngineConfig.max_batch_size`` split into
        sequential sub-batches (order preserved).
        """
        if not prompts:
            return []
        faults.maybe_fail("generate")
        max_new = (
            self.sampling.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        if max_new <= 0:
            return [[] for _ in prompts]

        cap = self.engine_config.max_batch_size
        if len(prompts) > cap:
            # one base key, folded per sub-batch: a pinned seed stays
            # reproducible without every sub-batch sampling identically
            base = self._next_rng(seed)
            out: List[List[int]] = []
            for sub, i in enumerate(range(0, len(prompts), cap)):
                out.extend(
                    self._generate_batch(
                        prompts[i : i + cap], max_new,
                        jax.random.fold_in(base, sub), info=info,
                    )
                )
            return out
        return self._generate_batch(
            prompts, max_new, self._next_rng(seed), info=info
        )

    def _generate_batch(
        self,
        prompts: Sequence[Sequence[int]],
        max_new: int,
        rng: jax.Array,
        info: Optional[Dict] = None,
    ) -> List[List[int]]:
        """One device call for <= max_batch_size prompts with a decided rng."""
        maxlen = max(len(p) for p in prompts)
        largest = max(self.engine_config.prompt_buckets)
        cap = self.engine_config.max_chunked_prompt
        if maxlen > cap:
            # the ONLY truncation in the engine — and a loud one
            logger.warning(
                "prompt of %d tokens exceeds max_chunked_prompt=%d; "
                "left-truncating to the most recent %d tokens",
                maxlen, cap, cap,
            )
            maxlen = cap
        if maxlen <= largest:
            S = self._bucket_len(maxlen)
            chunk = None
            max_new = self._clamp_max_new(S, max_new)
        else:
            # chunked prefill: pad to a multiple of the largest bucket and
            # run the prompt through the cache chunk by chunk — no silent
            # truncation. Decode keeps the same room the largest single-shot
            # bucket gets (max_seq_len - largest), bounding cache HBM at
            # T = S + that budget even for adversarial max_new_tokens.
            chunk = largest
            S = -(-maxlen // chunk) * chunk
            budget = max(1, self.engine_config.max_seq_len - largest)
            max_new = max(1, min(max_new, budget))
        with tracing.span("launch"):
            B = self._bucket_batch(len(prompts))

            tokens = np.full((B, S), self.pad_id, np.int32)
            pad_mask = np.zeros((B, S), np.int32)
            for i, p in enumerate(prompts):
                p = list(p)[-maxlen:]  # no-op below the cap (maxlen = max row len)
                tokens[i, S - len(p):] = p
                pad_mask[i, S - len(p):] = 1
            # empty rows (batch padding) get one BOS so real_len >= 1
            for i in range(len(prompts), B):
                tokens[i, -1] = self.config.bos_token_id
                pad_mask[i, -1] = 1

            spec = self._spec_applicable(len(prompts), chunk)
            fn = self._get_compiled(B, S, max_new, "spec" if spec else chunk)
            tokens_j, mask_j, rng_j = self._place_inputs(tokens, pad_mask, rng)
            iters = 0
            t_call = time.perf_counter()
            out_dev = fn(self.params, tokens_j, mask_j, rng_j)
        with tracing.span("fetch"):
            out = self._split_counters(np.asarray(out_dev))  # ONE fetch
        if spec:
            iters = int(out[0, max_new])  # packed in the slack slot
            out = out[:, :max_new]
        call_s = time.perf_counter() - t_call
        with tracing.span("deliver"):  # the trim, the stats and the goodput folds
            results: List[List[int]] = []
            eos = set(self.config.eos_token_ids)
            n_decode = 0
            for i in range(len(prompts)):
                row = []
                for t in out[i]:
                    if int(t) in eos:
                        break
                    row.append(int(t))
                results.append(row)
                n_decode += len(row)
            spec_accept = None
            if spec and int(iters) > 0:
                # tokens the VERIFY forwards emitted: answer tokens + the EOS
                # that ended it (if any) MINUS tok0 (sampled at prefill, not by
                # a verify); measured acceptance feeds the auto mode and the
                # /metrics counters
                emitted = len(results[0]) + (1 if len(results[0]) < max_new else 0) - 1
                self._spec_record(max(emitted, 0), int(iters))
                spec_accept = round(max(emitted, 0) / int(iters), 4)
            self._observe_generate(call_s, max((len(r) for r in results), default=1))
            with self._lock:
                self.stats.generate_calls += 1
                self.stats.prefill_tokens += int(pad_mask.sum())
                self.stats.decode_tokens += n_decode
            self._record_oneshot(
                call_s, bucket=S, batch=B, computed=int(pad_mask.sum()),
                decode_tokens=n_decode,
                decode_steps=max((len(r) for r in results), default=1),
                info=info,
            )
            if info is not None and spec_accept is not None and self.ledger.enabled:
                # one-shot speculation: the device-side matcher folds draft
                # outcomes into emitted/iters — the per-call acceptance mean
                # is the only per-request figure it can expose. Gated on the
                # ledger like every other goodput key: TPU_RAG_GOODPUT=0
                # means NO goodput block in info, not a partial one
                info.setdefault("goodput", {})["spec_accept_len_mean"] = spec_accept
            if info is not None and spec and int(iters) > 0:
                # approximation fingerprint (obs/shadow.py): speculation ran
                # for this request — byte-identical by contract, and exactly
                # what the shadow auditor exists to verify on live traffic
                ap = info.setdefault("approx", [])
                if "spec_verify" not in ap:
                    ap.append("spec_verify")
        return results

    def _place_inputs(self, tokens: np.ndarray, pad_mask: np.ndarray, rng: jax.Array):
        """Match the shardings the executable was lowered with."""
        if self.mesh is None:
            return jnp.asarray(tokens), jnp.asarray(pad_mask), rng
        rep = self.mesh.replicated
        return (
            jax.device_put(jnp.asarray(tokens), rep),
            jax.device_put(jnp.asarray(pad_mask), rep),
            jax.device_put(rng, rep),
        )

    def warmup(
        self,
        batch_sizes: Sequence[int] = (1,),
        buckets: Optional[Sequence[int]] = None,
        max_new_tokens: Optional[int] = None,
    ):
        """AOT-compile the executables requests will hit — compile time only,
        nothing executes (readiness gating, survey §5 failure-detection note)."""
        buckets = buckets or self.engine_config.prompt_buckets
        max_new = max_new_tokens or self.sampling.max_new_tokens
        for b in batch_sizes:
            for s in buckets:
                mb = self._bucket_batch(b)
                mn = self._clamp_max_new(s, max_new)
                # STATIC config decides what to warm — never the runtime
                # acceptance EMA (_spec_applicable), which would skip the
                # spec compile on a re-warm after a low-acceptance phase and
                # push the full AOT compile into the next reprobed request
                spec_mode = self.engine_config.speculative
                if mb == 1 and spec_mode in ("prompt_lookup", "auto"):
                    self._get_compiled(1, s, mn, "spec")
                    if spec_mode == "auto":
                        # auto can fall back to the vanilla loop on measured
                        # low acceptance — warm that executable too
                        self._get_compiled(1, s, mn)
                else:
                    self._get_compiled(mb, s, mn)

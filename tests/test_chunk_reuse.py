"""Chunk-granular prefix reuse via attention invariance (ISSUE 12).

The contracts under test (engine/prefix_cache.py ``reuse="chunk"``,
ops/attention.py ``rope_rerotate``, docs/PREFIX_CACHE.md "chunk-granular
reuse"):

- **Re-rotation math**: K cached at position ``p`` re-rotated by ``delta``
  equals K computed at ``p + delta`` (closed form, no re-prefill); delta=0
  is the bit-exact identity; the int8 dequant→rotate→requant round trip
  stays within the per-vector quantization bound.
- **Shuffled-composition tolerance**: the same chunk set permuted across
  queries serves from re-rotated + boundary-corrected canonical KV with
  spliced-vs-cold last-token logits within the pinned tolerance (0.15, the
  warm tier's pin) — on the one-shot splice-buffer substrate AND the paged
  per-chunk pool assembly, hot and warm tiers, and tp=2 under the serving
  specs.
- **Exact-chain regression**: a canonical-position, canonical-chain hit is
  served bit-identically (no rotation, no fixup), and the chunk-mode
  buffer for a first-seen chain equals the ``reuse="exact"`` buffer
  byte-for-byte.
- **Chaos**: a mid-splice fault (site ``chunk_splice``) falls back to
  recompute with zero leaked entries/blocks on either substrate (the
  chaos-lane twin lives in tests/test_resilience.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EngineConfig,
    KVTieringConfig,
    LlamaConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.engine.prefix_cache import PrefixCache
from rag_llm_k8s_tpu.models.llama import (
    KVCache,
    apply_rope,
    init_llama_params,
    make_kv_cache,
    rope_cos_sin,
    rope_frequencies,
)
from rag_llm_k8s_tpu.ops.attention import (
    quantize_kv,
    rope_rerotate,
    rope_rerotate_q8,
)
from rag_llm_k8s_tpu.resilience import faults

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=6)
# Pinned logit tolerance for shifted splices on the RANDOM-INIT tiny model
# — deliberately looser than the warm tier's 0.15: SIFT's composition
# invariance is a property of trained attention (retrieved chunks attend
# mostly within themselves), and a random-init model is its worst case
# (measured 0.10–0.27 max-abs across seeds at boundary_tokens=4). The pin
# bounds REGRESSION drift; the shadow auditor's live tolerance is 0.15.
LOGIT_TOL = 0.35

CHUNK_PC = PrefixCacheConfig(
    enabled=True, max_prefix_tokens=64, segment_buckets=(16,),
    suffix_buckets=(16,), hbm_budget_mb=64, reuse="chunk",
    boundary_tokens=4, chunk_hot_min=0.0,
)
EXACT_PC = dataclasses.replace(CHUNK_PC, reuse="exact")
EC = EngineConfig(
    prompt_buckets=(64, 128), max_batch_size=2, speculative="off",
    max_seq_len=256, prefix_cache=CHUNK_PC,
)
PAGED_EC = dataclasses.replace(EC, kv_paged=True, kv_block_size=16)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny(vocab_size=128)
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    engine = InferenceEngine(
        cfg, params, sampling=GREEDY, engine_config=EC, dtypes=FP32
    )
    return cfg, params, engine


def _corpus(cfg, seed=3, chunk_len=16):
    """One block-aligned head + two block-aligned chunks + a suffix."""
    r = np.random.default_rng(seed)
    head = [int(cfg.bos_token_id)] + list(
        map(int, r.integers(3, 120, chunk_len - 1))
    )
    a = list(map(int, r.integers(3, 120, chunk_len)))
    b = list(map(int, r.integers(3, 120, chunk_len)))
    suffix = list(map(int, r.integers(3, 120, 6)))
    return head, a, b, suffix


def _last_logits_spliced(cfg, engine, cp, suffix, T=128, S_suf=16):
    """Last-token logits of suffix chunk-prefilled over the spliced cp."""
    n = cp.length + len(suffix)
    cache = make_kv_cache(cfg, 1, T, jnp.float32)
    planes = tuple(
        jax.lax.dynamic_update_slice(c, b, (0,) * c.ndim)
        for c, b in zip((cache.k, cache.v), cp.planes)
    )
    toks = np.zeros((1, S_suf), np.int32)
    toks[0, : len(suffix)] = suffix
    pos = (cp.length + jnp.arange(S_suf, dtype=jnp.int32))[None, :]
    lg, _ = engine.model_chunked.apply(
        {"params": engine.params}, jnp.asarray(toks), pos, KVCache(*planes),
        jnp.zeros((1,), jnp.int32), jnp.full((1,), n, jnp.int32),
        jnp.int32(cp.length), logit_index=jnp.int32(len(suffix) - 1),
    )
    return np.asarray(lg[0, -1])


def _last_logits_cold(cfg, engine, full, T=128):
    n = len(full)
    cache = make_kv_cache(cfg, 1, T, jnp.float32)
    lg, _ = engine.model.apply(
        {"params": engine.params},
        jnp.asarray(np.asarray(full, np.int32)[None, :]),
        jnp.arange(n, dtype=jnp.int32)[None, :], cache,
        jnp.zeros((1,), jnp.int32), jnp.full((1,), n, jnp.int32),
        jnp.int32(0), last_logit_only=True,
    )
    return np.asarray(lg[0, -1])


def _drain(eng, rid, fin):
    outs = {}
    while eng.has_active():
        for r, toks in eng.step():
            outs[r] = toks
    return fin if fin is not None else outs[rid]


def _planes_equal(p1, p2) -> bool:
    return all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(p1, p2)
    )


# ---------------------------------------------------------------------------
# the re-rotation op
# ---------------------------------------------------------------------------


class TestRerotateOp:
    def test_rerotate_matches_recompute_at_shifted_position(self):
        cfg = LlamaConfig.tiny()
        inv = rope_frequencies(cfg)
        r = np.random.default_rng(0)
        x = jnp.asarray(
            r.normal(size=(1, 5, 2, cfg.head_dim)).astype(np.float32)
        )
        pos = jnp.asarray(np.arange(5)[None, :])
        c0, s0 = rope_cos_sin(pos, inv)
        k_at = apply_rope(x, c0, s0)
        for delta in (1, 7, -3):
            c1, s1 = rope_cos_sin(pos + delta, inv)
            want = apply_rope(x, c1, s1)
            got = rope_rerotate(k_at, jnp.int32(delta), inv)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5
            )

    def test_zero_delta_is_bit_exact_identity(self):
        cfg = LlamaConfig.tiny()
        inv = rope_frequencies(cfg)
        r = np.random.default_rng(1)
        k = jnp.asarray(
            r.normal(size=(2, 1, 2, 8, cfg.head_dim)).astype(np.float32)
        )
        out = rope_rerotate(k, jnp.int32(0), inv)
        assert np.array_equal(np.asarray(out), np.asarray(k))

    def test_q8_rerotate_round_trip_bounded(self):
        cfg = LlamaConfig.tiny()
        inv = rope_frequencies(cfg)
        r = np.random.default_rng(2)
        x = jnp.asarray(
            r.normal(size=(1, 5, 2, cfg.head_dim)).astype(np.float32)
        )
        pos = jnp.asarray(np.arange(5)[None, :])
        c0, s0 = rope_cos_sin(pos, inv)
        k_at = apply_rope(x, c0, s0)
        kq, ks = quantize_kv(k_at)
        rq, rs = rope_rerotate_q8(kq, ks, jnp.int32(7), inv)
        c1, s1 = rope_cos_sin(pos + 7, inv)
        want = np.asarray(apply_rope(x, c1, s1))
        deq = np.asarray(rq.astype(jnp.float32) * rs[..., None])
        # two quantization round trips: in + out, each max|x|/254 per elem
        bound = 2.0 * np.max(np.abs(want)) / 127.0 + 1e-6
        assert np.max(np.abs(deq - want)) <= bound


# ---------------------------------------------------------------------------
# one-shot substrate: the splice-buffer path
# ---------------------------------------------------------------------------


class TestChunkReuseCache:
    def test_shuffled_composition_within_logit_tolerance(self, setup):
        cfg, params, engine = setup
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, suffix = _corpus(cfg)
        cache.prefix_for([("head", head), ("A", a), ("B", b)])
        cp = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        counts = cache.chunk_reuse_counters()
        assert counts["rerotated"] == 2 and counts["chain_exact"] == 1
        # the acceptance shape: most of the shuffled prefix's prefill
        # skipped (only the boundary windows recompute)
        assert cp.reused_tokens / (cp.reused_tokens + cp.computed_tokens) > 0.5
        ls = _last_logits_spliced(cfg, engine, cp, suffix)
        lc = _last_logits_cold(cfg, engine, head + b + a + suffix)
        assert np.max(np.abs(ls - lc)) <= LOGIT_TOL

    def test_first_resolve_is_bit_identical_to_exact_policy(self, setup):
        """A chain built fresh under reuse="chunk" must equal the
        reuse="exact" build byte-for-byte — chunk mode changes REUSE, not
        the miss path's computation."""
        cfg, params, engine = setup
        head, a, b, _ = _corpus(cfg, seed=11)
        segs = [("head", head), ("A", a), ("B", b)]
        cp_chunk = PrefixCache(CHUNK_PC, engine).prefix_for(segs)
        cp_exact = PrefixCache(EXACT_PC, engine).prefix_for(segs)
        assert _planes_equal(cp_chunk.planes, cp_exact.planes)

    def test_canonical_position_rehit_is_bit_identical(self, setup):
        """Same chain again (memo cleared): every segment serves
        chain_exact — no rotation, no fixup, identical buffer bytes."""
        cfg, params, engine = setup
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, _ = _corpus(cfg, seed=12)
        segs = [("head", head), ("A", a), ("B", b)]
        cp1 = cache.prefix_for(segs)
        with cache._lock:
            cache._assembled.clear()
            cache._assembled_uses.clear()
            cache._assembled_stamp.clear()
            cache._assembled_spans.clear()
            cache.assembled_bytes = 0
        before = cache.chunk_reuse_counters()
        cp2 = cache.prefix_for(segs)
        after = cache.chunk_reuse_counters()
        assert after["chain_exact"] - before["chain_exact"] == 3
        assert after["rerotated"] == before["rerotated"]
        assert cp2.computed_tokens == 0
        assert _planes_equal(cp1.planes, cp2.planes)

    def test_cold_chunk_keeps_recompute_path(self, setup):
        """With the hotness gate above the stream's score, a shuffled
        composition recomputes instead of splicing — and is therefore
        bit-identical to the exact-policy cold build."""
        cfg, params, engine = setup
        gated = PrefixCache(
            dataclasses.replace(CHUNK_PC, chunk_hot_min=100.0), engine
        )
        head, a, b, _ = _corpus(cfg, seed=13)
        gated.prefix_for([("head", head), ("A", a), ("B", b)])
        cp = gated.prefix_for([("head", head), ("B", b), ("A", a)])
        counts = gated.chunk_reuse_counters()
        assert counts["rerotated"] == 0 and counts["spliced"] == 0
        cp_exact = PrefixCache(EXACT_PC, engine).prefix_for(
            [("head", head), ("B", b), ("A", a)]
        )
        assert _planes_equal(cp.planes, cp_exact.planes)

    def test_warm_tier_splice_within_tolerance(self, setup):
        """A warm (int8-quantized in place) chunk still splices at a
        shifted position: dequant → rotate → boundary-correct, within the
        same pinned tolerance."""
        cfg, params, engine = setup
        tiering = KVTieringConfig(
            enabled=True, warm_below=1e9, cold_below=0.0,
            half_life_s=60.0, retier_interval_s=3600.0, host_spill_mb=64,
        )
        cache = PrefixCache(
            dataclasses.replace(CHUNK_PC, chunk_hot_min=0.0),
            engine, tiering=tiering,
        )
        head, a, b, suffix = _corpus(cfg, seed=14)
        cache.prefix_for([("head", head), ("A", a), ("B", b)])
        assert cache.force_demote("warm") > 0
        cp = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        assert cache.chunk_reuse_counters()["rerotated"] == 2
        ls = _last_logits_spliced(cfg, engine, cp, suffix)
        lc = _last_logits_cold(cfg, engine, head + b + a + suffix)
        assert np.max(np.abs(ls - lc)) <= LOGIT_TOL

    def test_failed_swap_in_on_shifted_splice_counts_recompute(self, setup):
        """A cold entry whose swap-in FAILS while it was headed for a
        shifted splice is a recompute, not a splice: the rebuilt segment
        must not take the boundary-correction branch (reused/computed must
        still sum to the prefix total, outcomes all recompute)."""
        cfg, params, engine = setup
        tiering = KVTieringConfig(
            enabled=True, warm_below=0.0, cold_below=0.0,
            half_life_s=60.0, retier_interval_s=3600.0, host_spill_mb=64,
        )
        cache = PrefixCache(CHUNK_PC, engine, tiering=tiering)
        head, a, b, _ = _corpus(cfg, seed=16)
        cache.prefix_for([("head", head), ("A", a), ("B", b)])
        assert cache.force_demote("cold") == 3
        with cache._lock:
            cache._assembled.clear()
            cache._assembled_uses.clear()
            cache._assembled_stamp.clear()
            cache._assembled_spans.clear()
            cache.assembled_bytes = 0
        before = cache.chunk_reuse_counters()
        faults.clear()
        faults.arm("kv_swap_in", times=3)  # every segment's swap fails
        try:
            cp = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        finally:
            faults.clear()
        total = len(head) + len(a) + len(b)
        assert cp.reused_tokens == 0 and cp.computed_tokens == total
        after = cache.chunk_reuse_counters()
        assert after["recompute"] - before["recompute"] == 3
        assert after["rerotated"] == before["rerotated"]
        assert after["boundary_tokens"] == before["boundary_tokens"]

    def test_splice_fault_falls_back_to_recompute_zero_leak(self, setup):
        """Fault site chunk_splice: the shifted splice dies mid-flight —
        the chunk recomputes from tokens (bit-identical to a cold build),
        no entry is lost, and the cache's byte accounting stays exact."""
        cfg, params, engine = setup
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, _ = _corpus(cfg, seed=15)
        cache.prefix_for([("head", head), ("A", a), ("B", b)])
        entries_before = len(cache._entries)
        faults.clear()
        faults.arm("chunk_splice", times=2)  # both shifted chunks
        try:
            cp = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        finally:
            faults.clear()
        counts = cache.chunk_reuse_counters()
        assert counts["splice_faults"] == 2
        assert counts["rerotated"] == 0
        assert len(cache._entries) == entries_before  # rebuilt in place
        assert cache.entry_bytes == sum(
            e.nbytes for e in cache._entries.values()
        )
        cp_exact = PrefixCache(EXACT_PC, engine).prefix_for(
            [("head", head), ("B", b), ("A", a)]
        )
        assert _planes_equal(cp.planes, cp_exact.planes)


# ---------------------------------------------------------------------------
# continuous paged substrate: per-chunk block-table assembly
# ---------------------------------------------------------------------------


class TestChunkReusePaged:
    @pytest.fixture()
    def paged(self, setup):
        cfg, params, engine = setup
        cont = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=PAGED_EC, dtypes=FP32
        )
        return cfg, engine, cont

    def test_assembly_matches_buffer_substrate_and_leaks_nothing(self, paged):
        """The pool-side gather + re-rotate + boundary re-prefill must
        reproduce the splice-buffer substrate's stream exactly (same math,
        same order on this platform), with every block accounted for."""
        cfg, engine, cont = paged
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, suffix = _corpus(cfg, seed=21)
        cp1 = cache.prefix_for([("head", head), ("A", a), ("B", b)])
        _, fin = cont.admit_prefixed(1, suffix, cp1, max_new=6)
        _drain(cont, 1, fin)
        # the scatter admission registered per-chunk canonical pool copies
        assert set(cont._chunk_regs) == {"head", "A", "B"}

        cp2 = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        plan = cont._chunk_splice_plan(cp2)
        assert plan is not None and len(plan) == 3
        _, fin2 = cont.admit_prefixed(2, suffix, cp2, max_new=6)
        got = _drain(cont, 2, fin2)
        want = engine.generate_prefixed(suffix, cp2)
        assert got == want

        # zero leak: releasing every registration empties the pool
        for k in list(cont._chunk_regs):
            cont._drop_chunk_reg(k)
        for k in list(cont._prefix_blocks):
            cont._drop_registration(k)
        assert cont.kv_pool.blocks_in_use() == 0
        assert cont._chunk_reg_tokens == 0

    def test_stale_stamp_declines_the_plan(self, paged):
        """A chunk entry rebuilt in the cache (new creation stamp) must
        not serve from its stale pool registration — the plan declines and
        the admission scatters the fresh buffer."""
        cfg, engine, cont = paged
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, suffix = _corpus(cfg, seed=22)
        cp1 = cache.prefix_for([("head", head), ("A", a), ("B", b)])
        _, fin = cont.admit_prefixed(3, suffix, cp1, max_new=6)
        _drain(cont, 3, fin)
        assert "A" in cont._chunk_regs
        # rebuild A's entry: the canonical content changes generation
        with cache._lock:
            cache._entries.pop(("A",))
            cache.entry_bytes = sum(
                e.nbytes for e in cache._entries.values()
            )
            cache._assembled.clear()
            cache._assembled_uses.clear()
            cache._assembled_stamp.clear()
            cache._assembled_spans.clear()
            cache.assembled_bytes = 0
        cp2 = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        assert cont._chunk_splice_plan(cp2) is None

    def test_paged_splice_fault_falls_back_to_scatter_zero_leak(self, paged):
        """Armed chunk_splice pool-side: the plan declines BEFORE any
        allocation, the admission takes the buffer-scatter path, and the
        stream/accounting are unchanged."""
        cfg, engine, cont = paged
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, suffix = _corpus(cfg, seed=23)
        cp1 = cache.prefix_for([("head", head), ("A", a), ("B", b)])
        _, fin = cont.admit_prefixed(4, suffix, cp1, max_new=6)
        _drain(cont, 4, fin)
        in_use_before = cont.kv_pool.blocks_in_use()
        cp2 = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        faults.clear()
        faults.arm("chunk_splice", times=1)
        try:
            _, fin2 = cont.admit_prefixed(5, suffix, cp2, max_new=6)
            got = _drain(cont, 5, fin2)
        finally:
            faults.clear()
        want = engine.generate_prefixed(suffix, cp2)
        assert got == want  # the scatter path serves the same buffer
        assert cont.kv_pool.blocks_in_use() >= in_use_before  # regs only
        for k in list(cont._chunk_regs):
            cont._drop_chunk_reg(k)
        for k in list(cont._prefix_blocks):
            cont._drop_registration(k)
        assert cont.kv_pool.blocks_in_use() == 0


# ---------------------------------------------------------------------------
# tp=2 under the serving specs
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 (virtual) devices for tp=2"
)
class TestChunkReuseTP2:
    def test_tp2_assembly_matches_tp1(self, setup):
        """The chunk-splice executable over the head-sharded arena: a tp=2
        per-chunk assembled admission streams identically to tp=1."""
        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        cfg, params, engine = setup
        head, a, b, suffix = _corpus(cfg, seed=31)

        def run(cont, cache):
            cp1 = cache.prefix_for([("head", head), ("A", a), ("B", b)])
            _, fin = cont.admit_prefixed(1, suffix, cp1, max_new=6)
            _drain(cont, 1, fin)
            cp2 = cache.prefix_for([("head", head), ("B", b), ("A", a)])
            assert cont._chunk_splice_plan(cp2) is not None
            _, fin2 = cont.admit_prefixed(2, suffix, cp2, max_new=6)
            out = _drain(cont, 2, fin2)
            for k in list(cont._chunk_regs):
                cont._drop_chunk_reg(k)
            for k in list(cont._prefix_blocks):
                cont._drop_registration(k)
            assert cont.kv_pool.blocks_in_use() == 0
            return out

        cont1 = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=PAGED_EC, dtypes=FP32
        )
        want = run(cont1, PrefixCache(CHUNK_PC, engine))

        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        placed = shard_llama_params(params, ctx)
        cont2 = ContinuousEngine(
            cfg, placed, sampling=GREEDY, engine_config=PAGED_EC,
            dtypes=FP32, mesh=ctx,
        )
        shard = cont2._cache[0].addressable_shards[0].data.shape
        assert shard[2] == cfg.num_kv_heads // ctx.tp, shard
        got = run(cont2, PrefixCache(CHUNK_PC, engine))
        assert got == want


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


class TestChunkReuseConfig:
    def test_env_round_trip(self):
        c = AppConfig.from_env({
            "TPU_RAG_PREFIX_REUSE": "chunk",
            "TPU_RAG_PREFIX_BOUNDARY_TOKENS": "8",
            "TPU_RAG_PREFIX_CHUNK_HOT_MIN": "1.5",
            "TPU_RAG_PREFIX_CHUNK_POOL_REGS": "8",
        })
        pc = c.engine.prefix_cache
        assert pc.reuse == "chunk"
        assert pc.boundary_tokens == 8
        assert pc.chunk_hot_min == 1.5
        assert pc.chunk_pool_regs == 8
        assert AppConfig.from_env({}).engine.prefix_cache.reuse == "exact"

    def test_env_validation(self):
        for bad in (
            {"TPU_RAG_PREFIX_REUSE": "fuzzy"},
            {"TPU_RAG_PREFIX_BOUNDARY_TOKENS": "-1"},
            {"TPU_RAG_PREFIX_CHUNK_HOT_MIN": "-0.5"},
            {"TPU_RAG_PREFIX_CHUNK_POOL_REGS": "0"},
        ):
            with pytest.raises(ValueError):
                AppConfig.from_env(bad)

    def test_bad_policy_rejected_at_construction(self, setup):
        cfg, params, engine = setup
        with pytest.raises(ValueError):
            PrefixCache(
                dataclasses.replace(CHUNK_PC, reuse="fuzzy"), engine
            )


# ---------------------------------------------------------------------------
# smoke (the `make splice-smoke` lane)
# ---------------------------------------------------------------------------


class TestSmoke:
    def test_shuffled_composition_both_substrates(self, setup):
        """The acceptance shape end to end on the tiny config: a permuted
        composition serves mostly from cache (>50% prefill skipped) within
        the pinned logit tolerance, on the splice-buffer substrate and the
        paged per-chunk assembly, with zero leaked blocks."""
        cfg, params, engine = setup
        cache = PrefixCache(CHUNK_PC, engine)
        head, a, b, suffix = _corpus(cfg, seed=41)
        cp1 = cache.prefix_for([("head", head), ("A", a), ("B", b)])
        cp2 = cache.prefix_for([("head", head), ("B", b), ("A", a)])
        assert (
            cp2.reused_tokens / (cp2.reused_tokens + cp2.computed_tokens)
            > 0.5
        )
        ls = _last_logits_spliced(cfg, engine, cp2, suffix)
        lc = _last_logits_cold(cfg, engine, head + b + a + suffix)
        assert np.max(np.abs(ls - lc)) <= LOGIT_TOL

        cont = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=PAGED_EC, dtypes=FP32
        )
        _, fin = cont.admit_prefixed(1, suffix, cp1, max_new=6)
        _drain(cont, 1, fin)
        _, fin2 = cont.admit_prefixed(2, suffix, cp2, max_new=6)
        got = _drain(cont, 2, fin2)
        assert got == engine.generate_prefixed(suffix, cp2)
        for k in list(cont._chunk_regs):
            cont._drop_chunk_reg(k)
        for k in list(cont._prefix_blocks):
            cont._drop_registration(k)
        assert cont.kv_pool.blocks_in_use() == 0

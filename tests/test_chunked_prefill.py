"""Unified ragged sync windows: chunked prefill interleaved with decode
(ISSUE 16).

The load-bearing contract is BYTE-IDENTICAL streams between the paged
engine with interleaving ON and OFF — greedy AND seeded sampling — across
mixed-length admission groups, mid-flight admission, chaos resets landing
mid-chunk, pool preemption of partially-prefilled admissions, prefixed
batchmates, speculative verify windows and tp=2. Interleaving may only
change WHEN a prompt's prefill compute runs (sliced across windows that
also decode), never which tokens any stream carries. The rest is the
planner's unit surface (budget split arithmetic, decode-lane
reservation), block accounting (zero leaks through preempt / evict /
reset), the mixed window's goodput attribution, and the config knobs.

``TestSmoke`` is the `make interleave-smoke` lane (greedy + seeded
identity plus the mid-chunk reset chaos case).
"""

import dataclasses
import threading

import jax
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import (
    ContinuousEngine,
    ContinuousScheduler,
)
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import flight, goodput
from rag_llm_k8s_tpu.resilience import faults

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=10)
PAGED = EngineConfig(
    prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64,
    kv_paged=True, kv_block_size=16,
)
# chunk width 8 so the longer prompts below spread across 2-3 windows
INTER = dataclasses.replace(
    PAGED, interleave_prefill=True, prefill_chunk_tokens=8
)
# mixed buckets, including prompts longer than one chunk
PROMPTS = [
    [5, 6, 7, 8, 9, 10, 11],
    [12, 13, 14],
    [3] * 20,
    [9] * 25,
]


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def drain(eng, reqs, seeds=None):
    """admit_many + step-to-completion → {rid: tokens}; asserts zero
    leaked blocks on the way out."""
    results = {}
    outs = eng.admit_many([
        (rid, p, mn, None if seeds is None else seeds[i])
        for i, (rid, p, mn) in enumerate(reqs)
    ])
    for (rid, _, _), res in zip(reqs, outs):
        if isinstance(res, BaseException):
            raise res
        _, fin = res
        if fin is not None:
            results[rid] = fin
    for _ in range(300):
        for rid, toks in eng.step():
            results[rid] = toks
        if not eng.has_active():
            break
    assert eng.kv_pool.blocks_in_use() == 0
    return results


# ---------------------------------------------------------------------------
# byte identity (the correctness gate) — the `make interleave-smoke` lane
# ---------------------------------------------------------------------------


class TestSmoke:
    """`make interleave-smoke`: greedy + seeded streams with interleaving
    ON are byte-identical to the phase-separated scheduler on the tiny
    config, including a chaos reset landing mid-chunk — and mixed windows
    actually ran (the identity must not be vacuous)."""

    def test_greedy_mixed_batch_byte_identity(self, setup):
        cfg, params = setup
        reqs = [(i + 1, p, 10) for i, p in enumerate(PROMPTS)]
        base = drain(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=PAGED, dtypes=FP32), reqs,
        )
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        inter = drain(eng, reqs)
        assert inter == base
        st = eng.ledger.state()
        assert "mixed" in st["kinds"], "no mixed window ever ran — vacuous"

    @pytest.mark.parametrize("temp", [0.7, 0.01])
    def test_seeded_sampling_mid_flight_byte_identity(self, setup, temp):
        """Seeded sampling: the final chunk folds ``(row_key, prompt_len)``
        — the exact key the one-shot admission folds — and decode lanes
        continue the same (seed, position) sequence, so sampled streams
        match bit-for-bit, including a request joining mid-flight (its
        chunks ride windows that decode the first request)."""
        cfg, params = setup
        samp = SamplingConfig(
            do_sample=True, temperature=temp, top_p=0.9, max_new_tokens=10
        )

        def run(eng_cfg):
            eng = ContinuousEngine(
                cfg, params, sampling=samp, engine_config=eng_cfg,
                dtypes=FP32,
            )
            results = {}
            _, fin = eng.admit(1, PROMPTS[0], 10, seed=123)
            if fin is not None:
                results[1] = fin
            eng.step()
            _, fin = eng.admit(2, PROMPTS[3], 10, seed=7)  # joins mid-flight
            if fin is not None:
                results[2] = fin
            for _ in range(300):
                for rid, toks in eng.step():
                    results[rid] = toks
                if not eng.has_active():
                    break
            assert eng.kv_pool.blocks_in_use() == 0
            return results

        assert run(INTER) == run(PAGED)

    def test_mid_chunk_reset_recovers_byte_identical(self, setup):
        """Chaos: an injected device fault while an admission is PARTWAY
        through its chunks — the reset drops the partial KV and the queue
        record, returns every block, and the resubmission reproduces the
        phase-separated stream exactly."""
        cfg, params = setup
        reqs = [(1, PROMPTS[3], 10), (2, PROMPTS[1], 10)]
        base = drain(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=PAGED, dtypes=FP32), reqs,
        )
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        eng.admit_many([(1, PROMPTS[3], 10, None), (2, PROMPTS[1], 10, None)])
        eng.step()  # first window: the 25-token prompt is now mid-chunk
        assert eng._chunk_admissions, "queue drained in one window — vacuous"
        assert eng._chunk_admissions[1]["progress"] > 0
        faults.arm("decode_step", times=1)
        with pytest.raises(faults.InjectedFault):
            eng.step()
        eng.reset()
        assert eng.kv_pool.blocks_in_use() == 0, "reset leaked blocks"
        assert not eng._chunk_admissions, "reset kept a dead admission"
        assert len(eng.free_slots()) == eng.B, "reset kept a reserved row"
        assert drain(eng, reqs) == base

    def test_mid_chunk_reset_recovers_through_scheduler(self, setup):
        """The same fault through the scheduler's recovery path: the
        in-flight chunked admission resubmits from its prompt and the
        caller never sees the fault."""
        cfg, params = setup
        base = drain(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=PAGED, dtypes=FP32),
            [(1, PROMPTS[2], 10)],
        )
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        try:
            faults.arm("decode_step", times=1)
            out = sched.submit(PROMPTS[2], max_new_tokens=10, timeout=120)
            assert out == base[1]
            assert faults.armed() == {}, "the fault never fired"
            assert eng.kv_pool.blocks_in_use() == 0
        finally:
            sched.shutdown()


# ---------------------------------------------------------------------------
# window planner: budget split arithmetic
# ---------------------------------------------------------------------------


class TestWindowPlanner:
    def test_budget_slices_admissions_fifo(self, setup):
        """budget=6, chunk=4, nothing decoding: the oldest admission takes
        a full chunk, the leftover budget slices the second — and the
        split is journaled (`window_budget` + per-chunk
        `prefill_chunk_sched` flight events)."""
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                INTER, prefill_chunk_tokens=4, window_token_budget=6
            ),
            dtypes=FP32,
        )
        seq0 = flight.recorder().events_emitted
        eng.admit_many([(1, [3] * 10, 4, None), (2, [9] * 6, 4, None)])
        eng.step()
        assert eng._chunk_admissions[1]["progress"] == 4
        assert eng._chunk_admissions[2]["progress"] == 2
        wb = [
            e for e in flight.recorder().snapshot(etype="window_budget")
            if e["seq"] >= seq0
        ]
        assert wb and wb[0]["budget"] == 6
        assert wb[0]["decode_lanes"] == 0
        assert wb[0]["chunk_tokens"] == 6 and wb[0]["chunks"] == 2
        sc = [
            e for e in flight.recorder().snapshot(etype="prefill_chunk_sched")
            if e["seq"] >= seq0
        ]
        assert [(e["rid"], e["tokens"], e["final"]) for e in sc] == [
            (1, 4, 0), (2, 2, 0),
        ]
        while eng.has_active() or eng._chunk_admissions:
            eng.step()
        assert eng.kv_pool.blocks_in_use() == 0

    def test_decode_lanes_come_off_the_budget(self, setup):
        """Every active decode row costs one token of the window budget
        BEFORE admissions slice the rest — decode never stops for
        admission, admission gets the leftovers."""
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                INTER, window_token_budget=5
            ),
            dtypes=FP32,
        )
        eng.admit_many([(1, PROMPTS[1], 8, None)])  # 3 tokens: one window
        while eng._chunk_admissions:
            eng.step()
        assert sum(1 for s in eng.slots if s.active) == 1
        eng.admit_many([(2, [3] * 20, 4, None)])
        eng.step()
        # budget 5 - 1 decode lane = 4 chunk tokens, not chunk_tokens=8
        assert eng._chunk_admissions[2]["progress"] == 4
        while eng.has_active() or eng._chunk_admissions:
            eng.step()
        assert eng.kv_pool.blocks_in_use() == 0

    def test_auto_budget_default(self, setup):
        """window_token_budget=0 → max_batch_size + prefill_chunk_tokens:
        a full decode batch still advances AND one full chunk fits."""
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        assert eng.window_budget == PAGED.max_batch_size + 8

    def test_incremental_block_allocation(self, setup):
        """A queued admission holds blocks for exactly its PROGRESS, not
        its prompt — the whole point of incremental admission."""
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        eng.admit_many([(1, [9] * 25, 4, None)])
        eng.step()  # one 8-token chunk → 1 block of 16, not the 2 for 25
        rec = eng._chunk_admissions[1]
        assert rec["progress"] == 8
        assert len(eng._slot_blocks[rec["row"]]) == 1
        assert eng.kv_pool.blocks_in_use() == 1
        while eng.has_active() or eng._chunk_admissions:
            eng.step()
        assert eng.kv_pool.blocks_in_use() == 0


# ---------------------------------------------------------------------------
# block accounting: preempt / evict / reset of partial admissions
# ---------------------------------------------------------------------------


class TestPartialAdmissionAccounting:
    def test_pool_preemption_byte_identity_zero_leaks(self, setup):
        """A pool sized for half the batch's growth forces preemption
        WHILE admissions hold partial prefills: resubmission still
        reproduces the phase-separated streams, zero leaked blocks."""
        cfg, params = setup
        want = drain(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=PAGED, dtypes=FP32),
            [(i + 1, p, 40) for i, p in enumerate(PROMPTS)],
        )
        tight = dataclasses.replace(INTER, kv_pool_blocks=8)
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=tight, dtypes=FP32
        )
        sched = ContinuousScheduler(eng)
        try:
            outs = [None] * len(PROMPTS)
            errs = [None] * len(PROMPTS)

            def run(i):
                try:
                    outs[i] = sched.submit(
                        PROMPTS[i], max_new_tokens=40, timeout=300
                    )
                except BaseException as e:  # noqa: BLE001
                    errs[i] = e

            threads = [
                threading.Thread(target=run, args=(i,))
                for i in range(len(PROMPTS))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert errs == [None] * len(PROMPTS), errs
            assert outs == [want[i + 1] for i in range(len(PROMPTS))]
            assert eng.kv_pool.blocks_in_use() == 0
        finally:
            sched.shutdown()

    def test_evicting_a_partial_admission_frees_everything(self, setup):
        """Deadline eviction mid-prefill (the scheduler's `_evict_expired`
        calls this): the reserved row, the queue record and every
        partially-written block all release."""
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        eng.admit_many([(1, [9] * 25, 8, None)])
        eng.step()
        assert eng.kv_pool.blocks_in_use() > 0
        eng.evict_requests([1])
        assert 1 not in eng._chunk_admissions
        assert eng.kv_pool.blocks_in_use() == 0
        assert len(eng.free_slots()) == eng.B
        # the engine still serves after the eviction
        assert drain(eng, [(2, PROMPTS[0], 5)])[2]

    def test_reset_drops_queued_admissions(self, setup):
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        eng.admit_many([(1, [9] * 25, 8, None)])
        eng.step()
        eng.reset()
        assert not eng._chunk_admissions
        assert eng.kv_pool.blocks_in_use() == 0
        assert len(eng.free_slots()) == eng.B


# ---------------------------------------------------------------------------
# composition: prefix cache + speculative verify windows
# ---------------------------------------------------------------------------


class TestComposition:
    def test_prefixed_batchmate_byte_identity(self, setup):
        """A prefix-cache admission (splice path) decoding WHILE plain
        admissions chunk through mixed windows: both streams match the
        interleave-off engine."""
        cfg0 = LlamaConfig.tiny(vocab_size=128)
        params = init_llama_params(jax.random.PRNGKey(0), cfg0, FP32)
        pc = PrefixCacheConfig(
            enabled=True, max_prefix_tokens=48, segment_buckets=(16,),
            suffix_buckets=(16,), hbm_budget_mb=64,
        )
        ec = EngineConfig(
            prompt_buckets=(64,), max_batch_size=2, speculative="off",
            max_seq_len=128, prefix_cache=pc,
        )
        oneshot = InferenceEngine(
            cfg0, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
            engine_config=ec, dtypes=FP32,
        )
        rng = np.random.default_rng(9)
        head = [cfg0.bos_token_id] + list(map(int, rng.integers(3, 120, 7)))
        chunk = list(map(int, rng.integers(3, 120, 11)))
        suffix = list(map(int, rng.integers(3, 120, 6)))
        plain = list(map(int, rng.integers(3, 120, 20)))
        segments = [("head:inter", head), ("chunk:inter", chunk)]

        def run(inter_on):
            eng_cfg = dataclasses.replace(
                ec, kv_paged=True, kv_block_size=16,
                interleave_prefill=inter_on, prefill_chunk_tokens=8,
            )
            cont = ContinuousEngine(
                cfg0, params,
                sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
                engine_config=eng_cfg, dtypes=FP32,
            )
            cp = oneshot.prefix_cache.prefix_for(segments)
            outs = {}
            _, fin = cont.admit_prefixed(1, suffix, cp, max_new=8)
            if fin is not None:
                outs[1] = fin
            # the plain admission chunks while the spliced row decodes
            for (rid, _), res in zip([(2, plain)],
                                     cont.admit_many([(2, plain, 8, None)])):
                _, f2 = res
                if f2 is not None:
                    outs[rid] = f2
            for _ in range(300):
                for r, toks in cont.step():
                    outs[r] = toks
                if not cont.has_active():
                    break
            # NOTE: no zero-block assertion — the prefix REGISTRATION
            # legitimately retains its blocks for future admissions
            return outs

        assert run(True) == run(False)

    def test_speculative_verify_composes_byte_identical(self, setup):
        """Mixed windows take routing priority while admissions queue;
        verify windows resume once it drains — both shapes are
        draw-invariant, so streams match plain PAGED and speculation is
        non-vacuous."""
        cfg, params = setup
        # repeat-heavy prompts so prompt-lookup drafting actually fires
        reqs = [
            (1, [3, 17, 42, 3, 17, 42, 3, 17] * 2, 10),
            (2, [11] * 20, 10),
        ]
        base = drain(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=PAGED, dtypes=FP32), reqs,
        )
        both = dataclasses.replace(
            INTER, spec_paged=True, spec_paged_tokens=4
        )
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=both, dtypes=FP32
        )
        got = drain(eng, reqs)
        assert got == base
        assert "mixed" in eng.ledger.state()["kinds"], "vacuous: no mixed"
        assert eng.stats.spec_verify_steps > 0, "vacuous: no verify step"


# ---------------------------------------------------------------------------
# goodput attribution of mixed windows
# ---------------------------------------------------------------------------


class TestGoodputMixed:
    def test_mixed_window_attribution_and_conservation(self, setup):
        """Chunked-prefill lanes land in `prefill_compute` (NOT the
        `padding_bubble` the phase-separated scheduler burned), decode
        lanes that kept their token in `decode_useful`, categories
        conserve against busy time within 5%, and the offline
        reconstruction counts the same useful decode tokens."""
        cfg, params = setup
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=INTER, dtypes=FP32
        )
        # the ring must cover the whole run: a file this worker ran before
        # (tests/test_replay.py) can leave the process recorder at 64 events
        flight.configure(enabled=True, capacity=8192)
        seq0 = flight.recorder().events_emitted
        drain(eng, [(i + 1, p, 10) for i, p in enumerate(PROMPTS)])
        st = eng.ledger.state()
        mixed = st["kinds"].get("mixed")
        assert mixed and mixed["busy_s"] > 0
        assert st["categories"]["prefill_compute"] > 0
        assert st["categories"]["decode_useful"] > 0
        busy = st["busy_s"]
        assert busy > 0
        assert abs(busy - sum(st["categories"].values())) / busy < 0.05
        events = [
            e for e in flight.recorder().snapshot(etype="goodput_window")
            if e["seq"] >= seq0
        ]
        assert any(e.get("kind") == "mixed" for e in events)
        for e in events:
            cats = sum(e.get(c, 0.0) for c in goodput.WINDOW_CATEGORIES)
            assert cats == pytest.approx(e["dur_ms"], abs=0.01)
        rebuilt = goodput.state_from_events(events)
        assert rebuilt["useful_decode_tokens"] == pytest.approx(
            st["useful_decode_tokens"]
        )


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------


class TestConfig:
    def test_construction_validation(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="requires kv_paged"):
            ContinuousEngine(
                cfg, params, sampling=GREEDY,
                engine_config=dataclasses.replace(
                    INTER, kv_paged=False
                ),
                dtypes=FP32,
            )
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            ContinuousEngine(
                cfg, params, sampling=GREEDY,
                engine_config=dataclasses.replace(
                    INTER, prefill_chunk_tokens=0
                ),
                dtypes=FP32,
            )
        with pytest.raises(ValueError, match="window_token_budget"):
            ContinuousEngine(
                cfg, params, sampling=GREEDY,
                engine_config=dataclasses.replace(
                    INTER, window_token_budget=2  # < max_batch_size + 1
                ),
                dtypes=FP32,
            )

    def test_env_round_trip(self, monkeypatch):
        for k, v in (
            ("TPU_RAG_KV_PAGED", "1"),
            ("TPU_RAG_INTERLEAVE_PREFILL", "1"),
            ("TPU_RAG_PREFILL_CHUNK_TOKENS", "48"),
            ("TPU_RAG_WINDOW_TOKEN_BUDGET", "96"),
        ):
            monkeypatch.setenv(k, v)
        cfg = AppConfig.from_env()
        assert cfg.engine.interleave_prefill is True
        assert cfg.engine.prefill_chunk_tokens == 48
        assert cfg.engine.window_token_budget == 96
        monkeypatch.setenv("TPU_RAG_INTERLEAVE_PREFILL", "2")
        with pytest.raises(ValueError, match="TPU_RAG_INTERLEAVE_PREFILL"):
            AppConfig.from_env()
        monkeypatch.setenv("TPU_RAG_INTERLEAVE_PREFILL", "1")
        monkeypatch.setenv("TPU_RAG_WINDOW_TOKEN_BUDGET", "-1")
        with pytest.raises(ValueError, match="WINDOW_TOKEN_BUDGET"):
            AppConfig.from_env()
        monkeypatch.setenv("TPU_RAG_WINDOW_TOKEN_BUDGET", "96")
        monkeypatch.setenv("TPU_RAG_PREFILL_CHUNK_TOKENS", "0")
        with pytest.raises(ValueError, match="PREFILL_CHUNK_TOKENS"):
            AppConfig.from_env()
        # cross-field: interleave without the paged arena is rejected
        monkeypatch.setenv("TPU_RAG_PREFILL_CHUNK_TOKENS", "48")
        monkeypatch.setenv("TPU_RAG_KV_PAGED", "0")
        with pytest.raises(ValueError, match="requires kv_paged"):
            AppConfig.from_env()


# ---------------------------------------------------------------------------
# tensor parallel
# ---------------------------------------------------------------------------


class TestChunkedPrefillTP:
    def test_tp2_byte_identity(self, setup):
        """Mixed windows over the HEAD-SHARDED arena: tp=2 interleaved
        streams match tp=1 interleaved and tp=2 phase-separated — the tp
        split must not change a single token of any stream."""
        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        cfg, params = setup
        reqs = [(1, PROMPTS[2], 8), (2, PROMPTS[0], 8)]
        base_tp1 = drain(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=INTER, dtypes=FP32), reqs,
        )
        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        sharded = shard_llama_params(params, ctx)
        base_tp2 = drain(
            ContinuousEngine(
                cfg, sharded, sampling=GREEDY, engine_config=PAGED,
                dtypes=FP32, mesh=ctx,
            ),
            reqs,
        )
        eng = ContinuousEngine(
            cfg, sharded, sampling=GREEDY, engine_config=INTER,
            dtypes=FP32, mesh=ctx,
        )
        inter_tp2 = drain(eng, reqs)
        assert inter_tp2 == base_tp2 == base_tp1
        assert "mixed" in eng.ledger.state()["kinds"], "vacuous tp=2 identity"

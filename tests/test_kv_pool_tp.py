"""Tensor-parallel paged serving (ISSUE 6): the head-sharded block-pool
arena + shard-aware paged kernels over the tp mesh axis.

The load-bearing contract is the acceptance pin: paged tp=2 greedy decode
streams are BYTE-IDENTICAL to both dense tp=2 and paged tp=1 on
mixed-length right-padded batches — the tp split changes only WHERE each
kv head's bytes live (every device holds K/tp heads of every physical
block), never an attended value. Around it: shard_map'd interpret-mode
kernel↔oracle parity under the exact serving partition specs
(ops.attention.paged_partition_specs), block accounting under preemption
at tp=2, the per-device arena gauge, and the construction validation that
replaced PR 5's blanket tp>1 rejection.

Runs on the conftest-forced 8-virtual-device CPU platform (the
``make tp2-smoke`` lane runs exactly this file).
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    MeshConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)
ENG = EngineConfig(prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64)
PAGED = dataclasses.replace(ENG, kv_paged=True, kv_block_size=16)
PROMPTS = [[3, 17, 42, 7, 99], [5, 5, 8], [11] * 12, [2, 9]]

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 (virtual) devices for tp=2"
)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()  # 4 q heads / 2 kv heads: tp=2 tiles exactly
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    oracle = InferenceEngine(
        cfg, params, sampling=GREEDY, engine_config=ENG, dtypes=FP32
    )
    ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
    placed = shard_llama_params(params, ctx)
    return cfg, params, placed, ctx, oracle


def drain(eng, reqs):
    """admit_many + step-to-completion → {rid: tokens}."""
    results = {}
    outs = eng.admit_many([(rid, p, mn, None) for rid, p, mn in reqs])
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
    return results


# ---------------------------------------------------------------------------
# engine parity (THE acceptance pin)
# ---------------------------------------------------------------------------


class TestPagedTpParity:
    def test_tp2_streams_match_dense_tp2_and_paged_tp1(self, setup):
        """Byte-identical greedy streams across paged tp=2 / dense tp=2 /
        paged tp=1 on a mixed-length batch, with zero leaked blocks and
        the arena REALLY head-sharded (K/tp kv heads per device shard)."""
        cfg, params, placed, ctx, oracle = setup
        want = {i: oracle.generate([p])[0] for i, p in enumerate(PROMPTS)}
        reqs = [(i, p, GREEDY.max_new_tokens) for i, p in enumerate(PROMPTS)]

        paged1 = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=PAGED, dtypes=FP32
        )
        assert drain(paged1, reqs) == want
        assert paged1.kv_pool.blocks_in_use() == 0

        dense2 = ContinuousEngine(
            cfg, placed, sampling=GREEDY, engine_config=ENG, dtypes=FP32,
            mesh=ctx,
        )
        assert drain(dense2, reqs) == want

        paged2 = ContinuousEngine(
            cfg, placed, sampling=GREEDY, engine_config=PAGED, dtypes=FP32,
            mesh=ctx,
        )
        shard = paged2._cache[0].addressable_shards[0].data.shape
        assert shard[2] == cfg.num_kv_heads // ctx.tp, shard
        assert drain(paged2, reqs) == want
        assert paged2.kv_pool.blocks_in_use() == 0

    def test_tp2_multi_step_sync_and_mid_flight_admission(self, setup):
        """k>1 sync windows over the sharded arena + a request joining
        mid-generation: same streams as the solo oracle."""
        cfg, _, placed, ctx, oracle = setup
        p1, p2 = PROMPTS[0], PROMPTS[2]
        want1 = oracle.generate([p1])[0]
        want2 = oracle.generate([p2])[0]
        eng = ContinuousEngine(
            cfg, placed, sampling=GREEDY,
            engine_config=dataclasses.replace(PAGED, decode_sync_steps=4),
            dtypes=FP32, mesh=ctx,
        )
        eng.admit(1, p1, GREEDY.max_new_tokens)
        results = {}
        for rid, toks in eng.step():
            results[rid] = toks
        eng.admit(2, p2, GREEDY.max_new_tokens)  # joins mid-flight
        while eng.has_active():
            for rid, toks in eng.step():
                results[rid] = toks
        assert results == {1: want1, 2: want2}
        assert eng.kv_pool.blocks_in_use() == 0

    def test_tp2_int8_arena_matches_dense(self, setup):
        """The _q8 paged kernels shard the same way: int8 arena + sharded
        scale planes on the mesh reproduce the dense int8 stream."""
        cfg, params, placed, ctx, _ = setup
        eng8 = dataclasses.replace(ENG, prompt_buckets=(32,), kv_quant="int8")
        paged8 = dataclasses.replace(eng8, kv_paged=True, kv_block_size=32)
        reqs = [(i, p, 8) for i, p in enumerate(PROMPTS[:2])]
        d = drain(
            ContinuousEngine(
                cfg, params, sampling=GREEDY, engine_config=eng8, dtypes=FP32
            ),
            reqs,
        )
        p = drain(
            ContinuousEngine(
                cfg, placed, sampling=GREEDY, engine_config=paged8,
                dtypes=FP32, mesh=ctx,
            ),
            reqs,
        )
        assert d == p

    def test_tp2_preemption_resumes_with_parity_and_zero_leak(self, setup):
        """Pool exhaustion mid-decode on the SHARDED arena: preemption,
        resubmission, and block accounting are tp-oblivious (the allocator
        is per-row and replicated host-side) — every stream matches the
        solo oracle and the pool drains to zero."""
        cfg, _, placed, ctx, oracle = setup
        want = [oracle.generate([p], max_new_tokens=40)[0] for p in PROMPTS]
        tight = dataclasses.replace(PAGED, kv_pool_blocks=8)
        eng = ContinuousEngine(
            cfg, placed, sampling=GREEDY, engine_config=tight, dtypes=FP32,
            mesh=ctx,
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
            assert outs == want
            assert eng.kv_pool.blocks_in_use() == 0
        finally:
            sched.shutdown()

    def test_per_device_arena_gauge_reports_the_split(self, setup):
        """rag_kv_pool_device_bytes: one child per mesh device, each
        reading exactly arena_global / tp (the head-sharded HBM claim)."""
        cfg, _, placed, ctx, _ = setup
        eng = ContinuousEngine(
            cfg, placed, sampling=GREEDY, engine_config=PAGED, dtypes=FP32,
            mesh=ctx,
        )
        reg = obs_metrics.MetricsRegistry()
        eng.bind_metrics(reg)
        total = sum(p.nbytes for p in eng._cache)
        n_dev = len(list(ctx.mesh.devices.flat))
        # dp=4 × tp=2: every device holds a (K/tp) shard — 1/tp of the
        # GLOBAL arena each (replication across dp does not dilute a
        # device's resident bytes)
        per_dev = {k: v for k, v in eng._arena_device_bytes.items()}
        assert len(per_dev) == n_dev
        assert all(v == total / ctx.tp for v in per_dev.values()), per_dev
        text = reg.render_prometheus()
        assert "rag_kv_pool_device_bytes" in text

    def test_validate_tp_layout_replaces_the_blanket_rejection(self, setup):
        """tp that does not divide the kv-head count fails at construction
        with the head-sharding constraint spelled out; a dividing tp (the
        other tests here) constructs — the old 'does not support tp>1'
        error is gone."""
        cfg, params, _, _, _ = setup
        ctx4 = make_mesh(MeshConfig(dp=2, sp=1, tp=4))
        with pytest.raises(ValueError, match="divisible by"):
            ContinuousEngine(
                cfg, shard_llama_params(params, ctx4), sampling=GREEDY,
                engine_config=PAGED, dtypes=FP32, mesh=ctx4,
            )
        # the config-level validator is the engine's source of truth
        PAGED.validate_tp_layout(2, cfg.num_kv_heads)  # divides: no raise
        with pytest.raises(ValueError, match="kv-head"):
            PAGED.validate_tp_layout(4, cfg.num_kv_heads)
        ENG.validate_tp_layout(4, cfg.num_kv_heads)  # dense: tp-agnostic


# ---------------------------------------------------------------------------
# shard_map'd kernel ↔ oracle parity (interpret mode, the SERVING specs)
# ---------------------------------------------------------------------------


class TestShardedPagedKernelParity:
    """The shard-aware kernels under the exact partition rules serving
    lowers (ops.attention.paged_partition_specs): each shard streams its
    local K/tp head slice of the arena; the stitched output must match the
    unsharded XLA oracle bit-for-near-bit. The TPU lane re-runs compiled;
    interpret mode pins the kernel LOGIC per shard on CPU."""

    def _mesh(self):
        return make_mesh(MeshConfig(dp=4, sp=1, tp=2)).mesh

    def _tables(self, B, MB, bs, kv_len):
        tables = np.zeros((B, MB), np.int32)
        phys = 1
        for b in range(B):
            for j in range(-(-int(kv_len[b]) // bs)):
                tables[b, j] = phys
                phys += 1
        return tables

    def test_sharded_paged_decode_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.attention import (
            paged_decode_attention,
            paged_decode_attention_xla,
            paged_partition_specs,
        )

        rng = np.random.default_rng(0)
        B, H, K, hd, bs, MB = 3, 4, 2, 16, 16, 4
        L, N = 2, 1 + 3 * MB
        ka = jnp.asarray(rng.standard_normal((L, N, K, bs, hd)).astype(np.float32))
        va = jnp.asarray(rng.standard_normal((L, N, K, bs, hd)).astype(np.float32))
        kv_len = np.array([5, 33, 64], np.int32)
        tables = self._tables(B, MB, bs, kv_len)
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
        in_specs, out_spec = paged_partition_specs("decode")
        fn = jax.shard_map(
            lambda q_, k_, v_, t_, l_, lay_: paged_decode_attention(
                q_, k_, v_, t_, l_, lay_, interpret=True
            ),
            mesh=self._mesh(), in_specs=in_specs, out_specs=out_spec,
            check_vma=False,
        )
        for lay in range(L):
            lay1 = jnp.asarray(lay, jnp.int32).reshape(1)
            got = fn(q, ka, va, jnp.asarray(tables), jnp.asarray(kv_len), lay1)
            want = paged_decode_attention_xla(
                q, ka, va, jnp.asarray(tables), jnp.asarray(kv_len), lay1
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5
            )

    def test_sharded_paged_chunk_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.attention import (
            paged_chunk_attention,
            paged_chunk_attention_xla,
            paged_partition_specs,
        )

        rng = np.random.default_rng(1)
        B, S, H, K, hd, bs, MB = 2, 8, 4, 2, 16, 16, 4
        L, N = 2, 1 + 2 * MB
        ka = jnp.asarray(rng.standard_normal((L, N, K, bs, hd)).astype(np.float32))
        va = jnp.asarray(rng.standard_normal((L, N, K, bs, hd)).astype(np.float32))
        kv_len = np.array([20, 41], np.int32)
        wi = kv_len - S
        tables = self._tables(B, MB, bs, kv_len)
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
        in_specs, out_spec = paged_partition_specs("chunk")
        fn = jax.shard_map(
            lambda q_, k_, v_, t_, l_, lay_, wi_: paged_chunk_attention(
                q_, k_, v_, t_, l_, lay_, wi_, bq=4, interpret=True
            ),
            mesh=self._mesh(), in_specs=in_specs, out_specs=out_spec,
            check_vma=False,
        )
        lay1 = jnp.asarray(1, jnp.int32).reshape(1)
        got = fn(
            q, ka, va, jnp.asarray(tables), jnp.asarray(kv_len), lay1,
            jnp.asarray(wi),
        )
        want = paged_chunk_attention_xla(
            q, ka, va, jnp.asarray(tables), jnp.asarray(kv_len), lay1,
            jnp.asarray(wi),
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_sharded_paged_q8_decode_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.attention import (
            paged_decode_attention_q8,
            paged_decode_attention_xla_q8,
            paged_partition_specs,
        )

        rng = np.random.default_rng(2)
        B, H, K, hd, bs, MB = 2, 4, 2, 16, 32, 2
        L, N = 2, 1 + 2 * MB
        ka = rng.integers(-127, 128, (L, N, K, bs, hd)).astype(np.int8)
        va = rng.integers(-127, 128, (L, N, K, bs, hd)).astype(np.int8)
        ks = rng.uniform(0.001, 0.02, (L, N, K, bs)).astype(np.float32)
        vs = rng.uniform(0.001, 0.02, (L, N, K, bs)).astype(np.float32)
        kv_len = np.array([10, 50], np.int32)
        tables = self._tables(B, MB, bs, kv_len)
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
        in_specs, out_spec = paged_partition_specs("decode", q8=True)
        fn = jax.shard_map(
            lambda q_, k_, v_, ks_, vs_, t_, l_, lay_: paged_decode_attention_q8(
                q_, k_, v_, ks_, vs_, t_, l_, lay_, interpret=True
            ),
            mesh=self._mesh(), in_specs=in_specs, out_specs=out_spec,
            check_vma=False,
        )
        lay1 = jnp.asarray(0, jnp.int32).reshape(1)
        args = (
            q, jnp.asarray(ka), jnp.asarray(va), jnp.asarray(ks),
            jnp.asarray(vs), jnp.asarray(tables), jnp.asarray(kv_len), lay1,
        )
        got = fn(*args)
        want = paged_decode_attention_xla_q8(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def test_sharded_paged_q8_chunk_matches_oracle(self):
        """The fused q8 paged chunk kernel (it replaced PR 5's gather
        oracle) under the SERVING partition specs — warm-tier chunked
        prefill is shard-aware like every other paged path."""
        from rag_llm_k8s_tpu.ops.attention import (
            paged_chunk_attention_q8,
            paged_chunk_attention_xla_q8,
            paged_partition_specs,
        )

        rng = np.random.default_rng(3)
        B, S, H, K, hd, bs, MB = 2, 8, 4, 2, 16, 16, 4
        L, N = 2, 1 + 2 * MB
        ka = rng.integers(-127, 128, (L, N, K, bs, hd)).astype(np.int8)
        va = rng.integers(-127, 128, (L, N, K, bs, hd)).astype(np.int8)
        ks = rng.uniform(0.001, 0.02, (L, N, K, bs)).astype(np.float32)
        vs = rng.uniform(0.001, 0.02, (L, N, K, bs)).astype(np.float32)
        kv_len = np.array([20, 41], np.int32)
        wi = kv_len - S
        tables = self._tables(B, MB, bs, kv_len)
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
        in_specs, out_spec = paged_partition_specs("chunk", q8=True)
        fn = jax.shard_map(
            lambda q_, k_, v_, ks_, vs_, t_, l_, lay_, wi_: (
                paged_chunk_attention_q8(
                    q_, k_, v_, ks_, vs_, t_, l_, lay_, wi_, bq=4,
                    interpret=True,
                )
            ),
            mesh=self._mesh(), in_specs=in_specs, out_specs=out_spec,
            check_vma=False,
        )
        lay1 = jnp.asarray(1, jnp.int32).reshape(1)
        args = (
            q, jnp.asarray(ka), jnp.asarray(va), jnp.asarray(ks),
            jnp.asarray(vs), jnp.asarray(tables), jnp.asarray(kv_len), lay1,
            jnp.asarray(wi),
        )
        got = fn(*args)
        want = paged_chunk_attention_xla_q8(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def test_unknown_mode_spec_is_refused(self):
        from rag_llm_k8s_tpu.ops.attention import paged_partition_specs

        # the q8 chunk spec EXISTS since the fused kernel landed
        in_specs, _ = paged_partition_specs("chunk", q8=True)
        assert len(in_specs) == 9
        with pytest.raises(ValueError, match="unknown mode"):
            paged_partition_specs("prefill")

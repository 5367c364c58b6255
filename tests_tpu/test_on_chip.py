"""On-chip correctness: Pallas kernels vs XLA oracles, engine end-to-end.

These are the hardware counterparts of the interpret-mode tests in
``tests/`` — same oracles, real Mosaic compilation, real MXU/VPU numerics.
Parity checks run under ``jax.default_matmul_precision("highest")`` so both
sides accumulate in true fp32 (at default precision the MXU rounds inputs
to bf16 and the two implementations differ by rounding noise, not bugs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)


class TestKnnKernel:
    def test_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.knn import knn_topk_pallas, knn_topk_xla

        rng = np.random.RandomState(0)
        N, D, Q, k = 2048, 1024, 4, 5
        emb = jnp.asarray(rng.randn(N, D).astype(np.float32))
        emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
        queries = emb[:Q] + 0.01 * jnp.asarray(rng.randn(Q, D).astype(np.float32))
        norms = jnp.sum(emb * emb, axis=1)[None, :]

        with jax.default_matmul_precision("highest"):
            v_got, i_got = knn_topk_pallas(queries, emb, norms, k=k)
            v_ref, i_ref = knn_topk_xla(queries, emb, norms, k=k)
        np.testing.assert_array_equal(np.asarray(i_got), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(v_got), np.asarray(v_ref), rtol=1e-4, atol=1e-5)


class TestAttentionKernels:
    def test_flash_prefill_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.attention import attention_xla, flash_attention

        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        B, S, H, K, hd = 2, 512, 8, 2, 128
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        kv_start = jnp.array([0, 100], jnp.int32)
        with jax.default_matmul_precision("highest"):
            got = flash_attention(q, k, v, kv_start=kv_start, causal=True)
            want = attention_xla(q, k, v, kv_start=kv_start, causal=True)
        valid = (jnp.arange(S)[None, :] >= kv_start[:, None])[:, :, None, None]
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, got, 0)),
            np.asarray(jnp.where(valid, want, 0)),
            rtol=2e-4, atol=2e-4,
        )

    def test_windowed_prefill_takes_its_window_in_one_step(self):
        """72 query heads over 8 of 128 under a window of 512 in a 4096
        bucket (``flash_window_step``: 128 queries against one slice of 640
        keys), bf16 as served: against the oracle and against the block walk
        (``bk`` names the walk) on rows with a left pad inside the first
        window, inside a later query block and a frontier short of the
        bucket; NaN outside the live slots reaches no output."""
        from rag_llm_k8s_tpu.ops.attention import attention_xla, flash_attention, flash_window_step

        B, S, H, K, hd, W = 3, 4096, 72, 8, 128, 512
        assert flash_window_step(S, H // K, hd, hd, W) == (128, 640)
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.bfloat16)
        kv_start = jnp.array([0, 150, 1000], jnp.int32)
        kv_len = jnp.array([S, S, 3000], jnp.int32)
        pos = jnp.arange(S)[None, :, None, None]
        ok = (pos >= kv_start[:, None, None, None]) & (pos < kv_len[:, None, None, None])
        kn, vn = jnp.where(ok, k, jnp.nan), jnp.where(ok, v, jnp.nan)
        got = np.asarray(flash_attention(q, kn, vn, kv_start, kv_len, window=W), np.float32)
        walk = np.asarray(flash_attention(q, kn, vn, kv_start, kv_len, window=W, bk=512), np.float32)
        assert np.isfinite(got).all()
        # bf16 probabilities into the PV matmul and a bf16 result on both kernels
        np.testing.assert_allclose(got, walk, rtol=2e-2, atol=2e-2)
        # the oracle a row and two KV heads at a time (its scores are [heads, S, S] float32)
        f32 = lambda x: jnp.where(ok, x, 0).astype(jnp.float32)  # noqa: E731
        for b in range(B):
            with jax.default_matmul_precision("highest"):
                want = attention_xla(q[b:b + 1, :, :18].astype(jnp.float32), f32(k)[b:b + 1, :, :2],
                                     f32(v)[b:b + 1, :, :2], kv_start[b:b + 1], kv_len[b:b + 1], window=W)
            np.testing.assert_allclose(got[b:b + 1, :, :18], np.asarray(want), rtol=3e-2, atol=3e-2)
        assert not got[1, :150].any() and not got[2, :1000].any()

    def test_decode_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.attention import decode_attention, decode_attention_xla

        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        L, B, H, K, T, hd = 2, 4, 8, 2, 640, 128
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
        kv_start = jnp.array([0, 17, 300, 0], jnp.int32)
        kv_len = jnp.array([T, 400, 301, 128], jnp.int32)
        for lay in range(L):
            with jax.default_matmul_precision("highest"):
                got = decode_attention(q, kc, vc, kv_start, kv_len, jnp.int32(lay))
                want = decode_attention_xla(q, kc, vc, kv_start, kv_len, jnp.int32(lay))
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    @pytest.mark.parametrize("name", [
        "decode_attention_q8[8,8,4,128]", "decode_attention[4,2,4,128]",
        "mla_decode_attention[8,128,512]", "mla_decode_attention[8,64,512]"])
    def test_decode_kernels_at_the_serving_shapes(self, name):
        """The benchmark's four batched cells' decode calls (bf16 / int8 KV,
        T = 4352, the cells' left pads): ``chip_smoke.py``'s cases, one each."""
        import chip_smoke

        assert set(chip_smoke.SERVING_DECODE) >= {name}
        (run, rtol, atol), = [(r, rt, at) for n, r, rt, at in chip_smoke.kernel_cases(0) if n == name]
        got, want = run()
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=rtol, atol=atol)

    def test_decode_q8_matches_oracle_on_mosaic(self):
        """int8-KV decode kernel on real Mosaic vs its XLA oracle — the
        epilogue-scaled dequant (scores x k_scale, probs x v_scale) must
        reproduce the dense math at quantization tolerance."""
        from rag_llm_k8s_tpu.ops.attention import (
            decode_attention_q8,
            decode_attention_xla_q8,
            quantize_kv,
        )

        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        L, B, H, K, T, hd = 2, 4, 8, 2, 640, 128
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
        kq, kscale = quantize_kv(kc)
        vq, vscale = quantize_kv(vc)
        kv_start = jnp.array([0, 17, 300, 0], jnp.int32)
        kv_len = jnp.array([T, 400, 301, 128], jnp.int32)
        for lay in range(L):
            with jax.default_matmul_precision("highest"):
                got = decode_attention_q8(
                    q, kq, vq, kscale, vscale, kv_start, kv_len, jnp.int32(lay)
                )
                want = decode_attention_xla_q8(
                    q, kq, vq, kscale, vscale, kv_start, kv_len, jnp.int32(lay)
                )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=3e-3, atol=3e-3
            )

    def test_engine_int8_kv_generates(self):
        """One-shot engine with kv_quant=int8 end-to-end on chip: greedy ids
        must match the bf16-cache engine exactly on the tiny model."""
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        cfg = LlamaConfig.tiny()
        DT = DTypePolicy()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, DT)
        outs = {}
        for kvq in ("bf16", "int8"):
            eng = InferenceEngine(
                cfg, params,
                sampling=SamplingConfig(do_sample=False, max_new_tokens=16),
                engine_config=EngineConfig(
                    prompt_buckets=(128,), max_batch_size=2, kv_quant=kvq
                ),
                dtypes=DT,
            )
            outs[kvq] = eng.generate([[cfg.bos_token_id, 5, 7, 9], [cfg.bos_token_id, 3]])
        assert outs["bf16"] == outs["int8"]

    def test_chunk_prefill_matches_oracle(self):
        from rag_llm_k8s_tpu.ops.attention import (
            chunk_attention_xla,
            chunk_prefill_attention,
        )

        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        L, B, S, H, K, T, hd = 2, 2, 256, 8, 2, 1024, 128
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
        kv_start = jnp.array([0, 40], jnp.int32)
        for wi in (0, 256, T - S):
            kv_len = jnp.full((B,), wi + S, jnp.int32)
            for lay in range(L):
                with jax.default_matmul_precision("highest"):
                    got = chunk_prefill_attention(
                        q, kc, vc, kv_start, kv_len, jnp.int32(lay), jnp.int32(wi)
                    )
                    want = chunk_attention_xla(
                        q, kc, vc, kv_start, kv_len, jnp.int32(lay), jnp.int32(wi)
                    )
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
                )



class TestEngineOnChip:
    def test_generate_pallas_vs_xla_logits_path(self):
        """Full model prefill + one decode step, Pallas vs XLA oracle, at a
        real (1B-proxy) layer shape."""
        from rag_llm_k8s_tpu.models.llama import (
            LlamaModel,
            init_llama_params,
            make_kv_cache,
            mask_window,
        )

        fp32 = DTypePolicy.fp32()
        cfg = LlamaConfig.llama_3_2_1b()
        cfg = type(cfg)(**{**cfg.__dict__, "num_layers": 2, "vocab_size": 2048})
        params = init_llama_params(jax.random.PRNGKey(0), cfg, fp32)
        B, S, T = 2, 256, 384
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 3, cfg.vocab_size)
        pad_mask = jnp.ones((B, S), jnp.int32).at[1, :100].set(0)
        kv_start, _ = mask_window(pad_mask)
        kv_len = jnp.full((B,), S, jnp.int32)
        pos = jnp.clip(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
        real_len = jnp.sum(pad_mask, axis=-1)

        def run_once(impl):
            with jax.default_matmul_precision("highest"):
                model = LlamaModel(cfg, fp32, attn_impl=impl)
                cache = make_kv_cache(cfg, B, T, jnp.float32)
                plog, cache = jax.jit(
                    lambda p, t: model.apply(
                        {"params": p}, t, pos, cache, kv_start, kv_len, jnp.int32(0)
                    )
                )(params, tokens)
                dlog, _ = jax.jit(
                    lambda p, t, c: model.apply(
                        {"params": p}, t, real_len[:, None].astype(jnp.int32), c,
                        kv_start, jnp.full((B,), S + 1, jnp.int32), jnp.int32(S),
                    )
                )(params, tokens[:, -1:], cache)
            return np.asarray(plog), np.asarray(dlog)

        p_ref, d_ref = run_once("xla")
        # a non-finite ORACLE on the chip is a failure like any other
        assert np.isfinite(p_ref).all() and np.isfinite(d_ref).all()
        p_got, d_got = run_once("pallas")
        valid = np.asarray(pad_mask).astype(bool)[:, :, None]
        np.testing.assert_allclose(
            np.where(valid, p_got, 0), np.where(valid, p_ref, 0), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(d_got, d_ref, rtol=1e-4, atol=1e-4)

    def test_engine_generate_smoke(self):
        """The real serving engine generates on hardware through the Pallas
        path: deterministic greedy, correct lengths, EOS-free tail."""
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        cfg = LlamaConfig.tiny(vocab_size=512)
        cfg = type(cfg)(**{**cfg.__dict__, "num_heads": 8, "num_kv_heads": 8, "head_dim": 64})
        dtypes = DTypePolicy()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, dtypes)
        eng = InferenceEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
            engine_config=EngineConfig(prompt_buckets=(16,), max_batch_size=4),
            dtypes=dtypes,
        )
        prompts = [[3, 5, 7], [11, 13, 17, 19, 23]]
        out1 = eng.generate(prompts)
        out2 = eng.generate(prompts)
        assert out1 == out2  # greedy determinism through the kernel path
        assert all(len(o) <= 8 for o in out1)
        assert all(t not in cfg.eos_token_ids for o in out1 for t in o)


class TestContinuousOnChip:
    def test_mid_flight_admission_parity(self):
        """Slot-based decode on real hardware: scatter cache writes + fused
        decode kernel produce the one-shot engine's greedy tokens, including
        for a request admitted mid-generation."""
        from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        DT = DTypePolicy()  # production bf16 policy
        cfg = LlamaConfig.tiny()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, DT)
        greedy = SamplingConfig(do_sample=False, max_new_tokens=8)
        ecfg = EngineConfig(prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64)
        oracle = InferenceEngine(cfg, params, sampling=greedy, engine_config=ecfg, dtypes=DT)
        eng = ContinuousEngine(cfg, params, sampling=greedy, engine_config=ecfg, dtypes=DT)

        p1, p2 = [3, 17, 42, 7, 99], [5, 5, 8]
        want1 = oracle.generate([p1])[0]
        want2 = oracle.generate([p2])[0]
        eng.admit(1, p1, greedy.max_new_tokens)
        results = {}
        for _ in range(3):
            for rid, toks in eng.step():
                results[rid] = toks
        eng.admit(2, p2, greedy.max_new_tokens)
        while eng.has_active():
            for rid, toks in eng.step():
                results[rid] = toks
        assert results[1] == want1
        assert results[2] == want2

    def test_continuous_int8_kv_parity_on_chip(self):
        """Continuous batching over an int8 KV cache on real hardware:
        quantize-on-write scatter + the q8 decode kernel reproduce the
        one-shot int8-KV engine's greedy ids."""
        from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        DT = DTypePolicy()
        cfg = LlamaConfig.tiny()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, DT)
        greedy = SamplingConfig(do_sample=False, max_new_tokens=8)
        ecfg = EngineConfig(
            prompt_buckets=(16,), max_batch_size=2, max_seq_len=64,
            kv_quant="int8",
        )
        oracle = InferenceEngine(cfg, params, sampling=greedy, engine_config=ecfg, dtypes=DT)
        want = oracle.generate([[3, 17, 42, 7]])[0]
        eng = ContinuousEngine(cfg, params, sampling=greedy, engine_config=ecfg, dtypes=DT)
        assert eng._cache[0].dtype == jnp.int8
        eng.admit(1, [3, 17, 42, 7], greedy.max_new_tokens)
        results = {}
        while eng.has_active():
            for rid, toks in eng.step():
                results[rid] = toks
        assert results[1] == want


class Test8BShapesOnChip:
    def test_single_layer_and_lm_head_microbench(self):
        """True 8B geometry on ONE chip, as far as 16 GB HBM allows: a
        single stacked decoder layer + embed/lm_head (~2.5 GB bf16 weights)
        runs prefill-4096 and fused-kernel decode. Whole-model 8B bf16
        weights are ~16 GB — at or past a single v5e's HBM — so serving 8B
        is a tp>=2 deployment by budget: tp=4 holds ~4 GB weights +
        ~2.2 GB KV (B8 T4352) + activations per chip. Numbers recorded in
        docs/8B.md."""
        import dataclasses
        import time

        from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        cfg = dataclasses.replace(LlamaConfig.llama_3_1_8b(), num_layers=1)
        DT = DTypePolicy()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, DT)
        eng = InferenceEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=32),
            engine_config=EngineConfig(prompt_buckets=(4096,), max_batch_size=1),
            dtypes=DT,
        )
        prompt = list(range(5, 4000))
        t0 = time.monotonic()
        eng.warmup(batch_sizes=(1,), buckets=(4096,), max_new_tokens=32)
        compile_s = time.monotonic() - t0
        t0 = time.monotonic()
        out = eng.generate([prompt], max_new_tokens=32)[0]
        e2e_s = time.monotonic() - t0
        assert len(out) == 32
        # steady-state decode: amortize a second call (cache warm)
        t0 = time.monotonic()
        eng.generate([prompt], max_new_tokens=32)
        e2e2_s = time.monotonic() - t0
        print(
            f"\n8B-L1 on chip: compile {compile_s:.1f}s, "
            f"prefill4096+32tok {e2e_s * 1e3:.0f} ms (warm {e2e2_s * 1e3:.0f} ms)"
        )

    def test_full_depth_8b_int8_serves_on_one_chip(self):
        """The WHOLE 32-layer 8B model on ONE v5e chip via weight-only int8
        (~8.0 GiB weights vs ~15 GiB bf16): builds the quantized-layout tree
        at true shapes, runs prefill + greedy decode through the production
        engine, and records decode throughput. This is the artifact behind
        docs/8B.md's single-chip serving claim — the reference's actual
        model scale (download_model.py:5) executing end-to-end on hardware
        the bf16 layout cannot fit."""
        import time

        import jax.numpy as jnp

        from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.models.llama import (
            init_llama_params,
            quantize_llama_params,
        )

        cfg = LlamaConfig.llama_3_1_8b()
        DT = DTypePolicy()
        shapes = jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), cfg, DT))
        qshapes = jax.eval_shape(quantize_llama_params, shapes)
        params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), qshapes)
        weight_gib = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
        ) / 2**30
        assert weight_gib < 9.0, f"int8 8B should be ~8 GiB, got {weight_gib:.2f}"

        B, S, NEW = 8, 128, 64
        eng = InferenceEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=NEW),
            engine_config=EngineConfig(
                prompt_buckets=(S,), max_batch_size=B, weight_quant="int8"
            ),
            dtypes=DT,
        )
        assert eng.model.quantized  # pass-through: tree already int8
        prompts = [[cfg.bos_token_id] * S] * B
        t0 = time.monotonic()
        eng.warmup(batch_sizes=(B,), buckets=(S,))
        compile_s = time.monotonic() - t0
        outs = eng.generate(prompts)
        assert all(len(o) == NEW for o in outs)
        t0 = time.monotonic()
        outs = eng.generate(prompts)
        tok_s = sum(len(o) for o in outs) / (time.monotonic() - t0)
        print(
            f"\n8B int8 FULL DEPTH on one chip: {weight_gib:.2f} GiB weights, "
            f"compile {compile_s:.1f}s, decode {tok_s:.0f} tok/s (B={B})"
        )
        assert tok_s > 100  # sanity floor; measured ~610 at B=8


class TestSingleFetchOnChip:
    def test_fused_rag_generate_matches_host_assembly(self):
        """Hardware counterpart of tests/test_fused_rag.py: device-side
        prompt assembly (generate_rag) must emit the same greedy tokens as
        the host-assembled prompt through the SAME engine, on real Mosaic
        kernels, and cost exactly ONE device->host fetch."""
        import numpy as np

        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.index.store import VectorStore
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        DT = DTypePolicy()
        cfg = LlamaConfig.tiny(vocab_size=512)
        params = init_llama_params(jax.random.PRNGKey(0), cfg, DT)
        eng = InferenceEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
            engine_config=EngineConfig(prompt_buckets=(256,), max_batch_size=2),
            dtypes=DT,
        )

        def seg_ids(md):
            return [3 + (b % 500) for b in (
                f"Document '{md['filename']}' (chunk {md['chunk_id']}): "
                f"{md['text']}\n\n"
            ).encode()]

        store = VectorStore(dim=8)
        rng = np.random.default_rng(0)
        texts = ["alpha beta gamma", "delta epsilon", "zeta eta"]
        store.add(
            [rng.standard_normal(8).astype(np.float32) for _ in texts],
            [{"filename": "f.pdf", "chunk_id": i, "text": t} for i, t in enumerate(texts)],
        )
        store.attach_token_source(seg_ids)
        toks_dev, lens_dev = store.token_snapshot()

        a = [cfg.bos_token_id] + [3 + (b % 500) for b in b"SYS\n\nContext: "]
        b = [3 + (x % 500) for x in b"\n\nUser: what?\n\nChatbot:"]
        d = np.linspace(0.1, 0.5, 3, dtype=np.float32)
        packed = jnp.asarray(
            np.concatenate([d, np.asarray([2, 0, 1], np.float32)])[None, :]
        )
        host_ids = list(a)
        for i in (2, 0, 1):
            host_ids += seg_ids(store._metadata[i])
        host_ids += b
        assert len(host_ids) <= 256
        want = eng.generate([host_ids])[0]
        got = eng.generate_rag(
            np.asarray(a, np.int32), np.asarray(b, np.int32),
            packed, toks_dev, lens_dev, n_chunks=3,
        )
        assert got == want


class TestShortcutBlockKernelsOnChip:
    """The latent-attention sparse-expert family's kernels at the widths of
    its shortcut-connected configuration (64 heads of 128 nope + 64 rope
    against value 128; experts 6144 <-> 2048), against their XLA forms."""

    def test_grouped_matmul_at_6144_and_2048(self):
        from rag_llm_k8s_tpu.ops import moe

        rng = np.random.default_rng(0)
        sizes = jnp.asarray([300, 0, 5, 130, 0, 0, 77, 0, 0, 0, 0, 0, 0, 0, 0, 0], jnp.int32)  # 16 held
        for k, n in ((6144, 2048), (2048, 6144)):  # gate / up, then down
            lhs = jnp.asarray(rng.standard_normal((512, k)), jnp.bfloat16)
            rhs = jnp.asarray(rng.standard_normal((2, 16, k, n)) / np.sqrt(k), jnp.bfloat16)
            got, stored, _ = moe.grouped_matmul(lhs, rhs, sizes, jnp.int32(1))
            want, _, _ = moe._grouped_xla(lhs, rhs, sizes, jnp.int32(1))
            rows = int(sizes.sum())
            assert int(stored) == rows
            np.testing.assert_allclose(np.asarray(got[:rows], np.float32), np.asarray(want[:rows], np.float32),
                                       rtol=2e-2, atol=2e-2)  # both accumulate in float32: bf16's last place

    def test_grouped_matmul_where_every_expert_is_held(self):
        """2048 <-> 1536 over 64 groups of one row's prefill: ``grouped_blocks``
        gives 256-row tiles and the whole k, a tile spans several groups."""
        from rag_llm_k8s_tpu.ops import moe

        rng = np.random.default_rng(2)
        sizes = rng.multinomial(12000, rng.dirichlet(np.full(64, 2.0))).astype(np.int32)
        sizes[[3, 40]] = 0
        rows = int(sizes.sum())
        for k, n in ((2048, 1536), (1536, 2048)):
            assert moe.grouped_blocks(16384, 64, k, n, 2)[:2] == (256, k)
            lhs = jnp.asarray(rng.standard_normal((16384, k)), jnp.bfloat16)
            rhs = jnp.asarray(rng.standard_normal((2, 64, k, n)) / np.sqrt(k), jnp.bfloat16)
            got, stored, tile_rows = moe.grouped_matmul(lhs, rhs, jnp.asarray(sizes), jnp.int32(1))
            want, _, _ = moe._grouped_xla(lhs, rhs, jnp.asarray(sizes), jnp.int32(1))
            assert int(stored) == rows and rows <= int(tile_rows) <= rows + 64 * 256
            np.testing.assert_allclose(np.asarray(got[:rows], np.float32), np.asarray(want[:rows], np.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_mla_kernels_at_64_heads(self):
        from rag_llm_k8s_tpu.ops import mla

        rng = np.random.default_rng(1)
        B, S, H, C, R, dn, dv = 2, 1024, 64, 512, 64, 128, 128
        f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
        q_nope, q_rope, c_kv, k_rope = f(B, S, H, dn), f(B, S, H, R), f(B, S, C), f(B, S, R)
        w = f(C, H, dn + dv) / np.sqrt(C)
        kv_start, kv_len = jnp.asarray([0, 402], jnp.int32), jnp.asarray([S, S - 3], jnp.int32)
        scale = 192 ** -0.5
        with jax.default_matmul_precision("highest"):
            kv = jnp.einsum("bsc,chd->bshd", c_kv, w)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None], (B, S, H, R))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            want = mla.mla_prefill_attention_xla(q, k, kv[..., dn:], kv_start, kv_len, scale=scale)
            got = mla.mla_flash_attention(q, k, kv[..., dn:], kv_start, kv_len, scale=scale)
            live = (jnp.arange(S)[None, :] >= kv_start[:, None])[:, :, None, None]
            np.testing.assert_allclose(np.asarray(jnp.where(live, got, 0)), np.asarray(jnp.where(live, want, 0)),
                                       rtol=2e-4, atol=2e-4)
            q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w[..., :dn])
            o_lat = mla.latent_attention_xla(q_lat, q_rope, c_kv[None], k_rope[None], kv_start, kv_len,
                                             jnp.int32(0), jnp.int32(0), scale=scale)
            last = S - 4
            one = mla.mla_decode_attention(
                q_lat[:, last:last + 1], q_rope[:, last:last + 1], c_kv[None], k_rope[None], kv_start,
                jnp.full((B,), last + 1, jnp.int32), jnp.int32(0), scale=scale)
        np.testing.assert_allclose(np.asarray(one[:, 0]), np.asarray(o_lat[:, last]), rtol=2e-4, atol=2e-4)


class TestRouterKernelOnChip:
    @pytest.mark.parametrize("name", [
        "route[32768,256] top-8 of 4 of 8 groups", "route[32768,768] top-12, softmax", "route[32768,256] top-10"])
    def test_route_kernel_at_the_serving_shapes(self, name):
        """``ops/moe.py route`` at the three sparse-expert cells' prefill
        shapes: the kernel's experts are the jnp body's, tied rows and the
        ``-inf`` tail included, its weights the same to float32 rounding
        (``chip_smoke.py phase_route``'s checks, one case each; it raises
        where they differ)."""
        import chip_smoke

        chip_smoke.phase_route(0, {name: chip_smoke.SERVING_ROUTE[name]})


class TestDeltaRuleKernelOnChip:
    def test_the_kernel_is_the_xla_chunk_form_at_the_served_shape(self):
        """``ops/delta_rule.py``'s kernel against the XLA chunk form at the
        highest matmul precision, at the shape ``kimi-linear-ep16.solo``
        prefills: one row of a 4096 bucket, 3400 live positions behind left
        pads, 32 heads of 128, ``v`` in bfloat16, from a state that is not
        zero. Interpret mode cannot see the MXU's passes; this can."""
        from rag_llm_k8s_tpu.ops import delta_rule as dr

        B, S, H, D, live = 1, 4096, 32, 128, 3400
        ks = jax.random.split(jax.random.PRNGKey(50), 6)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, H, D))
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (B, S, H, D)).astype(jnp.bfloat16)
        real = jnp.arange(S) >= S - live
        # alpha log-uniform from 1e-3 to 0.9999 a channel: a memory of one token to one of thousands
        g = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, D), minval=np.log(1e-4), maxval=np.log(6.9)))
        g = jnp.where(real[None, :, None, None], g, 0.0)
        beta = jnp.where(real[None, :, None], jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H))), 0.0)
        s0 = 0.1 * jax.random.normal(ks[5], (B, H, D, D))
        first = jnp.int32((S - live) // dr.CHUNK)
        with jax.default_matmul_precision("highest"):
            want_o, want_s = jax.jit(lambda *a: dr.delta_rule_chunked_xla(*a, first_chunk=first))(q, k, v, g, beta, s0)
            got_o, got_s = dr.delta_rule_chunked(q, k, v, g, beta, s0, first_chunk=first, impl="pallas")
        assert not np.asarray(got_o[:, :first * dr.CHUNK]).any()  # the skipped chunks' rows are zeros
        err_o = float(jnp.abs(got_o - want_o).max())
        err_s = float(jnp.abs(got_s - want_s).max())
        print(f"delta_rule_chunked on the chip: |o| error {err_o:.3g} (of {float(jnp.abs(want_o).max()):.3g}), "
              f"state error {err_s:.3g} (of {float(jnp.abs(want_s).max()):.3g})")
        assert err_o <= 2e-5 and err_s <= 2e-5


class TestBlockWindowKernelOnChip:
    def test_the_prefill_kernel_is_the_dense_form_at_the_served_shape(self):
        """``ops/block_window.py window_summary_flash_attention`` at the shape
        ``evabyte-pp4.closed8`` prefills (one row of the 20480 bucket, 32 heads
        of 128, windows of 2048 in chunks of 16, bf16, 35 live blocks of 512):
        the window's part of a query block is ONE step there
        (``window_summary_plan``). Against the float32 dense form a head at a
        time (its scores are ``[20480, 21760]``); NaN in the summaries no live
        query may see reaches nothing."""
        from rag_llm_k8s_tpu.ops import block_window as bw

        N, S, hd, W, C, live = 32, 20480, 128, 2048, 16, 35 * 512
        assert bw.window_summary_plan(S, W, C, hd) == (512, 512, True)
        ks = jax.random.split(jax.random.PRNGKey(54), 5)
        q, k, v = (jax.random.normal(key, (N, S, hd), jnp.bfloat16) for key in ks[:3])
        mu, phi = (jax.random.normal(key, (N, hd), jnp.bfloat16) * 1.5 / hd ** 0.5 for key in ks[3:])
        sk, sv = bw.pool_chunks(k, v, mu, phi, C, "pallas")
        dead = jnp.arange(S // C)[None, :, None] >= (live - 1) // W * (W // C)
        got = bw.window_summary_flash_attention(q, k, v, jnp.where(dead, jnp.nan, sk), jnp.where(dead, jnp.nan, sv),
                                                live, window=W, chunk=C)
        got = np.asarray(got[:, :live], np.float32)
        assert np.isfinite(got).all()
        f32 = lambda x, h: x[h:h + 1].astype(jnp.float32)  # noqa: E731
        for h in (0, N - 1):
            with jax.default_matmul_precision("highest"):
                want = bw.window_summary_attention_xla(*(f32(x, h) for x in (q, k, v, sk, sv)), window=W, chunk=C)
            err = float(np.abs(got[h] - np.asarray(want)[0, :live]).max())
            print(f"window_summary_flash_attention on the chip, head {h}: |o| error {err:.3g}")
            # bf16 probabilities into the PV matmul and a bf16 result
            np.testing.assert_allclose(got[h], np.asarray(want)[0, :live], rtol=3e-2, atol=3e-2)


class TestExecutableStoreOnChip:
    def test_a_second_boot_loads_every_declared_executable(self, tmp_path):
        """Boot a tiny fused-RAG service twice on one compile cache directory
        (fresh engine, encoder and service: nothing of the first boot in
        memory). The second boot traces nothing (every declared build reads
        ``stored``: Mosaic kernels, donated caches and all come back from
        ``core/compile_cache.py``'s store), answers token-equal, counts the same
        attention kernels, and one program lowered again hashes to what its
        manifest recorded."""
        import os

        from jax._src import compilation_cache

        from rag_llm_k8s_tpu.core import compile_cache
        from rag_llm_k8s_tpu.core.config import AppConfig, EncoderConfig
        from rag_llm_k8s_tpu.engine.batching import BatchScheduler
        from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine
        from rag_llm_k8s_tpu.index.store import VectorStore
        from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
        from rag_llm_k8s_tpu.models.llama import init_llama_params
        from rag_llm_k8s_tpu.obs import tracing
        from rag_llm_k8s_tpu.server.app import RagService, create_app

        class ByteTokenizer:
            def encode(self, text):
                return [b + 3 for b in text.encode("utf-8")]

            def decode(self, ids, skip_special_tokens=True):
                return bytes((i - 3) % 256 for i in ids if i >= 3).decode("utf-8", "replace")

        dtypes = DTypePolicy()
        # heads of 128 on both models: the widths the kernels serve (Mosaic
        # refuses the decode walk at a head of 64; compiled for a described
        # v5e before the first chip run)
        llama_cfg = LlamaConfig.tiny(vocab_size=512)
        llama_cfg = type(llama_cfg)(**{
            **llama_cfg.__dict__, "num_heads": 8, "num_kv_heads": 2, "head_dim": 128,
            "hidden_size": 1024, "intermediate_size": 2048, "max_seq_len": 1024})
        enc_cfg = EncoderConfig(
            vocab_size=300, hidden_size=512, intermediate_size=1024, num_layers=2, num_heads=4,
            max_position_embeddings=128, embed_dim=512, max_encode_len=64)
        llama_params = init_llama_params(jax.random.PRNGKey(0), llama_cfg, dtypes)
        enc_params = init_encoder_params(jax.random.PRNGKey(1), enc_cfg, dtypes)
        texts = ["alpha beta gamma", "delta epsilon", "zeta eta theta"]

        def gained(before, now):
            return {k: n - before.get(k, 0) for k, n in now.items() if n != before.get(k, 0)}

        def boot():
            engine = InferenceEngine(
                llama_cfg, llama_params,
                sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
                engine_config=EngineConfig(prompt_buckets=(128, 256), max_batch_size=4,
                                           max_seq_len=512, rag_fused=True),
                dtypes=dtypes)
            encoder = EncoderRunner(enc_cfg, enc_params, dtypes=dtypes, length_buckets=(32,),
                                    max_batch=4)
            store = VectorStore(dim=enc_cfg.hidden_size)
            svc = RagService(
                AppConfig(model=llama_cfg, encoder=enc_cfg, system_message="SYS"), engine,
                ByteTokenizer(), encoder, ByteTokenizer(), store,
                scheduler=BatchScheduler(engine, max_wait_ms=25.0))
            events, kernels = tracing.compile_census()[1], tracing.kernel_builds()
            vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
            store.add(list(vecs), [{"filename": "f", "chunk_id": i, "text": t}
                                   for i, t in enumerate(texts)])
            svc.warmup()
            try:
                client = create_app(svc).test_client()
                answers = []
                for prompt in ("what is alpha?", "and zeta?"):
                    r = client.post("/generate", json={"prompt": prompt})
                    assert r.status_code == 200, r.get_json()
                    answers.append(r.get_json()["generated_text"])
            finally:
                svc.shutdown()
            declared = {k: n for k, n in gained(events, tracing.compile_census()[1]).items()
                        if k[0] != "undeclared"}
            return engine, answers, declared, gained(kernels, tracing.kernel_builds())

        was = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        compilation_cache.reset_cache()
        try:
            _, cold_answers, cold, cold_kernels = boot()
            assert cold and not [k for k in cold if k[1] == "stored"]
            store_dir = compile_cache.store_dir()
            names = [n for n in os.listdir(store_dir) if n.endswith(".rexe")]
            assert len(names) == sum(cold.values())  # every declared executable was kept
            engine, warm_answers, warm, warm_kernels = boot()
            assert {k[1] for k in warm} == {"stored"}, warm
            assert sum(warm.values()) == sum(cold.values())
            assert warm_answers == cold_answers and warm_kernels == cold_kernels
            assert [n for n in os.listdir(store_dir) if n.endswith(".rexe")] == names
            # the key still covers its program: lower one again, by hand
            key = next(k for k in engine._compiled if k[3] is None)
            jitted, avals = engine._build_generate(*key)
            entry = compile_cache.entry_for("generate", key, avals, engine._build_identity)
            manifest = compile_cache.read_manifest(entry.path)
            assert manifest["lowered_sha256"] == compile_cache.lowered_text_sha256(
                jitted.trace(*avals).lower())
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
            compilation_cache.reset_cache()

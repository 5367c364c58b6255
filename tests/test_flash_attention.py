"""Flash-attention kernel numerics (interpret mode on CPU) vs dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.ops.attention import attention_xla, flash_attention


def _problem(seed, B=2, S=256, H=4, K=2, hd=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _problem(0)
        got = flash_attention(q, k, v, causal=causal, bq=64, bk=64, interpret=True)
        want = attention_xla(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_left_pad_window(self):
        """kv_start models the engine's left-padded rows; valid rows match."""
        q, k, v = _problem(1)
        B, S = q.shape[:2]
        kv_start = jnp.array([0, 37], jnp.int32)
        got = flash_attention(q, k, v, kv_start=kv_start, causal=True, bq=64, bk=64, interpret=True)
        want = attention_xla(q, k, v, kv_start=kv_start, causal=True)
        valid = (jnp.arange(S)[None, :] >= kv_start[:, None])[:, :, None, None]
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, got, 0)),
            np.asarray(jnp.where(valid, want, 0)),
            rtol=2e-4,
            atol=2e-5,
        )

    def test_kv_len_frontier(self):
        q, k, v = _problem(2)
        kv_len = jnp.array([256, 150], jnp.int32)
        got = flash_attention(q, k, v, kv_len=kv_len, causal=False, bq=64, bk=64, interpret=True)
        want = attention_xla(q, k, v, kv_len=kv_len, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_gqa_head_mapping(self):
        q, k, v = _problem(3, H=8, K=2)
        got = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
        want = attention_xla(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_rectangular_blocks(self):
        q, k, v = _problem(4, S=128)
        got = flash_attention(q, k, v, causal=True, bq=32, bk=128, interpret=True)
        want = attention_xla(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


class TestDecodeAttention:
    """Fused decode kernel (interpret mode) vs dense oracle."""

    def _problem(self, seed, B=2, H=8, K=2, T=256, hd=64, L=3, dtype=jnp.float32):
        from rag_llm_k8s_tpu.ops.attention import decode_attention, decode_attention_xla

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, 1, H, hd), dtype)
        k_cache = jax.random.normal(ks[1], (L, B, K, T, hd), dtype)
        v_cache = jax.random.normal(ks[2], (L, B, K, T, hd), dtype)
        return q, k_cache, v_cache, decode_attention, decode_attention_xla

    def test_matches_oracle_per_layer(self):
        """Layer indirection: the kernel must read exactly layer ``lay``'s
        slice of the stacked cache (scalar-prefetched block indexing)."""
        q, kc, vc, kernel, oracle = self._problem(0)
        T = kc.shape[3]
        kv_start = jnp.array([0, 37], jnp.int32)
        kv_len = jnp.array([T, 150], jnp.int32)
        for lay in range(kc.shape[0]):
            got = kernel(q, kc, vc, kv_start, kv_len, jnp.int32(lay), bk=64, interpret=True)
            want = oracle(q, kc, vc, kv_start, kv_len, jnp.int32(lay))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_single_valid_slot(self):
        """Window of width 1 (first decode after a 1-token prompt)."""
        q, kc, vc, kernel, oracle = self._problem(1)
        kv_start = jnp.array([5, 200], jnp.int32)
        kv_len = kv_start + 1
        lay = jnp.int32(1)
        got = kernel(q, kc, vc, kv_start, kv_len, lay, bk=64, interpret=True)
        want = oracle(q, kc, vc, kv_start, kv_len, lay)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_mha_no_grouping(self):
        q, kc, vc, kernel, oracle = self._problem(2, H=4, K=4)
        T = kc.shape[3]
        kv_start = jnp.array([0, 0], jnp.int32)
        kv_len = jnp.array([T, T // 2], jnp.int32)
        lay = jnp.int32(2)
        got = kernel(q, kc, vc, kv_start, kv_len, lay, bk=128, interpret=True)
        want = oracle(q, kc, vc, kv_start, kv_len, lay)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


class TestDecodeAttentionQ8:
    """int8-KV decode kernel (interpret mode) vs its oracle and vs bf16."""

    def _problem(self, seed, B=2, H=8, K=2, T=256, hd=64, L=3):
        from rag_llm_k8s_tpu.ops.attention import quantize_kv

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        k_cache = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
        v_cache = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
        kq, kscale = quantize_kv(k_cache)
        vq, vscale = quantize_kv(v_cache)
        return q, k_cache, v_cache, kq, kscale, vq, vscale

    def test_quantize_kv_roundtrip(self):
        from rag_llm_k8s_tpu.ops.attention import quantize_kv

        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 64), jnp.float32)
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8 and s.shape == (4, 8)
        deq = q.astype(jnp.float32) * s[..., None]
        # per-element error bounded by half a quantization step
        assert float(jnp.max(jnp.abs(deq - x) - s[..., None] / 2)) <= 1e-6

    def test_kernel_matches_q8_oracle_per_layer(self):
        """The int8 kernel and the int8 XLA oracle see the SAME quantized
        payload, so they must agree to kernel-numerics tolerance."""
        from rag_llm_k8s_tpu.ops.attention import (
            decode_attention_q8,
            decode_attention_xla_q8,
        )

        q, _, _, kq, kscale, vq, vscale = self._problem(0)
        T = kq.shape[3]
        kv_start = jnp.array([0, 37], jnp.int32)
        kv_len = jnp.array([T, 150], jnp.int32)
        for lay in range(kq.shape[0]):
            got = decode_attention_q8(
                q, kq, vq, kscale, vscale, kv_start, kv_len, jnp.int32(lay),
                bk=64, interpret=True,
            )
            want = decode_attention_xla_q8(
                q, kq, vq, kscale, vscale, kv_start, kv_len, jnp.int32(lay)
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
            )

    def test_q8_close_to_bf16_attention(self):
        """End result stays close to the unquantized cache path: int8 KV is
        a ~0.4%-per-element perturbation, and softmax-weighted averaging
        keeps the output error at the same order."""
        from rag_llm_k8s_tpu.ops.attention import (
            decode_attention_q8,
            decode_attention_xla,
        )

        q, kc, vc, kq, kscale, vq, vscale = self._problem(1)
        T = kc.shape[3]
        kv_start = jnp.array([3, 0], jnp.int32)
        kv_len = jnp.array([T - 5, T], jnp.int32)
        lay = jnp.int32(1)
        got = decode_attention_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, lay, bk=64, interpret=True
        )
        want = decode_attention_xla(q, kc, vc, kv_start, kv_len, lay)
        err = float(
            jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-9)
        )
        assert err < 0.02, f"relative error vs bf16 cache: {err}"

    def test_single_valid_slot(self):
        from rag_llm_k8s_tpu.ops.attention import (
            decode_attention_q8,
            decode_attention_xla_q8,
        )

        q, _, _, kq, kscale, vq, vscale = self._problem(2)
        kv_start = jnp.array([5, 200], jnp.int32)
        kv_len = kv_start + 1
        lay = jnp.int32(2)
        got = decode_attention_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, lay, bk=64, interpret=True
        )
        want = decode_attention_xla_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, lay
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_uninitialized_scale_slots_do_not_poison(self):
        """Slots past the frontier carry NaN scales (as donated device
        memory can); the masked dequant must still produce finite output."""
        from rag_llm_k8s_tpu.ops.attention import (
            decode_attention_q8,
            decode_attention_xla_q8,
        )

        q, _, _, kq, kscale, vq, vscale = self._problem(3)
        T = kq.shape[3]
        valid = jnp.arange(T)[None, None, None, :] < 100
        kscale = jnp.where(valid, kscale, jnp.nan)
        vscale = jnp.where(valid, vscale, jnp.nan)
        kv_start = jnp.array([0, 10], jnp.int32)
        kv_len = jnp.array([100, 100], jnp.int32)
        lay = jnp.int32(0)
        got = decode_attention_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, lay, bk=64, interpret=True
        )
        assert bool(jnp.all(jnp.isfinite(got)))
        want = decode_attention_xla_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, lay
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )


class TestModelPallasPath:
    """Full LlamaModel with Pallas attention (interpret) vs the XLA oracle
    model — proves the kernels are THE serving path, not an island."""

    def _models_and_inputs(self, mesh=None):
        from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
        from rag_llm_k8s_tpu.models.llama import (
            LlamaModel,
            init_llama_params,
            make_kv_cache,
            mask_window,
        )

        fp32 = DTypePolicy.fp32()
        # head counts divisible by tp=4 so the shard_map path engages on mesh8
        cfg = LlamaConfig.tiny()
        cfg = type(cfg)(**{**cfg.__dict__, "num_heads": 8, "num_kv_heads": 8})
        params = init_llama_params(jax.random.PRNGKey(0), cfg, fp32)
        oracle = LlamaModel(cfg, fp32, attn_impl="xla")
        pallas = LlamaModel(cfg, fp32, attn_impl="pallas_interpret", mesh=mesh)
        return cfg, params, oracle, pallas, fp32, make_kv_cache, mask_window

    def _run_prefill_decode(self, model, cfg, params, make_kv_cache, tokens, pad_mask, T):
        from rag_llm_k8s_tpu.models.llama import mask_window

        B, S = tokens.shape
        cache = make_kv_cache(cfg, B, T, jnp.float32)
        kv_start, _ = mask_window(pad_mask)
        pos = jnp.clip(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
        real_len = jnp.sum(pad_mask, axis=-1)
        plog, cache = model.apply(
            {"params": params}, tokens, pos, cache,
            kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0),
        )
        # one decode step: feed the last real token again at slot S
        dlog, _ = model.apply(
            {"params": params}, tokens[:, -1:], real_len[:, None].astype(jnp.int32),
            cache, kv_start, jnp.full((B,), S + 1, jnp.int32), jnp.int32(S),
        )
        return plog, dlog

    def test_prefill_and_decode_parity(self):
        cfg, params, oracle, pallas, fp32, mkc, mw = self._models_and_inputs()
        B, S, T = 2, 64, 128
        tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 3, cfg.vocab_size)
        pad_mask = jnp.ones((B, S), jnp.int32).at[1, :17].set(0)  # row 1 left-padded
        p_ref, d_ref = self._run_prefill_decode(oracle, cfg, params, mkc, tokens, pad_mask, T)
        p_got, d_got = self._run_prefill_decode(pallas, cfg, params, mkc, tokens, pad_mask, T)
        valid = pad_mask.astype(bool)[:, :, None]
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, p_got, 0)),
            np.asarray(jnp.where(valid, p_ref, 0)),
            rtol=5e-4, atol=5e-4,
        )
        np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_ref), rtol=5e-4, atol=5e-4)

    def test_shard_map_tp_parity(self, mesh8):
        """Pallas kernels under shard_map over the tp axis of an 8-virtual-device
        mesh match the unsharded oracle — the multi-chip serving attention."""
        cfg, params, oracle, pallas, fp32, mkc, mw = self._models_and_inputs(mesh=mesh8.mesh)
        B, S, T = 2, 64, 128
        tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 3, cfg.vocab_size)
        pad_mask = jnp.ones((B, S), jnp.int32).at[0, :9].set(0)
        p_ref, d_ref = self._run_prefill_decode(oracle, cfg, params, mkc, tokens, pad_mask, T)
        with jax.set_mesh(mesh8.mesh):
            p_got, d_got = self._run_prefill_decode(pallas, cfg, params, mkc, tokens, pad_mask, T)
        valid = pad_mask.astype(bool)[:, :, None]
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, p_got, 0)),
            np.asarray(jnp.where(valid, p_ref, 0)),
            rtol=5e-4, atol=5e-4,
        )
        np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_ref), rtol=5e-4, atol=5e-4)


class TestChunkPrefillAttention:
    """Cache-wide chunked-prefill kernel (interpret mode) vs dense oracle,
    and the chunked path's equivalence to single-shot prefill."""

    def _problem(self, seed, B=2, S=64, H=8, K=2, T=256, hd=64, L=3, dtype=jnp.float32):
        from rag_llm_k8s_tpu.ops.attention import (
            chunk_attention_xla,
            chunk_prefill_attention,
        )

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
        k_cache = jax.random.normal(ks[1], (L, B, K, T, hd), dtype)
        v_cache = jax.random.normal(ks[2], (L, B, K, T, hd), dtype)
        return q, k_cache, v_cache, chunk_prefill_attention, chunk_attention_xla

    def test_matches_oracle_per_layer_and_offset(self):
        q, kc, vc, kernel, oracle = self._problem(0)
        S, T = q.shape[1], kc.shape[3]
        kv_start = jnp.array([0, 23], jnp.int32)
        for wi in (0, 64, T - S):  # first chunk, interior chunk, last chunk
            kv_len = jnp.full((2,), wi + S, jnp.int32)
            for lay in range(kc.shape[0]):
                got = kernel(q, kc, vc, kv_start, kv_len, jnp.int32(lay),
                             jnp.int32(wi), bq=32, bk=64, interpret=True)
                want = oracle(q, kc, vc, kv_start, kv_len, jnp.int32(lay), jnp.int32(wi))
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
                )

    def test_first_chunk_equals_flash_prefill(self):
        """At write_index=0 with kv_len=S the chunked kernel must reproduce
        plain causal prefill over the fresh K/V (written into the cache)."""
        q, kc, vc, kernel, _ = self._problem(1, S=128)
        B, S, H, hd = q.shape
        K = kc.shape[2]
        lay = 1
        fresh_k = jax.random.normal(jax.random.PRNGKey(7), (B, S, K, hd))
        fresh_v = jax.random.normal(jax.random.PRNGKey(8), (B, S, K, hd))
        kc = kc.at[lay, :, :, :S].set(fresh_k.transpose(0, 2, 1, 3))
        vc = vc.at[lay, :, :, :S].set(fresh_v.transpose(0, 2, 1, 3))
        kv_start = jnp.array([0, 5], jnp.int32)
        kv_len = jnp.full((B,), S, jnp.int32)
        got = kernel(q, kc, vc, kv_start, kv_len, jnp.int32(lay), jnp.int32(0),
                     bq=64, bk=64, interpret=True)
        want = flash_attention(q, fresh_k, fresh_v, kv_start, kv_len,
                               causal=True, bq=64, bk=64, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


class TestChunkPrefillAttentionQ8:
    """int8-KV chunked-prefill kernel (interpret mode) vs its q8 oracle and
    vs the bf16 cache path — the long-prompt int8 serving path must never
    materialize a bf16 layer slice, so the kernel dequantizes in epilogues."""

    def _problem(self, seed, B=2, S=64, H=8, K=2, T=256, hd=64, L=3):
        from rag_llm_k8s_tpu.ops.attention import quantize_kv

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k_cache = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
        v_cache = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
        kq, kscale = quantize_kv(k_cache)
        vq, vscale = quantize_kv(v_cache)
        return q, k_cache, v_cache, kq, kscale, vq, vscale

    def test_matches_q8_oracle_per_layer_and_offset(self):
        from rag_llm_k8s_tpu.ops.attention import (
            chunk_attention_xla_q8,
            chunk_prefill_attention_q8,
        )

        q, _, _, kq, kscale, vq, vscale = self._problem(0)
        S, T = q.shape[1], kq.shape[3]
        kv_start = jnp.array([0, 23], jnp.int32)
        for wi in (0, 64, T - S):  # first chunk, interior chunk, last chunk
            kv_len = jnp.full((2,), wi + S, jnp.int32)
            for lay in range(kq.shape[0]):
                got = chunk_prefill_attention_q8(
                    q, kq, vq, kscale, vscale, kv_start, kv_len,
                    jnp.int32(lay), jnp.int32(wi), bq=32, bk=64, interpret=True,
                )
                want = chunk_attention_xla_q8(
                    q, kq, vq, kscale, vscale, kv_start, kv_len,
                    jnp.int32(lay), jnp.int32(wi),
                )
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
                )

    def test_q8_close_to_bf16_chunk_path(self):
        from rag_llm_k8s_tpu.ops.attention import (
            chunk_attention_xla,
            chunk_prefill_attention_q8,
        )

        q, kc, vc, kq, kscale, vq, vscale = self._problem(1)
        S, T = q.shape[1], kc.shape[3]
        wi, lay = 64, jnp.int32(1)
        kv_start = jnp.array([3, 0], jnp.int32)
        kv_len = jnp.full((2,), wi + S, jnp.int32)
        got = chunk_prefill_attention_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, lay, jnp.int32(wi),
            bq=32, bk=64, interpret=True,
        )
        want = chunk_attention_xla(q, kc, vc, kv_start, kv_len, lay, jnp.int32(wi))
        err = float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-9))
        assert err < 0.02, f"relative error vs bf16 cache: {err}"

    def test_uninitialized_scale_slots_do_not_poison(self):
        """Slots past the frontier can hold NaN scales (donated device
        memory): the window mask must zero them before they touch the
        accumulator."""
        from rag_llm_k8s_tpu.ops.attention import (
            chunk_attention_xla_q8,
            chunk_prefill_attention_q8,
        )

        q, _, _, kq, kscale, vq, vscale = self._problem(2)
        S, T = q.shape[1], kq.shape[3]
        wi = 64
        kv_len = jnp.full((2,), wi + S, jnp.int32)
        kv_start = jnp.zeros((2,), jnp.int32)
        nan_tail = jnp.where(jnp.arange(T)[None, None, None, :] >= wi + S,
                             jnp.nan, 1.0)
        kscale = kscale * nan_tail
        vscale = vscale * nan_tail
        got = chunk_prefill_attention_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, jnp.int32(0),
            jnp.int32(wi), bq=32, bk=64, interpret=True,
        )
        assert not bool(jnp.any(jnp.isnan(got))), "NaN scales leaked"
        want = chunk_attention_xla_q8(
            q, kq, vq, kscale, vscale, kv_start, kv_len, jnp.int32(0), jnp.int32(wi)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )


class TestChunkAttentionGrouped:
    """The GQA-grouped chunk kernels (interpret mode): small chunks — a
    speculative verify step's positions — with a KV head's G query heads and
    the S positions as one matmul's rows, against the dense oracles and
    against the per-head kernels they stand in for."""

    T, BK, L = 256, 64, 3

    def _problem(self, seed, B, S, G, K=2, hd=64):
        from rag_llm_k8s_tpu.ops.attention import quantize_kv

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, S, K * G, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (self.L, B, K, self.T, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (self.L, B, K, self.T, hd), jnp.float32)
        return q, kc, vc, quantize_kv(kc), quantize_kv(vc)

    def _offsets(self, S):
        # first slot; straddling a bk edge; the last S slots of the cache
        return (0, self.BK - (S + 1) // 2, self.T - S)

    @pytest.mark.parametrize("q8", [False, True], ids=["bf16", "q8"])
    @pytest.mark.parametrize("B", [1, 2])
    @pytest.mark.parametrize("G", [1, 4])
    @pytest.mark.parametrize("S", [1, 5, 16, 32])
    def test_matches_oracle_and_per_head_kernel(self, S, G, B, q8):
        from rag_llm_k8s_tpu.ops import attention as A

        q, kc, vc, (kq, ksc), (vq, vsc) = self._problem(S * 8 + G + B, B, S, G)
        cache = (kq, vq, ksc, vsc) if q8 else (kc, vc)
        grouped = A.chunk_attention_grouped_q8 if q8 else A.chunk_attention_grouped
        per_head = A.chunk_prefill_attention_q8 if q8 else A.chunk_prefill_attention
        oracle = A.chunk_attention_xla_q8 if q8 else A.chunk_attention_xla
        kv_start = jnp.array([0, 23][:B], jnp.int32)  # row 1 is left-padded
        for wi in self._offsets(S):
            kv_len = jnp.full((B,), wi + S, jnp.int32)
            for lay in range(self.L):
                tail = (kv_start, kv_len, jnp.int32(lay), jnp.int32(wi))
                got = grouped(q, *cache, *tail, bk=self.BK, interpret=True)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(oracle(q, *cache, *tail)),
                    rtol=2e-4, atol=2e-5,
                )
                # same blocks, same recurrence: the per-head kernel's result
                np.testing.assert_allclose(
                    np.asarray(got),
                    np.asarray(per_head(q, *cache, *tail, bk=self.BK, interpret=True)),
                    rtol=1e-6, atol=1e-6,
                )

    @pytest.mark.parametrize("S,G", [(16, 4), (5, 1)])
    def test_nan_scales_outside_the_window_do_not_poison(self, S, G):
        """Left pad below ``kv_start`` and slots past ``kv_len`` can hold NaN
        scales (donated device memory): they are zeroed under the window
        mask and must not reach the output."""
        from rag_llm_k8s_tpu.ops.attention import (
            chunk_attention_grouped_q8,
            chunk_attention_xla_q8,
        )

        q, _, _, (kq, ksc), (vq, vsc) = self._problem(3, 2, S, G)
        wi = self.BK - 3
        kv_start = jnp.array([7, 40], jnp.int32)
        kv_len = jnp.full((2,), wi + S, jnp.int32)
        t = jnp.arange(self.T)[None, None, None, :]
        outside = (t >= wi + S) | (t < kv_start[None, :, None, None])
        ksc = jnp.where(outside, jnp.nan, ksc)
        vsc = jnp.where(outside, jnp.nan, vsc)
        tail = (kv_start, kv_len, jnp.int32(1), jnp.int32(wi))
        got = chunk_attention_grouped_q8(
            q, kq, vq, ksc, vsc, *tail, bk=self.BK, interpret=True
        )
        assert not bool(jnp.any(jnp.isnan(got))), "NaN scales leaked"
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(chunk_attention_xla_q8(q, kq, vq, ksc, vsc, *tail)),
            rtol=2e-4, atol=2e-5,
        )

    def test_block_budget_shrinks_with_head_count(self):
        """The K/V block is sized from what a cell holds in VMEM a cache slot:
        at the benchmark's widths the preferred 512 stays; 32 KV heads of
        bf16 halve it twice."""
        from rag_llm_k8s_tpu.ops import attention as A

        def bk_of(K, G, S, dtype):
            q = jax.ShapeDtypeStruct((1, S, K * G, 128), jnp.bfloat16)
            kv = jax.ShapeDtypeStruct((2, 1, K, 1024, 128), dtype)
            i1 = jax.ShapeDtypeStruct((1,), jnp.int32)
            i0 = jax.ShapeDtypeStruct((), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda *a: A.chunk_attention_grouped(*a, interpret=True)
            )(q, kv, kv, i1, i1, i0, i0)
            (call,) = _pallas_calls(jaxpr)
            return call.params["grid_mapping"].grid[1]

        assert bk_of(8, 4, 16, jnp.bfloat16) == 1024 // 512
        assert bk_of(32, 1, 32, jnp.bfloat16) == 1024 // 128

    @pytest.mark.parametrize("kv_quant", ["bf16", "int8"])
    @pytest.mark.parametrize("S,want", [
        (16, "chunk_attention_grouped"),  # a verify step: G*S = 32
        (64, "chunk_attention_grouped"),  # G*S = 128, the last that fits
        (512, "chunk_prefill_attention"),  # a prompt chunk
    ])
    def test_attend_picks_by_shape(self, S, want, kv_quant):
        """``LlamaModel._attend(mode="chunk")`` reads static shapes only:
        the grouped kernel at ``G*S <= 128``, the per-head one for prompt
        chunks — counted in the jaxpr by ``pallas_call`` name, and in the
        tally ``/metrics`` serves."""
        from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
        from rag_llm_k8s_tpu.models.llama import (
            LlamaModel,
            init_llama_params,
            make_kv_cache,
        )
        from rag_llm_k8s_tpu.obs import tracing

        cfg = LlamaConfig.tiny()  # 4 query heads over 2 KV heads
        fp32 = DTypePolicy.fp32()
        model = LlamaModel(
            cfg, fp32, attn_impl="pallas_interpret", chunked=True, kv_quant=kv_quant
        )
        params = jax.eval_shape(
            lambda: init_llama_params(jax.random.PRNGKey(0), cfg, fp32)
        )
        cache = jax.eval_shape(lambda: make_kv_cache(cfg, 1, 1024, jnp.float32, kv_quant))
        name = want + ("_q8" if kv_quant == "int8" else "")
        before = tracing.kernel_builds().get(("chunk", name), 0)
        jaxpr = jax.make_jaxpr(
            lambda p, c: model.apply(
                {"params": p}, jnp.zeros((1, S), jnp.int32),
                jnp.zeros((1, S), jnp.int32), c, jnp.zeros((1,), jnp.int32),
                jnp.full((1,), 256 + S, jnp.int32), jnp.int32(256),
            )
        )(params, cache)
        names = {c.params["name"] for c in _pallas_calls(jaxpr)}
        assert names == {name}, names
        assert tracing.kernel_builds()[("chunk", name)] > before


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, sub-jaxprs included."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found

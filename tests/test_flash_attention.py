"""Flash-attention kernel numerics (interpret mode on CPU) vs dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.ops.attention import (
    attention_xla,
    flash_attention,
    _flash_call,
    _flash_fits,
    flash_block_plan,
    flash_blocks,
)


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


def _flash_streamed(q, k, v, kv_start, kv_len, causal, bq, bk, interpret):
    """``flash_attention`` with the K/V blocks streamed a grid step, as a
    sequence too long for resident strips gets them."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    lay = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, S, hd)  # noqa: E731
    out = _flash_call(lay(q), lay(k), lay(v), kv_start, kv_len, scale=hd**-0.5, causal=causal,
                      bq=bq, bk=bk, interpret=interpret, name="flash_attention", resident=False)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _valid_rows(q, kv_start, causal):
    """Query rows the engine reads: behind the left pad (under ``causal`` a
    pad row has no live key and both sides emit what they emit)."""
    S = q.shape[1]
    if not causal:
        return jnp.ones((q.shape[0], S, 1, 1), bool)
    return (jnp.arange(S)[None, :] >= kv_start[:, None])[:, :, None, None]


class TestFlashBlockPlan:
    """``flash_block_plan`` against the dense mask: the kernel's loop bounds
    are this function's, so what it skips must be dead and what it calls
    interior must need no mask."""

    S = 1024

    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    @pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
    @pytest.mark.parametrize("frontier", ["one", "mid", "S"])
    @pytest.mark.parametrize("start", ["0", "1", "bk-1", "bk", "402", "950", "S-1"])
    def test_plan_matches_dense_mask(self, start, frontier, bq, bk, causal):
        S = self.S
        kv_start = {"0": 0, "1": 1, "bk-1": bk - 1, "bk": bk, "402": 402, "950": 950, "S-1": S - 1}[start]
        kv_len = {"one": kv_start + 1, "mid": min(S, kv_start + (S - kv_start) // 2 + 37), "S": S}[frontier]
        kv_len = max(kv_len, kv_start + 1)
        pos = np.arange(S)
        live = (pos[None, :] >= kv_start) & (pos[None, :] < kv_len)
        live = np.broadcast_to(live, (S, S)).copy()
        if causal:
            live &= pos[None, :] <= pos[:, None]
        for qi in range(S // bq):
            lo, hi, int_lo, int_hi = (int(x) for x in flash_block_plan(qi, kv_start, kv_len, S, bq, bk, causal))
            for kj in range(S // bk):
                tile = live[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
                visited = lo <= kj <= hi
                assert visited == bool(tile.any()), (qi, kj, lo, hi)
                if visited and int_lo <= kj <= int_hi:
                    assert tile.all(), (qi, kj, "interior block with a masked pair")
                if visited and tile.all() and kj != lo:
                    # nothing forces it, but a fully live block called edge is wasted masking
                    assert int_lo <= kj <= int_hi, (qi, kj, int_lo, int_hi)

    def test_empty_window_visits_nothing(self):
        for causal in (True, False):
            lo, hi, _, _ = flash_block_plan(1, 300, 300, 1024, 256, 256, causal)
            assert int(hi) < int(lo)

    def test_default_blocks_by_shape(self):
        """The rule reads shapes only: 1024 folded rows and 1024 keys a step,
        the keys cut in two blocks and bq capped at 512 under a diagonal, and
        a smaller query block when the resident K/V strips are large."""
        assert flash_blocks(4096, 4, 128, 128) == (256, 512)  # Mistral, Llama-3.1-8B
        assert flash_blocks(4096, 1, 192, 128) == (512, 512)  # the MLA expanded form
        assert flash_blocks(1536, 1, 64, 64, causal=False) == (512, 512)  # the encoder's snug bucket
        assert flash_blocks(2048, 1, 64, 64, causal=False) == (1024, 1024)  # no diagonal: 1024 keys ONE block
        assert flash_blocks(3072, 1, 64, 64, causal=False) == (512, 1024)  # strips of 3 MiB: half the rows
        assert flash_blocks(8192, 4, 128, 128)[0] == 128  # strips of 8 MiB: half the rows
        assert flash_blocks(64, 2, 16, 16) == (64, 64)  # blocks never exceed the sequence

    @pytest.mark.parametrize("S,G,dq,dv,fits", [
        (4096, 4, 128, 128, True), (8192, 4, 128, 128, True), (8192, 1, 192, 128, True),
        (16384, 4, 128, 128, False), (16384, 1, 192, 128, False), (131072, 4, 128, 128, False),
    ])
    def test_long_sequences_stream_their_keys(self, S, G, dq, dv, fits):
        """Where a KV head's strips leave no room in VMEM the rule keeps its
        full query block and ``_flash_call`` streams the key blocks."""
        bq, bk = flash_blocks(S, G, dq, dv)
        assert _flash_fits(S, G * bq, bk, 2, dq, dv, 2) == fits
        if not fits:
            assert (bq, bk) == (min(512, 1024 // G), 512)


class TestFlashLiveTriangle:
    """The kernel's visits follow the plan: parity with the dense oracle where
    rows of one batch disagree about which blocks are edge blocks."""

    def _check(self, q, k, v, kv_start, kv_len, causal, streamed=False, **blocks):
        attend = _flash_streamed if streamed else flash_attention
        got = attend(q, k, v, kv_start=kv_start, kv_len=kv_len, causal=causal, interpret=True, **blocks)
        want = attention_xla(q, k, v, kv_start=kv_start, kv_len=kv_len, causal=causal)
        assert not bool(jnp.any(jnp.isnan(got)))
        m = _valid_rows(q, kv_start, causal)
        np.testing.assert_allclose(
            np.asarray(jnp.where(m, got, 0)), np.asarray(jnp.where(m, want, 0)),
            rtol=2e-4, atol=2e-5,
        )
        return got

    @pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    @pytest.mark.parametrize("G", [1, 4])
    def test_rows_with_different_windows(self, G, causal, streamed):
        """Row 0 is unpadded (block 1 is interior for it), row 1's window
        starts inside block 1 (an edge block there), row 2 is all pad but one
        slot, row 3's frontier cuts a block; wide steps and single ones, over
        a resident strip and over key blocks streamed a grid step."""
        q, k, v = _problem(5 + G, B=4, S=512, H=2 * G, K=2, hd=32)
        kv_start = jnp.array([0, 100, 300, 64], jnp.int32)
        kv_len = jnp.array([512, 512, 301, 333], jnp.int32)
        self._check(q, k, v, kv_start, kv_len, causal, streamed, bq=64, bk=64)
        self._check(q, k, v, kv_start, kv_len, causal, streamed, bq=128, bk=32)

    @pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
    @pytest.mark.parametrize("G", [1, 4])
    def test_single_valid_slot(self, G, streamed):
        """A row that is all pad but its last slot: one live pair."""
        q, k, v = _problem(9, B=2, S=256, H=2 * G, K=2, hd=32)
        kv_start = jnp.array([255, 17], jnp.int32)
        got = self._check(q, k, v, kv_start, jnp.array([256, 256], jnp.int32), True, streamed, bq=64, bk=64)
        # the one live query attends its own slot alone: v of that slot
        np.testing.assert_allclose(
            np.asarray(got[0, 255].reshape(2, G, 32)), np.asarray(jnp.broadcast_to(v[0, 255][:, None], (2, G, 32))),
            rtol=2e-4, atol=2e-5,
        )

    @pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    @pytest.mark.parametrize("G", [1, 4])
    def test_nan_outside_the_window_does_not_poison(self, G, causal, streamed):
        """K/V rows in the left pad and past the frontier can hold anything
        (uninitialized device memory): a NaN there must not reach a live row,
        in an edge block (zeroed under the mask) or by a skipped block."""
        q, k, v = _problem(11, B=2, S=256, H=2 * G, K=2, hd=32)
        kv_start = jnp.array([70, 0], jnp.int32)
        kv_len = jnp.array([256, 150], jnp.int32)
        t = jnp.arange(256)[None, :, None, None]
        outside = (t < kv_start[:, None, None, None]) | (t >= kv_len[:, None, None, None])
        attend = _flash_streamed if streamed else flash_attention
        got = attend(q, jnp.where(outside, jnp.nan, k), jnp.where(outside, jnp.nan, v),
                     kv_start=kv_start, kv_len=kv_len, causal=causal, bq=64, bk=64, interpret=True)
        assert not bool(jnp.any(jnp.isnan(got))), "NaN leaked from outside the window"
        want = attention_xla(q, k, v, kv_start=kv_start, kv_len=kv_len, causal=causal)
        m = _valid_rows(q, kv_start, causal)
        np.testing.assert_allclose(
            np.asarray(jnp.where(m, got, 0)), np.asarray(jnp.where(m, want, 0)), rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
    def test_pad_query_blocks_are_zero(self, streamed):
        """A query block wholly in the left pad visits nothing and writes zeros."""
        q, k, v = _problem(13, B=1, S=256, H=4, K=2, hd=32)
        attend = _flash_streamed if streamed else flash_attention
        got = attend(q, k, v, kv_start=jnp.array([130], jnp.int32), kv_len=jnp.array([256], jnp.int32),
                     causal=True, bq=64, bk=64, interpret=True)
        assert float(jnp.max(jnp.abs(got[0, :130]))) == 0.0

    @pytest.mark.parametrize("S,kv_len", [(1536, 1536), (1536, 1100), (2048, 1999), (192, 64)])
    def test_default_blocks_at_an_encoder_row(self, S, kv_len):
        """Not causal: the rule's blocks of 1024 keys (512 at 1536), one a
        step, on rows padded on the right."""
        q, k, v = _problem(17, B=2, S=S, H=2, K=2, hd=16)
        self._check(q, k, v, jnp.zeros((2,), jnp.int32), jnp.array([kv_len, S], jnp.int32), False)


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

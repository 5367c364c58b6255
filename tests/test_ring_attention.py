"""Ring attention vs dense attention oracle on the 8-virtual-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import MeshConfig
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.parallel.ring_attention import ring_attention_sharded


def dense_attention(q, k, v, causal=True, kv_valid=None):
    """Reference: full-materialization GQA attention, fp32."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    s = s * (hd**-0.5)
    ok = jnp.ones((B, S, S), bool)
    if kv_valid is not None:
        ok = ok & kv_valid[:, None, :]
    if causal:
        pos = jnp.arange(S)
        ok = ok & (pos[None, None, :] <= pos[None, :, None])
    s = jnp.where(ok[:, None, None, :, :].transpose(0, 1, 2, 3, 4), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd)


@pytest.fixture(scope="module")
def sp_mesh(devices8):
    return make_mesh(MeshConfig(dp=1, sp=8, tp=1), devices=devices8)


def _problem(seed, B=2, S=64, H=4, K=2, hd=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    return q, k, v


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, sp_mesh, causal):
        q, k, v = _problem(0)
        got = ring_attention_sharded(sp_mesh, q, k, v, causal=causal)
        want = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_respects_kv_validity(self, sp_mesh):
        """Masked (padded) key positions must not contribute."""
        q, k, v = _problem(1)
        B, S = q.shape[:2]
        kv_valid = jnp.arange(S)[None, :] < 40  # last 24 positions padded
        kv_valid = jnp.broadcast_to(kv_valid, (B, S))
        got = ring_attention_sharded(sp_mesh, q, k, v, causal=False, kv_valid=kv_valid)
        want = dense_attention(q, k, v, causal=False, kv_valid=kv_valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_gqa_grouping(self, sp_mesh):
        q, k, v = _problem(2, H=8, K=2)
        got = ring_attention_sharded(sp_mesh, q, k, v, causal=True)
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_gradients_flow(self, sp_mesh):
        """Ring attention must be differentiable (training over long seqs)."""
        q, k, v = _problem(3, B=1, S=32)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention_sharded(sp_mesh, q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v) ** 2)

        g_ring = jax.grad(loss_ring)(q, k, v)
        g_dense = jax.grad(loss_dense)(q, k, v)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_dense), rtol=1e-3, atol=1e-4)


class TestModelSequenceParallel:
    """Ring attention is REACHABLE: a model built with an sp>1 mesh runs its
    prefill/training attention as the ring (previously dead code)."""

    @pytest.fixture(scope="class")
    def sp_mix_mesh(self, devices8):
        return make_mesh(MeshConfig(dp=2, sp=2, tp=2), devices=devices8)

    def test_prefill_logits_match_sp1(self, sp_mix_mesh):
        import dataclasses

        from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
        from rag_llm_k8s_tpu.models.llama import (
            LlamaModel,
            init_llama_params,
            make_kv_cache,
        )

        FP32 = DTypePolicy.fp32()
        cfg = dataclasses.replace(
            LlamaConfig.tiny(), num_heads=4, num_kv_heads=2, head_dim=8,
            hidden_size=32,
        )
        params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
        B, S = 2, 32
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 2, cfg.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        window = jnp.array([0, 5], jnp.int32), jnp.full((B,), S, jnp.int32)

        ref = LlamaModel(cfg, FP32, attn_impl="xla")
        cache = make_kv_cache(cfg, B, S, jnp.float32)
        want, _ = ref.apply({"params": params}, tokens, pos, cache, *window, jnp.int32(0))

        ring_model = LlamaModel(cfg, FP32, attn_impl="xla", mesh=sp_mix_mesh.mesh)
        cache = make_kv_cache(cfg, B, S, jnp.float32)
        with jax.set_mesh(sp_mix_mesh.mesh):
            got, _ = jax.jit(
                lambda p, t: ring_model.apply(
                    {"params": p}, t, pos, cache, *window, jnp.int32(0)
                )
            )(params, tokens)
        # rows attend only their valid windows; compare valid query positions
        for b, start in enumerate([0, 5]):
            np.testing.assert_allclose(
                np.asarray(got)[b, start:], np.asarray(want)[b, start:],
                rtol=2e-4, atol=2e-5,
            )

    def test_train_step_grads_match_sp1(self, sp_mix_mesh):
        import dataclasses

        from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
        from rag_llm_k8s_tpu.engine.training import make_train_step
        from rag_llm_k8s_tpu.models.llama import init_llama_params

        FP32 = DTypePolicy.fp32()
        cfg = dataclasses.replace(
            LlamaConfig.tiny(), num_heads=4, num_kv_heads=2, head_dim=8,
            hidden_size=32,
        )
        params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
        B, S = 4, 32
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 2, cfg.vocab_size)
        mask = jnp.ones((B, S), jnp.int32)

        init_opt, step_sp1 = make_train_step(cfg, FP32)
        _, _, loss1 = jax.jit(step_sp1)(params, init_opt(params), tokens, mask)

        init_opt2, step_ring = make_train_step(cfg, FP32, mesh=sp_mix_mesh.mesh)
        with jax.set_mesh(sp_mix_mesh.mesh):
            p2, _, loss2 = jax.jit(step_ring)(params, init_opt2(params), tokens, mask)
        np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
        # updated params must match too (gradients flowed through the ring)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            ),
            jax.device_get(jax.jit(step_sp1)(params, init_opt(params), tokens, mask)[0]),
            jax.device_get(p2),
        )

"""Core config + mesh tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rag_llm_k8s_tpu.core import AppConfig, LlamaConfig, MeshConfig, RetrievalConfig, SamplingConfig
from rag_llm_k8s_tpu.core.config import SYSTEM_MESSAGE
from rag_llm_k8s_tpu.core.mesh import make_mesh, single_device_mesh


class TestReferenceParityDefaults:
    """Defaults must reproduce the reference's hardcoded constants (SURVEY §5 config)."""

    def test_retrieval_defaults(self):
        r = RetrievalConfig()
        assert r.chunk_size == 1000  # rag.py:39
        assert r.chunk_overlap == 200  # rag.py:39
        assert r.k == 5  # rag.py:114
        assert r.context_top_n == 3  # rag.py:164
        assert r.embed_dim == 1024  # bge-m3 dim, rag.py:60

    def test_sampling_defaults(self):
        s = SamplingConfig()
        assert s.max_new_tokens == 150  # rag.py:172
        assert s.temperature == 0.7
        assert s.top_p == 0.9

    def test_server_defaults(self):
        c = AppConfig()
        assert c.server.port == 5001  # rag.py:204
        assert c.server.model_path == "/models"  # rag.py:18
        assert c.server.pdf_dir == "/pdfs"  # rag.py:20

    def test_system_message_parity(self):
        assert "based ONLY on the given context" in SYSTEM_MESSAGE
        assert "I don't have enough information" in SYSTEM_MESSAGE

    def test_llama_8b_architecture(self):
        m = LlamaConfig.llama_3_1_8b()
        assert m.hidden_size == 4096
        assert m.num_layers == 32
        assert m.num_kv_heads == 8
        assert m.vocab_size == 128256
        assert m.rope_theta == 500000.0
        assert m.rope_scaling.factor == 8.0

    def test_llama_family_parameter_counts(self):
        """Config shapes reproduce each family member's published size —
        the invariant that guards against transcription slips in the
        classmethods (checked analytically; no tensors built)."""
        def n_params(m):
            attn = m.num_heads * m.head_dim + 2 * m.num_kv_heads * m.head_dim
            per_layer = (
                m.hidden_size * attn                      # wq wk wv
                + m.num_heads * m.head_dim * m.hidden_size  # wo
                + 3 * m.hidden_size * m.intermediate_size   # gate up down
                + 2 * m.hidden_size                          # norms
            )
            total = m.num_layers * per_layer + m.hidden_size
            total += m.vocab_size * m.hidden_size  # embedding
            if not m.tie_word_embeddings:
                total += m.vocab_size * m.hidden_size  # lm_head
            return total

        # published sizes (billions): 1.24, 3.21, 8.03, 70.6
        for cfg, want_b in [
            (LlamaConfig.llama_3_2_1b(), 1.24),
            (LlamaConfig.llama_3_2_3b(), 3.21),
            (LlamaConfig.llama_3_1_8b(), 8.03),
            (LlamaConfig.llama_3_1_70b(), 70.6),
        ]:
            got_b = n_params(cfg) / 1e9
            assert abs(got_b - want_b) / want_b < 0.01, (cfg, got_b, want_b)

    def test_70b_dims_divide_tp8(self):
        m = LlamaConfig.llama_3_1_70b()
        for dim in (m.hidden_size, m.intermediate_size, m.vocab_size,
                    m.num_heads, m.num_kv_heads):
            assert dim % 8 == 0

    def test_from_env_model_path(self):
        c = AppConfig.from_env({"MODEL_PATH": "/tmp/m", "TPU_RAG_PORT": "8080"})
        assert c.server.model_path == "/tmp/m"
        assert c.server.index_path == "/tmp/m/tpu_index"
        assert c.server.port == 8080

    def test_from_env_mesh(self):
        c = AppConfig.from_env({"TPU_RAG_MESH": "dp=2,tp=4"})
        assert c.mesh.dp == 2 and c.mesh.tp == 4

    def test_from_env_warm_full_ladder(self):
        c = AppConfig.from_env({"TPU_RAG_WARM_FULL_LADDER": "1"})
        assert c.engine.warm_full_ladder is True
        assert AppConfig.from_env({}).engine.warm_full_ladder is False
        with pytest.raises(ValueError):
            AppConfig.from_env({"TPU_RAG_WARM_FULL_LADDER": "true"})

    def test_from_env_speculative(self):
        c = AppConfig.from_env(
            {"TPU_RAG_SPECULATIVE": "prompt_lookup", "TPU_RAG_DO_SAMPLE": "0"}
        )
        assert c.engine.speculative == "prompt_lookup"
        assert c.sampling.do_sample is False
        with pytest.raises(ValueError):
            AppConfig.from_env({"TPU_RAG_SPECULATIVE": "ngram"})
        with pytest.raises(ValueError):
            AppConfig.from_env({"TPU_RAG_DO_SAMPLE": "yes"})

    def test_from_env_sync_steps(self):
        c = AppConfig.from_env({"TPU_RAG_SYNC_STEPS": "8"})
        assert c.engine.decode_sync_steps == 8
        with pytest.raises(ValueError):
            AppConfig.from_env({"TPU_RAG_SYNC_STEPS": "0"})

    def test_from_env_resilience(self):
        c = AppConfig.from_env({
            "TPU_RAG_ADMISSION_MAX_CONCURRENCY": "4",
            "TPU_RAG_ADMISSION_MAX_QUEUE": "0",
            "TPU_RAG_ADMISSION_RETRY_AFTER_S": "2.5",
            "TPU_RAG_DEADLINE_MS": "30000",
            "TPU_RAG_BREAKER_RESETS": "5",
            "TPU_RAG_BREAKER_WINDOW_S": "60",
            "TPU_RAG_INFLIGHT_RETRIES": "2",
            "TPU_RAG_RETRY_BACKOFF_MS": "10",
        })
        r = c.resilience
        assert r.admission_max_concurrency == 4
        assert r.admission_max_queue == 0
        assert r.admission_retry_after_s == 2.5
        assert r.deadline_ms == 30000
        assert r.breaker_reset_threshold == 5
        assert r.breaker_window_s == 60.0
        assert r.inflight_retries == 2
        assert r.retry_backoff_ms == 10.0
        # defaults survive an empty env
        d = AppConfig.from_env({}).resilience
        assert d.deadline_ms == 120_000 and d.inflight_retries == 1

    def test_from_env_kv_tiering(self):
        c = AppConfig.from_env({
            "TPU_RAG_KV_TIERING": "1",
            "TPU_RAG_KV_TIERING_WARM_BELOW": "0.5",
            "TPU_RAG_KV_TIERING_COLD_BELOW": "0.1",
            "TPU_RAG_KV_TIERING_HALF_LIFE_S": "120",
            "TPU_RAG_KV_TIERING_HOST_MB": "2048",
            "TPU_RAG_KV_TIERING_INTERVAL_S": "2.5",
        })
        t = c.engine.kv_tiering
        assert t.enabled and t.warm_below == 0.5 and t.cold_below == 0.1
        assert t.half_life_s == 120.0 and t.host_spill_mb == 2048
        assert t.retier_interval_s == 2.5
        # off by default; cross-field rules enforced with the env applied
        assert not AppConfig.from_env({}).engine.kv_tiering.enabled
        for bad in (
            {"TPU_RAG_KV_TIERING": "yes"},
            {"TPU_RAG_KV_TIERING_COLD_BELOW": "0.9"},  # > warm_below
            {"TPU_RAG_KV_TIERING_HALF_LIFE_S": "0"},
            {"TPU_RAG_KV_TIERING_HOST_MB": "0"},
        ):
            with pytest.raises(ValueError):
                AppConfig.from_env(bad)

    def test_from_env_resilience_validation(self):
        for bad in (
            {"TPU_RAG_ADMISSION_MAX_CONCURRENCY": "0"},
            {"TPU_RAG_ADMISSION_MAX_QUEUE": "-1"},
            {"TPU_RAG_DEADLINE_MS": "0"},
            {"TPU_RAG_BREAKER_RESETS": "0"},
            {"TPU_RAG_BREAKER_WINDOW_S": "0"},
            {"TPU_RAG_INFLIGHT_RETRIES": "-1"},
        ):
            with pytest.raises(ValueError):
                AppConfig.from_env(bad)


class TestMesh:
    def test_resolved_auto_tp(self):
        assert MeshConfig(dp=2, sp=1, tp=-1).resolved(8) == (2, 1, 4)
        assert MeshConfig().resolved(8) == (1, 1, 8)
        with pytest.raises(ValueError):
            MeshConfig(dp=3, sp=1, tp=-1).resolved(8)

    def test_make_mesh_shapes(self, devices8):
        ctx = make_mesh(MeshConfig(dp=2, sp=1, tp=4), devices=devices8)
        assert ctx.dp == 2 and ctx.sp == 1 and ctx.tp == 4
        assert ctx.n_devices == 8

    def test_sharded_matmul_over_tp(self, mesh_tp8):
        """A TP-sharded matmul must produce identical numerics to unsharded."""
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (16, 64), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
        ws = jax.device_put(w, mesh_tp8.sharding(None, "tp"))
        xs = jax.device_put(x, mesh_tp8.replicated)

        @jax.jit
        def f(x, w):
            return x @ w

        out = f(xs, ws)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), rtol=1e-4, atol=1e-4)
        # output stays sharded over tp on its last dim
        assert out.sharding.spec == P(None, "tp")

    def test_single_device_mesh(self):
        ctx = single_device_mesh()
        assert ctx.n_devices == 1
        assert ctx.tp == 1


class TestCompileCache:
    """core/compile_cache.py: the cache can be placed from outside, and
    otherwise sits at ONE fixed path inside the checkout."""

    def test_env_wins_and_nothing_is_set_in_code(self, monkeypatch, tmp_path):
        from rag_llm_k8s_tpu.core import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_fixed_path_in_the_checkout(self, monkeypatch):
        import os

        from rag_llm_k8s_tpu.core import compile_cache

        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = compile_cache.ensure_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert path == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert compile_cache.ensure_compile_cache() == path  # never moves
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_entry_count(self, tmp_path):
        from rag_llm_k8s_tpu.core.compile_cache import cache_entry_count

        assert cache_entry_count(str(tmp_path / "absent")) == 0
        for name in ("jit_f-abc-cache", "jit_f-abc-atime", "jit_g-def-cache"):
            (tmp_path / name).write_bytes(b"x")
        assert cache_entry_count(str(tmp_path)) == 2


def test_serving_device_kind_reads_the_mesh(mesh8):
    from rag_llm_k8s_tpu.core.mesh import serving_device_kind

    assert serving_device_kind(mesh8) == serving_device_kind() == "cpu"

"""Seeded synthetic inputs (utils/synth.py): what chip_smoke.py serves."""

import dataclasses

import jax
import numpy as np

from rag_llm_k8s_tpu.core.config import DTypePolicy, EncoderConfig, LlamaConfig
from rag_llm_k8s_tpu.rag.pdf import extract_text
from rag_llm_k8s_tpu.utils.synth import (
    synth_encoder_params,
    synth_llama_params,
    synth_pdf,
)

CFG = dataclasses.replace(
    LlamaConfig.tiny(vocab_size=512), tie_word_embeddings=False, hidden_size=128
)


def _leaves(tree):
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32)
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


class TestSynthLlamaParams:
    def test_same_seed_same_numbers_other_seed_other_numbers(self):
        a = _leaves(synth_llama_params(CFG, DTypePolicy(), 3, quant="int8"))
        b = _leaves(synth_llama_params(CFG, DTypePolicy(), 3, quant="int8"))
        c = _leaves(synth_llama_params(CFG, DTypePolicy(), 4, quant="int8"))
        assert a.keys() == b.keys() == c.keys()
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert any(not np.array_equal(a[k], c[k]) for k in a)

    def test_sharded_birth_holds_the_same_numbers(self, mesh_tp8):
        """A tp-sharded tree and an unsharded one from one seed are equal:
        the four-chip smoke compares tp=4 with tp=1 on that footing."""
        cfg = dataclasses.replace(CFG, num_heads=8, num_kv_heads=8, head_dim=16)
        plain = _leaves(synth_llama_params(cfg, DTypePolicy(), 5))
        placed = synth_llama_params(cfg, DTypePolicy(), 5, mesh=mesh_tp8)
        wq = placed["layers"]["attn"]["wq"]["kernel"]
        assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 8
        assert all(np.array_equal(plain[k], v) for k, v in _leaves(placed).items())

    def test_layouts_norms_and_muted_eos(self):
        from rag_llm_k8s_tpu.models.llama import init_llama_params, quantize_llama_params

        dt = DTypePolicy()
        for quant in ("bf16", "int8"):
            want = jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), CFG, dt))
            if quant == "int8":
                want = jax.eval_shape(quantize_llama_params, want)
            got = synth_llama_params(CFG, dt, 0, quant=quant)
            assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == jax.tree.map(
                lambda x: (x.shape, x.dtype), want
            )
            # stacked RMSNorm weights are [L, D] leaves named "scale": 1, not noise
            assert np.all(np.asarray(got["layers"]["input_norm"]["scale"], np.float32) == 1)
            head = np.asarray(got["lm_head_q" if quant == "int8" else "lm_head"], np.float32)
            assert not head[:, list(CFG.eos_token_ids)].any()
            assert head[:, 5].any()

    def test_recite_gain_makes_streams_follow_cycles(self):
        from rag_llm_k8s_tpu.core.config import EngineConfig, SamplingConfig
        from rag_llm_k8s_tpu.engine.engine import InferenceEngine

        def stream(gain):
            params = synth_llama_params(CFG, DTypePolicy(), 1, recite_gain=gain)
            eng = InferenceEngine(
                CFG, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=48),
                engine_config=EngineConfig(prompt_buckets=(16,), max_seq_len=64,
                                           speculative="off"),
            )
            return eng.generate([[1, 5, 9, 7]])[0]

        def cycle_steps(s):  # successor within the period-8 cycle of ids
            return sum(b == a - a % 8 + (a + 1) % 8 for a, b in zip(s, s[1:]))

        assert len(stream(0.0)) == 48  # EOS muted: the full budget
        assert cycle_steps(stream(8.0)) > 40 > 8 > cycle_steps(stream(0.0))


def test_encoder_params_are_seeded():
    cfg = EncoderConfig.tiny()
    a, b = (_leaves(synth_encoder_params(cfg, DTypePolicy(), s)) for s in (2, 2))
    assert all(np.array_equal(a[k], b[k]) for k in a)


def test_synth_pdf_is_seeded_multipage_and_extractable():
    assert synth_pdf(3) == synth_pdf(3) != synth_pdf(4)
    text = extract_text(synth_pdf(3, n_pages=3, words_per_page=100))
    assert all(f"Section {i} of the seeded corpus 3." in text for i in (1, 2, 3))
    assert 300 < len(text.split()) < 400


def test_leaf_kinds_go_by_path_not_rank():
    """Layers are stacked, so an RMSNorm weight is an [L, D] leaf named
    "scale": only its module's name says it is a norm (it was once drawn as
    embedding noise). Every leaf of both layouts has one of five kinds."""
    import jax.numpy as jnp

    from rag_llm_k8s_tpu.models.llama import synth_leaf_kind

    bf16, f32 = jnp.bfloat16, jnp.float32
    assert synth_leaf_kind(("layers", "input_norm", "scale"), bf16) == "norm"
    assert synth_leaf_kind(("layers", "post_attn_norm", "scale"), bf16) == "norm"
    assert synth_leaf_kind(("final_norm", "scale"), bf16) == "norm"
    assert synth_leaf_kind(("layers", "attn", "wq", "qscale"), f32) == "quant_scale"
    assert synth_leaf_kind(("lm_head_scale",), f32) == "quant_scale"
    assert synth_leaf_kind(("layers", "mlp", "w_up", "kernel_q"), jnp.int8) == "kernel_q"
    assert synth_leaf_kind(("lm_head_q",), jnp.int8) == "kernel_q"
    assert synth_leaf_kind(("layers", "attn", "wo", "kernel"), bf16) == "kernel"
    assert synth_leaf_kind(("lm_head",), bf16) == "kernel"
    assert synth_leaf_kind(("embedding",), bf16) == "embedding"
    # and the builder follows it: stacked norms come out as ones
    params = synth_llama_params(CFG, DTypePolicy(), 0, quant="int8")
    for name in ("input_norm", "post_attn_norm"):
        scale = np.asarray(params["layers"][name]["scale"], np.float32)
        assert scale.ndim == 2 and (scale == 1.0).all()

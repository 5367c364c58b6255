"""8B-geometry streaming-load proof (CI-sized).

The reference serves Meta-Llama-3.1-8B from a 4-shard safetensors layout
(/root/reference/llm/download_model.py:14-25). These tests prove the
framework's streaming loader + TP placement at TRUE 8B tensor shapes —
hidden 4096, intermediate 14336, 32 q / 8 kv heads, vocab 128 256, bf16 on
disk — with the layer count reduced to 2 so CI stays fast (the streaming
claim is exactly that host memory does NOT scale with layer count; the
full-depth run lives in scripts/validate_8b.py, results in docs/8B.md).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import loader_probe
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
from rag_llm_k8s_tpu.models.loader import load_safetensors_params
from rag_llm_k8s_tpu.parallel.sharding import make_streaming_put
from rag_llm_k8s_tpu.utils.synth import write_synth_checkpoint

CFG_8B_L2 = dataclasses.replace(LlamaConfig.llama_3_1_8b(), num_layers=2)
GB = 1 << 30


@pytest.fixture(scope="module")
def synth_dir(tmp_path_factory):
    out = tmp_path_factory.mktemp("synth8b")
    paths = write_synth_checkpoint(str(out), CFG_8B_L2, n_shards=4)
    assert len(paths) == 4  # the real PVC layout: 4 shard files
    return str(out)


def assert_streamed(got):
    """The TRANSIENT host overhead of a probed load, above its final resident
    set, is a few vocab-sized tensors and not the checkpoint."""
    embed_bytes = CFG_8B_L2.vocab_size * CFG_8B_L2.hidden_size * 2
    transient = got["peak"] - max(got["rss_after"], got["peak_before"])
    assert transient < 3 * embed_bytes + 512 * (1 << 20), (
        f"transient host overhead {transient / GB:.2f} GB suggests the "
        f"loader materialized more than a streamed group"
    )


class TestStreaming8B:
    def test_tp_streamed_load_shapes_shardings_and_memory(self, synth_dir):
        """Stream the 4-shard checkpoint onto the 8-device mesh: every tensor
        must arrive TP-sharded at true 8B shapes in bf16, with transient host
        overhead bounded by a couple of single tensors — NOT the checkpoint
        size (the reference's from_pretrained materializes the whole model)."""
        ckpt_bytes = sum(
            os.path.getsize(os.path.join(synth_dir, f))
            for f in os.listdir(synth_dir)
        )
        assert ckpt_bytes > 2 * GB  # true-shape sanity: L=2 slice is ~3 GB

        # the load runs in a process of its own (tests/loader_probe.py says
        # why): what it loaded, and what its host memory did, come back as facts
        got = loader_probe.probe(synth_dir, "llama_3_1_8b", 2)
        leaves = got["leaves"]

        # ---- geometry: stacked [L, ...] at true 8B shapes, bf16 ----------
        c = CFG_8B_L2
        assert leaves["embedding"]["shape"] == [c.vocab_size, c.hidden_size]
        assert leaves["layers/attn/wq/kernel"]["shape"] == [
            2, c.hidden_size, c.num_heads * c.head_dim
        ]
        assert leaves["layers/attn/wk/kernel"]["shape"] == [
            2, c.hidden_size, c.num_kv_heads * c.head_dim
        ]
        assert leaves["layers/mlp/w_gate/kernel"]["shape"] == [
            2, c.hidden_size, c.intermediate_size
        ]
        assert leaves["lm_head"]["shape"] == [c.hidden_size, c.vocab_size]
        assert leaves["embedding"]["dtype"] == "bfloat16"
        assert leaves["layers/mlp/w_gate/kernel"]["dtype"] == "bfloat16"

        # ---- sharding: the big matmuls actually split over tp=8 ----------
        for name in ("layers/attn/wq/kernel", "layers/mlp/w_gate/kernel", "lm_head"):
            leaf = leaves[name]
            assert leaf["shard0_nbytes"] * 8 == leaf["nbytes"], (name, leaf["spec"])

        # ---- memory: transient overhead, not checkpoint-sized ------------
        # on the CPU mesh the PLACED params necessarily stay resident in
        # host RAM (they'd leave for HBM on real chips), so the streaming
        # claim is about the TRANSIENT above the final resident set: at most
        # a couple of vocab-sized tensors (embed read + lm_head transpose),
        # never the multi-GB whole-checkpoint spike from_pretrained makes.
        assert got["peak"] > got["peak_before"]  # the load is what set the high-water mark
        assert_streamed(got)

    def test_int8_streamed_load_is_sharded_and_quantized(self, synth_dir):
        """The int8 deployment mode (`quant="int8"`) over the same checkpoint:
        tensors must arrive TP-sharded in the quantized layout without the
        bf16 tree ever materializing, and the loaded tree must run a forward.
        A property of the loader, so it is shown at the widths this file
        already streams (it stood at one 70B layer until PR 57)."""
        c = CFG_8B_L2
        got = loader_probe.probe(synth_dir, "llama_3_1_8b", 2, quant="int8", forward_tokens=4)
        leaves = got["leaves"]
        wq = {k: leaves[f"layers/attn/wq/{k}"] for k in ("kernel_q", "qscale")}
        assert wq["kernel_q"]["dtype"] == "int8"
        assert wq["kernel_q"]["shape"] == [2, c.hidden_size, c.num_heads * c.head_dim]
        assert "tp" in wq["kernel_q"]["spec"]
        assert wq["qscale"]["dtype"] == "float32"
        assert leaves["layers/mlp/w_gate/kernel_q"]["shape"] == [2, c.hidden_size, c.intermediate_size]
        # EVERY projection group must be quantized — a per-group dtype check
        # (the byte bound alone can't see one small group slipping to bf16)
        for grp, names in (("attn", ("wq", "wk", "wv", "wo")),
                           ("mlp", ("w_gate", "w_up", "w_down"))):
            for name in names:
                sub = f"layers/{grp}/{name}"
                assert leaves[f"{sub}/kernel_q"]["dtype"] == "int8", (grp, name)
                assert leaves[f"{sub}/qscale"]["dtype"] == "float32", (grp, name)
                assert f"{sub}/kernel" not in leaves, (grp, name)
        assert leaves["lm_head_q"]["dtype"] == "int8"  # 8B is untied
        assert leaves["embedding"]["dtype"] == "bfloat16"  # gather-only
        # int8 halves the placed bytes of everything but the embedding
        # (bf16 by design): ~1.87 GiB against the ~2.77 GiB bf16 tree. The
        # bound must sit BELOW the bf16 figure or a silently-skipped
        # quantization of the head or the MLPs would still pass.
        dev_bytes = sum(x["nbytes"] for x in leaves.values())
        assert dev_bytes < 2.2 * GB, f"{dev_bytes / GB:.2f} GiB"

        # streaming claim, as for bf16 above
        assert_streamed(got)

        # the loaded quantized tree must drive a forward end to end
        assert got["logits_shape"] == [1, 4, c.vocab_size]
        assert got["logits_finite"]

    def test_loaded_tree_runs_a_forward(self, synth_dir, mesh_tp8):
        """The placed 8B-shaped tree must actually execute one sharded
        forward step (zero weights → finite zero logits)."""
        from rag_llm_k8s_tpu.models.llama import LlamaModel, make_kv_cache

        put = make_streaming_put(mesh_tp8, dtype=jnp.bfloat16)
        params = load_safetensors_params(
            synth_dir, CFG_8B_L2, DTypePolicy(), put=put
        )
        model = LlamaModel(CFG_8B_L2, DTypePolicy(), attn_impl="xla")
        B, S = 1, 8
        cache = make_kv_cache(CFG_8B_L2, B, 128, jnp.bfloat16)
        logits, _ = jax.jit(
            lambda p, t: model.apply(
                {"params": p}, t, jnp.broadcast_to(jnp.arange(S), (B, S)),
                cache, jnp.zeros((B,), jnp.int32), jnp.full((B,), S, jnp.int32),
                jnp.int32(0), last_logit_only=True,
            )
        )(params, jnp.ones((B, S), jnp.int32))
        assert np.isfinite(np.asarray(logits)).all()

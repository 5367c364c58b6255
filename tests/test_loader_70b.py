"""70B-geometry streaming-load proof (CI-sized).

Llama-3.1-70B is the family's tp=8 deployment (`LlamaConfig.llama_3_1_70b`:
hidden 8192, intermediate 28672, 64 q / 8 kv heads — every sharded dim
divides a v5e-8 exactly, like 8B). One TRUE-shape layer (~6 GB bf16 on
disk) streams through the loader in the int8 deployment mode
(`quant="int8"`, the ~9 GB/chip configuration from the config docstring):
tensors must arrive TP-sharded in the quantized layout without the bf16
tree ever materializing, and the loaded tree must run a forward.
"""

import dataclasses

import loader_probe
import pytest

from rag_llm_k8s_tpu.core.config import LlamaConfig
from rag_llm_k8s_tpu.utils.synth import write_synth_checkpoint

CFG_70B_L1 = dataclasses.replace(LlamaConfig.llama_3_1_70b(), num_layers=1)


@pytest.fixture(scope="module")
def synth_dir(tmp_path_factory):
    out = tmp_path_factory.mktemp("synth70b")
    write_synth_checkpoint(str(out), CFG_70B_L1, n_shards=2)
    return str(out)


class TestStreaming70B:
    def test_int8_streamed_load_is_sharded_and_quantized(self, synth_dir):
        # the load runs in a process of its own (tests/loader_probe.py says
        # why): what it loaded, and what its host memory did, come back as facts
        got = loader_probe.probe(synth_dir, "llama_3_1_70b", 1, quant="int8", forward_tokens=4)
        leaves = got["leaves"]
        wq = {k: leaves[f"layers/attn/wq/{k}"] for k in ("kernel_q", "qscale")}
        assert wq["kernel_q"]["dtype"] == "int8"
        assert wq["kernel_q"]["shape"] == [1, 8192, 64 * 128]
        assert "tp" in wq["kernel_q"]["spec"]
        assert wq["qscale"]["dtype"] == "float32"
        assert leaves["layers/mlp/w_gate/kernel_q"]["shape"] == [1, 8192, 28672]
        # EVERY projection group must be quantized — a per-group dtype check
        # (the byte bound alone can't see one small group slipping to bf16)
        for grp, names in (("attn", ("wq", "wk", "wv", "wo")),
                           ("mlp", ("w_gate", "w_up", "w_down"))):
            for name in names:
                sub = f"layers/{grp}/{name}"
                assert leaves[f"{sub}/kernel_q"]["dtype"] == "int8", (grp, name)
                assert leaves[f"{sub}/qscale"]["dtype"] == "float32", (grp, name)
                assert f"{sub}/kernel" not in leaves, (grp, name)
        assert leaves["lm_head_q"]["dtype"] == "int8"  # 70B is untied
        assert leaves["embedding"]["dtype"] == "bfloat16"  # gather-only
        # int8 halves the placed bytes vs the ~5.5 GiB bf16 layer-1 tree
        # (embedding stays bf16 by design): ~3.7 GiB actual. The bound must
        # sit BELOW the bf16 figure or a silently-skipped quantization of
        # any kernel group would still pass.
        dev_bytes = sum(x["nbytes"] for x in leaves.values())
        assert dev_bytes < 4.5 * (1 << 30), f"{dev_bytes / (1 << 30):.2f} GiB"

        # streaming claim (same contract test_loader_8b.py pins): the
        # TRANSIENT host overhead above the final resident set stays at a
        # few vocab-sized tensors, never the whole bf16 checkpoint
        embed_bytes = CFG_70B_L1.vocab_size * CFG_70B_L1.hidden_size * 2
        transient = got["peak"] - max(got["rss_after"], got["peak_before"])
        assert got["peak"] > got["peak_before"]  # the load is what set the high-water mark
        assert transient < 3 * embed_bytes + 512 * (1 << 20), (
            f"transient host overhead {transient / (1 << 30):.2f} GiB suggests "
            "the loader materialized more than a streamed group"
        )

        # the loaded quantized tree must drive a forward end to end
        assert got["logits_shape"] == [1, 4, CFG_70B_L1.vocab_size]
        assert got["logits_finite"]

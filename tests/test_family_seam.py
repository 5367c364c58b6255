"""The family seam (``models/families.py``, PR 43): what the rest of the program
knows of a decoder family is its configuration class, its module and its one
row. Every literal here was computed ON THE PARENT COMMIT (902ff64) before the
arithmetic and the refusals moved: ``ledger_for(...).roofline`` for the
numbers, ``refuse_unsupported`` for the twenty messages. (The gated-convolution
family's, PR 45, are its own first readings: it came through the seam.)"""

import dataclasses
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import pytest

import longcat_flash_reference
from rag_llm_k8s_tpu.core.config import (
    BlockWindowConfig, ConvMoEConfig, CrossDecoderConfig, EngineConfig, GoodputConfig, HybridSSMConfig, LatentMoEConfig, LlamaConfig,
    MeshConfig, PrefixCacheConfig, SSDMoEConfig, WindowedMoEConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.models import families
from rag_llm_k8s_tpu.obs import goodput

PACKAGE = pathlib.Path(families.__file__).resolve().parents[1]
INT8 = dict(weight_quant="int8", kv_quant="int8")
LAGUNA_PERIOD = ("full_attention",) + ("sliding_attention",) * 3


def mistral(**widths):
    return dataclasses.replace(LlamaConfig(), rope_scaling=None, rope_theta=1e6, **widths)


# id -> (the configuration, EngineConfig overrides, (FLOPs a token, weight bytes, KV bytes a position))
ROOFLINES = {
    # the configurations the families' own test files build
    "llama-tiny-bf16": (LlamaConfig.tiny, {}, (180224.0, 180224.0, 256.0)),
    "llama-tiny-int8": (LlamaConfig.tiny, INT8, (180224.0, 90112.0, 160.0)),
    "latent_moe-tiny": (lambda: LatentMoEConfig.tiny(vocab_size=300), {}, (242176.0, 242176.0, 144.0)),
    "latent_moe-shortcut-tiny": (lambda: longcat_flash_reference.tiny_config(vocab_size=300), {},
                                 (392704.0, 392704.0, 192.0)),
    "windowed_moe-tiny": (lambda: WindowedMoEConfig.tiny(vocab_size=300), {}, (834560.0, 834560.0, 896.0)),
    "block_window-tiny": (lambda: BlockWindowConfig.tiny(vocab_size=40), {}, (204800.0, 204800.0, 128.0)),
    "hybrid_ssm-tiny": (lambda: HybridSSMConfig.tiny(vocab_size=48), {}, (796672.0, 904192.0, 128.0)),
    # PR 45's family, by its own arithmetic: 4 experts a token-layer, the bytes of the experts a step hits
    "conv_moe-tiny": (lambda: ConvMoEConfig.tiny(vocab_size=48), {}, (825344.0, 829440.0, 256.0)),
    # published widths, at the depth, vocabulary and expert share a cell serves
    "llama-3.1-8b": (LlamaConfig, {}, (15009316864.0, 15009316864.0, 131072.0)),
    "mistral-7b-v0.3-int8": (lambda: mistral(vocab_size=32768), INT8, (14227079168.0, 7113539584.0, 67584.0)),
    "mistral-nemo-12b": (lambda: mistral(vocab_size=131072, hidden_size=5120, num_layers=40), {},
                         (23152558080.0, 23152558080.0, 163840.0)),
    "dots-vlm1-ep16": (lambda: LatentMoEConfig(vocab_size=16160, num_layers=5, first_k_dense=1, ep_size=16,
                                               ep_rank=1), {}, (3438608384.0, 3438608384.0, 5760.0)),
    "longcat-flash-ep32": (lambda: LatentMoEConfig(
        vocab_size=16384, hidden_size=6144, intermediate_size=12288, num_layers=4, first_k_dense=0,
        num_heads=64, n_routed_experts=512, n_shared_experts=0, num_experts_per_tok=12, n_group=1,
        topk_group=1, zero_expert_num=256, sublayers_per_layer=2, ep_size=32, ep_rank=7, rope_scaling=None,
    ), {}, (5387583488.0, 5387583488.0, 9216.0)),
    "laguna-s-2.1-ep16": (lambda: WindowedMoEConfig(
        vocab_size=12544, layer_types=LAGUNA_PERIOD * 4 + ("full_attention",),
        num_attention_heads_per_layer=(48, 72, 72, 72) * 4 + (48,),
        mlp_layer_types=("dense",) + ("sparse",) * 16, ep_size=16,
    ), {}, (2776596480.0, 2776596480.0, 69632.0)),
    "evabyte-6.5b-stage": (BlockWindowConfig, {}, (3258974208.0, 3258974208.0, 8192.0)),
    "jamba2-3b": (HybridSSMConfig, {}, (6052249600.0, 6070886400.0, 1024.0)),
    "lfm2-24b-a2b-stage": (lambda: ConvMoEConfig(tie_word_embeddings=False), {},
                           (1474297856.0, 1474428928.0, 4096.0)),
    # PR 53's family, by its own arithmetic: ONE plane grows with the context and eight layers read it (a
    # position's 2 x 10 x 128 values, twice 8), the window layers' 512 slots and the states ride the weights' bytes
    "cross_decoder-tiny": (lambda: CrossDecoderConfig.tiny(vocab_size=48), {}, (1030144.0, 1104896.0, 384.0)),
    "phi4-mini-flash": (lambda: CrossDecoderConfig(tie_word_embeddings=False), {},
                        (7702118400.0, 7729541120.0, 40960.0)),
    # PR 55's family, by its own arithmetic, BY LAYER KIND (a layer is a mixer OR a feed-forward part): a
    # Mamba-2 layer's two projections (4096 x 18560 + 8192 x 4096), the one attention layer's four, an expert
    # layer's router, two latent projections, shared expert and 22 x 128 / 512 = 5.5 experts of two matrices
    # (1024 x 2688 each) a token; the five states (float32 [128, 64, 128], read and written a step) and kept
    # convolution inputs ride the weights' bytes; K/V bytes of the ONE attention layer only (2 x 2 x 128 x 2)
    "ssd_moe-tiny": (lambda: SSDMoEConfig.tiny(vocab_size=48), {}, (724992.0, 781056.0, 128.0)),
    "nemotron-3-super-ep4": (lambda: SSDMoEConfig(vocab_size=32768, num_hidden_layers=11,
                                                  hybrid_override_pattern="MEMEMEM*EME", ep_size=4), {},
                             (2283536384.0, 2326093824.0, 1024.0)),
}


@pytest.mark.parametrize("case", ROOFLINES)
def test_roofline_terms_are_the_parent_s(case):
    build, overrides, (flops, weight_bytes, kv_bytes) = ROOFLINES[case]
    roofline = goodput.ledger_for(build(), EngineConfig(**overrides), "TPU v5 lite").roofline
    assert roofline.flops_per_token == flops
    assert roofline.weight_bytes == weight_bytes
    assert roofline.kv_bytes_per_token == kv_bytes
    assert (roofline.peak_flops, roofline.peak_bytes) == (197.0e12, 819.0e9)


TINY = {
    "latent_moe": LatentMoEConfig.tiny, "windowed_moe": WindowedMoEConfig.tiny,
    "block_window": BlockWindowConfig.tiny, "hybrid_ssm": HybridSSMConfig.tiny, "conv_moe": ConvMoEConfig.tiny,
}
# mechanism -> every way an operator asks for it: (EngineConfig overrides, (tp, sp), engine)
ASKED = {
    "continuous": (({}, None, "continuous"), (dict(batching="continuous"), None, "one-shot")),
    "prefix_cache": ((dict(prefix_cache=PrefixCacheConfig(enabled=True)), None, "one-shot"),),
    "kv_quant": ((dict(kv_quant="int8"), None, "one-shot"),),
    "weight_quant": ((dict(weight_quant="int8"), None, "one-shot"),),
    "mesh": (({}, (2, 1), "one-shot"), ({}, (1, 2), "one-shot")),
}
REFUSALS = {
    ('latent_moe', 'continuous'):
        "the latent-attention sparse-expert family (LatentMoEConfig) cannot be served with the continuous engine (batching='continuous') or its paged KV pool yet: per-row frontiers and block tables are written for per-head K/V planes, not the latent cache; use batching='coalesce'",
    ('latent_moe', 'prefix_cache'):
        'the latent-attention sparse-expert family (LatentMoEConfig) cannot be served with the KV prefix cache (prefix_cache.enabled) yet: splicing a latent row needs only its rope slice re-rotated, which rerotate_prefix_planes does not do',
    ('latent_moe', 'kv_quant'):
        "the latent-attention sparse-expert family (LatentMoEConfig) cannot be served with kv_quant='int8' yet: the latent cache has no int8 planes",
    ('latent_moe', 'weight_quant'):
        "the latent-attention sparse-expert family (LatentMoEConfig) cannot be served with weight_quant='int8' yet: quantize_llama_params does not know this tree (stacked experts, the router)",
    ('latent_moe', 'mesh'):
        'the latent-attention sparse-expert family (LatentMoEConfig) cannot be served with tp=2, sp=1 yet: the latent projections and the expert stack have no partition rules; experts across chips need the all-to-all',
    ('windowed_moe', 'continuous'):
        "the windowed-attention sparse-expert family (WindowedMoEConfig) cannot be served with the continuous engine (batching='continuous') or its paged KV pool yet: the block pool has one table kind and every plane its full length: sliding layers want a ring of window slots and a table of their own; use 'coalesce'",
    ('windowed_moe', 'prefix_cache'):
        "the windowed-attention sparse-expert family (WindowedMoEConfig) cannot be served with the KV prefix cache (prefix_cache.enabled) yet: a spliced segment's sliding layers saw another window than the prompt's, and rerotate_prefix_planes knows one rotary table, not one a layer kind",
    ('windowed_moe', 'kv_quant'):
        "the windowed-attention sparse-expert family (WindowedMoEConfig) cannot be served with kv_quant='int8' yet: the windowed prefill and the chunk form read bf16 planes only",
    ('windowed_moe', 'weight_quant'):
        "the windowed-attention sparse-expert family (WindowedMoEConfig) cannot be served with weight_quant='int8' yet: quantize_llama_params does not know this tree (projections that differ in shape by layer kind, stacked experts, the router)",
    ('windowed_moe', 'mesh'):
        'the windowed-attention sparse-expert family (WindowedMoEConfig) cannot be served with tp=2, sp=1 yet: this tree has no partition rules (72 and 48 query heads over 8 KV heads split differently), and experts across chips need the all-to-all',
    ('block_window', 'continuous'):
        "the block-window pooled-summary family (BlockWindowConfig) cannot be served with the continuous engine (batching='continuous') or its paged KV pool yet: the block pool has one table kind of full-length planes: this cache is a ring of window slots and a plane of pooled summaries, a second table kind; use 'coalesce'",
    ('block_window', 'prefix_cache'):
        "the block-window pooled-summary family (BlockWindowConfig) cannot be served with the KV prefix cache (prefix_cache.enabled) yet: a pooled summary is position-free only up to its keys' rotation, and a spliced segment's windows and chunks fall elsewhere than the prompt's",
    ('block_window', 'kv_quant'):
        "the block-window pooled-summary family (BlockWindowConfig) cannot be served with kv_quant='int8' yet: the ring and the summary plane have no int8 form",
    ('block_window', 'weight_quant'):
        "the block-window pooled-summary family (BlockWindowConfig) cannot be served with weight_quant='int8' yet: quantize_llama_params does not know this tree (stacked layers, the pooling vectors)",
    ('block_window', 'mesh'):
        "the block-window pooled-summary family (BlockWindowConfig) cannot be served with tp=2, sp=1 yet: this tree has no partition rules, and a prompt row's windows are walked on one chip",
    ('hybrid_ssm', 'continuous'):
        "the hybrid state-space family (HybridSSMConfig) cannot be served with the continuous engine (batching='continuous') or its paged KV pool yet: a recurrent state has no blocks to page, and preemption, resume and a per-row frontier need snapshots of it that nothing takes yet; use 'coalesce'",
    ('hybrid_ssm', 'prefix_cache'):
        "the hybrid state-space family (HybridSSMConfig) cannot be served with the KV prefix cache (prefix_cache.enabled) yet: a recurrent state can be reused only for an exact prefix, and only if a snapshot was kept at its end: a spliced segment's keys and values say nothing of it",
    ('hybrid_ssm', 'kv_quant'):
        "the hybrid state-space family (HybridSSMConfig) cannot be served with kv_quant='int8' yet: the state is float32 and the attention layers' planes have no int8 form here",
    ('hybrid_ssm', 'weight_quant'):
        "the hybrid state-space family (HybridSSMConfig) cannot be served with weight_quant='int8' yet: quantize_llama_params does not know this tree (leaves stacked by layer kind, float32 A_log, D and time-step bias)",
    ('hybrid_ssm', 'mesh'):
        'the hybrid state-space family (HybridSSMConfig) cannot be served with tp=2, sp=1 yet: this tree has no partition rules (one KV head cannot be split, and a scan over a sequence split across chips hands its state from chip to chip)',
    ('conv_moe', 'continuous'):
        "the gated-convolution sparse-expert family (ConvMoEConfig) cannot be served with the continuous engine (batching='continuous') or its paged KV pool yet: a convolution's kept inputs have no blocks to page, and preemption, resume and a per-row frontier need snapshots of them that nothing takes yet; use 'coalesce'",
    ('conv_moe', 'prefix_cache'):
        "the gated-convolution sparse-expert family (ConvMoEConfig) cannot be served with the KV prefix cache (prefix_cache.enabled) yet: a convolution's state can be reused only for an exact prefix, and only if a snapshot was kept at its end: a spliced segment's keys and values say nothing of it",
    ('conv_moe', 'kv_quant'):
        "the gated-convolution sparse-expert family (ConvMoEConfig) cannot be served with kv_quant='int8' yet: the attention layers' planes (heads of 64) and the kept inputs have no int8 form here",
    ('conv_moe', 'weight_quant'):
        "the gated-convolution sparse-expert family (ConvMoEConfig) cannot be served with weight_quant='int8' yet: quantize_llama_params does not know this tree (operators that differ in shape by layer kind, the taps, stacked experts, the router)",
    ('conv_moe', 'mesh'):
        'the gated-convolution sparse-expert family (ConvMoEConfig) cannot be served with tp=2, sp=1 yet: this tree has no partition rules (a depthwise convolution splits by channel, the heads by KV head), and experts across chips need the all-to-all',
}


@pytest.mark.parametrize("family,mechanism", REFUSALS)
def test_every_refusal_reads_as_before(family, mechanism):
    config = TINY[family]()
    for overrides, axes, engine in ASKED[mechanism]:
        mesh = types.SimpleNamespace(tp=axes[0], sp=axes[1]) if axes else None
        expected = REFUSALS[family, mechanism]
        if axes:  # the literal is the parent's at tp=2, sp=1; a mesh is named by its own axes
            expected = expected.replace("tp=2, sp=1", f"tp={axes[0]}, sp={axes[1]}")
        with pytest.raises(NotImplementedError) as raised:
            families.refuse_unsupported(config, EngineConfig(**overrides), mesh, engine=engine)
        assert str(raised.value) == expected
    # every mechanism at once: the first of the five in their order is the one named
    everything = EngineConfig(batching="continuous", prefix_cache=PrefixCacheConfig(enabled=True), **INT8)
    with pytest.raises(NotImplementedError) as raised:
        families.refuse_unsupported(config, everything, types.SimpleNamespace(tp=2, sp=2))
    assert str(raised.value) == REFUSALS[family, "continuous"]
    # and the Llama family, which has no reasons, is served with all of them
    families.refuse_unsupported(LlamaConfig.tiny(), everything, types.SimpleNamespace(tp=2, sp=2),
                                engine="continuous")


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    """A sixth family's class: fields, validation, its arithmetic."""

    vocab_size: int = 32
    hidden_size: int = 8
    num_layers: int = 2

    def __post_init__(self):
        if self.hidden_size % 2:
            raise ValueError("hidden_size is even")

    def roofline_terms(self, weight_quant="bf16", kv_quant="bf16"):
        params = self.num_layers * self.hidden_size ** 2 + self.vocab_size * self.hidden_size
        return 2.0 * params, 2.0 * params, 2.0 * self.num_layers * self.hidden_size


class ToyModel:
    """What its module holds: a model, a cache, the counters the cache carries."""

    COUNTER_NAMES = ("toy_rows",)

    def __init__(self, config, dtypes, attn_impl):
        self.config, self.dtypes, self.attn_impl = config, dtypes, attn_impl

    @staticmethod
    def make_cache(config, batch_size, max_seq_len, dtype):
        return jnp.zeros((config.num_layers, batch_size, max_seq_len, config.hidden_size), dtype)

    @staticmethod
    def fold_counters(row):
        return {"toy_rows": int(row[0])}


def toy_row():
    return families.replicated_row(
        "toy", ToyConfig, ToyModel, ToyModel.make_cache,
        refuses={"kv_quant": "a toy has no int8 planes", "mesh": "a toy is one chip's"},
        counters_width=1, counter_names=ToyModel.COUNTER_NAMES, fold_counters=ToyModel.fold_counters)


def test_a_sixth_family_is_a_class_a_module_and_a_row(monkeypatch):
    monkeypatch.setattr(families, "_TABLE", ((ToyConfig, toy_row),) + families._TABLE)
    monkeypatch.setattr(families, "_BUILT", dict(families._BUILT))
    config = ToyConfig()
    family = families.of(config)
    assert family.name == "the toy family (ToyConfig)"
    assert families.of(LlamaConfig.tiny()).name == "the Llama family (LlamaConfig)"  # the others still theirs
    # a cache, a model and its counters
    assert families.make_cache(config, 3, 16, jnp.float32).shape == (2, 3, 16, 8)
    model = family.build_model(config, None, EngineConfig(), None, fused=True, quantized=False)
    assert isinstance(model, ToyModel) and model.attn_impl == EngineConfig().attn_impl
    assert (family.counters_width, family.counter_names) == (1, ("toy_rows",))
    assert family.fold_counters([7]) == {"toy_rows": 7}
    # a roofline: goodput.py asks the configuration and knows no family
    roofline = goodput.ledger_for(config, EngineConfig(), "TPU v5 lite").roofline
    assert (roofline.flops_per_token, roofline.weight_bytes, roofline.kv_bytes_per_token) == (768.0, 768.0, 32.0)
    # replicated specs, and the one rule refuses a mesh that would split the tree (tp OR sp)
    shapes = {"embedding": jax.ShapeDtypeStruct((32, 8), jnp.float32),
              "layers": {"w": jax.ShapeDtypeStruct((2, 8, 8), jnp.float32)}}
    devices = jax.devices()
    specs = family.param_specs(shapes, make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=devices[:1]))
    assert specs == {"embedding": jax.sharding.PartitionSpec(None, None),
                     "layers": {"w": jax.sharding.PartitionSpec(None, None, None)}}
    for axes in (dict(sp=1, tp=2), dict(sp=2, tp=1)):
        with pytest.raises(NotImplementedError, match=rf"tp={axes['tp']}, sp={axes['sp']}: the toy tree"):
            family.param_specs(shapes, make_mesh(MeshConfig(dp=1, **axes), devices=devices[:2]))
    # named refusals for what its row gives a reason for; the rest is served
    with pytest.raises(NotImplementedError) as raised:
        families.refuse_unsupported(config, EngineConfig(kv_quant="int8"), None)
    assert str(raised.value) == ("the toy family (ToyConfig) cannot be served with kv_quant='int8' yet: "
                                 "a toy has no int8 planes")
    with pytest.raises(NotImplementedError, match=r"tp=1, sp=2 yet: a toy is one chip's"):
        families.refuse_unsupported(config, EngineConfig(), types.SimpleNamespace(tp=1, sp=2))
    families.refuse_unsupported(config, EngineConfig(weight_quant="int8", batching="continuous"), None)
    assert family.checkpoint_loader_refusal == (
        "the checkpoint loader has no name map for the toy family's tensors; "
        "serve it through assemble_service with a parameter tree of your own")

    # a configuration that lacks the arithmetic is named, never priced as another family
    @dataclasses.dataclass(frozen=True)
    class BareConfig:
        num_layers: int = 2
        hidden_size: int = 64

    with pytest.raises(TypeError, match="BareConfig has no roofline_terms"):
        goodput.ledger_for(BareConfig(), EngineConfig(), "TPU v5 lite")
    off = EngineConfig(goodput=GoodputConfig(enabled=False))
    assert goodput.ledger_for(BareConfig(), off, "TPU v5 lite").roofline is None  # a ledger that prices nothing


FAMILY_NAMES = re.compile(
    r"hasattr\(model_config|LatentMoEConfig|WindowedMoEConfig|BlockWindowConfig|HybridSSMConfig|ConvMoEConfig"
    r"|roofline_for_(latent_moe|windowed_moe|block_window|hybrid_ssm|conv_moe)")


def test_nothing_outside_the_seam_names_a_family():
    scanned = [PACKAGE / "engine" / "engine.py"]
    for folder in ("obs", "parallel", "server"):
        scanned += sorted((PACKAGE / folder).rglob("*.py"))
    assert len(scanned) > 10
    found = [f"{path.relative_to(PACKAGE)}:{n}: {line.strip()}"
             for path in scanned for n, line in enumerate(path.read_text().splitlines(), 1)
             if FAMILY_NAMES.search(line)]
    assert not found, found
    # the seam itself: one partition rule for a family without one, one function that refuses
    sharding = (PACKAGE / "parallel" / "sharding.py").read_text()
    assert re.findall(r"^def (\w*param_specs)\(", sharding, re.M) == ["llama_param_specs", "replicated_param_specs"]
    seam = pathlib.Path(families.__file__).read_text()
    assert seam.count("raise NotImplementedError") == 1 and "lambda" not in seam

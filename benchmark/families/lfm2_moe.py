"""``model_type: "lfm2_moe"``: the LFM2 mixture-of-experts decoder (most
layers a GATED SHORT CONVOLUTION of three taps, every fourth a grouped-query
attention layer whose queries and keys are normed over the head before they
are rotated; the first ``num_dense_layers`` layers' FFN a dense SwiGLU, every
later one 64 sigmoid-routed experts of which a token takes 4), served as ONE
STAGE of a pipeline: the layers of the stage whole (every expert, every head,
the whole vocabulary), the final norm and the head on this chip so that
``/generate`` answers. The program runs it through ``models/conv_moe.py``.

The contract is ``lib/serve.py FAMILY_CONTRACT``; what every family shares
(statistics, ``draw_head``, ``prng_key``) comes from ``lib/serve.py``. JAX is
imported inside the functions. The one thing looked at on import: whether the
program HAS this family's module. A checkout from before it fails here, at
once and by name, before a tokenizer is trained or a device is touched.

The published model ties its head to the embedding. ``serve.draw_head`` is
untied by construction and every family serves it (every speculation number
rests on answers that recite), so the cell serves an untied head: the
program's model takes both (``tie_word_embeddings``), tier 1 tests the tied
form against the reference, and the file lists the departure under ``assumed``.
"""

from __future__ import annotations

import math
import os

from benchmark.lib import serve

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "conv_moe.py")):
    raise ImportError("model_type 'lfm2_moe': this checkout's program has no models/conv_moe.py "
                      "(the gated-convolution sparse-expert family), so it cannot serve the configuration")

# published config.json key -> ConvMoEConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_dense_layers": "num_dense_layers",
    "num_attention_heads": "num_attention_heads",
    "num_key_value_heads": "num_key_value_heads",
    "conv_L_cache": "conv_L_cache",
    "conv_bias": "conv_bias",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "use_expert_bias": "use_expert_bias",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "ep_size": "ep_size",  # not a published key: the ranks that share a layer's experts (1: every expert is here)
    "bos_token_id": "bos_token_id",  # not a published key: the stand-in tokenizer's
}
# read here, not (or not as they stand) fields of the program's configuration
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + ("layer_types", "num_hidden_layers", "rope_parameters")
# published keys that select nothing in this decoder but must hold these values
FIXED: dict = {}
# the family's own weight statistics (the file's ``assumed`` says why): std as
# a multiple of 1/sqrt(fan_in)
IN_GAIN = 1.0  # W_in: B, C and u of unit spread, so that the two gates are products of three live factors
CONV_GAIN = 1.0  # the taps at std 1/sqrt(conv_L_cache): three different numbers a channel, not a flat mean
OUT_GAIN = 0.5  # W_out: a conv operator adds a tenth or more of the stream a layer, 8 times
QK_SCALE = 1.2  # the q and k norms' scales: scores of spread 1.44 over ~3 k keys, so that a softmax is no plain mean
QK_GAIN = 1.0  # W_q, W_k: behind the norms their scale is gone; this keeps the norms clear of eps
VO_GAIN = 1.0  # W_v, W_o: attention adds a tenth or more of the stream a layer
ROUTER_GAIN = 1.0  # logits of unit spread: sigmoid scores across (0, 1), not all at 1/2
ROUTER_BIAS_STD = 0.05  # the selection-only bias: moves choices, never weights
# the other sparse families' 0.5 (PR 44's chip readings with every expert held: at 1.0 the sound distance is
# 0.07 to 0.31, because the chosen weights sum to ONE and an expert that bf16 swaps at the top-4's edge moves
# the stream by a quarter of an expert's output, as much as rounding every expert to fp8)
EXPERT_GAIN = 0.5
DENSE_GAIN = 1.0  # the two dense SwiGLUs (a sixteenth of the stage's matmuls a token): not a hundredth of the stream
# --allow-cpu-rehearsal: a dense conv layer, then two periods of (attention, conv), 8 experts top-2
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=["conv", "full_attention", "conv", "full_attention", "conv"], num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
    max_position_embeddings=1024)


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import ConvMoEConfig

    if len(cfg["layer_types"]) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"layer_types names {len(cfg['layer_types'])} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    rope = cfg.get("rope_parameters", {"rope_type": "default", "rope_theta": 1000000})
    if rope.get("rope_type", "default") != "default":
        raise ValueError("rope_parameters: this decoder rotates by the plain table (rope_type 'default')")
    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return ConvMoEConfig(
        layer_types=tuple(cfg["layer_types"]), rope_theta=float(rope["rope_theta"]),
        eos_token_ids=(int(cfg["eos_token_id"]),), tie_word_embeddings=False, **fields)


def layer_loop_trips(cfg: dict) -> int:
    """A trip of the one ``lax.scan`` is a PERIOD of the operators' pattern
    (``ConvMoEConfig.period``); the dense layers sit in front of it."""
    return model_config(cfg).num_periods


def leaf_draw(path, config):
    """``(kind, value)`` of one leaf of the ``ConvMoEModel`` tree: ``norm``
    (every entry ``value``: 1, or ``QK_SCALE`` on q and k), ``bias`` (the
    router's selection bias, normal of std ``value``), ``embedding`` (unit
    std) or ``kernel`` (normal of std ``value / sqrt(fan_in)``, the fan-in the
    leaf's second-to-last axis: the three taps of ``conv_w [3, hidden]`` too)."""
    name = path[-1]
    if any("norm" in part for part in path):
        return "norm", QK_SCALE if path[-2] in ("q_norm", "k_norm") else 1.0
    if name == "router_bias":
        return "bias", ROUTER_BIAS_STD
    if name == "embedding":
        return "embedding", 1.0
    if "experts" in path:
        return "kernel", EXPERT_GAIN
    part = path[-2] if name == "kernel" else name
    return "kernel", {"conv_w": CONV_GAIN, "in_proj": IN_GAIN, "out_proj": OUT_GAIN, "wq": QK_GAIN, "wk": QK_GAIN,
                      "wv": VO_GAIN, "wo": VO_GAIN, "router": ROUTER_GAIN, "w_gate": DENSE_GAIN, "w_up": DENSE_GAIN,
                      "w_down": DENSE_GAIN}[part]


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``ConvMoEModel`` layout, every
    leaf born on its device in its serving dtype, in ONE jitted call. The
    statistics are ``lib/serve.py``'s (a unit-std embedding, ``serve.draw_head``'s
    head) with the family's own for the operators, the router and both kinds
    of FFN (``leaf_draw``). Keys are folded from the root in the sorted order
    of the body's paths, the head's last: a leaf is its path's place in that
    order (``tests/recorded_weights_lfm2_moe.json`` pins them)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.conv_moe import init_conv_moe_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    if config.tie_word_embeddings:
        raise ValueError("the benchmark serves serve.draw_head's untied head: tie_word_embeddings=False")
    shapes = jax.eval_shape(lambda: init_conv_moe_params(jax.random.PRNGKey(0), config, dtypes))
    flat = traverse_util.flatten_dict(shapes)
    specs = traverse_util.flatten_dict(families.of(config).param_specs(shapes, mesh))
    body = sorted(p for p in flat if p != ("lm_head",))

    def draw(path, s, key):
        kind, value = leaf_draw(path, config)
        if kind == "norm":
            return jnp.full(s.shape, value, s.dtype)
        if kind == "bias":
            return (jax.random.normal(key, s.shape, jnp.float32) * value).astype(s.dtype)
        std = 1.0 if kind == "embedding" else value / math.sqrt(s.shape[-2])

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if path[0] in ("periods", "experts"):  # stacked over the loop's trips: one slice per step
            return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))
        return block(key, s.shape)

    def make(root):
        out = {p: draw(p, flat[p], jax.random.fold_in(root, i)) for i, p in enumerate(body)}
        (out[("lm_head",)],) = serve.draw_head(
            jax.random.fold_in(root, len(flat)), out[("embedding",)], config.eos_token_ids,
            recite_gain, flat[("lm_head",)].dtype)
        return out

    shardings = {p: NamedSharding(mesh.mesh, specs[p]) for p in flat}
    return traverse_util.unflatten_dict(
        jax.jit(make, out_shardings=shardings)(serve.prng_key(seed, 0)))

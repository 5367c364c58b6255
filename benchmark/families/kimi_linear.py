"""``model_type: "kimi_linear"``: Kimi-Linear-48B-A3B (27 layers, three of
every four a LINEAR-ATTENTION layer by the gated delta rule with a decay a
channel, the fourth multi-head latent attention with nothing rotated; layer 0
a dense SwiGLU, every later one 256 sigmoid-routed experts of which a token
takes 8, beside a shared expert), served as ONE CHIP'S SHARE of an
expert-parallel deployment at its WHOLE depth (``ep_size`` ranks share each
layer's routed experts; this chip is ``ep_rank``). The program runs it through
``models/delta_moe.py``.

The contract is ``lib/serve.py FAMILY_CONTRACT``; what every family shares
(statistics, ``draw_head``, ``prng_key``) comes from ``lib/serve.py``. JAX is
imported inside the functions. The one thing looked at on import: whether the
program HAS this family's module. A checkout from before it fails here, at
once and by name, before a tokenizer is trained or a device is touched.
"""

from __future__ import annotations

import math
import os

from benchmark.lib import serve

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "delta_moe.py")):
    raise ImportError("model_type 'kimi_linear': this checkout's program has no models/delta_moe.py "
                      "(the gated delta-rule sparse-expert family), so it cannot serve the configuration")

# published config.json key -> DeltaMoEConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_hidden_layers": "num_hidden_layers",
    "first_k_dense_replace": "first_k_dense_replace",
    "num_attention_heads": "num_attention_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_use_nope": "mla_use_nope",
    "num_experts": "num_experts",
    "num_shared_experts": "num_shared_experts",
    "num_experts_per_token": "num_experts_per_token",
    "moe_renormalize": "moe_renormalize",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_expert_group": "num_expert_group",
    "topk_group": "topk_group",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "model_max_length": "max_seq_len",
    "tie_word_embeddings": "tie_word_embeddings",
    "ep_size": "ep_size",  # not a published key: the ranks that share a layer's routed experts
    "ep_rank": "ep_rank",  # not a published key: which of them this chip is
    "bos_token_id": "bos_token_id",  # not a published key: the stand-in tokenizer's
}
# read here, not (or not as they stand) fields of the program's configuration
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + ("linear_attn_config", "head_dim", "num_key_value_heads")
# published keys that select nothing in this decoder but must hold these values
FIXED = {"hidden_act": "silu", "moe_layer_freq": 1, "moe_router_activation_func": "sigmoid",
         "use_grouped_topk": True, "num_nextn_predict_layers": 0, "rope_scaling": None}
# the family's own weight statistics (the file's ``assumed`` says why): std as
# a multiple of 1/sqrt(fan_in)
QKV_GAIN = 1.0  # W_qkv: unit-spread inputs of the convolution, so that the SiLU behind the taps bends them
CONV_GAIN = 1.0  # the taps at std 1/sqrt(4): four different numbers a channel, not a flat mean
LOW_RANK_GAIN = 1.0  # f_a, f_b, g_a, g_b: a unit-spread lift under the softplus and under the output gate
BETA_GAIN = 1.0  # W_b: beta across (0, 1), not all at 1/2
KDA_OUT_GAIN = 1.0  # W_o of a linear layer: the mixer adds half the stream's size a layer, twenty times
MLA_Q_GAIN = 1.5  # W_q: scores of spread ~2 over ~3.5 k keys, so that a softmax is no plain mean
MLA_KV_GAIN = 1.25  # W_dkv (its 64-wide slice is not normed) and W_ukv
MLA_OUT_GAIN = 0.5
ROUTER_GAIN = 1.0  # logits of unit spread: sigmoid scores across (0, 1)
ROUTER_BIAS_STD = 0.1  # the correction bias: moves choices, never weights
EXPERT_GAIN = 0.5  # routed and shared experts: the other sparse families' reading
DENSE_GAIN = 0.5  # layer 0's SwiGLU
A_RANGE = (1.0, 16.0)  # A = exp(A_log) drawn uniform (the published initialisation)
DT_RANGE = (0.001, 0.1)  # softplus(dt_bias) drawn log-uniform (the published initialisation)
# --allow-cpu-rehearsal: a dense linear layer, then K K M | K K K M | K M
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=10,
    first_k_dense_replace=1, head_dim=16, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=16, num_experts_per_token=4,
    ep_size=2, ep_rank=1, model_max_length=1024,
    linear_attn_config={"full_attn_layers": [4, 8, 10], "kda_layers": [1, 2, 3, 5, 6, 7, 9], "head_dim": 16,
                        "num_heads": 4, "short_conv_kernel_size": 4})


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import DeltaMoEConfig

    if int(cfg.get("num_key_value_heads", cfg["num_attention_heads"])) != int(cfg["num_attention_heads"]):
        raise ValueError("latent attention has one KV per query head: num_key_value_heads "
                         "must equal num_attention_heads")
    if "head_dim" in cfg and int(cfg["head_dim"]) != int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]):
        raise ValueError("head_dim is hidden_size / num_attention_heads (no mixer reads it)")
    la = cfg["linear_attn_config"]
    unknown = set(la) - {"full_attn_layers", "kda_layers", "head_dim", "num_heads", "short_conv_kernel_size"}
    if unknown:
        raise ValueError(f"linear_attn_config: unknown keys {sorted(unknown)}")
    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return DeltaMoEConfig(
        full_attn_layers=tuple(la["full_attn_layers"]), kda_layers=tuple(la["kda_layers"]),
        kda_num_heads=int(la["num_heads"]), kda_head_dim=int(la["head_dim"]), kda_gate_rank=int(la["head_dim"]),
        short_conv_kernel_size=int(la["short_conv_kernel_size"]),
        eos_token_ids=(int(cfg["eos_token_id"]),), **fields)


def layer_loop_trips(cfg: dict) -> int:
    """A trip of the one ``lax.scan`` is a LAYER (its mixer a branch of the
    trip): every sparse layer; the leading dense ones sit in front of it."""
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def leaf_draw(path, config):
    """``(kind, value)`` of one leaf of the ``DeltaMoEModel`` tree: ``norm``
    (ones), ``bias`` (the router's correction bias, normal of std ``value``),
    ``a_log`` / ``dt_bias`` (the published initialisation), ``embedding``
    (unit std) or ``kernel`` (normal of std ``value / sqrt(fan_in)``, the
    fan-in the leaf's second-to-last axis: the four taps of ``conv_w`` too)."""
    name = path[-1]
    if name in ("A_log", "dt_bias"):
        return name.lower(), 0.0
    if any("norm" in part for part in path):
        return "norm", 1.0
    if name == "router_bias":
        return "bias", ROUTER_BIAS_STD
    if name == "embedding":
        return "embedding", 1.0
    if "experts" in path or "shared" in path:
        return "kernel", EXPERT_GAIN
    part = path[-2] if name == "kernel" else name
    if path[0] == "mla_layers":
        return "kernel", {"wq": MLA_Q_GAIN, "wkv_a": MLA_KV_GAIN, "wkv_b": MLA_KV_GAIN, "wo": MLA_OUT_GAIN}[part]
    if path[0] == "kda_layers":
        return "kernel", {"wqkv": QKV_GAIN, "conv_w": CONV_GAIN, "f_a": LOW_RANK_GAIN, "f_b": LOW_RANK_GAIN,
                          "g_a": LOW_RANK_GAIN, "g_b": LOW_RANK_GAIN, "b_proj": BETA_GAIN, "wo": KDA_OUT_GAIN}[part]
    return "kernel", {"router": ROUTER_GAIN, "w_gate": DENSE_GAIN, "w_up": DENSE_GAIN, "w_down": DENSE_GAIN}[part]


STACKED = ("layers", "experts", "kda_layers", "mla_layers")  # stacked over a leading axis: one slice a draw


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``DeltaMoEModel`` layout, every
    leaf born on its device in its serving dtype, in ONE jitted call. The
    statistics are ``lib/serve.py``'s (a unit-std embedding, ``serve.draw_head``'s
    head) with the family's own for the mixers, the router and both kinds of
    FFN (``leaf_draw``). Keys are folded from the root in the sorted order of
    the body's paths, the head's last: a leaf is its path's place in that
    order (``tests/recorded_weights_kimi_linear.json`` pins them)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.delta_moe import init_delta_moe_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    shapes = jax.eval_shape(lambda: init_delta_moe_params(jax.random.PRNGKey(0), config, dtypes))
    flat = traverse_util.flatten_dict(shapes)
    specs = traverse_util.flatten_dict(families.of(config).param_specs(shapes, mesh))
    body = sorted(p for p in flat if p != ("lm_head",))

    def draw(path, s, key):
        kind, value = leaf_draw(path, config)
        if kind == "norm":
            return jnp.full(s.shape, value, s.dtype)
        if kind == "bias":
            return (jax.random.normal(key, s.shape, jnp.float32) * value).astype(s.dtype)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, s.shape, jnp.float32, *A_RANGE)).astype(s.dtype)
        if kind == "dt_bias":  # the inverse softplus of a log-uniform time step
            dt = jnp.exp(jax.random.uniform(key, s.shape, jnp.float32, *(math.log(x) for x in DT_RANGE)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(s.dtype)
        std = 1.0 if kind == "embedding" else value / math.sqrt(s.shape[-2])

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if path[0] in STACKED:
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

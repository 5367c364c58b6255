"""``model_type: "longcat_flash"``: LongCat-Flash's decoder: every layer two
multi-head-latent-attention sublayers with a dense SwiGLU each, and ONE
shortcut-connected expert layer that branches off the first sublayer's normed
stream and joins the residual after the second's FFN; a softmax router over
the routed experts AND the zero-computation (identity) experts after them,
no groups, no shared expert, weights not renormalised; both LoRA scales on
the latent attention. Served as ONE CHIP'S SHARE of an expert-parallel
deployment (``ep_size`` ranks share each layer's routed experts; this chip is
``ep_rank``; attention, dense FFNs, router and zero experts whole on every
chip). The program runs it through ``models/latent_moe.py``, the family
``dots_vlm`` runs through too.

The contract is ``lib/serve.py FAMILY_CONTRACT``; what every family shares
(statistics, ``draw_head``, ``prng_key``) comes from ``lib/serve.py``.
Nothing runs at import and JAX is imported inside the functions.
"""

from __future__ import annotations

import math

from benchmark.lib import serve

# published config.json key -> LatentMoEConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "ffn_hidden_size": "intermediate_size",
    "expert_ffn_hidden_size": "moe_intermediate_size",
    "num_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_scale_q_lora": "mla_scale_q_lora",
    "mla_scale_kv_lora": "mla_scale_kv_lora",
    "n_routed_experts": "n_routed_experts",
    "zero_expert_num": "zero_expert_num",
    "moe_topk": "num_experts_per_tok",
    "routed_scaling_factor": "routed_scaling_factor",
    "ep_size": "ep_size",
    "ep_rank": "ep_rank",  # not a published key: which of the ep_size ranks this chip is
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "bos_token_id": "bos_token_id",
}
PUBLISHED_KEYS = tuple(HF_TO_CONFIG)
# keys that select nothing in this decoder but must hold these values for it
# to be the published block (the last four are the family's convention: the
# catalog's config does not carry them). The identity is the one kind of zero
# expert the program serves (it has no field for another): refused here
FIXED = {"attention_bias": False, "attention_method": "MLA", "zero_expert_type": "identity",
         "hidden_act": "silu", "norm_topk_prob": False, "router_bias": False,
         "tie_word_embeddings": False}
# what the block is beyond its keys: no leading dense layer, no shared expert,
# no groups, softmax scores that are not renormalised, identity zero experts
# (the program's default), two sublayers a layer, plain RoPE, an untied head
BLOCK = dict(first_k_dense=0, n_shared_experts=0, n_group=1, topk_group=1, scoring_func="softmax",
             norm_topk_prob=False, sublayers_per_layer=2, rope_scaling=None)
# the family's own weight statistics (the file's ``assumed`` says why): std as
# a multiple of 1/sqrt(fan_in). serve.LAYER_GAIN (0.25) is not used: at it an
# attention or a dense FFN adds 1% of the residual and a fault in one passes
# ``correct`` (references/longcat_flash.py has the readings at both)
DENSE_PATH_GAIN = 0.55  # both attention sublayers' and both dense FFNs' kernels: each adds about a tenth
ROUTER_GAIN = 1.0  # logits of unit spread: the 12 chosen of 768 weigh 0.04-0.1 each (x 6), 0.7 together
EXPERT_GAIN = 1.0  # at weights of 0.04-0.1 an expert at 0.5 is under 0.5% of the residual
ROUTER_BIAS_STD = 1.0 / 768  # e_score_correction_bias on the scores' own scale (their mean)
# --allow-cpu-rehearsal: two layers (four cache planes), 16 routed + 8 zero
# experts of which rank 1 of 2 holds 8, top-6
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=16, zero_expert_num=8, moe_topk=6, ep_size=2, ep_rank=1,
    max_position_embeddings=1024)


def model_config(cfg: dict):
    import dataclasses

    from rag_llm_k8s_tpu.core.config import LatentMoEConfig

    lacks = sorted(set(BLOCK) - {f.name for f in dataclasses.fields(LatentMoEConfig)})
    if lacks:
        raise NotImplementedError(
            f"this program's LatentMoEConfig has no {lacks}: it cannot run model_type 'longcat_flash'")
    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return LatentMoEConfig(eos_token_ids=(int(cfg["eos_token_id"]),), **BLOCK, **fields)


def layer_loop_trips(cfg: dict) -> int:
    """Every layer (both sublayers and the expert layer) is one trip of the
    one ``lax.scan``."""
    return int(cfg["num_layers"])


def leaf_draw(path, config):
    """``(kind, fan_in, gain)`` of one leaf of the ``LatentMoEModel`` tree:
    ``norm`` (ones), ``bias`` (the router's correction bias), ``embedding``
    (unit std) or ``kernel`` (normal of std ``gain / sqrt(fan_in)``)."""
    name = path[-1]
    if any("norm" in part for part in path):
        return "norm", 0, 0.0
    if name == "router_bias":
        return "bias", 0, ROUTER_BIAS_STD
    if name == "embedding":
        return "embedding", 0, 1.0
    D = config.hidden_size
    if "experts" in path:
        return "kernel", config.moe_intermediate_size if "w_down" in path else D, EXPERT_GAIN
    if "router" in path:
        return "kernel", D, ROUTER_GAIN
    fan_in = {"wq_b": config.q_lora_rank, "wkv_b": config.kv_lora_rank,
              "wo": config.num_heads * config.v_head_dim,
              "w_down": config.intermediate_size}.get(path[-2], D)
    return "kernel", fan_in, DENSE_PATH_GAIN


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``LatentMoEModel`` layout, every
    leaf born on its device in its serving dtype, in ONE jitted call. The
    statistics are ``lib/serve.py``'s in kind (RMSNorm weights 1, a unit-std
    embedding, normal kernels of std ``gain / sqrt(fan_in)``,
    ``serve.draw_head``'s head) with the family's own gains (``leaf_draw``).
    Keys are folded from the root in the sorted order of the body's paths, the
    head's last: a leaf is its path's place in that order
    (``tests/recorded_weights_longcat_flash.json``
    pins them)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.latent_moe import init_latent_moe_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    shapes = jax.eval_shape(lambda: init_latent_moe_params(jax.random.PRNGKey(0), config, dtypes))
    flat = traverse_util.flatten_dict(shapes)
    specs = traverse_util.flatten_dict(families.of(config).param_specs(shapes, mesh))
    body = sorted(p for p in flat if p != ("lm_head",))

    def draw(path, s, key):
        kind, fan_in, gain = leaf_draw(path, config)
        if kind == "norm":
            return jnp.ones(s.shape, s.dtype)
        std = gain if kind == "bias" else 1.0 if kind == "embedding" else gain / math.sqrt(fan_in)

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if path[0] in ("layers", "experts"):  # stacked over the layers: one layer per loop step
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

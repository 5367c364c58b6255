"""``model_type: "dots_vlm"``: the language model of dots.vlm1 (the
DeepSeek-V3 block key for key): multi-head latent attention over a latent
cache, leading dense layers, then 256-expert sigmoid-routed MoE layers with a
shared expert, served as ONE CHIP'S SHARE of an expert-parallel deployment
(``ep_size`` ranks share each layer's routed experts; this chip is
``ep_rank``). The program runs it through ``models/latent_moe.py``.

Not served, and said so in the configuration file: the NaViT vision tower
(``/generate`` takes text) and the multi-token-prediction module
(``num_nextn_predict_layers`` must be 0 here: a draft head after the last
layer, no part of the logits).

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
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "first_k_dense",
    "num_attention_heads": "num_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_routed_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
    "ep_size": "ep_size",
    "ep_rank": "ep_rank",  # not a published key: which of the ep_size ranks this chip is
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_word_embeddings",
    "bos_token_id": "bos_token_id",
}
# read here, not fields of the program's configuration
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + ("rope_scaling", "num_key_value_heads")
# published keys that select nothing in this decoder but must hold these
# values for it to be the published block (MTP: the one value served)
FIXED = {"hidden_act": "silu", "attention_bias": False, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "moe_layer_freq": 1, "seq_aux": True,
         "num_nextn_predict_layers": 0}
# the family's own weight statistics beside serve.LAYER_GAIN (the file's
# ``assumed`` says why): std as a multiple of 1/sqrt(fan_in)
ROUTER_GAIN = 1.0  # logits of unit spread: sigmoid scores across (0, 1), not all at 1/2
EXPERT_GAIN = 0.5  # routed and shared experts: a share of the residual a control on them can show
ROUTER_BIAS_STD = 0.05  # e_score_correction_bias: moves choices, never weights
# --allow-cpu-rehearsal: a leading dense layer, two MoE layers, 4 groups of 4
# experts of which rank 1 of 2 holds 8
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2, ep_size=2, ep_rank=1,
    max_position_embeddings=1024,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                  "mscale_all_dim": 1, "original_max_position_embeddings": 256})


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import LatentMoEConfig, YarnScalingConfig

    if int(cfg.get("num_key_value_heads", cfg["num_attention_heads"])) != int(cfg["num_attention_heads"]):
        raise ValueError("latent attention has one KV per query head: num_key_value_heads "
                         "must equal num_attention_heads")
    rs = cfg.get("rope_scaling")
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling.type={rs.get('type')!r}; this decoder runs 'yarn' only")
        rs = YarnScalingConfig(**{k: rs[k] for k in (
            "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
            "original_max_position_embeddings")})
    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return LatentMoEConfig(rope_scaling=rs, eos_token_ids=(int(cfg["eos_token_id"]),), **fields)


def layer_loop_trips(cfg: dict) -> int:
    """The MoE layers are the trips of the one ``lax.scan``; the leading
    dense layers sit outside it."""
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


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
    if "experts" in path or "shared" in path:
        fan_in = config.moe_intermediate_size * (config.n_shared_experts if "shared" in path else 1) \
            if "w_down" in path else D
        return "kernel", fan_in, EXPERT_GAIN
    if "router" in path:
        return "kernel", D, ROUTER_GAIN
    fan_in = {"wq_b": config.q_lora_rank, "wkv_b": config.kv_lora_rank,
              "wo": config.num_heads * config.v_head_dim,
              "w_down": config.intermediate_size}.get(path[-2], D)
    return "kernel", fan_in, serve.LAYER_GAIN


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``LatentMoEModel`` layout, every
    leaf born on its device in its serving dtype, in ONE jitted call. The
    statistics are ``lib/serve.py``'s (RMSNorm weights 1, a unit-std
    embedding, projection kernels of std ``LAYER_GAIN / sqrt(fan_in)``,
    ``serve.draw_head``'s head) with the family's own gains for the router
    and the experts (``leaf_draw``). Keys are folded from the root in the
    sorted order of the body's paths, the head's last: a leaf is its path's
    place in that order (``tests/recorded_weights_dots_vlm.json`` pins them)."""
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
        if kind == "bias":
            return (jax.random.normal(key, s.shape, jnp.float32) * gain).astype(s.dtype)
        std = 1.0 if kind == "embedding" else gain / math.sqrt(fan_in)

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if path[0] in ("layers", "experts"):  # stacked over the MoE layers: one layer per loop step
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

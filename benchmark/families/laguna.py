"""``model_type: "laguna"``: the Laguna decoder (GQA over 8 KV heads whose
layers are full or sliding-window attention with different query-head counts
and rotary tables, a per-head gate on attention's output, a leading dense
layer, then 256-expert sigmoid-routed top-10 MoE layers with a shared expert),
served as ONE CHIP'S SHARE of an expert-parallel deployment (``ep_size`` ranks
share each layer's routed experts; this chip is ``ep_rank``). The program runs
it through ``models/windowed_moe.py``.

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

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "windowed_moe.py")):
    raise ImportError("model_type 'laguna': this checkout's program has no models/windowed_moe.py "
                      "(the windowed-attention sparse-expert family), so it cannot serve the configuration")

# published config.json key -> WindowedMoEConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "shared_expert_intermediate_size": "shared_expert_intermediate_size",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "sliding_window": "sliding_window",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "moe_routed_scaling_factor": "moe_routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
    "moe_router_logit_softcapping": "moe_router_logit_softcapping",
    "moe_apply_router_weight_on_input": "moe_apply_router_weight_on_input",
    "ep_size": "ep_size",
    "ep_rank": "ep_rank",  # not a published key: which of the ep_size ranks this chip is
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_word_embeddings",
    "bos_token_id": "bos_token_id",
}
# read here, not (or not as they stand) fields of the program's configuration
PER_LAYER = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "gating_types")
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + PER_LAYER + (
    "rope_parameters", "num_hidden_layers", "num_attention_heads", "mlp_only_layers")
# published keys that select nothing in this decoder but must hold these
# values for it to be the published block
FIXED = {"attention_bias": False, "decoder_sparse_step": 1, "gating": "per-head"}
# the family's own weight statistics beside serve.LAYER_GAIN (the file's
# ``assumed`` says why): std as a multiple of 1/sqrt(fan_in)
ROUTER_GAIN = 1.0  # logits of unit spread: sigmoid scores across (0, 1), not all at 1/2
EXPERT_GAIN = 0.5  # routed and shared experts: a share of the residual a control on them can show
GATE_GAIN = 1.0  # the gate's pre-activation of unit spread: softplus from 0.3 to 1.3, not all ln 2
# W_q, W_k: attention scores of spread QK_GAIN ** 2 on a sliding layer (2.4 times that on a full
# one: YaRN's factor on the rotated half). At lib/serve.py's 0.25 the spread is 0.06, every softmax
# is a plain mean of its keys, and a sliding layer run as a full one moves no logit by more than
# the program's own bf16 distance (chip readings at 0.25: PERF.md section 6, PR 33)
QK_GAIN = 1.2
VO_GAIN = 1.0  # W_v, W_o: attention adds a tenth to a quarter of the residual stream a layer, not a hundredth
ROUTER_BIAS_STD = 0.05  # the selection-only bias: moves choices, never weights
# --allow-cpu-rehearsal: a dense full layer, then two periods of (two sliding
# layers of 9 query heads a KV head, one full layer of 6), 16 experts of which
# rank 1 of 2 holds 8
_KINDS = ["full_attention"] + ["sliding_attention", "sliding_attention", "full_attention"] * 2
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_hidden_layers=7, num_attention_heads=12,
    num_key_value_heads=2, head_dim=16, sliding_window=64, layer_types=_KINDS,
    num_attention_heads_per_layer=[18 if k == "sliding_attention" else 12 for k in _KINDS],
    mlp_layer_types=["dense"] + ["sparse"] * 6, gating_types=["per_head"] * 7, mlp_only_layers=[0],
    num_experts=16, num_experts_per_tok=4, ep_size=2, ep_rank=1, max_position_embeddings=1024,
    rope_parameters={
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                           "original_max_position_embeddings": 256, "beta_slow": 1, "beta_fast": 32,
                           "attention_factor": 1.1386, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}})


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import RopeParameters, WindowedMoEConfig

    depth = int(cfg["num_hidden_layers"])
    for key in PER_LAYER:
        if len(cfg[key]) != depth:
            raise ValueError(f"{key} names {len(cfg[key])} layers, num_hidden_layers is {depth}")
    if set(cfg["gating_types"]) != {"per_head"}:
        raise ValueError("gating_types: this decoder gates per head in every layer")
    dense = [i for i, t in enumerate(cfg["mlp_layer_types"]) if t == "dense"]
    if dense != [int(i) for i in cfg.get("mlp_only_layers", dense)]:
        raise ValueError("mlp_only_layers and mlp_layer_types disagree on which layers are dense")
    full = {h for h, k in zip(cfg["num_attention_heads_per_layer"], cfg["layer_types"]) if k == "full_attention"}
    if full and full != {int(cfg.get("num_attention_heads", next(iter(full))))}:
        raise ValueError("num_attention_heads is a full-attention layer's head count")
    rope = tuple((kind, RopeParameters(**{k: p[k] for k in p})) for kind, p in sorted(cfg["rope_parameters"].items()))
    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return WindowedMoEConfig(
        layer_types=tuple(cfg["layer_types"]), mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        num_attention_heads_per_layer=tuple(int(h) for h in cfg["num_attention_heads_per_layer"]),
        rope_parameters=rope, eos_token_ids=(int(cfg["eos_token_id"]),), **fields)


def layer_loop_trips(cfg: dict) -> int:
    """A trip of the one ``lax.scan`` is a PERIOD (the sliding layers and the
    full one that closes them); the leading dense layers sit outside it."""
    ffn, kinds = list(cfg["mlp_layer_types"]), list(cfg["layer_types"])
    lead = next((i for i, t in enumerate(ffn) if t != "dense"), len(ffn))
    rest = kinds[lead:]
    period = rest.index("full_attention") + 1 if "full_attention" in rest else 1
    return (int(cfg["num_hidden_layers"]) - lead) // period


def leaf_draw(path, config):
    """``(kind, fan_in, gain)`` of one leaf of the ``WindowedMoEModel`` tree:
    ``norm`` (ones), ``bias`` (the router's selection bias), ``embedding``
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
        width = config.shared_expert_intermediate_size if "shared" in path else config.moe_intermediate_size
        return "kernel", width if "w_down" in path else D, EXPERT_GAIN
    if "router" in path:
        return "kernel", D, ROUTER_GAIN
    if "wg" in path:
        return "kernel", D, GATE_GAIN
    if "wo" in path:  # fan-in: the layer's own query heads (it is the leaf's second-to-last axis)
        return "kernel", None, VO_GAIN
    if "wq" in path or "wk" in path or "wv" in path:
        return "kernel", D, VO_GAIN if "wv" in path else QK_GAIN
    return "kernel", config.intermediate_size if path[-2] == "w_down" else D, serve.LAYER_GAIN


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``WindowedMoEModel`` layout, every
    leaf born on its device in its serving dtype, in ONE jitted call. The
    statistics are ``lib/serve.py``'s (RMSNorm weights 1, a unit-std
    embedding, projection kernels of std ``LAYER_GAIN / sqrt(fan_in)``,
    ``serve.draw_head``'s head) with the family's own gains for the router,
    the experts and the gate (``leaf_draw``). Keys are folded from the root
    in the sorted order of the body's paths, the head's last: a leaf is its
    path's place in that order (``tests/recorded_weights_laguna.json`` pins them)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.windowed_moe import init_windowed_moe_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    shapes = jax.eval_shape(lambda: init_windowed_moe_params(jax.random.PRNGKey(0), config, dtypes))
    flat = traverse_util.flatten_dict(shapes)
    specs = traverse_util.flatten_dict(families.of(config).param_specs(shapes, mesh))
    body = sorted(p for p in flat if p != ("lm_head",))

    def draw(path, s, key):
        kind, fan_in, gain = leaf_draw(path, config)
        if kind == "norm":
            return jnp.ones(s.shape, s.dtype)
        if kind == "bias":
            return (jax.random.normal(key, s.shape, jnp.float32) * gain).astype(s.dtype)
        std = 1.0 if kind == "embedding" else gain / math.sqrt(fan_in or s.shape[-2])

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

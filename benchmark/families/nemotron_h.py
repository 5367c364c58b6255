"""``model_type: "nemotron_h"``: NVIDIA-Nemotron-3-Super-120B-A12B (88 layers,
each ONE of a Mamba-2 mixer ``M``, a rope-free GQA attention ``*`` or a
latent-space expert layer ``E``, by ``hybrid_override_pattern``; 512
sigmoid-routed experts of which a token takes 22, each two matrices with a
squared relu between, inside a 1024-wide latent; a shared expert on the
stream), served as ONE CHIP OF A FOUR-CHIP PIPELINE STAGE: the first period of
the pattern (11 layers) with ``ep_size`` ranks sharing each expert layer's
routed experts (this chip is ``ep_rank``). The program runs it through
``models/ssd_moe.py``.

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

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "ssd_moe.py")):
    raise ImportError("model_type 'nemotron_h': this checkout's program has no models/ssd_moe.py "
                      "(the state-space-duality latent-expert family), so it cannot serve the configuration")

# the keys handed to SSDMoEConfig as they stand: the class keeps the published names
CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads",
    "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "chunk_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_latent_size", "moe_shared_expert_intermediate_size", "n_shared_experts", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon", "max_position_embeddings",
    "tie_word_embeddings",
    "time_step_min", "time_step_max", "time_step_floor",  # the time step's initialisation range: no clamps
    "ep_size",  # not a published key: the ranks that share a layer's routed experts
    "ep_rank",  # not a published key: which of them this chip is
    "bos_token_id",  # not a published key: the stand-in tokenizer's
)
# read here and no field of the program's configuration: ``expand``, which the mixer's width must agree with
PUBLISHED_KEYS = CONFIG_KEYS + ("expand",)
# published keys that select nothing in this decoder but must hold these
# values. ``intermediate_size`` is the '-' (dense MLP) layer's, of which the
# pattern holds none; ``rope_theta`` and ``partial_rotary_factor`` are read by
# nothing (the block rotates nothing: the file's ``assumed``); the
# multi-token-prediction block is left out (``num_nextn_predict_layers`` 0)
FIXED = {"attention_bias": False, "intermediate_size": 2688, "mamba_hidden_act": "silu", "mamba_proj_bias": False,
         "mlp_bias": False, "mlp_hidden_act": "relu2", "moe_shared_expert_overlap": False,
         "mtp_hybrid_override_pattern": "*E", "norm_eps": 1e-05, "num_logits_to_keep": 1,
         "num_nextn_predict_layers": 0, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
         "residual_in_fp32": False, "rope_theta": 10000, "sliding_window": None, "use_bias": False,
         "use_conv_bias": True, "use_mamba_kernels": True}
# the family's own weight statistics (the file's ``assumed`` says why): std as
# a multiple of 1/sqrt(fan_in)
IN_GAIN = 1.0  # W_in's z, x and dt columns: unit spread, so that the SiLUs and the softplus are not linear
BC_GAIN = 3.0  # W_in's B and C columns (nothing norms them): the state's part of y beside D * x's
CONV_GAIN = 1.0  # the taps at std 1/sqrt(4): four different numbers a channel, not a flat mean
CONV_BIAS_STD = 0.1  # use_conv_bias: a bias that is there
MAMBA_OUT_GAIN = 0.5  # W_out: the gated group norm hands it unit-spread channels
Q_GAIN = 1.5  # W_q: scores of spread ~1.5 over ~3.5 k keys, so that a softmax is no plain mean
KV_GAIN = 1.0
ATTN_OUT_GAIN = 0.5
ROUTER_GAIN = 1.0  # logits of unit spread: sigmoid scores across (0, 1)
ROUTER_BIAS_STD = 0.1  # the correction bias: moves choices, never weights
LATENT_GAIN = 1.0  # W_down, W_up: a unit-spread latent, so that the squared relu bends it
# routed experts, both matrices (the other sparse families' reading). A squared relu is homogeneous, so the two
# gains only size an expert's output: at 1.0 a token's 22nd and 23rd choice, 0.02 of a logit apart among 512
# scores, swap under bf16's noise and each swap moves the stream by a sixth of itself (my chip run, PR 55:
# the served stream against the program's OWN exact path read 0.31 where 0.15 is allowed); at 0.5 a swap is 2%
EXPERT_GAIN = 0.5
SHARED_UP_GAIN, SHARED_DOWN_GAIN = 1.0, 0.5
# --allow-cpu-rehearsal: the published period's shape at toy widths
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=128, num_hidden_layers=7, hybrid_override_pattern="MEM*EME", mamba_num_heads=8,
    mamba_head_dim=16, n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8, expand=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, n_routed_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=48, moe_latent_size=64, moe_shared_expert_intermediate_size=96, n_shared_experts=1,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=5, layer_norm_epsilon=1e-5,
    ep_size=2, ep_rank=1, max_position_embeddings=1024, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=0.0001)


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import SSDMoEConfig

    if int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"]) != int(cfg["expand"]) * int(cfg["hidden_size"]):
        raise ValueError("mamba_num_heads * mamba_head_dim must be expand * hidden_size")
    return SSDMoEConfig(eos_token_ids=(int(cfg["eos_token_id"]),), **{k: cfg[k] for k in CONFIG_KEYS if k in cfg})


def layer_loop_trips(cfg: dict) -> int:
    """A trip of the one ``lax.scan`` is a LAYER (its kind a branch of the
    trip), and every layer is in it."""
    return int(cfg["num_hidden_layers"])


def leaf_draw(name: str, shape, config):
    """``(kind, value)`` of one leaf of the ``SSDMoEModel`` tree (flat names,
    a kind's leaves stacked over its layers): ``const`` (every entry
    ``value``), ``normal`` (of std ``value``), ``in_proj`` (normal of std
    ``value``, the B and C columns at ``BC_GAIN``), ``a_log`` or ``dt_bias``
    (the published initialisation)."""
    if name == "mamba_A_log":
        return "a_log", 0.0
    if name == "mamba_dt_bias":
        return "dt_bias", 0.0
    if "norm" in name or name == "mamba_D":
        return "const", 1.0
    if name == "mamba_conv_b":
        return "normal", CONV_BIAS_STD
    if name == "moe_router_bias":
        return "normal", ROUTER_BIAS_STD
    if name == "embedding":
        return "normal", 1.0
    fan_in = shape[-2]
    if name == "mamba_in_proj":
        return "in_proj", IN_GAIN / math.sqrt(fan_in)
    gain = {"mamba_conv_w": CONV_GAIN, "mamba_out_proj": MAMBA_OUT_GAIN, "attn_wq": Q_GAIN, "attn_wk": KV_GAIN,
            "attn_wv": KV_GAIN, "attn_wo": ATTN_OUT_GAIN, "moe_router": ROUTER_GAIN,
            "moe_latent_down": LATENT_GAIN, "moe_latent_up": LATENT_GAIN, "moe_shared_up": SHARED_UP_GAIN,
            "moe_shared_down": SHARED_DOWN_GAIN, "experts_w_up": EXPERT_GAIN, "experts_w_down": EXPERT_GAIN}[name]
    return "normal", gain / math.sqrt(fan_in)


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``SSDMoEModel`` layout, every
    leaf born on its device in its serving dtype, in ONE jitted call. The
    statistics are ``lib/serve.py``'s (a unit-std embedding,
    ``serve.draw_head``'s head) with the family's own for the three layer
    kinds (``leaf_draw``; ``A_log``, ``D``, the time step's bias and the
    routers' correction bias float32). A stacked leaf is drawn a layer at a
    time and the held experts an expert at a time. Keys are folded from the
    root in the sorted order of the body's names, the head's last
    (``tests/recorded_weights_nemotron_h.json`` pins them). ``A_log`` and
    the time step's bias are the model's own initialisers (log U(1, 16) a
    head; the inverse softplus of a log-uniform draw over the configuration's
    ``time_step_min .. time_step_max``, floored at ``time_step_floor``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.ssd_moe import a_log_init, dt_bias_init, init_ssd_moe_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    shapes = jax.eval_shape(lambda: init_ssd_moe_params(jax.random.PRNGKey(0), config, dtypes))
    specs = families.of(config).param_specs(shapes, mesh)
    body = sorted(n for n in shapes if n != "lm_head")
    Di, GN = config.d_inner, config.n_groups * config.ssm_state_size
    columns = jnp.concatenate([jnp.full((2 * Di,), 1.0), jnp.full((2 * GN,), BC_GAIN / IN_GAIN),
                               jnp.full((config.mamba_num_heads,), 1.0)])  # z | x | B | C | dt

    def draw(name, s, key):
        kind, value = leaf_draw(name, s.shape, config)
        if kind == "const":
            return jnp.full(s.shape, value, s.dtype)
        if kind in ("a_log", "dt_bias"):
            return (a_log_init if kind == "a_log" else dt_bias_init(config))(key, s.shape, s.dtype)
        scale = value * columns if kind == "in_proj" else value

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * scale).astype(s.dtype)

        if name == "embedding":
            return block(key, s.shape)
        if name.startswith("experts_"):  # [expert layers, held, in, out]: an expert a step
            return jax.lax.map(lambda ks: jax.lax.map(lambda k: block(k, s.shape[2:]), ks),
                               jax.random.split(key, s.shape[:2]))
        return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))

    def make(root):
        out = {n: draw(n, shapes[n], jax.random.fold_in(root, i)) for i, n in enumerate(body)}
        (out["lm_head"],) = serve.draw_head(jax.random.fold_in(root, len(shapes)), out["embedding"],
                                            config.eos_token_ids, recite_gain, shapes["lm_head"].dtype)
        return out

    shardings = {n: NamedSharding(mesh.mesh, specs[n]) for n in shapes}
    return jax.jit(make, out_shardings=shardings)(serve.prng_key(seed, 0))

"""``model_type: "jamba"``: the Jamba decoder (most layers a Mamba-1 mixer
with RMS norms on the time step, ``B`` and ``C``; every
``attn_layer_period``-th a grouped-query attention layer with no position term
at all; a dense SwiGLU in every layer), served WHOLE on one chip: every layer,
every head, the whole vocabulary. The program runs it through
``models/hybrid_ssm.py``.

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

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "hybrid_ssm.py")):
    raise ImportError("model_type 'jamba': this checkout's program has no models/hybrid_ssm.py "
                      "(the hybrid state-space family), so it cannot serve the configuration")

# published config.json key -> HybridSSMConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_hidden_layers",
    "num_attention_heads": "num_attention_heads",
    "num_key_value_heads": "num_key_value_heads",
    "attn_layer_period": "attn_layer_period",
    "attn_layer_offset": "attn_layer_offset",
    "mamba_d_state": "mamba_d_state",
    "mamba_d_conv": "mamba_d_conv",
    "mamba_expand": "mamba_expand",
    "mamba_dt_rank": "mamba_dt_rank",
    "mamba_conv_bias": "mamba_conv_bias",
    "mamba_proj_bias": "mamba_proj_bias",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "bos_token_id": "bos_token_id",  # not a published key: the stand-in tokenizer's
}
# published, and not handed to the program as it stands: the head is served
# untied (above), whatever this says
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + ("tie_word_embeddings",)
# published keys that select nothing in this decoder but must hold these
# values for it to be the published block: one expert makes every
# feed-forward part a dense SwiGLU whatever the expert period says
FIXED = {"hidden_act": "silu", "sliding_window": None, "num_experts": 1, "num_experts_per_tok": 1,
         "expert_layer_period": 2, "expert_layer_offset": 1, "use_mamba_kernels": True,
         "num_logits_to_keep": 1}
# the family's own weight statistics beside serve.LAYER_GAIN (the file's
# ``assumed`` says why): std as a multiple of 1/sqrt(fan_in)
IN_GAIN = 1.0  # W_in: u and z of unit spread, so that silu and the gate are not linear
X_GAIN = 1.0  # W_x (its outputs are RMS-normed: the gain only keeps them clear of eps)
DT_GAIN = 1.0  # W_dt: the time step moves by e^+-1 with the token, so that the scan selects
OUT_GAIN = 0.5  # W_out: the mixer adds a tenth or more of the stream a layer, 26 times
BC_SCALE = 2.0  # the B and C norms' scales: the state's part of y is a quarter of D * u's, not a sixtieth
CONV_GAIN = 1.0  # the convolution's taps, std 1/sqrt(d_conv)
QK_GAIN = 1.2  # W_q, W_k: scores of spread 1.44 over ~3 k keys, so that a softmax is no plain mean
VO_GAIN = 1.0  # W_v, W_o
DT_MIN, DT_MAX = 1e-3, 1e-1  # the time step's bias: softplus^-1 of a log-uniform draw (Mamba's initialisation)
# --allow-cpu-rehearsal: four layers of which 1 and 3 are attention
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=1, attn_layer_period=2, attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank=4, max_position_embeddings=1024)


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import HybridSSMConfig

    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return HybridSSMConfig(eos_token_ids=(int(cfg["eos_token_id"]),), tie_word_embeddings=False, **fields)


def layer_loop_trips(cfg: dict) -> int:
    """Layers of both kinds are trips of ONE ``lax.scan`` over the depth."""
    return int(cfg["num_hidden_layers"])


def leaf_draw(name: str, shape, config):
    """``(kind, value)`` of one leaf of the ``HybridSSMModel`` tree: ``const``
    (every entry ``value``), ``normal`` (of std ``value``), ``a_log`` (``log(1
    .. d_state)`` a channel) or ``dt_bias``."""
    if name == "ssm_A_log":
        return "a_log", 0.0
    if name == "ssm_dt_bias":
        return "dt_bias", 0.0
    if name in ("ssm_b_norm", "ssm_c_norm"):
        return "const", BC_SCALE
    if "norm" in name or name == "ssm_D":
        return "const", 1.0
    if name == "ssm_conv_b":
        return "const", 0.0
    if name == "embedding":
        return "normal", 1.0
    fan_in = shape[-2]
    gain = {"ssm_in_proj": IN_GAIN, "ssm_x_proj": X_GAIN, "ssm_dt_proj": DT_GAIN, "ssm_out_proj": OUT_GAIN,
            "ssm_conv_w": CONV_GAIN, "attn_wq": QK_GAIN, "attn_wk": QK_GAIN, "attn_wv": VO_GAIN,
            "attn_wo": VO_GAIN}.get(name, serve.LAYER_GAIN)
    return "normal", gain / math.sqrt(fan_in)


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``HybridSSMModel`` layout (flat
    names, leaves stacked by layer kind), every leaf born on its device in its
    serving dtype, in ONE jitted call. The statistics are ``lib/serve.py``'s
    (norm scales 1; a unit-std embedding; the SwiGLU of std ``LAYER_GAIN /
    sqrt(fan_in)``; ``serve.draw_head``) with the family's own for the two
    mixers (``leaf_draw``; ``A_log``, ``D`` and the time step's bias float32).
    Keys are folded from the root in the sorted order of the body's names,
    the head's last (``tests/recorded_weights_jamba.json`` pins them)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.hybrid_ssm import init_hybrid_ssm_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    if config.tie_word_embeddings:
        raise ValueError("the benchmark serves serve.draw_head's untied head: tie_word_embeddings=False")
    shapes = jax.eval_shape(lambda: init_hybrid_ssm_params(jax.random.PRNGKey(0), config, dtypes))
    specs = families.of(config).param_specs(shapes, mesh)
    body = sorted(n for n in shapes if n != "lm_head")

    def draw(name, s, key):
        kind, value = leaf_draw(name, s.shape, config)
        if kind == "const":
            return jnp.full(s.shape, value, s.dtype)
        if kind == "a_log":  # [layers, d_state, d_inner]
            return jnp.broadcast_to(jnp.log(jnp.arange(1, s.shape[1] + 1, dtype=jnp.float32))[None, :, None],
                                    s.shape).astype(s.dtype)
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, s.shape, jnp.float32, math.log(DT_MIN), math.log(DT_MAX)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(s.dtype)

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * value).astype(s.dtype)

        if name != "embedding":  # stacked over layers: one slice a step
            return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))
        return block(key, s.shape)

    def make(root):
        out = {n: draw(n, shapes[n], jax.random.fold_in(root, i)) for i, n in enumerate(body)}
        (out["lm_head"],) = serve.draw_head(jax.random.fold_in(root, len(shapes)), out["embedding"],
                                            config.eos_token_ids, recite_gain, shapes["lm_head"].dtype)
        return out

    shardings = {n: NamedSharding(mesh.mesh, specs[n]) for n in shapes}
    return jax.jit(make, out_shardings=shardings)(serve.prng_key(seed, 0))

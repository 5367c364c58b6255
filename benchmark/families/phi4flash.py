"""``model_type: "phi4flash"``: Phi-4-mini-flash-reasoning, a
decoder-hybrid-decoder (the first 18 of 32 layers Mamba-1 beside window-512
and one full DIFFERENTIAL-attention layer; the last 14 keep no state of their
own: 7 cross-attention layers read layer 17's one K/V plane, 7 gated memory
units read layer 16's scan output; a LayerNorm with mean and bias and a dense
SwiGLU in every layer; no position term), served WHOLE on one chip: every
layer, every head, the whole vocabulary. The program runs it through
``models/cross_decoder.py``.

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

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "cross_decoder.py")):
    raise ImportError("model_type 'phi4flash': this checkout's program has no models/cross_decoder.py "
                      "(the decoder-hybrid-decoder family), so it cannot serve the configuration")

# published config.json key -> CrossDecoderConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_hidden_layers",
    "num_attention_heads": "num_attention_heads",
    "num_key_value_heads": "num_key_value_heads",
    "mb_per_layer": "mb_per_layer",
    "sliding_window": "sliding_window",
    "layer_norm_eps": "layer_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "mamba_d_state": "mamba_d_state",  # the four state-space sizes are not published keys: the
    "mamba_d_conv": "mamba_d_conv",  # file states the family's published defaults (``assumed``)
    "mamba_expand": "mamba_expand",
    "mamba_dt_rank": "mamba_dt_rank",
    "bos_token_id": "bos_token_id",  # not a published key: the stand-in tokenizer's
}
# published, and not handed to the program as it stands: the head is served
# untied (above), whatever this says
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + ("tie_word_embeddings",)
# published keys that select nothing in this decoder but must hold these values
FIXED = {"hidden_act": "silu", "embd_pdrop": 0, "resid_pdrop": 0, "mlp_bias": False, "lm_head_bias": False}
# the family's own weight statistics beside serve.LAYER_GAIN (the file's
# ``assumed`` says why): std as a multiple of 1/sqrt(fan_in)
IN_GAIN = 1.0  # W_in of a Mamba layer and of a memory unit: unit spread, so that silu and the gates are not linear
X_GAIN = 1.0  # W_x's time-step columns
BC_GAIN = 3.0  # W_x's B and C columns (nothing norms them here): the state's part of m beside D * u's
DT_GAIN = 1.7  # W_dt: the time step moves by e^+-1 with the token, so that the scan selects
OUT_GAIN = 0.5  # W_out of a Mamba layer and of a memory unit, W_o of an attention mixer
CONV_GAIN = 1.0  # the convolution's taps, std 1/sqrt(d_conv)
QK_GAIN = 1.5  # W_q, W_k: scores of spread 2.25, so that a softmax over 12 k keys is no plain mean
V_GAIN = 1.0
BIAS_STD = 0.1  # b_q, b_k, b_v, b_o
LAMBDA_STD = 0.1  # the four lambda vectors, as published: lambda ~ lambda_init
DT_MIN, DT_MAX = 1e-3, 1e-1  # the time step's bias: softplus^-1 of a log-uniform draw (Mamba's initialisation)
# --allow-cpu-rehearsal: 8 layers (two (Mamba, window) pairs, the memory's
# layer, the full layer, one (memory unit, cross) pair)
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, sliding_window=64, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=4, max_position_embeddings=1024)


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import CrossDecoderConfig

    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return CrossDecoderConfig(eos_token_ids=(int(cfg["eos_token_id"]),), tie_word_embeddings=False, **fields)


def layer_loop_trips(cfg: dict) -> int:
    """A fresh prompt's prefill holds ONE loop: the (Mamba, window) pairs.
    The memory's layer and the full layer stand behind it and the
    cross-decoder's one position is written out (``models/cross_decoder.py``)."""
    return int(cfg["num_hidden_layers"]) // 4


def leaf_draw(name: str, shape, config):
    """``(kind, value)`` of one leaf of the ``CrossDecoderModel`` tree:
    ``const`` (every entry ``value``), ``normal`` (of std ``value``),
    ``x_proj`` (normal, the B and C columns at ``BC_GAIN``), ``a_log``
    (``log(1 .. d_state)`` a channel) or ``dt_bias``."""
    if name == "ssm_A_log":
        return "a_log", 0.0
    if name == "ssm_dt_bias":
        return "dt_bias", 0.0
    if name.endswith("_b") or name == "ssm_conv_b":  # the LayerNorms' biases, the convolution's
        return "const", 0.0
    if "norm" in name or name.endswith("_subln") or name == "ssm_D":
        return "const", 1.0
    if "_lambda_" in name:
        return "normal", LAMBDA_STD
    if name.rsplit("_", 1)[-1] in ("bq", "bk", "bv", "bo"):
        return "normal", BIAS_STD
    if name == "embedding":
        return "normal", 1.0
    fan_in = shape[-2]
    if name == "ssm_x_proj":
        return "x_proj", 1.0 / math.sqrt(fan_in)
    gain = {"ssm_in_proj": IN_GAIN, "gmu_in_proj": IN_GAIN, "ssm_dt_proj": DT_GAIN, "ssm_out_proj": OUT_GAIN,
            "gmu_out_proj": OUT_GAIN, "ssm_conv_w": CONV_GAIN, "attn_wq": QK_GAIN, "attn_wk": QK_GAIN,
            "cross_wq": QK_GAIN, "attn_wv": V_GAIN, "attn_wo": OUT_GAIN, "cross_wo": OUT_GAIN,
            }.get(name, serve.LAYER_GAIN)
    return "normal", gain / math.sqrt(fan_in)


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``CrossDecoderModel`` layout
    (flat names, leaves stacked by layer kind), every leaf born on its device
    in its serving dtype. TWO jitted calls, the embedding and the head first:
    ``serve.draw_head`` holds a float32 ``[D, V]`` or two while it runs (2 GB
    each at 200064 entries), which beside the whole body would crowd the
    chip. The statistics are ``lib/serve.py``'s (norm scales 1; a unit-std
    embedding; the SwiGLU of std ``LAYER_GAIN / sqrt(fan_in)``;
    ``serve.draw_head``) with the family's own for the mixers (``leaf_draw``;
    ``A_log``, ``D``, the time step's bias and the lambdas float32). Keys are
    folded from the root in the sorted order of the body's names, the head's
    last (``tests/recorded_weights_phi4flash.json`` pins them)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.cross_decoder import init_cross_decoder_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    if config.tie_word_embeddings:
        raise ValueError("the benchmark serves serve.draw_head's untied head: tie_word_embeddings=False")
    shapes = jax.eval_shape(lambda: init_cross_decoder_params(jax.random.PRNGKey(0), config, dtypes))
    specs = families.of(config).param_specs(shapes, mesh)
    body = sorted(n for n in shapes if n != "lm_head")
    R, N = config.mamba_dt_rank, config.mamba_d_state

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
        scale = value
        if kind == "x_proj":  # [.., d_inner, R + 2 N]: the time step's columns, then B's and C's
            scale = value * jnp.concatenate([jnp.full((R,), X_GAIN), jnp.full((2 * N,), BC_GAIN)])

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * scale).astype(s.dtype)

        if name != "embedding":  # stacked over layers: one slice a step
            return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))
        return block(key, s.shape)

    def key_of(root, name):
        return jax.random.fold_in(root, body.index(name))

    def ends(root):
        embedding = draw("embedding", shapes["embedding"], key_of(root, "embedding"))
        (head,) = serve.draw_head(jax.random.fold_in(root, len(shapes)), embedding, config.eos_token_ids,
                                  recite_gain, shapes["lm_head"].dtype)
        return {"embedding": embedding, "lm_head": head}

    def middle(root):
        return {n: draw(n, shapes[n], key_of(root, n)) for n in body if n != "embedding"}

    def shardings(names):
        return {n: NamedSharding(mesh.mesh, specs[n]) for n in names}

    root = serve.prng_key(seed, 0)
    out = jax.jit(ends, out_shardings=shardings(("embedding", "lm_head")))(root)
    jax.block_until_ready(out)
    out.update(jax.jit(middle, out_shardings=shardings([n for n in body if n != "embedding"]))(root))
    return out

"""``model_type: "mistral"``: the dense grouped-query decoder block (RMSNorm,
RoPE by halves, SwiGLU, an untied head) that the program runs through
``models/llama.py`` with numbers only.

A family is where a configuration file becomes the program's model and its
seeded weights. ``lib/serve.py load_family`` finds this file by the
configuration's published ``model_type`` and holds it to the contract
``serve.FAMILY_CONTRACT`` names; what every family shares (the keys every file
has, the statistics, the reciting head, ``prng_key``) it takes from
``lib/serve.py``. Nothing here runs at import, and JAX is imported inside the
functions: reading a configuration touches no device.
"""

from __future__ import annotations

import math

from benchmark.lib import serve

# published config.json key -> LlamaConfig field
HF_TO_LLAMA = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_word_embeddings",
    "bos_token_id": "bos_token_id",
}
PUBLISHED_KEYS = tuple(HF_TO_LLAMA)
# published keys that select nothing in this decoder but must hold these
# values for it to be the published block
FIXED = {"hidden_act": "silu", "sliding_window": None, "rope_scaling": None,
         "attention_bias": False, "mlp_bias": False}
# --allow-cpu-rehearsal: the block at a size the CPU finishes in a minute
REHEARSAL_MODEL = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                       head_dim=16, max_position_embeddings=1024)


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import LlamaConfig

    fields = {dst: cfg[src] for src, dst in HF_TO_LLAMA.items() if src in cfg}
    return LlamaConfig(rope_scaling=None, eos_token_ids=(int(cfg["eos_token_id"]),), **fields)


def layer_loop_trips(cfg: dict) -> int:
    """Every layer is a trip of the one ``lax.scan`` over the stacked block."""
    return int(cfg["num_hidden_layers"])


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``LlamaModel`` layout, every
    leaf born on its device(s) in its serving dtype and sharding, in ONE
    jitted call. The statistics are those of ``utils/synth.py
    synth_llama_params`` (PR 21): RMSNorm weights 1; projection kernels of
    std ``0.25/sqrt(fan_in)``; a unit-std embedding; the head is
    ``serve.draw_head``'s. The keys are folded from the root in the sorted
    order of the body's paths, the head's last: a leaf is its path's place in
    that order, so the tree may not gain or lose a leaf without redrawing
    every cell's weights (``tests/recorded_weights.json`` pins them)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models.llama import (
        init_llama_params, quantize_llama_params, synth_leaf_kind,
    )
    from rag_llm_k8s_tpu.parallel.sharding import llama_param_specs

    shapes = jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), config, dtypes))
    if quant == "int8":
        shapes = jax.eval_shape(quantize_llama_params, shapes)
    elif quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: expected 'bf16' or 'int8'")
    flat = traverse_util.flatten_dict(shapes)
    specs = traverse_util.flatten_dict(llama_param_specs(shapes, mesh))
    D = config.hidden_size
    head_paths = [p for p in (("lm_head",), ("lm_head_q",), ("lm_head_scale",)) if p in flat]
    body = sorted(p for p in flat if p not in head_paths)

    def draw(path, s, key):
        kind = synth_leaf_kind(path, s.dtype)
        if kind == "norm":
            return jnp.ones(s.shape, s.dtype)
        fan_in = config.intermediate_size if "w_down" in path else D
        if kind == "quant_scale":
            return jnp.full(s.shape, serve.LAYER_GAIN / (serve.INT8_UNIFORM_STD * math.sqrt(fan_in)),
                            s.dtype)

        def block(k, shape):
            if kind == "kernel_q":
                return jax.random.randint(k, shape, -126, 127, jnp.int8)
            std = 1.0 if kind == "embedding" else serve.LAYER_GAIN / math.sqrt(fan_in)
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if s.ndim == 3:  # stacked [L, in, out]: one layer per loop step
            return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))
        return block(key, s.shape)

    def make(root):
        out = {p: draw(p, flat[p], jax.random.fold_in(root, i)) for i, p in enumerate(body)}
        if head_paths:
            leaves = serve.draw_head(
                jax.random.fold_in(root, len(flat)), out[("embedding",)], config.eos_token_ids,
                recite_gain, flat[head_paths[0]].dtype)
            out.update(zip(head_paths, leaves))
        return out

    shardings = {p: NamedSharding(mesh.mesh, specs[p]) for p in flat}
    root = serve.prng_key(seed, 0)
    return traverse_util.unflatten_dict(jax.jit(make, out_shardings=shardings)(root))

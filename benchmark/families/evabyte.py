"""``model_type: "evabyte"``: the EvaByte decoder (a byte vocabulary of 320,
multi-head attention of 32 heads whose query sees its own window of 2048
positions exactly and one pooled key and value for every 16 positions of an
earlier window, a float32 residual stream, unit-offset norms, eight
next-byte heads), served as ONE STAGE of a pipeline-parallel deployment: the
first ``num_hidden_layers`` layers, whole, with the embedding and the head.
The program runs it through ``models/block_window.py``.

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

if not os.path.exists(os.path.join(serve.REPO, "rag_llm_k8s_tpu", "models", "block_window.py")):
    raise ImportError("model_type 'evabyte': this checkout's program has no models/block_window.py "
                      "(the block-window pooled-summary family), so it cannot serve the configuration")

# published config.json key -> BlockWindowConfig field
HF_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_hidden_layers",
    "num_attention_heads": "num_attention_heads",
    "num_key_value_heads": "num_key_value_heads",
    "window_size": "window_size",
    "chunk_size": "chunk_size",
    "num_pred_heads": "num_pred_heads",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_word_embeddings",
    "bos_token_id": "bos_token_id",  # not a published key: the stand-in tokenizer's
}
# published, and read by nothing here: the context the publisher trained at
# (positions come from the engine, there is no table) and how it drew its weights
PUBLISHED_KEYS = tuple(HF_TO_CONFIG) + ("max_seq_length", "init_std", "init_fn", "init_cutoff_factor", "lazy_init")
# published keys that select nothing in this decoder but must hold these
# values for it to be the published block
FIXED = {"attention_bias": False, "attention_class": "eva", "fp32_ln": False, "fp32_logits": True,
         "fp32_skip_add": True, "hidden_act": "silu", "mixedp_attn": True, "norm_add_unit_offset": True,
         "num_chunks": None, "rope_scaling": None}
# the family's own weight statistics beside serve.LAYER_GAIN (the file's
# ``assumed`` says why): std as a multiple of 1/sqrt(fan_in)
QK_GAIN = 1.2  # W_q, W_k: attention scores of spread QK_GAIN ** 2, so that a softmax is no plain mean
VO_GAIN = 1.0  # W_v, W_o: attention adds a tenth or more of the residual stream a layer
POOL_GAIN = 1.5  # mu, phi (std POOL_GAIN / sqrt(head_dim)): pooling logits of spread QK_GAIN * POOL_GAIN
# --allow-cpu-rehearsal: windows of 128 positions in chunks of 8, four heads of 16
REHEARSAL_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, window_size=128, chunk_size=8, num_pred_heads=8, max_position_embeddings=1024)


def model_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import BlockWindowConfig

    fields = {dst: cfg[src] for src, dst in HF_TO_CONFIG.items() if src in cfg}
    return BlockWindowConfig(eos_token_ids=(int(cfg["eos_token_id"]),), **fields)


def layer_loop_trips(cfg: dict) -> int:
    """Every layer is a trip of the one ``lax.scan`` (a prefill runs it once a row)."""
    return int(cfg["num_hidden_layers"])


def leaf_draw(name: str, config):
    """``(kind, std)`` of one leaf of the ``BlockWindowModel`` tree: ``norm``
    (a unit-offset scale's offset: zeros) or ``normal`` of that std."""
    D = config.hidden_size
    if "norm" in name:
        return "norm", 0.0
    if name == "embedding":
        return "normal", 1.0
    if name.endswith(("_mu", "_phi")):
        return "normal", POOL_GAIN / math.sqrt(config.head_dim)
    if name.endswith(("_wq", "_wk")):
        return "normal", QK_GAIN / math.sqrt(D)
    if name.endswith(("_wv", "_wo")):
        return "normal", VO_GAIN / math.sqrt(D)
    return "normal", serve.LAYER_GAIN / math.sqrt(config.intermediate_size if name.endswith("_w_down") else D)


def make_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``BlockWindowModel`` layout (flat
    names, the layers' leaves stacked), every leaf born on its device in its
    serving dtype, in ONE jitted call. The statistics are ``lib/serve.py``'s
    (norm scales 1, i.e. offsets 0; a unit-std embedding; the FFN of std
    ``LAYER_GAIN / sqrt(fan_in)``) with the family's own gains for the
    attention projections and the pooling vectors (``leaf_draw``). The head
    is ``[hidden, num_pred_heads * vocab]``, head-major: head 0 is
    ``serve.draw_head``'s (the reciting head every family gets), heads 1..
    plain unit-std columns. Keys are folded from the root in the sorted order
    of the body's names, the head's last (``tests/recorded_weights_evabyte.json``
    pins them)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.block_window import init_block_window_params

    if quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: this family is served in 'bf16' only")
    shapes = jax.eval_shape(lambda: init_block_window_params(jax.random.PRNGKey(0), config, dtypes))
    specs = families.of(config).param_specs(shapes, mesh)
    body = sorted(n for n in shapes if n != "lm_head")
    D, V = config.hidden_size, config.vocab_size

    def draw(name, s, key):
        kind, std = leaf_draw(name, config)
        if kind == "norm":
            return jnp.zeros(s.shape, s.dtype)

        def block(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if name.startswith("layers_"):  # stacked over the loop's trips: one slice a step
            return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))
        return block(key, s.shape)

    def make(root):
        out = {n: draw(n, shapes[n], jax.random.fold_in(root, i)) for i, n in enumerate(body)}
        head_key, rest_key = jax.random.split(jax.random.fold_in(root, len(shapes)))
        dtype = shapes["lm_head"].dtype
        (first,) = serve.draw_head(head_key, out["embedding"], config.eos_token_ids, recite_gain, dtype)
        rest = jax.random.normal(rest_key, (D, (config.num_pred_heads - 1) * V), jnp.float32) / math.sqrt(D)
        out["lm_head"] = jnp.concatenate([first, rest.astype(dtype)], axis=1)
        return out

    shardings = {n: NamedSharding(mesh.mesh, specs[n]) for n in shapes}
    return jax.jit(make, out_shardings=shardings)(serve.prng_key(seed, 0))

"""Synthetic model inputs made from a seed (validation / smoke / benchmarks).

Three builders, none of which needs a download (zero egress here and on the
chip machine): an HF-layout checkpoint writer, seeded device-resident param
trees for the decoder and the encoder, and a seeded multi-page PDF.

Checkpoint writer
-----------------

Writes a ``model-0000X-of-0000N.safetensors`` shard set with EXACTLY the
tensor names, dtypes and shapes of a real HF Llama checkpoint — the same
on-disk surface ``download_model.py`` stages into the model PVC
(/root/reference/llm/download_model.py:14-25) — so the streaming loader
(`models/loader.py`) and TP placement (`parallel/sharding.py`) can be proven
at true 8B geometry without the 16 GB download this environment cannot make
(zero egress). Tensors are zero-filled: the proof targets memory behavior,
dtype handling and sharding math, not numerics (covered by the tiny
round-trip parity tests).
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

from rag_llm_k8s_tpu.core.config import LlamaConfig


def llama_tensor_specs(config: LlamaConfig) -> List[Tuple[str, Tuple[int, ...]]]:
    """(hf_name, shape) for every tensor of a Llama checkpoint, in the
    embed → layers → norm/lm_head order real shard indexes follow."""
    D, I = config.hidden_size, config.intermediate_size
    H, K, hd, V = config.num_heads, config.num_kv_heads, config.head_dim, config.vocab_size
    specs: List[Tuple[str, Tuple[int, ...]]] = [
        ("model.embed_tokens.weight", (V, D)),
    ]
    for i in range(config.num_layers):
        p = f"model.layers.{i}."
        specs += [
            (p + "self_attn.q_proj.weight", (H * hd, D)),
            (p + "self_attn.k_proj.weight", (K * hd, D)),
            (p + "self_attn.v_proj.weight", (K * hd, D)),
            (p + "self_attn.o_proj.weight", (D, H * hd)),
            (p + "mlp.gate_proj.weight", (I, D)),
            (p + "mlp.up_proj.weight", (I, D)),
            (p + "mlp.down_proj.weight", (D, I)),
            (p + "input_layernorm.weight", (D,)),
            (p + "post_attention_layernorm.weight", (D,)),
        ]
    specs.append(("model.norm.weight", (D,)))
    if not config.tie_word_embeddings:
        specs.append(("lm_head.weight", (V, D)))
    return specs


def write_synth_checkpoint(
    out_dir: str,
    config: LlamaConfig,
    n_shards: int = 4,
    dtype=None,
) -> List[str]:
    """Write a zero-filled ``n_shards``-file safetensors checkpoint for
    ``config`` (default dtype: bfloat16, like the staged Meta weights).
    Tensors are assigned to shards by cumulative byte budget, matching how
    real HF shard indexes split a model. Returns the shard paths."""
    import ml_dtypes
    from safetensors.numpy import save_file

    dtype = np.dtype(ml_dtypes.bfloat16) if dtype is None else np.dtype(dtype)
    specs = llama_tensor_specs(config)
    total = sum(int(np.prod(s)) * dtype.itemsize for _, s in specs)
    budget = -(-total // n_shards)

    os.makedirs(out_dir, exist_ok=True)
    paths: List[str] = []
    shard: Dict[str, np.ndarray] = {}
    used, shard_i = 0, 1

    def flush():
        nonlocal shard, used, shard_i
        if not shard:
            return
        path = os.path.join(
            out_dir, f"model-{shard_i:05d}-of-{n_shards:05d}.safetensors"
        )
        save_file(shard, path)
        paths.append(path)
        shard, used, shard_i = {}, 0, shard_i + 1

    for name, shape in specs:
        nbytes = int(np.prod(shape)) * dtype.itemsize
        if shard and used + nbytes > budget and shard_i < n_shards:
            flush()
        shard[name] = np.zeros(shape, dtype)
        used += nbytes
    flush()
    return paths


# ---------------------------------------------------------------------------
# seeded params, generated ON the device(s)
# ---------------------------------------------------------------------------

# dequantized std of an int8 kernel drawn uniformly from [-126, 126], per
# unit of scale: 126 / sqrt(3)
_INT8_UNIFORM_STD = 72.75
# projection kernels' std as a multiple of 1/sqrt(fan_in) (synth_llama_params)
_LAYER_GAIN = 0.25
# cycle length of the optional "reciting" output head (synth_llama_params)
_RECITE_PERIOD = 8


def synth_llama_params(
    config: LlamaConfig,
    dtypes,
    seed: int,
    quant: str = "bf16",
    mesh=None,
    recite_gain: float = 0.0,
):
    """Seeded random Llama params in the ``LlamaModel`` layout, generated on
    the device one leaf at a time — an 8 GiB tree never exists on the host.

    ``quant="int8"`` yields the weight-only int8 layout
    (``quantize_llama_params``) directly; with ``mesh`` every leaf is born in
    its tensor-parallel sharding (``llama_param_specs``), so a tree larger
    than one chip never sits on one. Values depend on ``seed`` only — not on
    the sharding (partitionable threefry) — so a tp=4 and a tp=1 tree from
    the same seed hold the same numbers.

    Shapes the numerics, not the timing (decode cost is shape/dtype-bound):
    RMSNorm weights are 1; projection kernels have std
    ``_LAYER_GAIN / sqrt(fan_in)`` (0.25x init: layers perturb the residual
    stream instead of randomizing it, which keeps logit noise between two
    exact-in-theory paths far below the auditor's tolerance); the embedding
    has unit std and the untied output head gives logits of ~unit std, so
    greedy streams are well defined and finite through all layers. The
    head's EOS columns are zero (logit exactly 0, never the argmax), so
    every stream runs its full token budget whatever the seed.

    ``recite_gain`` makes streams partly periodic, which is the statistic
    prompt-lookup speculation needs to have anything to draft (answers that
    repeat their history): token ids are grouped into cycles of
    ``_RECITE_PERIOD`` and ``recite_gain / D`` times the embedding of each
    token's cycle PREDECESSOR is added to its head column, so the successor
    of the last token gains a logit of ~``recite_gain`` while the largest
    of V unit-normal logits sits near 4.4. At ~5 a greedy stream follows a
    cycle for a handful of steps, jumps, and follows another; 0 leaves the
    head purely random.
    """
    import math

    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models.llama import (
        init_llama_params,
        quantize_llama_params,
        synth_leaf_kind,
    )
    from rag_llm_k8s_tpu.parallel.sharding import llama_param_specs

    if quant not in ("bf16", "int8"):
        raise ValueError(f"quant={quant!r}: expected 'bf16' or 'int8'")
    shapes = jax.eval_shape(
        lambda: init_llama_params(jax.random.PRNGKey(0), config, dtypes)
    )
    if quant == "int8":
        shapes = jax.eval_shape(quantize_llama_params, shapes)
    flat = traverse_util.flatten_dict(shapes)
    specs = (
        traverse_util.flatten_dict(llama_param_specs(shapes, mesh))
        if mesh is not None else {}
    )
    D = config.hidden_size

    def sharding(path):
        return NamedSharding(mesh.mesh, specs[path]) if mesh is not None else None

    def draw(path, s, key):
        kind = synth_leaf_kind(path, s.dtype)
        if kind == "norm":
            return jnp.ones(s.shape, s.dtype)
        # the CONTRACTED dim: intermediate for the MLP down-projection,
        # hidden everywhere else
        fan_in = config.intermediate_size if "w_down" in path else D
        if kind == "quant_scale":
            return jnp.full(
                s.shape, _LAYER_GAIN / (_INT8_UNIFORM_STD * math.sqrt(fan_in)),
                s.dtype,
            )

        def block(k, shape):
            if kind == "kernel_q":
                # int8 directly (an int32 intermediate on the stacked MLP
                # leaves would cost ~7.5 GiB); maxval 127, not 128 — the
                # bound is cast to int8 and 128 would wrap to -128
                return jax.random.randint(k, shape, -126, 127, jnp.int8)
            std = 1.0 if kind == "embedding" else _LAYER_GAIN / math.sqrt(fan_in)
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if s.ndim == 3:  # stacked [L, in, out]: one layer per loop step
            return jax.lax.map(
                lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0])
            )
        return block(key, s.shape)

    def draw_head(key, embedding):
        """The untied [D, V] output head (+ its per-column int8 scale)."""
        V = config.vocab_size
        w = jax.random.normal(key, (D, V), jnp.float32) / math.sqrt(D)
        if recite_gain:
            v = jnp.arange(V)
            base = v - v % _RECITE_PERIOD
            pred = jnp.minimum(base + (v - base - 1) % _RECITE_PERIOD, V - 1)
            w = w + (recite_gain / D) * embedding[pred].T.astype(jnp.float32)
        w = w.at[:, jnp.asarray(config.eos_token_ids)].set(0.0)
        if quant == "bf16":
            return (w.astype(flat[("lm_head",)].dtype),)
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / 127.0, 1e-8)
        return jnp.round(w / scale[None, :]).astype(jnp.int8), scale

    head_paths = [p for p in (("lm_head",), ("lm_head_q",), ("lm_head_scale",)) if p in flat]
    root = jax.random.PRNGKey(seed)
    out = {}
    for i, (path, s) in enumerate(sorted(flat.items())):
        if path in head_paths:
            continue
        out[path] = jax.jit(
            lambda key, path=path, s=s: draw(path, s, key),
            out_shardings=sharding(path),
        )(jax.random.fold_in(root, i))
    if head_paths:  # untied configs; a tied head IS the embedding
        leaves = jax.jit(
            draw_head, out_shardings=tuple(sharding(p) for p in head_paths)
        )(jax.random.fold_in(root, len(flat)), out[("embedding",)])
        out.update(zip(head_paths, leaves))
    return traverse_util.unflatten_dict(out)


def synth_encoder_params(config, dtypes, seed: int):
    """Seeded random bge-m3-layout encoder params, initialized on device."""
    import jax

    from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params

    return jax.jit(lambda key: init_encoder_params(key, config, dtypes))(
        jax.random.PRNGKey(seed)
    )


# ---------------------------------------------------------------------------
# seeded PDF
# ---------------------------------------------------------------------------

_PDF_WORDS = (
    "radar technique tool platform language framework trial assess hold adopt "
    "team delivery pipeline service data model retrieval generation index "
    "vector cluster latency throughput security review practice architecture "
    "migration observability testing deployment container runtime compiler "
    "kernel memory bandwidth schedule batch request cache context"
).split()


def synth_pdf(seed: int, n_pages: int = 12, words_per_page: int = 500) -> bytes:
    """A seeded multi-page text PDF (uncompressed content streams, one
    Helvetica font): ``n_pages * words_per_page`` words of prose-like text
    with per-page section markers so chunks embed apart. The default
    (6000 words plus markers) ingests to 9 reference-size chunks — enough that a top-3
    context fills the 4096-token prompt bucket."""
    rs = np.random.RandomState(seed)
    objs: List[bytes] = []
    kids = " ".join(f"{4 + 2 * i} 0 R" for i in range(n_pages))
    objs.append(b"<< /Type /Catalog /Pages 2 0 R >>")
    objs.append(f"<< /Type /Pages /Kids [{kids}] /Count {n_pages} >>".encode())
    objs.append(b"<< /Type /Font /Subtype /Type1 /BaseFont /Helvetica >>")
    for page in range(n_pages):
        words = [_PDF_WORDS[j] for j in rs.randint(0, len(_PDF_WORDS), words_per_page)]
        lines = [f"Section {page + 1} of the seeded corpus {seed}."]
        for j in range(0, len(words), 12):
            lines.append(" ".join(words[j : j + 12]) + f" item{page}x{j}.")
        body = " ".join(f"({ln} ) Tj T*" for ln in lines)
        content = f"BT /F1 12 Tf 14 TL 72 720 Td {body} ET".encode()
        objs.append(
            f"<< /Type /Page /Parent 2 0 R /Contents {5 + 2 * page} 0 R "
            "/Resources << /Font << /F1 3 0 R >> >> >>".encode()
        )
        objs.append(
            b"<< /Length %d >>\nstream\n%s\nendstream" % (len(content), content)
        )
    out = [b"%PDF-1.4\n"]
    for n, obj in enumerate(objs, start=1):
        out.append(b"%d 0 obj %s endobj\n" % (n, obj))
    out.append(b"trailer << /Root 1 0 R >>\n%%EOF")
    return b"".join(out)

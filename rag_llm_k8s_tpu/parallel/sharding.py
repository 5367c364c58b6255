"""Tensor-parallel sharding rules for model parameters.

The reference runs the whole 8B model in one CPU process (survey §2c — no
parallelism of any kind). Here the Megatron-style TP layout is expressed as
PartitionSpecs over the ``tp`` mesh axis and applied with ``device_put``; XLA
then emits the ICI collectives (all-gather after attention/MLP row-parallel
matmuls, etc.) during jit compilation — no hand-written comm code.

Layout (param shapes are the stacked ``[L, ...]`` scan layout):

    embedding  [V, D]        -> P('tp', None)    vocab-sharded lookup (+psum by XLA)
    wq/wk/wv   [L, D, H*hd]  -> shard output dim  (column parallel: heads split)
    wo         [L, H*hd, D]  -> shard input dim   (row parallel: psum after)
    w_gate/up  [L, D, F]     -> shard output dim  (column parallel)
    w_down     [L, F, D]     -> shard input dim   (row parallel)
    lm_head    [D, V]        -> shard vocab       (logits sharded; sampling's
                                                   argmax/top-p reduce over tp)
    norms      [.., D]       -> replicated

A dim that doesn't divide the tp axis degrades to replicated for that axis
(keeps tiny test configs valid); on the real 8B over v5e-8 every sharded dim
divides exactly (4096, 14336, 128256, heads 32/kv 8).
"""

from __future__ import annotations

from typing import Tuple

import jax
from flax import traverse_util
from jax.sharding import NamedSharding, PartitionSpec as P

from rag_llm_k8s_tpu.core.mesh import MeshContext

# rules keyed by (path suffix); value = spec template over array dims.
# Weight-only int8 trees (models.llama.quantize_llama_params) shard their
# "kernel_q" exactly like the bf16 "kernel"; per-output-channel "qscale"
# vectors shard with the kernel's OUTPUT axis (column-parallel projections)
# and replicate where the kernel is row-parallel (output axis unsharded).
_RULES: Tuple[Tuple[Tuple[str, ...], Tuple[object, ...]], ...] = (
    (("embedding",), ("tp", None)),
    (("embedding_q",), ("tp", None)),
    (("embedding_scale",), ("tp",)),
    (("lm_head",), (None, "tp")),
    (("lm_head_q",), (None, "tp")),
    (("lm_head_scale",), ("tp",)),
    (("attn", "wq", "kernel"), (None, None, "tp")),
    (("attn", "wk", "kernel"), (None, None, "tp")),
    (("attn", "wv", "kernel"), (None, None, "tp")),
    (("attn", "wo", "kernel"), (None, "tp", None)),
    (("mlp", "w_gate", "kernel"), (None, None, "tp")),
    (("mlp", "w_up", "kernel"), (None, None, "tp")),
    (("mlp", "w_down", "kernel"), (None, "tp", None)),
    (("attn", "wq", "kernel_q"), (None, None, "tp")),
    (("attn", "wk", "kernel_q"), (None, None, "tp")),
    (("attn", "wv", "kernel_q"), (None, None, "tp")),
    (("attn", "wo", "kernel_q"), (None, "tp", None)),
    (("mlp", "w_gate", "kernel_q"), (None, None, "tp")),
    (("mlp", "w_up", "kernel_q"), (None, None, "tp")),
    (("mlp", "w_down", "kernel_q"), (None, "tp", None)),
    (("attn", "wq", "qscale"), (None, "tp")),
    (("attn", "wk", "qscale"), (None, "tp")),
    (("attn", "wv", "qscale"), (None, "tp")),
    (("mlp", "w_gate", "qscale"), (None, "tp")),
    (("mlp", "w_up", "qscale"), (None, "tp")),
    # wo/w_down scales: output axis is the unsharded hidden dim -> replicated
    # (default rule), matching the psum XLA inserts after row-parallel matmuls
)


# leaf names of the weight-only int8 layout (models.llama.QuantDense /
# quantize_llama_params). "qscale" is distinct from RMSNorm's "scale" by
# construction, so name alone identifies a quantized artifact.
_QUANT_LEAVES = frozenset(
    {"kernel_q", "qscale", "lm_head_q", "lm_head_scale", "embedding_q", "embedding_scale"}
)


def is_quant_leaf(path: Tuple[str, ...]) -> bool:
    """True for int8 kernels and their fp32 scale vectors — leaves whose
    dtype must survive placement untouched (never cast to the bf16 policy)."""
    return path[-1] in _QUANT_LEAVES


def _spec_for_path(path: Tuple[str, ...], ndim: int) -> Tuple[object, ...]:
    for suffix, template in _RULES:
        if path[-len(suffix):] == suffix:
            return template
    return (None,) * ndim  # norms, biases: replicated


def _fit_spec(template: Tuple[object, ...], shape, ctx: MeshContext) -> P:
    """Drop shardings whose dim doesn't divide the axis size."""
    fitted = []
    for dim, ax in zip(shape, template):
        if ax is None:
            fitted.append(None)
        else:
            fitted.append(ax if dim % ctx.axis_size(ax) == 0 else None)
    return P(*fitted)


def llama_param_specs(params, ctx: MeshContext):
    """PartitionSpec pytree matching ``params`` (the LlamaModel layout)."""
    flat = traverse_util.flatten_dict(params)
    specs = {
        path: _fit_spec(_spec_for_path(path, leaf.ndim), leaf.shape, ctx)
        for path, leaf in flat.items()
    }
    return traverse_util.unflatten_dict(specs)


def replicated_param_specs(params, ctx: MeshContext, tree: str):
    """PartitionSpec pytree with every leaf replicated: THE partition rule of
    a family that has none of its own (``models/families.py`` binds ``tree``,
    the name a refusal gives the tree, from the family's row). Such a family
    is served at tp = sp = 1: a mesh with more is refused here in the words
    of ``families.refuse_unsupported``, so a caller that takes its specs from
    the row directly cannot hand a replicated tree to a mesh that would split it."""
    if ctx.tp > 1 or ctx.sp > 1:
        raise NotImplementedError(
            f"tp={ctx.tp}, sp={ctx.sp}: the {tree} tree has no "
            "partition rules (tp and sp must be 1)"
        )
    flat = traverse_util.flatten_dict(params)
    return traverse_util.unflatten_dict({p: P(*(None,) * leaf.ndim) for p, leaf in flat.items()})


def shard_params(params, specs, ctx: MeshContext):
    """Place a param pytree on the mesh per its spec tree.

    (dict-flattened rather than jax.tree.map'd: PartitionSpec subclasses tuple,
    which tree utilities would wrongly traverse as a container.)
    """
    flat_p = traverse_util.flatten_dict(params)
    flat_s = traverse_util.flatten_dict(specs)
    placed = {
        path: jax.device_put(leaf, NamedSharding(ctx.mesh, flat_s[path]))
        for path, leaf in flat_p.items()
    }
    return traverse_util.unflatten_dict(placed)


def shard_llama_params(params, ctx: MeshContext):
    """One-call TP placement of a Llama param tree."""
    return shard_params(params, llama_param_specs(params, ctx), ctx)


def make_streaming_put(ctx: MeshContext, dtype=None):
    """A ``put(path, np_array)`` callback for the safetensors loaders: each
    tensor goes straight from host to its TP shards (never materializing the
    full model on one device). Casting happens host-side BEFORE the transfer
    so an fp32 checkpoint doesn't ship double-width bytes over PCIe."""

    def put(path: Tuple[str, ...], arr):
        if dtype is not None and arr.dtype != dtype and not is_quant_leaf(path):
            arr = arr.astype(dtype)
        spec = _fit_spec(_spec_for_path(path, arr.ndim), arr.shape, ctx)
        return jax.device_put(arr, NamedSharding(ctx.mesh, spec))

    return put

"""The seam between a model configuration's TYPE and what serves it.

One table: a configuration type -> its ``Family`` (model, cache, parameter
shardings, the counters its cache carries, what it cannot be served with
yet). ``InferenceEngine`` asks ``of(config)`` and names no family itself; a
new decoder is a row here and a module beside ``models/llama.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import (
    BlockWindowConfig, HybridSSMConfig, LatentMoEConfig, LlamaConfig, WindowedMoEConfig,
)


@dataclasses.dataclass(frozen=True)
class Family:
    """What serves one type of model configuration."""

    name: str  # as a refusal names it
    build_model: Callable  # (config, dtypes, engine_config, mesh, fused=, quantized=) -> nn.Module
    make_cache: Callable  # (config, batch, max_seq_len, dtype, quant) -> a fresh cache
    param_specs: Callable  # (params, mesh) -> PartitionSpec pytree
    # int32 counters the cache carries (``cache.counters``: a sparse-expert
    # family's assignments, a dense one's prefill token rows computed): the
    # generate programs append them to their one fetched array, and
    # ``fold_counters`` turns a fetched row into {name: increment} for ``/metrics``
    counters_width: int = 0
    counter_names: Tuple[str, ...] = ()
    fold_counters: Optional[Callable] = None
    # (engine_config, mesh, engine) -> (mechanism, why) of the first thing
    # this family cannot be served with yet, or None
    unsupported: Callable = lambda engine_config, mesh, engine: None
    # why server.main.build_service cannot load it from safetensors (None: it can)
    checkpoint_loader_refusal: Optional[str] = None
    # (config, position, fed) -> how many of a verify step's ``fed`` positions
    # from ``position`` on may write the cache (None: all of them; a ring
    # cannot take back a write past its window's end)
    verify_span: Optional[Callable] = None
    # (cache, kept) -> cache: after a verify step (the model built with
    # ``keep_steps=True``), how many of the fed positions the loop KEPT (None:
    # the frontier does the job; a state overwritten in place must be told)
    commit: Optional[Callable] = None


def _llama_model(config, dtypes, engine_config, mesh, *, fused: bool, quantized: bool):
    from rag_llm_k8s_tpu.models.llama import LlamaModel

    return LlamaModel(
        config,
        dtypes,
        attn_impl=engine_config.attn_impl,
        mesh=(mesh.mesh if mesh is not None and mesh.tp > 1 else None),
        fused_qkv=fused,
        quantized=quantized,
        kv_quant=engine_config.kv_quant,
    )


def _llama_cache(config, batch_size, max_seq_len, dtype, quant):
    from rag_llm_k8s_tpu.models.llama import make_kv_cache

    return make_kv_cache(config, batch_size, max_seq_len, dtype, quant=quant, counters=True)


def _llama_specs(params, mesh):
    from rag_llm_k8s_tpu.parallel.sharding import llama_param_specs

    return llama_param_specs(params, mesh)


def _llama() -> Family:
    from rag_llm_k8s_tpu.models import llama

    return Family(
        name="the Llama family (LlamaConfig)",
        build_model=_llama_model,
        make_cache=_llama_cache,
        param_specs=_llama_specs,
        counters_width=len(llama.COUNTER_NAMES),
        counter_names=llama.COUNTER_NAMES,
        fold_counters=llama.fold_counters,
    )


def _latent_moe() -> Family:
    from rag_llm_k8s_tpu.models import latent_moe as lm
    from rag_llm_k8s_tpu.parallel.sharding import latent_moe_param_specs

    def unsupported(engine_config, mesh, engine):
        if engine == "continuous":
            return ("the continuous engine (batching='continuous') or its paged KV pool",
                    "per-row frontiers and block tables are written for per-head K/V planes, "
                    "not the latent cache; use batching='coalesce'")
        if getattr(engine_config, "batching", "coalesce") == "continuous":
            return "batching='continuous'", "the slot engine has no latent cache; use 'coalesce'"
        pc = getattr(engine_config, "prefix_cache", None)
        if pc is not None and pc.enabled:
            return ("the KV prefix cache (prefix_cache.enabled)",
                    "splicing a latent row needs only its rope slice re-rotated, which "
                    "rerotate_prefix_planes does not do")
        if engine_config.kv_quant != "bf16":
            return f"kv_quant={engine_config.kv_quant!r}", "the latent cache has no int8 planes"
        if engine_config.weight_quant != "bf16":
            return (f"weight_quant={engine_config.weight_quant!r}",
                    "quantize_llama_params does not know this tree (stacked experts, the router)")
        if mesh is not None and (mesh.tp > 1 or getattr(mesh, "sp", 1) > 1):
            return (f"tp={mesh.tp}, sp={getattr(mesh, 'sp', 1)}",
                    "the latent projections and the expert stack have no partition rules; "
                    "experts across chips need the all-to-all")
        return None

    return Family(
        name="the latent-attention sparse-expert family (LatentMoEConfig)",
        build_model=lambda config, dtypes, engine_config, mesh, *, fused, quantized: lm.LatentMoEModel(
            config, dtypes, attn_impl=engine_config.attn_impl),
        make_cache=lambda config, batch_size, max_seq_len, dtype, quant: lm.make_latent_cache(
            config, batch_size, max_seq_len, dtype),
        param_specs=latent_moe_param_specs,
        counters_width=lm.N_COUNTERS,
        counter_names=tuple(lm.COUNTER_STATS),
        fold_counters=lm.fold_counters,
        unsupported=unsupported,
        checkpoint_loader_refusal=(
            "the checkpoint loader has no name map for the latent-attention "
            "sparse-expert family's tensors; serve it through assemble_service "
            "with a parameter tree of your own"),
    )


def _windowed_moe() -> Family:
    from rag_llm_k8s_tpu.models import windowed_moe as wm
    from rag_llm_k8s_tpu.parallel.sharding import windowed_moe_param_specs

    def unsupported(engine_config, mesh, engine):
        if engine == "continuous" or getattr(engine_config, "batching", "coalesce") == "continuous":
            return ("the continuous engine (batching='continuous') or its paged KV pool",
                    "the block pool has one table kind and every plane its full length: sliding "
                    "layers want a ring of window slots and a table of their own; use 'coalesce'")
        pc = getattr(engine_config, "prefix_cache", None)
        if pc is not None and pc.enabled:
            return ("the KV prefix cache (prefix_cache.enabled)",
                    "a spliced segment's sliding layers saw another window than the prompt's, "
                    "and rerotate_prefix_planes knows one rotary table, not one a layer kind")
        if engine_config.kv_quant != "bf16":
            return (f"kv_quant={engine_config.kv_quant!r}",
                    "the windowed prefill and the chunk form read bf16 planes only")
        if engine_config.weight_quant != "bf16":
            return (f"weight_quant={engine_config.weight_quant!r}",
                    "quantize_llama_params does not know this tree (projections that differ "
                    "in shape by layer kind, stacked experts, the router)")
        if mesh is not None and (mesh.tp > 1 or getattr(mesh, "sp", 1) > 1):
            return (f"tp={mesh.tp}, sp={getattr(mesh, 'sp', 1)}",
                    "this tree has no partition rules (72 and 48 query heads over 8 KV heads "
                    "split differently), and experts across chips need the all-to-all")
        return None

    return Family(
        name="the windowed-attention sparse-expert family (WindowedMoEConfig)",
        build_model=lambda config, dtypes, engine_config, mesh, *, fused, quantized: wm.WindowedMoEModel(
            config, dtypes, attn_impl=engine_config.attn_impl),
        make_cache=lambda config, batch_size, max_seq_len, dtype, quant: wm.make_windowed_cache(
            config, batch_size, max_seq_len, dtype),
        param_specs=windowed_moe_param_specs,
        counters_width=wm.N_COUNTERS,
        counter_names=wm.COUNTER_NAMES,
        fold_counters=wm.fold_counters,
        unsupported=unsupported,
        checkpoint_loader_refusal=(
            "the checkpoint loader has no name map for the windowed-attention "
            "sparse-expert family's tensors; serve it through assemble_service "
            "with a parameter tree of your own"),
    )


def _block_window() -> Family:
    from rag_llm_k8s_tpu.models import block_window as bwm
    from rag_llm_k8s_tpu.parallel.sharding import block_window_param_specs

    def unsupported(engine_config, mesh, engine):
        if engine == "continuous" or getattr(engine_config, "batching", "coalesce") == "continuous":
            return ("the continuous engine (batching='continuous') or its paged KV pool",
                    "the block pool has one table kind of full-length planes: this cache is a ring "
                    "of window slots and a plane of pooled summaries, a second table kind; use 'coalesce'")
        pc = getattr(engine_config, "prefix_cache", None)
        if pc is not None and pc.enabled:
            return ("the KV prefix cache (prefix_cache.enabled)",
                    "a pooled summary is position-free only up to its keys' rotation, and a spliced "
                    "segment's windows and chunks fall elsewhere than the prompt's")
        if engine_config.kv_quant != "bf16":
            return f"kv_quant={engine_config.kv_quant!r}", "the ring and the summary plane have no int8 form"
        if engine_config.weight_quant != "bf16":
            return (f"weight_quant={engine_config.weight_quant!r}",
                    "quantize_llama_params does not know this tree (stacked layers, the pooling vectors)")
        if mesh is not None and (mesh.tp > 1 or getattr(mesh, "sp", 1) > 1):
            return (f"tp={mesh.tp}, sp={getattr(mesh, 'sp', 1)}",
                    "this tree has no partition rules, and a prompt row's windows are walked on one chip")
        return None

    return Family(
        name="the block-window pooled-summary family (BlockWindowConfig)",
        build_model=lambda config, dtypes, engine_config, mesh, *, fused, quantized: bwm.BlockWindowModel(
            config, dtypes, attn_impl=engine_config.attn_impl),
        make_cache=lambda config, batch_size, max_seq_len, dtype, quant: bwm.make_block_window_cache(
            config, batch_size, max_seq_len, dtype),
        param_specs=block_window_param_specs,
        counters_width=bwm.N_COUNTERS,
        counter_names=bwm.COUNTER_NAMES,
        fold_counters=bwm.fold_counters,
        unsupported=unsupported,
        checkpoint_loader_refusal=(
            "the checkpoint loader has no name map for the block-window pooled-summary "
            "family's tensors; serve it through assemble_service with a parameter tree of your own"),
        verify_span=bwm.verify_span,
    )


def _hybrid_ssm() -> Family:
    from rag_llm_k8s_tpu.models import hybrid_ssm as hs
    from rag_llm_k8s_tpu.parallel.sharding import hybrid_ssm_param_specs

    def unsupported(engine_config, mesh, engine):
        if engine == "continuous" or getattr(engine_config, "batching", "coalesce") == "continuous":
            return ("the continuous engine (batching='continuous') or its paged KV pool",
                    "a recurrent state has no blocks to page, and preemption, resume and a per-row "
                    "frontier need snapshots of it that nothing takes yet; use 'coalesce'")
        pc = getattr(engine_config, "prefix_cache", None)
        if pc is not None and pc.enabled:
            return ("the KV prefix cache (prefix_cache.enabled)",
                    "a recurrent state can be reused only for an exact prefix, and only if a snapshot "
                    "was kept at its end: a spliced segment's keys and values say nothing of it")
        if engine_config.kv_quant != "bf16":
            return (f"kv_quant={engine_config.kv_quant!r}",
                    "the state is float32 and the attention layers' planes have no int8 form here")
        if engine_config.weight_quant != "bf16":
            return (f"weight_quant={engine_config.weight_quant!r}",
                    "quantize_llama_params does not know this tree (leaves stacked by layer kind, "
                    "float32 A_log, D and time-step bias)")
        if mesh is not None and (mesh.tp > 1 or getattr(mesh, "sp", 1) > 1):
            return (f"tp={mesh.tp}, sp={getattr(mesh, 'sp', 1)}",
                    "this tree has no partition rules (one KV head cannot be split, and a scan over "
                    "a sequence split across chips hands its state from chip to chip)")
        return None

    return Family(
        name="the hybrid state-space family (HybridSSMConfig)",
        build_model=lambda config, dtypes, engine_config, mesh, *, fused, quantized: hs.HybridSSMModel(
            config, dtypes, attn_impl=engine_config.attn_impl),
        make_cache=lambda config, batch_size, max_seq_len, dtype, quant: hs.make_hybrid_cache(
            config, batch_size, max_seq_len, dtype),
        param_specs=hybrid_ssm_param_specs,
        counters_width=hs.N_COUNTERS,
        counter_names=hs.COUNTER_NAMES,
        fold_counters=hs.fold_counters,
        unsupported=unsupported,
        checkpoint_loader_refusal=(
            "the checkpoint loader has no name map for the hybrid state-space family's "
            "tensors; serve it through assemble_service with a parameter tree of your own"),
        commit=hs.commit,
    )


# configuration type -> its family (a thunk where building it imports the model)
_TABLE: Tuple[Tuple[type, Callable[[], Family]], ...] = (
    (HybridSSMConfig, _hybrid_ssm),
    (BlockWindowConfig, _block_window),
    (WindowedMoEConfig, _windowed_moe),
    (LatentMoEConfig, _latent_moe),
    (LlamaConfig, _llama),
)
_BUILT: Dict[type, Family] = {}


def of(config) -> Family:
    """The family that serves ``config``, by its type."""
    for kind, build in _TABLE:
        if isinstance(config, kind):
            if kind not in _BUILT:
                _BUILT[kind] = build()
            return _BUILT[kind]
    raise TypeError(f"no decoder family serves a {type(config).__name__}")


def refuse_unsupported(config, engine_config, mesh, *, engine: str = "one-shot") -> None:
    """Raise ``NotImplementedError`` naming the first mechanism ``config``'s
    family cannot be served with (ROADMAP.md, "What the system cannot run yet")."""
    family = of(config)
    found = family.unsupported(engine_config, mesh, engine)
    if found:
        raise NotImplementedError(f"{family.name} cannot be served with {found[0]} yet: {found[1]}")


def make_cache(config, batch_size: int, max_seq_len: int, dtype=jnp.bfloat16, quant: str = "bf16"):
    """A fresh cache of the family's kind (per-head K/V planes, latents, a ring, a state)."""
    return of(config).make_cache(config, batch_size, max_seq_len, dtype, quant)

"""The seam between a model configuration's TYPE and what serves it.

One table: a configuration type -> its ``Family`` (model, cache, parameter
shardings, the counters its cache carries, why it refuses a serving mechanism
it cannot be served with yet). ``InferenceEngine`` asks ``of(config)`` and
names no family itself.

What a new decoder family costs (``tests/test_family_seam.py`` serves a toy one):

1. its class in ``core/config.py``: fields, validation, and ``roofline_terms``
   (its FLOPs a token, weight bytes and KV bytes a position: the goodput
   ledger asks the configuration, whatever its class);
2. ``models/<family>.py`` beside ``models/llama.py``: the model, the cache,
   the counters the cache carries and how a fetched row folds;
3. ONE row here (``replicated_row`` while it has no partition rules and no
   checkpoint name map): its name, the two constructors, the counters, and for
   each mechanism it cannot be served with yet the reason, as data;
4. ``obs/tracing.FINE_SCOPES`` only if it opens a scope name the tracer's
   vocabulary lacks.

``obs/goodput.py``, ``parallel/sharding.py``, the engine and the server are
not on that list.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import (
    BlockWindowConfig, ConvMoEConfig, CrossDecoderConfig, DeltaMoEConfig, HybridSSMConfig, LatentMoEConfig,
    LlamaConfig, SSDMoEConfig, WindowedMoEConfig,
)
from rag_llm_k8s_tpu.parallel.sharding import llama_param_specs, replicated_param_specs


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
    # mechanism (a key of ``refuse_unsupported``'s five) -> WHY this family
    # cannot be served with it yet (ROADMAP.md, "What the system cannot run
    # yet"); a mechanism without a reason is served
    refuses: Mapping[str, str] = dataclasses.field(default_factory=dict)
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


def _llama() -> Family:
    from rag_llm_k8s_tpu.models import llama

    # no reasons: every serving mechanism was written for this family's tree and cache
    return Family(
        name="the Llama family (LlamaConfig)",
        build_model=_llama_model,
        make_cache=_llama_cache,
        param_specs=llama_param_specs,
        counters_width=len(llama.COUNTER_NAMES),
        counter_names=llama.COUNTER_NAMES,
        fold_counters=llama.fold_counters,
    )


def _plain_model(model_class, config, dtypes, engine_config, mesh, *, fused: bool, quantized: bool):
    return model_class(config, dtypes, attn_impl=engine_config.attn_impl)


def _plain_cache(make, config, batch_size, max_seq_len, dtype, quant):
    return make(config, batch_size, max_seq_len, dtype)


def replicated_row(kind: str, config_type: type, model_class, make_cache: Callable,
                   refuses: Mapping[str, str], **fields) -> Family:
    """The row of a family served in bf16 on one chip's worth of mesh: a model
    and a cache built from the configuration alone, every parameter replicated
    (``parallel/sharding.py replicated_param_specs`` under the family's name),
    no checkpoint name map. ``fields`` are ``Family``'s own: the three of the
    counters, ``verify_span``, ``commit``."""
    return Family(
        name=f"the {kind} family ({config_type.__name__})",
        build_model=functools.partial(_plain_model, model_class),
        make_cache=functools.partial(_plain_cache, make_cache),
        param_specs=functools.partial(replicated_param_specs, tree=kind),
        refuses=refuses,
        checkpoint_loader_refusal=(
            f"the checkpoint loader has no name map for the {kind} family's tensors; "
            "serve it through assemble_service with a parameter tree of your own"),
        **fields)


def _latent_moe() -> Family:
    from rag_llm_k8s_tpu.models import latent_moe as lm

    return replicated_row(
        "latent-attention sparse-expert", LatentMoEConfig, lm.LatentMoEModel, lm.make_latent_cache,
        refuses={
            "continuous": "per-row frontiers and block tables are written for per-head K/V planes, "
                          "not the latent cache; use batching='coalesce'",
            "prefix_cache": "splicing a latent row needs only its rope slice re-rotated, which "
                            "rerotate_prefix_planes does not do",
            "kv_quant": "the latent cache has no int8 planes",
            "weight_quant": "quantize_llama_params does not know this tree (stacked experts, the router)",
            "mesh": "the latent projections and the expert stack have no partition rules; "
                    "experts across chips need the all-to-all",
        },
        counters_width=lm.N_COUNTERS, counter_names=tuple(lm.COUNTER_STATS), fold_counters=lm.fold_counters)


def _windowed_moe() -> Family:
    from rag_llm_k8s_tpu.models import windowed_moe as wm

    return replicated_row(
        "windowed-attention sparse-expert", WindowedMoEConfig, wm.WindowedMoEModel, wm.make_windowed_cache,
        refuses={
            "continuous": "the block pool has one table kind and every plane its full length: sliding "
                          "layers want a ring of window slots and a table of their own; use 'coalesce'",
            "prefix_cache": "a spliced segment's sliding layers saw another window than the prompt's, "
                            "and rerotate_prefix_planes knows one rotary table, not one a layer kind",
            "kv_quant": "the windowed prefill and the chunk form read bf16 planes only",
            "weight_quant": "quantize_llama_params does not know this tree (projections that differ "
                            "in shape by layer kind, stacked experts, the router)",
            "mesh": "this tree has no partition rules (72 and 48 query heads over 8 KV heads "
                    "split differently), and experts across chips need the all-to-all",
        },
        counters_width=wm.N_COUNTERS, counter_names=wm.COUNTER_NAMES, fold_counters=wm.fold_counters)


def _block_window() -> Family:
    from rag_llm_k8s_tpu.models import block_window as bwm

    return replicated_row(
        "block-window pooled-summary", BlockWindowConfig, bwm.BlockWindowModel, bwm.make_block_window_cache,
        refuses={
            "continuous": "the block pool has one table kind of full-length planes: this cache is a ring "
                          "of window slots and a plane of pooled summaries, a second table kind; use 'coalesce'",
            "prefix_cache": "a pooled summary is position-free only up to its keys' rotation, and a spliced "
                            "segment's windows and chunks fall elsewhere than the prompt's",
            "kv_quant": "the ring and the summary plane have no int8 form",
            "weight_quant": "quantize_llama_params does not know this tree (stacked layers, the pooling vectors)",
            "mesh": "this tree has no partition rules, and a prompt row's windows are walked on one chip",
        },
        counters_width=bwm.N_COUNTERS, counter_names=bwm.COUNTER_NAMES, fold_counters=bwm.fold_counters,
        verify_span=bwm.verify_span)


def _hybrid_ssm() -> Family:
    from rag_llm_k8s_tpu.models import hybrid_ssm as hs

    return replicated_row(
        "hybrid state-space", HybridSSMConfig, hs.HybridSSMModel, hs.make_hybrid_cache,
        refuses={
            "continuous": "a recurrent state has no blocks to page, and preemption, resume and a per-row "
                          "frontier need snapshots of it that nothing takes yet; use 'coalesce'",
            "prefix_cache": "a recurrent state can be reused only for an exact prefix, and only if a snapshot "
                            "was kept at its end: a spliced segment's keys and values say nothing of it",
            "kv_quant": "the state is float32 and the attention layers' planes have no int8 form here",
            "weight_quant": "quantize_llama_params does not know this tree (leaves stacked by layer kind, "
                            "float32 A_log, D and time-step bias)",
            "mesh": "this tree has no partition rules (one KV head cannot be split, and a scan over "
                    "a sequence split across chips hands its state from chip to chip)",
        },
        counters_width=hs.N_COUNTERS, counter_names=hs.COUNTER_NAMES, fold_counters=hs.fold_counters,
        commit=hs.commit)


def _conv_moe() -> Family:
    from rag_llm_k8s_tpu.models import conv_moe as cm

    return replicated_row(
        "gated-convolution sparse-expert", ConvMoEConfig, cm.ConvMoEModel, cm.make_conv_cache,
        refuses={
            "continuous": "a convolution's kept inputs have no blocks to page, and preemption, resume and a "
                          "per-row frontier need snapshots of them that nothing takes yet; use 'coalesce'",
            "prefix_cache": "a convolution's state can be reused only for an exact prefix, and only if a snapshot "
                            "was kept at its end: a spliced segment's keys and values say nothing of it",
            "kv_quant": "the attention layers' planes (heads of 64) and the kept inputs have no int8 form here",
            "weight_quant": "quantize_llama_params does not know this tree (operators that differ in shape "
                            "by layer kind, the taps, stacked experts, the router)",
            "mesh": "this tree has no partition rules (a depthwise convolution splits by channel, the heads "
                    "by KV head), and experts across chips need the all-to-all",
        },
        counters_width=cm.N_COUNTERS, counter_names=cm.COUNTER_NAMES, fold_counters=cm.fold_counters,
        commit=cm.commit)


def _delta_moe() -> Family:
    from rag_llm_k8s_tpu.models import delta_moe as dm

    return replicated_row(
        "gated delta-rule sparse-expert", DeltaMoEConfig, dm.DeltaMoEModel, dm.make_delta_cache,
        refuses={
            "continuous": "a float32 matrix state a head has no blocks to page, and preemption, resume and a "
                          "per-row frontier need snapshots of it (2 MB a row-layer) that nothing takes yet; "
                          "use 'coalesce'",
            "prefix_cache": "a recurrent matrix state can be reused only for an exact prefix, and only if a "
                            "snapshot was kept at its end: a spliced segment's latents say nothing of it",
            "kv_quant": "the state is float32 by construction (a rank-one correction every token) and the "
                        "latent planes and the kept convolution inputs have no int8 form",
            "weight_quant": "quantize_llama_params does not know this tree (mixers stacked by kind, float32 "
                            "A_log and time-step bias, stacked experts, the router)",
            "mesh": "this tree has no partition rules (the state splits by head, the latent projections do "
                    "not), and experts across chips need the all-to-all",
        },
        counters_width=dm.N_COUNTERS, counter_names=dm.COUNTER_NAMES, fold_counters=dm.fold_counters,
        commit=dm.commit)


def _cross_decoder() -> Family:
    from rag_llm_k8s_tpu.models import cross_decoder as cd

    return replicated_row(
        "decoder-hybrid-decoder", CrossDecoderConfig, cd.CrossDecoderModel, cd.make_cross_cache,
        refuses={
            "continuous": "a recurrent state has no blocks to page, and preemption, resume and a per-row "
                          "frontier need snapshots of it that nothing takes yet; use 'coalesce'",
            "prefix_cache": "a recurrent state can be reused only for an exact prefix, and only if a snapshot "
                            "was kept at its end: a spliced segment's keys and values say nothing of it",
            "kv_quant": "the state is float32 and the attention layers' planes have no int8 form here",
            "weight_quant": "quantize_llama_params does not know this tree (leaves stacked by layer kind, "
                            "float32 A_log, D, time-step bias and subtraction weights)",
            "mesh": "this tree has no partition rules (the heads split by pair, the scan by channel, and "
                    "a scan over a sequence split across chips hands its state from chip to chip)",
        },
        counters_width=cd.N_COUNTERS, counter_names=cd.COUNTER_NAMES, fold_counters=cd.fold_counters,
        commit=cd.commit)


def _ssd_moe() -> Family:
    from rag_llm_k8s_tpu.models import ssd_moe as sm

    return replicated_row(
        "state-space-duality latent-expert", SSDMoEConfig, sm.SSDMoEModel, sm.make_ssd_cache,
        refuses={
            "continuous": "a float32 matrix state a head has no blocks to page, and preemption, resume and a "
                          "per-row frontier need snapshots of it (4 MB a row-layer) that nothing takes yet; "
                          "use 'coalesce'",
            "prefix_cache": "a recurrent state can be reused only for an exact prefix, and only if a snapshot "
                            "was kept at its end: a spliced segment's keys and values say nothing of it",
            "kv_quant": "the state is float32 by construction and the attention layers' planes and the kept "
                        "convolution inputs have no int8 form here",
            "weight_quant": "quantize_llama_params does not know this tree (leaves stacked by layer kind, "
                            "float32 A_log, D and time-step bias, stacked experts, the router)",
            "mesh": "this tree has no partition rules (the state splits by head, two KV heads by two at "
                    "most), and experts across chips need the all-to-all",
        },
        counters_width=sm.N_COUNTERS, counter_names=sm.COUNTER_NAMES, fold_counters=sm.fold_counters,
        commit=sm.commit)


# configuration type -> its family (a thunk where building it imports the model)
_TABLE: Tuple[Tuple[type, Callable[[], Family]], ...] = (
    (SSDMoEConfig, _ssd_moe),
    (CrossDecoderConfig, _cross_decoder),
    (DeltaMoEConfig, _delta_moe),
    (ConvMoEConfig, _conv_moe),
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
    family cannot be served with (ROADMAP.md, "What the system cannot run yet"):
    THE place the five are tested, in this order, for every family; the row
    says only why."""
    family = of(config)
    pc = getattr(engine_config, "prefix_cache", None)
    tp, sp = (mesh.tp, getattr(mesh, "sp", 1)) if mesh is not None else (1, 1)
    asked = (  # (the row's key, the mechanism as the operator set it, whether they did)
        ("continuous", "the continuous engine (batching='continuous') or its paged KV pool",
         engine == "continuous" or getattr(engine_config, "batching", "coalesce") == "continuous"),
        ("prefix_cache", "the KV prefix cache (prefix_cache.enabled)", pc is not None and pc.enabled),
        ("kv_quant", f"kv_quant={engine_config.kv_quant!r}", engine_config.kv_quant != "bf16"),
        ("weight_quant", f"weight_quant={engine_config.weight_quant!r}", engine_config.weight_quant != "bf16"),
        ("mesh", f"tp={tp}, sp={sp}", tp > 1 or sp > 1),
    )
    for key, mechanism, is_set in asked:
        if is_set and key in family.refuses:
            raise NotImplementedError(
                f"{family.name} cannot be served with {mechanism} yet: {family.refuses[key]}")


def make_cache(config, batch_size: int, max_seq_len: int, dtype=jnp.bfloat16, quant: str = "bf16"):
    """A fresh cache of the family's kind (per-head K/V planes, latents, a ring, a state)."""
    return of(config).make_cache(config, batch_size, max_seq_len, dtype, quant)

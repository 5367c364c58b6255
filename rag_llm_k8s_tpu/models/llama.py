"""Flax Llama-3.x decoder, designed TPU-first.

Replaces the reference's CPU torch path — ``AutoModelForCausalLM.from_pretrained``
+ ``model.generate`` (/root/reference/llm/rag.py:24,172) — with an XLA-native
implementation:

- **Stacked layers + ``nn.scan``**: all 32 decoder blocks compile as ONE traced
  block scanned over a leading layer axis, so parameters arrive as ``[L, ...]``
  arrays (fast compile, trivially sharded, friendly to pjit).
- **GQA via grouped einsum** (no materialized head repetition): queries reshape
  to ``[B, S, kv_heads, group, head_dim]`` so the MXU sees large contractions.
- **One attention path for everything**: training, prefill and decode all write
  ``K,V`` into a fixed-size cache at ``write_index`` and attend over the whole
  cache under an additive bias. Static shapes throughout — no data-dependent
  control flow, so XLA compiles each (batch, bucket) shape exactly once.
- **bf16 storage/compute, fp32 where it matters**: RMSNorm statistics, RoPE
  phases, attention logits/softmax and final logits run in fp32
  (``DTypePolicy``), matching MXU-native mixed precision.
- **Llama-3.1 RoPE scaling** (NTK-by-parts, HF ``rope_type="llama3"``) so the
  staged Meta-Llama-3.1-8B-Instruct weights (download_model.py:5,17-25) produce
  identical positional geometry.

Sharding is NOT baked in here: parameters are plain pytrees; the TP/DP layouts
live in ``rag_llm_k8s_tpu/parallel/sharding.py`` and are applied by the engine
via NamedSharding — XLA inserts the ICI collectives.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops.attention import (
    attention_xla,
    chunk_attention_grouped,
    chunk_attention_grouped_q8,
    chunk_attention_xla,
    chunk_attention_xla_q8,
    chunk_prefill_attention,
    chunk_prefill_attention_q8,
    decode_attention,
    decode_attention_q8,
    decode_attention_xla,
    decode_attention_xla_q8,
    decode_slots_streamed,
    flash_attention,
    gqa_decode_step,
    grouped_chunk_fits,
    paged_chunk_attention,
    paged_chunk_attention_q8,
    paged_chunk_attention_xla,
    paged_chunk_attention_xla_q8,
    paged_decode_attention,
    paged_decode_attention_q8,
    paged_decode_attention_xla,
    paged_decode_attention_xla_q8,
    quantize_kv,
)

# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class KVCache:
    """Per-model KV cache: stacked over layers, written at a shared index.

    Shapes: ``k, v: [L, B, kv_heads, T_max, head_dim]`` — HEAD-MAJOR, so the
    decode kernel streams contiguous ``(block, head_dim)`` slabs per kv head
    straight from HBM (perfect VMEM tiling, no cache transposition ever).
    Prompts are LEFT-padded by the engine so every sequence in the batch
    appends at the same ``write_index`` — cache updates stay a
    ``dynamic_update_slice`` (scatter-free, MXU/DMA friendly) instead of a
    per-row scatter.

    ``kv_quant="int8"`` (EngineConfig): ``k``/``v`` hold int8 payloads and
    ``k_scale``/``v_scale`` ``[L, B, kv_heads, T_max]`` fp32 carry one
    symmetric scale per (token, head) vector — half the cache bytes per
    decode-step scan and half the HBM footprint. ``None`` on the bf16 path.

    ``counters`` (the one-shot engine's caches, ``models/families.py``):
    ``[len(COUNTER_NAMES)]`` int32 the model adds to on the device, where
    it decides how much of a prompt's bucket to compute and what a decode
    step's kernel fetches of the cache; they ride the generate programs'
    one fetch. ``None`` everywhere else.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    counters: Optional[jax.Array] = None


# what ``KVCache.counters`` counts, a fresh multi-token call at a time: token
# rows the layers' matmuls ran on, and token rows of the padded batch
# ("bucketed", not "bucket": a sample named ``*_bucket`` is a histogram's);
# and, a single-token step through the decode kernel at a time: the cache
# slots a layer's call fetches over the rows (``ops/attention.py
# decode_slots_streamed``: the kernel's own plan) and rows x the slots
# allocated (every layer of the step fetches the same, so a step counts once)
COUNTER_NAMES = ("prefill_tokens_computed", "prefill_tokens_bucketed",
                 "decode_slots_streamed", "decode_slots_allocated")


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    return {name: int(n) for name, n in zip(COUNTER_NAMES, row)}


def make_kv_cache(
    config: LlamaConfig,
    batch_size: int,
    max_seq_len: int,
    dtype: jnp.dtype = jnp.bfloat16,
    quant: str = "bf16",
    counters: bool = False,
) -> KVCache:
    shape = (
        config.num_layers,
        batch_size,
        config.num_kv_heads,
        max_seq_len,
        config.head_dim,
    )
    count = jnp.zeros((len(COUNTER_NAMES),), jnp.int32) if counters else None
    if quant == "int8":
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
            counters=count,
        )
    assert quant == "bf16", f"kv_quant={quant!r}: expected 'bf16' or 'int8'"
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype), counters=count)


def make_kv_arena(
    config: LlamaConfig,
    num_blocks: int,
    block_size: int,
    dtype: jnp.dtype = jnp.bfloat16,
    quant: str = "bf16",
) -> KVCache:
    """The PAGED cache: a ``[L, num_blocks, kv_heads, block_size, head_dim]``
    block-pool arena (same plane tuple as :func:`make_kv_cache`, with the
    per-row ``B × T`` axes replaced by the physical-block axis). Physical
    block 0 is the engine's reserved null block (engine/kv_pool.py); rows
    reach their blocks through int32 block tables, never by position."""
    shape = (
        config.num_layers,
        num_blocks,
        config.num_kv_heads,
        block_size,
        config.head_dim,
    )
    if quant == "int8":
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
        )
    assert quant == "bf16", f"kv_quant={quant!r}: expected 'bf16' or 'int8'"
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# RoPE (Llama-3.1 NTK-by-parts scaling)
# ---------------------------------------------------------------------------


def rope_frequencies(config: LlamaConfig) -> jax.Array:
    """Per-pair inverse frequencies ``[head_dim // 2]`` in fp32, with the
    Llama-3.1 wavelength-dependent rescaling applied when configured."""
    hd = config.head_dim
    freqs = 1.0 / (
        config.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    )
    s = config.rope_scaling
    if s is None:
        return freqs
    low_wavelen = s.original_max_position_embeddings / s.low_freq_factor
    high_wavelen = s.original_max_position_embeddings / s.high_freq_factor
    wavelen = 2.0 * jnp.pi / freqs
    # smooth interpolation between scaled and unscaled bands
    smooth = (s.original_max_position_embeddings / wavelen - s.low_freq_factor) / (
        s.high_freq_factor - s.low_freq_factor
    )
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = (1.0 - smooth) * freqs / s.factor + smooth * freqs
    return jnp.where(
        wavelen < high_wavelen, freqs, jnp.where(wavelen > low_wavelen, freqs / s.factor, scaled)
    )


def rope_cos_sin(
    positions: jax.Array, inv_freqs: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """``positions [B, S] -> cos, sin [B, S, head_dim // 2]`` (fp32)."""
    phase = positions.astype(jnp.float32)[..., None] * inv_freqs[None, None, :]
    return jnp.cos(phase), jnp.sin(phase)


def replicate_undividable_heads(t: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """Pin ``[B, S, heads, hd]`` projections whose head count does NOT tile
    the ``tp`` axis to an explicitly replicated layout.

    The projection kernels shard their flat ``heads*hd`` output column axis
    over ``tp`` whenever the byte count divides (parallel/sharding.py), so a
    head count that doesn't tile the axis leaves the reshaped ``[B, S,
    heads, hd]`` array sharded at SUB-HEAD granularity. That layout is not
    just slow — GSPMD (first seen on jax 0.4.x) miscompiles the
    slice+concat composite RoPE's rotate-by-halves builds over it whenever a
    second mesh axis (``dp``) is also populated: the jitted forward returns
    wrong VALUES (~0.3 absolute on tiny-config logits; eager is exact).
    tests/test_quant.py::TestQuantTP::test_rope_headcut_sharding_is_exact
    pins the miscompile shape. Heads that don't tile ``tp`` were never
    meaningfully sharded anyway — degrade them to replicated, the same rule
    ``_fit_spec`` applies to param dims. Head counts that DO tile the axis
    (every production config) never reach the constraint."""
    if mesh is None or "tp" not in mesh.axis_names:
        return t
    tp = mesh.shape["tp"]
    if tp <= 1 or t.shape[2] % tp == 0:
        return t
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(None, None, None, None))
    )


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x [B, S, H, head_dim]`` pairwise-by-halves (HF llama layout:
    the rotation pairs dim ``i`` with dim ``i + head_dim/2``)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x1.dtype)
    s = sin[:, :, None, :].astype(x1.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rerotate_prefix_planes(config: LlamaConfig, planes: Tuple, delta: int) -> Tuple:
    """Position-shift a cached segment-KV plane tuple by ``delta`` tokens:
    the K plane(s) re-rotate by the closed-form RoPE delta
    (:func:`ops.attention.rope_rerotate`) while V — position-free — passes
    through untouched. This is the attention-invariance primitive behind
    chunk-granular prefix reuse (``PrefixCacheConfig.reuse="chunk"``): a
    chunk's KV computed once at a canonical offset splices into any prompt
    position without re-prefill.

    ``planes`` is either ``(k, v)`` with payloads ``[L, 1, K, S, hd]`` or
    the int8 4-tuple ``(k, v, k_scale, v_scale)`` (scales ``[L, 1, K, S]``)
    — the quantized path goes dequant → rotate → requant with per-vector
    scale recomputation. ``delta == 0`` returns ``planes`` unchanged (the
    canonical-position hit stays bit-identical)."""
    from rag_llm_k8s_tpu.ops.attention import rope_rerotate, rope_rerotate_q8

    if int(delta) == 0:
        return planes
    inv = rope_frequencies(config)
    d = jnp.int32(delta)
    if len(planes) == 4:
        k_q, k_scale = rope_rerotate_q8(planes[0], planes[2], d, inv)
        return (k_q, planes[1], k_scale, planes[3])
    return (rope_rerotate(planes[0], d, inv), planes[1])


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, dtypes: DTypePolicy) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtypes.compute_dtype)


class RMSNorm(nn.Module):
    eps: float
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.dtypes.param_dtype)
        return rms_norm(x, scale, self.eps, self.dtypes)


class QuantDense(nn.Module):
    """Weight-only int8 linear: ``y = (x @ int8_kernel) * scale``.

    Decode is HBM-bandwidth-bound — every step re-reads every weight — so
    storing kernels as int8 halves the bytes streamed per step vs bf16. The
    int8 tensor is the ONLY copy in HBM: the ``astype`` rides the matmul's
    operand load (XLA fuses the convert; int8 values up to ±127 are exact in
    bf16) and the per-output-channel ``scale`` is a standard output epilogue
    fusion, so no dequantized kernel is ever materialized. fp32 per-channel
    scales bound the quantization error at ~0.4% RMS per channel.

    Params: ``kernel_q`` int8 ``[in, features]``, ``qscale`` fp32
    ``[features]`` (named to never collide with RMSNorm's ``scale``) —
    produced by :func:`quantize_llama_params`, never trained (serving-only;
    training stays bf16).
    """

    features: int
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kq = self.param(
            "kernel_q", nn.initializers.zeros, (x.shape[-1], self.features), jnp.int8
        )
        scale = self.param("qscale", nn.initializers.ones, (self.features,), jnp.float32)
        dt = self.dtypes.compute_dtype
        # The scale applies in the COMPUTE dtype. An fp32-result epilogue
        # (preferred_element_type=f32, scale, then downcast) was measured
        # and rejected: identical throughput at batch 64 but -12.5% at
        # batch 1 (408 -> 357 tok/s on-chip, 1B int8) — the fp32 result
        # blocks fusing the convert into the matmul, and at small batch
        # per-kernel overhead dominates. Accuracy is a wash: the output
        # rounds to bf16 either way, and the int8 rounding error (~1/254
        # per element) dominates the bf16 scale rounding (~0.4%); the
        # HF-logit and q8 parity bounds in tests/test_quant.py hold for
        # both variants.
        return jnp.dot(x, kq.astype(dt)) * scale.astype(dt)


def _make_dense(module: nn.Module, dt: DTypePolicy, quantized: bool):
    """The per-module linear factory: same call surface for the bf16 and the
    weight-only-int8 paths, so Attention/MLP stay layout-agnostic."""
    if quantized:
        return lambda feats, name: QuantDense(feats, dt, parent=module, name=name)
    return lambda feats, name: nn.Dense(
        feats, use_bias=False, dtype=dt.compute_dtype, param_dtype=dt.param_dtype,
        parent=module, name=name,
    )


# ---------------------------------------------------------------------------
# the live suffix of a left-padded prompt
# ---------------------------------------------------------------------------
#
# A bucket fixes the cache layout and the compiled shape, not the work: a
# fresh prompt call of which only the last position's logits leave computes
# its layers' norms and matmuls on the token suffix ``[off, S)``, ``off`` the
# largest rung of ``live_offsets(S)`` that is <= every row's ``kv_start``. Rows in
# front of it are pad in EVERY row of the batch (their keys are masked, their
# queries' outputs never read), so their projections are zeros and their
# residual stays the embedding. The rung is a traced scalar, chosen on the
# device inside the one executable; each rung is a branch of static shape.


def live_offsets(S: int) -> Tuple[int, ...]:
    """The rungs of leading tokens a fresh ``S``-token call may skip: eighths
    of the bucket up to half of it (a prompt under half a bucket lands a
    bucket lower), each a whole number of 8-row tiles. None for a bucket too
    small to gain. Eighths and not sixteenths by measurement (PERF.md, PR
    28): sixteenths compute a tenth less of a 3.1k-token prompt in the 4096
    bucket, and cost every executable's set-up, warm, 0.7 s of lowering and
    loading twice the branches where eighths cost 0.15 s."""
    if S <= 1024 or S % 64:
        return ()
    return tuple(range(0, S // 2, S // 8))


def _switch_live(rung: jax.Array, S: int, fn, *operands):
    """``fn(off, *operands)`` for the rung's ``off`` of ``live_offsets(S)``.
    The barrier keeps what reads the result out of the branches: moved in by
    the compiler (a norm's float32 copy of the residual, 537 MB a batch-8
    layer), it is written out of every branch and read again."""
    return jax.lax.optimization_barrier(jax.lax.switch(
        rung, [functools.partial(fn, off) for off in live_offsets(S)], *operands
    ))


def _dense_of(p: dict, layer: Optional[jax.Array], x: jax.Array, dt: DTypePolicy) -> jax.Array:
    """``nn.Dense`` / ``QuantDense`` as a function of its parameters ``p``,
    for a branch (a module cannot be called in one). ``layer`` None: ``p``
    is this layer's own, sliced by the layers' scan. Else ``p`` is the
    STACKED tree's (``[L, in, out]`` kernels) and the branch reads its layer
    here, inside the matmul that streams it: sliced by the scan in front of a
    conditional, a layer's weights are copied first (218 MB for Mistral-7B)."""
    at = (lambda a: a) if layer is None else functools.partial(
        jax.lax.dynamic_index_in_dim, index=layer, keepdims=False)
    cd = dt.compute_dtype
    dot = lambda x, w: jax.lax.dot_general(  # noqa: E731
        x, jax.lax.convert_element_type(w, cd), (((x.ndim - 1,), (0,)), ((), ())))
    if "kernel_q" in p:
        return dot(x, at(p["kernel_q"])) * jax.lax.convert_element_type(at(p["qscale"]), cd)
    return dot(jax.lax.convert_element_type(x, cd), at(p["kernel"]))


def _rows_from(x: jax.Array, off: int) -> jax.Array:
    """``x[:, off:]`` (lax, not numpy indexing: every rung's branches are
    traced in every executable's set-up)."""
    return jax.lax.slice_in_dim(x, off, x.shape[1], axis=1)


def _set_rows_from(x: jax.Array, off: int, rows: jax.Array) -> jax.Array:
    """``x.at[:, off:].set(rows)``, in place where ``x`` is dead after it."""
    return jax.lax.dynamic_update_slice_in_dim(x, rows, off, axis=1)


def resolve_attn_impl(attn_impl: str) -> str:
    if attn_impl not in ("auto", "pallas", "pallas_interpret", "xla"):
        raise ValueError(
            f"attn_impl={attn_impl!r}: expected one of "
            "'auto', 'pallas', 'pallas_interpret', 'xla'"
        )
    if attn_impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return attn_impl


def decode_walk_step(config: LlamaConfig, attn_impl: str, mesh, cache: "KVCache") -> Optional[int]:
    """The step a single-token call's decode kernel walks ``cache`` in on
    each device, or None where ``Attention._attend`` takes the XLA path (its
    rule: under tp the kernel runs on local heads when both head counts
    divide the axis, and not at all when they do not)."""
    tp = mesh.shape["tp"] if mesh is not None and "tp" in mesh.axis_names else 1
    H, K = config.num_heads, config.num_kv_heads
    if resolve_attn_impl(attn_impl) == "xla" or (tp > 1 and (H % tp or K % tp)):
        return None
    return gqa_decode_step(cache.k.shape[3], K // tp, H // K, config.head_dim, cache.k.dtype)


def attend(
    q, k, v, kv_start, kv_len, layer, *, mode: str, impl: str, mesh: Optional[Mesh] = None,
    write_index=None, scales=None, window: Optional[int] = None, ring=None,
) -> jax.Array:
    """The attention seam of every family that keeps per-head K/V planes:
    dispatch to the right backend (``impl``: a resolved ``attn_impl``). ``mode``:

    - ``"prefill"``: fresh ``k``/``v`` ``[B, S, K, hd]``, causal within S;
    - ``"decode"`` / ``"chunk"``: ``k``/``v`` are the FULL stacked
      head-major cache ``[L, B, K, T, hd]`` read at ``layer`` (no
      per-layer slice is ever materialized); ``chunk`` additionally takes
      ``write_index`` — query ``t`` sits at cache slot ``write_index + t``
      (offset causality over the populated prefix).

    ``scales`` (int8-KV only): ``(k_scale, v_scale) [L, B, K, T]`` fp32
    riding alongside an int8 cache. Decode and chunk both stream them
    through their q8 kernels (dequantization rides the matmul epilogues
    — no bf16 layer slice is ever materialized; the XLA oracle path
    dequantizes a slice, but it is the oracle, not the serving path).

    ``window`` (a sliding layer; bf16 planes only): a query sees its last
    ``window`` slots, itself among them. Prefill takes the flash kernel with
    the bound in its plan (``flash_attention_window``); a decode step hands
    the walk ``max(kv_start, kv_len - window)``, so the kernel fetches the
    window's steps and no others; a chunk over the cache takes the XLA form
    with the bound in its mask (the chunk kernels have none).

    ``ring`` (``Attention._attend_ring``): the sequence-parallel prefill.
    """
    assert window is None or scales is None, "a windowed layer's planes are not quantized"
    cache_kv = mode in ("decode", "chunk")
    # kv heads sit at dim 2 in both layouts ([L,B,K,T,hd] / [B,S,K,hd])
    H, K = q.shape[2], k.shape[2]
    tp = (
        mesh.shape["tp"]
        if mesh is not None and "tp" in mesh.axis_names
        else 1
    )
    sp = (
        mesh.shape["sp"]
        if mesh is not None and "sp" in mesh.axis_names
        else 1
    )
    if mode == "prefill" and sp > 1 and q.shape[1] % sp == 0 and ring is not None:
        # sequence parallelism: prefill/training attention runs as RING
        # attention over the sp axis — each device holds S/sp of the
        # sequence, K/V blocks rotate via ppermute on the ICI ring
        # (parallel/ring_attention.py). Differentiable (the training
        # path), composes with tp over heads.
        count_kernel_build(mode, "ring_attention")
        return ring(q, k, v, kv_start, kv_len, sp, tp)
    heads_shardable = tp > 1 and H % tp == 0 and K % tp == 0
    if impl != "xla" and tp > 1 and not heads_shardable:
        # head counts don't tile the tp axis: an unsharded Pallas call
        # inside the mesh program would force a per-layer full-cache
        # gather — the sharding-transparent XLA path is strictly better
        impl = "xla"
    if window is not None and mode == "chunk" and impl != "xla":
        count_kernel_build(mode, "chunk_attention_xla")
        return chunk_attention_xla(q, k, v, kv_start, kv_len, layer, write_index, window=window)
    if impl == "xla":
        count_kernel_build(mode, "xla")
        if mode == "decode":
            if scales is not None:
                return decode_attention_xla_q8(
                    q, k, v, scales[0], scales[1], kv_start, kv_len, layer
                )
            return decode_attention_xla(q, k, v, kv_start, kv_len, layer, window=window)
        if mode == "chunk":
            if scales is not None:
                return chunk_attention_xla_q8(
                    q, k, v, scales[0], scales[1], kv_start, kv_len,
                    layer, write_index,
                )
            return chunk_attention_xla(
                q, k, v, kv_start, kv_len, layer, write_index, window=window
            )
        return attention_xla(q, k, v, kv_start=kv_start, kv_len=kv_len, causal=True, window=window)

    # every fused kernel takes its arrays positionally, the cache kernels
    # in one order (q, k, v[, k_scale, v_scale], kv_start, kv_len, layer
    # [, write_index]): the choice below is of a function, by mode, by
    # whether scales ride along, and for a chunk by its static shape
    q8 = scales is not None
    if mode == "decode":
        fn = decode_attention_q8 if q8 else decode_attention
    elif mode == "chunk":
        # a small chunk (a speculative verify step's spec_tokens + 1
        # positions) folds a kv head's G query heads and the S positions
        # into one matmul's rows and streams the cache once a KV head;
        # prompt chunks of hundreds of rows keep the per-head kernel
        local_kv = K // tp if heads_shardable else K
        if grouped_chunk_fits(H // K, q.shape[1], local_kv):
            fn = chunk_attention_grouped_q8 if q8 else chunk_attention_grouped
        else:
            fn = chunk_prefill_attention_q8 if q8 else chunk_prefill_attention
    else:
        fn = flash_attention
    windowed = window is not None and mode == "prefill"
    count_kernel_build(mode, fn.__name__ + ("_window" if windowed else ""))
    kernel = functools.partial(fn, interpret=impl == "pallas_interpret",
                               **({"window": window} if windowed else {}))
    if window is not None and mode == "decode":
        kv_start = jnp.maximum(kv_start, kv_len - window)

    if heads_shardable:
        # heads are independent: shard the kernel over the tp axis, one
        # per-device Pallas call each on its local heads — no collectives
        hspec = P(None, None, "tp", None)
        if cache_kv:
            kvspec = P(None, None, "tp", None, None)
            scspec = (P(None, None, "tp", None),) * 2 if scales is not None else ()
            scalars = (P(None),) * (3 if mode == "chunk" else 2)
            kernel = jax.shard_map(
                kernel,
                mesh=mesh,
                in_specs=(hspec, kvspec, kvspec) + scspec + (P(None),) + scalars,
                out_specs=hspec,
                check_vma=False,
            )
        else:
            kernel = jax.shard_map(
                kernel,
                mesh=mesh,
                in_specs=(hspec, hspec, hspec, P(None), P(None)),
                out_specs=hspec,
                check_vma=False,
            )
    if mode == "decode":
        lay1 = jnp.asarray(layer, jnp.int32).reshape(1)
        if scales is not None:
            return kernel(q, k, v, scales[0], scales[1], kv_start, kv_len, lay1)
        return kernel(q, k, v, kv_start, kv_len, lay1)
    if mode == "chunk":
        lay1 = jnp.asarray(layer, jnp.int32).reshape(1)
        wi1 = jnp.asarray(write_index, jnp.int32).reshape(1)
        if scales is not None:
            return kernel(q, k, v, scales[0], scales[1], kv_start, kv_len, lay1, wi1)
        return kernel(q, k, v, kv_start, kv_len, lay1, wi1)
    return kernel(q, k, v, kv_start, kv_len)


class Attention(nn.Module):
    """GQA attention with two fused TPU paths and one differentiable oracle.

    - prefill / training (``S > 1``, ``write_index == 0``): blockwise Pallas
      flash attention over the FRESH ``[B, S, K, hd]`` keys/values — never the
      T-length cache, never a materialized score or bias array;
    - decode (``S == 1``): fused Pallas kernel streaming the head-major
      ``[B, K, T, hd]`` cache with the flash recurrence;
    - ``attn_impl="xla"``: dense einsum oracle (differentiable — the training
      path; also the CPU-test oracle the kernels are validated against).

    Masking is two ``[B]`` int32 vectors (``kv_start``, ``kv_len`` — the valid
    contiguous window) plus causality over cache slots. The reference's torch
    path and round 1's einsum both materialized a full ``[B, 1, S, T]`` fp32
    bias (~71 MB/row at the 4096 bucket); here no mask array exists at all.
    """

    config: LlamaConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    mesh: Optional[Mesh] = None  # enables shard_map-over-heads TP for kernels
    # STATIC chunked-prefill switch: S > 1 calls attend over the whole
    # populated cache prefix (offset causality) instead of just the fresh
    # K/V — the engine builds a separate model instance with chunked=True
    # for its long-prompt executables, so tracing never inspects write_index
    chunked: bool = False
    # STATIC per-row-frontier switch (continuous batching): decode calls take
    # write_index as a [B] vector — every row writes its fed token at its OWN
    # cache frontier (scatter), so rows at different generation depths share
    # one batch. The per-row [kv_start, kv_len) windows already handle the
    # masking; only the cache write changes.
    row_frontier: bool = False
    # STATIC fused-projection switch: q/k/v come from ONE [D, (H+2K)*hd]
    # matmul (param "wqkv") and gate/up from one [D, 2I] matmul
    # ("w_gateup" in MLP). Decode is dominated by per-kernel overhead at
    # small batch (same HBM bytes, fewer launches: measured ~110 us/layer).
    # Only valid UNSHARDED or tp=1 — a plain concat's column layout does not
    # align with a tp split across the q/k/v boundary; the engine fuses
    # params at construction exactly when tp == 1 (see fuse_llama_params).
    fused_qkv: bool = False
    # STATIC weight-only int8 switch: projections read QuantDense params
    # ({kernel_q, scale} from quantize_llama_params) instead of bf16 kernels.
    quantized: bool = False
    # STATIC int8-KV switch: the cache carry becomes (k, v, k_scale,
    # v_scale); fresh K/V quantize on write (ops.attention.quantize_kv) and
    # decode streams int8 blocks through decode_attention_q8.
    kv_quant: str = "bf16"
    # STATIC paged-KV switch (block-pool arena): the cache carry planes are
    # [L, N, K, block_size, hd] arenas and every call takes ``block_tables``
    # [B, MB] int32 mapping logical block j of row b to a physical pool
    # block. Paged rows are RIGHT-padded (logical positions start at 0, the
    # window is [0, kv_len), kv_start is ignored); writes scatter through
    # the table, attention streams only LIVE blocks (ops.attention paged
    # kernels). Valid for decode (row_frontier) and chunked prefill — fresh
    # whole-row prefill stays dense and is scattered in by the engine's
    # insert executable. tp>1 with head counts dividing the axis runs the
    # kernels shard-aware (shard_map over the head-sharded arena,
    # ops.attention.paged_partition_specs); otherwise the
    # sharding-transparent XLA paged path serves.
    paged: bool = False

    def _resolved_impl(self) -> str:
        return resolve_attn_impl(self.attn_impl)

    def _attend_paged(
        self, q, k, v, kv_len, layer, *, mode: str, block_tables,
        write_index=None, scales=None,
    ) -> jax.Array:
        """Paged-arena dispatch: ``k``/``v`` are the [L, N, K, bs, hd]
        arenas, the row's blocks resolve through ``block_tables``.

        tp>1 with head counts dividing the axis runs the paged kernels
        SHARD-AWARE: ``shard_map`` over the tp mesh axis with the
        head-sharded arena rules (``ops.attention.paged_partition_specs``)
        — each device streams its local K/tp head slice of the row's live
        blocks through the same SMEM-prefetched table indirection, so
        per-device decode bandwidth scales as live_tokens × K/tp; the
        cross-shard reduce is the wo psum XLA already inserts, exactly as
        on the dense tp path. ``attn_impl="xla"`` (and head counts that
        don't tile tp) takes the sharding-transparent gather-based
        oracles — every fused path (decode, chunk, and their q8 twins,
        including the paged q8 chunk kernel that replaced PR 5's gather
        oracle) has one."""
        from rag_llm_k8s_tpu.ops.attention import paged_partition_specs

        impl = self._resolved_impl()
        mesh = self.mesh
        tp = (
            mesh.shape["tp"]
            if mesh is not None and "tp" in mesh.axis_names
            else 1
        )
        # q heads at dim 2; arena kv heads at dim 2 ([L, N, K, bs, hd]).
        # K % tp == 0 implies H % tp == 0 (H = K * group), but check both —
        # the degradation must mirror the dense path's exactly
        H, K = q.shape[2], k.shape[2]
        heads_shardable = tp > 1 and H % tp == 0 and K % tp == 0
        if impl != "xla" and tp > 1 and not heads_shardable:
            # head counts don't tile the tp axis: an unsharded Pallas call
            # inside the mesh program would force a full-arena gather — the
            # sharding-transparent XLA path is strictly better
            impl = "xla"
        use_xla = impl == "xla"
        interpret = impl == "pallas_interpret"
        lay1 = jnp.asarray(layer, jnp.int32).reshape(1)

        def shard(kernel, specs_mode, q8):
            if not heads_shardable:
                return kernel
            in_specs, out_spec = paged_partition_specs(specs_mode, q8=q8)
            return jax.shard_map(
                kernel, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
                check_vma=False,
            )

        if mode == "decode":
            if use_xla:
                if scales is not None:
                    return paged_decode_attention_xla_q8(
                        q, k, v, scales[0], scales[1], block_tables, kv_len, lay1
                    )
                return paged_decode_attention_xla(
                    q, k, v, block_tables, kv_len, lay1
                )
            if scales is not None:
                kernel = shard(
                    lambda q_, k_, v_, ks_, vs_, t_, l_, lay_: (
                        paged_decode_attention_q8(
                            q_, k_, v_, ks_, vs_, t_, l_, lay_,
                            interpret=interpret,
                        )
                    ),
                    "decode", True,
                )
                return kernel(
                    q, k, v, scales[0], scales[1], block_tables, kv_len, lay1
                )
            kernel = shard(
                lambda q_, k_, v_, t_, l_, lay_: paged_decode_attention(
                    q_, k_, v_, t_, l_, lay_, interpret=interpret
                ),
                "decode", False,
            )
            return kernel(q, k, v, block_tables, kv_len, lay1)
        assert mode == "chunk", f"paged attention has no {mode!r} mode"
        B = q.shape[0]
        wi = jnp.broadcast_to(jnp.asarray(write_index, jnp.int32), (B,))
        if scales is not None:
            if use_xla:
                return paged_chunk_attention_xla_q8(
                    q, k, v, scales[0], scales[1], block_tables, kv_len,
                    lay1, wi,
                )
            # fused q8 paged chunk prefill: warm-tier (int8) admission
            # streams the int8 blocks directly with epilogue dequant —
            # PR 5's gather oracle spent the bandwidth int8 bought
            kernel = shard(
                lambda q_, k_, v_, ks_, vs_, t_, l_, lay_, wi_: (
                    paged_chunk_attention_q8(
                        q_, k_, v_, ks_, vs_, t_, l_, lay_, wi_,
                        interpret=interpret,
                    )
                ),
                "chunk", True,
            )
            return kernel(
                q, k, v, scales[0], scales[1], block_tables, kv_len, lay1, wi
            )
        if use_xla:
            return paged_chunk_attention_xla(q, k, v, block_tables, kv_len, lay1, wi)
        kernel = shard(
            lambda q_, k_, v_, t_, l_, lay_, wi_: paged_chunk_attention(
                q_, k_, v_, t_, l_, lay_, wi_, interpret=interpret
            ),
            "chunk", False,
        )
        return kernel(q, k, v, block_tables, kv_len, lay1, wi)

    def _attend(
        self, q, k, v, kv_start, kv_len, layer, *, mode: str, write_index=None,
        scales=None,
    ) -> jax.Array:
        """``attend`` with this module's backend and mesh."""
        return attend(
            q, k, v, kv_start, kv_len, layer, mode=mode, impl=self._resolved_impl(),
            mesh=self.mesh, write_index=write_index, scales=scales, ring=self._attend_ring,
        )

    def _attend_ring(self, q, k, v, kv_start, kv_len, sp: int, tp: int) -> jax.Array:
        """Sequence-parallel prefill attention: shard_map over ``sp`` (and
        ``tp`` when head counts divide it), ring K/V rotation inside."""
        from rag_llm_k8s_tpu.parallel.ring_attention import ring_attention

        mesh = self.mesh
        B, S, H, hd = q.shape
        K = k.shape[2]
        tp_axis = "tp" if (tp > 1 and H % tp == 0 and K % tp == 0) else None
        dp = mesh.shape["dp"] if "dp" in mesh.axis_names else 1
        dp_axis = "dp" if (dp > 1 and B % dp == 0) else None
        t = jnp.arange(S)
        valid = (t[None, :] >= kv_start[:, None]) & (t[None, :] < kv_len[:, None])

        hspec = P(dp_axis, "sp", tp_axis, None)
        fn = jax.shard_map(
            lambda q_, k_, v_, val_: ring_attention(
                q_, k_, v_, axis_name="sp", causal=True, kv_valid=val_
            ),
            mesh=mesh,
            in_specs=(hspec, hspec, hspec, P(dp_axis, "sp")),
            out_specs=hspec,
            check_vma=False,
        )
        return fn(q, k, v, valid).astype(q.dtype)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [B, S, D]
        kv: Tuple[jax.Array, jax.Array],  # FULL stacked cache [L, B, K, T, hd] ×2
        layer: jax.Array,  # scalar int32: this block's layer index
        kv_start: jax.Array,  # [B] int32: first valid cache slot
        kv_len: jax.Array,  # [B] int32: valid frontier (exclusive)
        cos: jax.Array,
        sin: jax.Array,
        write_index: jax.Array,  # scalar int32 ([B] when row_frontier/paged)
        block_tables=None,  # [B, MB] int32 (paged mode only)
        rung=None,  # int32 scalar: which of ``live_offsets(S)``, in a live-suffix prefill
        norm=None,  # with ``rung``: ``x`` is the residual stream, this its input norm
        bufs=None,  # with ``rung``: the projections' [B, S, features], zeros in front of the suffix
    ):
        """``(attention's output projection, new cache)``. Under ``rung``
        (see ``live_offsets``) norm and projections run on the live suffix
        alone: ``(the residual stream x with the suffix's rows added, new
        cache, the buffers as this layer leaves them)``."""
        c, dt = self.config, self.dtypes
        B, S, D = x.shape
        H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
        dense = _make_dense(self, dt, self.quantized)
        if rung is not None:
            names = ("wqkv",) if self.fused_qkv else ("wq", "wk", "wv")
            # attention's projections (a tenth of a layer's bytes) keep the
            # scan's own slices: the compiler copies most of them a layer at
            # a time as it is (it wants them transposed, for a head-major
            # result), and asked to read them from the stacked tree inside a
            # branch it re-lays the WHOLE stack, every layer
            # (tests/test_tpu_aot_compile.py)
            own = self.variables["params"]

            def project(off, h, *bufs):
                # the norm here, where it fuses into the matmuls' operand: in
                # front of the conditional its [B, S, D] is written and read.
                # Zeros in front of the suffix: the kernel and the cache
                # write below keep the bucket's shape, and a masked key has
                # to be finite
                xs = norm(_rows_from(h, off))
                return tuple(
                    _set_rows_from(buf, off, _dense_of(own[n], None, xs, dt))
                    for n, buf in zip(names, bufs)
                )

            qkv = _switch_live(rung, S, project, x, *bufs)
        else:
            qkv = tuple(
                dense(f * hd, n)(x)
                for n, f in (
                    (("wqkv", H + 2 * K),) if self.fused_qkv
                    else (("wq", H), ("wk", K), ("wv", K))
                )
            )
        if self.fused_qkv:
            q, k, v = jnp.split(qkv[0], [H * hd, (H + K) * hd], axis=-1)
        else:
            q, k, v = qkv
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, K, hd)
        v = v.reshape(B, S, K, hd)
        # head counts that don't tile tp must not stay sharded mid-head
        # through RoPE's slice+concat (see replicate_undividable_heads)
        q = replicate_undividable_heads(q, self.mesh)
        k = replicate_undividable_heads(k, self.mesh)
        v = replicate_undividable_heads(v, self.mesh)

        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        # in-place slice write into the ONE persistent cache buffer: the
        # stacked [L, ...] cache is a scan carry, so XLA aliases it across
        # layers and decode steps — no cache-sized copy ever happens (the
        # naive per-layer-output stacking costs GB/step of pure copy traffic)
        q8 = self.kv_quant == "int8"
        if q8:
            k_cache, v_cache, ks_cache, vs_cache = kv
            k_w, k_s = quantize_kv(k)  # [B, S, K, hd] int8, [B, S, K] fp32
            v_w, v_s = quantize_kv(v)
        else:
            k_cache, v_cache = kv
            k_w, v_w, k_s, v_s = k, v, None, None
        if self.paged:
            assert block_tables is not None, "paged attention needs block_tables"
            assert S == 1 or self.chunked, (
                "paged mode serves decode (S=1) and chunked prefill; fresh "
                "whole-row prefill stays dense (the engine scatters it in)"
            )
            # table-directed scatter write: token t of row b lands at
            # logical position pos = write_index_b (+ t when chunked) →
            # physical (block_tables[b, pos // bs], pos % bs). Built as a
            # masked full-plane write like the dense row_frontier path (an
            # XLA scatter re-materializes the arena — same trap the dense
            # path measured at 2.6-12x step time): per (block, slot) the
            # source token resolves by argmax over a [B*S, N, bs] mask and
            # rides a gather; slots no token targets keep the old plane.
            # Rows parked at the null block (inactive, or positions past a
            # chunk's real suffix) write junk into block 0, which no kernel
            # ever reads — that is the null block's whole job.
            N_blocks, bs_len = k_cache.shape[1], k_cache.shape[3]
            MB = block_tables.shape[1]
            pos = jnp.asarray(write_index, jnp.int32).reshape(B, -1)
            if self.chunked and S > 1:
                pos = pos[:, :1] + jnp.arange(S, dtype=jnp.int32)[None, :]
            blk_raw = pos // bs_len
            blk = jnp.clip(blk_raw, 0, MB - 1)
            phys = jnp.take_along_axis(block_tables.astype(jnp.int32), blk, axis=1)
            # positions past the table park in the NULL block (physical 0)
            # — clipping into logical block MB-1 would overwrite valid KV
            # at the top of the slot ladder (a speculative verify window's
            # junk lanes can run past a row's last logical block; so could
            # any chunked write near the window end, and a mixed ragged
            # window's decode rows carry chunk_width-1 junk lanes past
            # their frontier every step)
            phys = jnp.where(blk_raw < MB, phys, 0)
            off = pos % bs_len
            flat_phys = phys.reshape(-1)  # [B*S]
            flat_off = off.reshape(-1)
            m = (
                jnp.arange(N_blocks, dtype=jnp.int32)[None, :, None]
                == flat_phys[:, None, None]
            ) & (
                jnp.arange(bs_len, dtype=jnp.int32)[None, None, :]
                == flat_off[:, None, None]
            )  # [B*S, N, bs]
            src = jnp.argmax(m, axis=0)  # [N, bs] — source token per slot
            written = jnp.any(m, axis=0)  # [N, bs]

            def scatter_plane(cache, vals):
                # vals [B, S, K, hd] (payload) or [B, S, K] (scale plane)
                flat = vals.reshape((B * S,) + vals.shape[2:])
                g = jnp.moveaxis(jnp.take(flat, src, axis=0), 2, 1)  # [N, K, bs(, hd)]
                w = written[:, None, :] if g.ndim == 3 else written[:, None, :, None]
                return cache.at[layer].set(
                    jnp.where(w, g.astype(cache.dtype), cache[layer])
                )

            k_cache = scatter_plane(k_cache, k_w)
            v_cache = scatter_plane(v_cache, v_w)
            if q8:
                ks_cache = scatter_plane(ks_cache, k_s)
                vs_cache = scatter_plane(vs_cache, v_s)
        elif self.row_frontier and S == 1:
            # continuous batching: write_index is [B] — each row's token
            # lands at that row's own frontier. NOT a gather-scatter
            # (.at[layer, b, :, wi_b].set): that lowers to an XLA scatter
            # which re-materializes the cache (the round-5 capture, before
            # PR 1 and in git history, had it several times the one-shot
            # loop's step time, worse at B=64 than at B=8). A masked
            # full-plane write streams the layer's [B, K, T, hd] planes
            # exactly once and stays aliased under
            # the scan carry via the scalar-indexed .at[layer].set.
            T_len = k_cache.shape[3]
            wi_b = write_index.reshape(B, 1, 1, 1)
            m = jnp.arange(T_len, dtype=jnp.int32)[None, None, :, None] == wi_b
            k_cache = k_cache.at[layer].set(
                jnp.where(m, k_w[:, 0].astype(k_cache.dtype)[:, :, None, :], k_cache[layer])
            )
            v_cache = v_cache.at[layer].set(
                jnp.where(m, v_w[:, 0].astype(v_cache.dtype)[:, :, None, :], v_cache[layer])
            )
            if q8:
                m3 = (
                    jnp.arange(T_len, dtype=jnp.int32)[None, None, :]
                    == write_index.reshape(B, 1, 1)
                )
                ks_cache = ks_cache.at[layer].set(
                    jnp.where(m3, k_s[:, 0][:, :, None], ks_cache[layer])
                )
                vs_cache = vs_cache.at[layer].set(
                    jnp.where(m3, v_s[:, 0][:, :, None], vs_cache[layer])
                )
        else:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache,
                k_w.transpose(0, 2, 1, 3).astype(k_cache.dtype)[None],
                (layer, 0, 0, write_index, 0),
            )
            v_cache = jax.lax.dynamic_update_slice(
                v_cache,
                v_w.transpose(0, 2, 1, 3).astype(v_cache.dtype)[None],
                (layer, 0, 0, write_index, 0),
            )
            if q8:
                ks_cache = jax.lax.dynamic_update_slice(
                    ks_cache, k_s.transpose(0, 2, 1)[None], (layer, 0, 0, write_index)
                )
                vs_cache = jax.lax.dynamic_update_slice(
                    vs_cache, v_s.transpose(0, 2, 1)[None], (layer, 0, 0, write_index)
                )

        scales = (ks_cache, vs_cache) if q8 else None
        if self.paged:
            out = self._attend_paged(
                q, k_cache, v_cache, kv_len, layer,
                mode="decode" if S == 1 else "chunk",
                block_tables=block_tables,
                write_index=write_index if S > 1 else None,
                scales=scales,
            )
        elif S == 1:
            out = self._attend(
                q, k_cache, v_cache, kv_start, kv_len, layer,
                mode="decode", scales=scales,
            )
        elif self.chunked:
            # chunked prefill: this chunk's queries attend over the WHOLE
            # populated cache prefix (earlier chunks + this one) with offset
            # causality — query t sits at cache slot write_index + t
            out = self._attend(
                q, k_cache, v_cache, kv_start, kv_len, layer,
                mode="chunk", write_index=write_index, scales=scales,
            )
        else:
            # single-shot prefill/training writes at slot 0, so the fresh K/V
            # ARE the populated cache prefix — attend over S keys, not T cache
            # slots (always bf16: quantization touches only the cache). Under
            # ``rung`` the keys in front of the live suffix are zeros, masked
            # like any left pad, and the kernel skips their blocks. The
            # check is concrete-only: under tracing (nn.scan broadcasts every
            # argument as a tracer, as do init/eval_shape/grad) the value
            # can't be inspected, and every in-tree caller passes 0 for
            # non-chunked multi-token calls.
            if not isinstance(write_index, jax.core.Tracer):
                assert int(write_index) == 0, (
                    "multi-token calls must write at slot 0 — build the model "
                    "with chunked=True for prefill at write_index > 0"
                )
            out = self._attend(q, k, v, kv_start, kv_len, layer, mode="prefill")
        out = out.astype(dt.compute_dtype).reshape(B, S, H * hd)
        new_kv = (
            (k_cache, v_cache, ks_cache, vs_cache) if q8 else (k_cache, v_cache)
        )
        if rung is None:
            return dense(D, "wo")(out), new_kv

        def project_out(off, h, out):
            # the live rows join the residual in place: no [B, S, D] of
            # zeros and projections is built to be added outside
            return _set_rows_from(
                h, off, _rows_from(h, off) + _dense_of(own["wo"], None, _rows_from(out, off), dt))

        return _switch_live(rung, S, project_out, x, out), new_kv, qkv


class MLP(nn.Module):
    config: LlamaConfig
    dtypes: DTypePolicy
    fused: bool = False  # see Attention.fused_qkv
    quantized: bool = False  # see Attention.quantized

    @nn.compact
    def __call__(self, x: jax.Array, layer=None, live=None, norm=None) -> jax.Array:
        """The FFN of ``x``; under ``live`` (the rung of ``Attention.__call__``
        and the stacked tree's "mlp") ``x`` is the residual stream, ``norm``
        its norm, and the result the stream with the live rows' FFN added."""
        c, dt = self.config, self.dtypes
        if live is not None:
            rung, stacked = live
            dense = lambda _, name: functools.partial(  # noqa: E731
                _dense_of, stacked[name], layer, dt=dt)
        else:
            dense = _make_dense(self, dt, self.quantized)

        def ffn(x):
            if self.fused:
                gu = dense(2 * c.intermediate_size, "w_gateup")(x)
                gate, up = jnp.split(gu, 2, axis=-1)
            else:
                gate = dense(c.intermediate_size, "w_gate")(x)
                up = dense(c.intermediate_size, "w_up")(x)
            return dense(c.hidden_size, "w_down")(nn.silu(gate) * up)

        if live is None:
            return ffn(x)

        def add_ffn(off, h):
            hs = _rows_from(h, off)
            return _set_rows_from(h, off, hs + ffn(norm(hs)))

        return _switch_live(rung, x.shape[1], add_ffn, x)


class Block(nn.Module):
    """One decoder layer, written as an ``nn.scan`` body: the carry threads
    ``(h, full_kv_cache, layer_idx, bufs)`` through the stack so the cache is
    ONE in-place-updated buffer, never a per-layer scan output re-stacked each
    call (which would copy the whole multi-GB cache every decode step).
    ``bufs`` are a live-suffix prefill's projection buffers (``live_offsets``),
    empty otherwise."""

    config: LlamaConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    mesh: Optional[Mesh] = None
    chunked: bool = False
    row_frontier: bool = False
    fused_qkv: bool = False
    quantized: bool = False
    kv_quant: str = "bf16"
    paged: bool = False

    @nn.compact
    def __call__(self, carry, kv_start, kv_len, cos, sin, write_index,
                 block_tables, live=None):
        h, kv, layer, bufs = carry
        attn = Attention(
            self.config, self.dtypes, self.attn_impl, self.mesh, self.chunked,
            self.row_frontier, self.fused_qkv, self.quantized, self.kv_quant,
            self.paged, name="attn",
        )
        mlp = MLP(self.config, self.dtypes, self.fused_qkv, self.quantized, name="mlp")
        # sub-scopes of whichever phase traces the block (obs/tracing.py):
        # the rotation itself is applied inside ``attn``
        args = (kv, layer, kv_start, kv_len, cos, sin, write_index, block_tables)
        if live is not None:
            # (rung, stacked layers): norms and matmuls run in branches, on
            # the live suffix; rotation, the cache write and the kernel stay
            # here, at the bucket's shape
            rung, stacked = live
            own = self.variables["params"]
            norm = lambda name: functools.partial(  # noqa: E731
                rms_norm, scale=own[name]["scale"], eps=self.config.rms_norm_eps,
                dtypes=self.dtypes)
            with phase_scope("attn"):
                h, kv, bufs = attn(h, *args, rung=rung, norm=norm("input_norm"), bufs=bufs)
            with phase_scope("mlp"):
                h = mlp(h, layer, live=(rung, stacked["mlp"]), norm=norm("post_attn_norm"))
            return (h, kv, layer + 1, bufs), None
        with phase_scope("norm_rope"):
            x = RMSNorm(self.config.rms_norm_eps, self.dtypes, name="input_norm")(h)
        with phase_scope("attn"):
            attn_out, kv = attn(x, *args)
            h = h + attn_out
        with phase_scope("norm_rope"):
            x = RMSNorm(self.config.rms_norm_eps, self.dtypes, name="post_attn_norm")(h)
        with phase_scope("mlp"):
            h = h + mlp(x)
        return (h, kv, layer + 1, bufs), None


class LlamaModel(nn.Module):
    """The full decoder. One call signature for training, prefill and decode:

    ``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
    write_index)`` → ``(logits [B,S,V] fp32, new_cache)``.

    ``[kv_start, kv_len)`` is the contiguous window of valid cache slots per
    row (left-padded serving: ``[S - real_len, S)``; right-padded training:
    ``[0, real_len)`` — see ``mask_window``); causality over cache slots is
    applied on top. No mask/bias array is ever materialized.

    - training / logit-eval: ``T == S``, ``write_index = 0``;
    - prefill: bucketed ``S``, ``write_index = 0``, ``kv_len = S``;
    - decode: ``S = 1``, ``write_index = t``, ``kv_len = t + 1``.

    A bucket fixes the cache layout and the compiled shape; the prompt's
    matmuls follow the live suffix. A fresh multi-token call that asks for
    one position's logits (``last_logit_only``: a prompt's prefill) computes
    its projections and FFNs on the tokens behind the batch's smallest
    ``kv_start``, at the granularity of ``live_offsets(S)``; every other
    call, and every row of a batch with an unpadded row, computes all ``S``.
    """

    config: LlamaConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # see Attention.attn_impl ("xla" = differentiable)
    mesh: Optional[Mesh] = None
    chunked: bool = False  # see Attention.chunked (long-prompt prefill)
    row_frontier: bool = False  # see Attention.row_frontier (continuous batching)
    fused_qkv: bool = False  # see Attention.fused_qkv (tp=1 fused projections)
    quantized: bool = False  # see Attention.quantized (weight-only int8 serving)
    kv_quant: str = "bf16"  # see Attention.kv_quant (int8 KV cache)
    paged: bool = False  # see Attention.paged (block-pool KV arena)

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array,
        cache: KVCache,
        kv_start: jax.Array,
        kv_len: jax.Array,
        write_index: jax.Array,
        last_logit_only: bool = False,
        logit_index: Optional[jax.Array] = None,
        block_tables: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, KVCache]:
        c, dt = self.config, self.dtypes
        with phase_scope("embed"):
            if self.quantized and c.tie_word_embeddings:
                # tied head: the [V, D] table is re-read IN FULL by every decode
                # step's logit matmul, so it gets the int8 treatment too (per-row
                # scales serve both the gather and the logits epilogue below)
                embedding = self.param(
                    "embedding_q", nn.initializers.zeros,
                    (c.vocab_size, c.hidden_size), jnp.int8,
                )
                emb_scale = self.param(
                    "embedding_scale", nn.initializers.ones, (c.vocab_size,), jnp.float32
                )
                h = (
                    jnp.take(embedding, tokens, axis=0).astype(dt.compute_dtype)
                    * jnp.take(emb_scale, tokens, axis=0)[..., None].astype(dt.compute_dtype)
                )
            else:
                # untied (or unquantized): the embedding is only ever GATHERED
                # ([B, S] rows per step), so int8 would save no bandwidth
                embedding = self.param(
                    "embedding",
                    nn.initializers.normal(stddev=0.02),
                    (c.vocab_size, c.hidden_size),
                    dt.param_dtype,
                )
                h = jnp.take(embedding, tokens, axis=0).astype(dt.compute_dtype)

        with phase_scope("norm_rope"):
            cos, sin = rope_cos_sin(positions, rope_frequencies(c))

        # a fresh prompt call of which one position's logits leave computes
        # its matmuls on the live suffix (``live_offsets``); the rung is the
        # batch's smallest left pad, read here on the device
        B, S = tokens.shape
        fresh = S > 1 and not self.chunked and not self.paged
        offsets = live_offsets(S) if fresh and last_logit_only and not self.is_initializing() else ()
        live, bufs, skipped = None, (), 0
        if offsets:
            rung = jnp.minimum(jnp.min(kv_start) // offsets[1], len(offsets) - 1).astype(jnp.int32)
            live = (rung, self.variables["params"]["layers"])
            skipped = rung * offsets[1]
            # the projections' buffers ride the layers' loop: every layer's
            # branch writes its suffix rows in place, and the rows in front
            # stay the zeros they start as (padding each result back to the
            # bucket is a copy of it, 0.9 ms a batch-8 layer)
            H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
            widths = (H + 2 * K,) if self.fused_qkv else (H, K, K)
            bufs = tuple(jnp.zeros((B, S, w * hd), dt.compute_dtype) for w in widths)
        counters = cache.counters
        if fresh and counters is not None:
            counters = counters.at[:2].add(jnp.stack([B * (S - skipped), B * S]).astype(counters.dtype))
        if S == 1 and not self.paged and counters is not None:
            step = decode_walk_step(c, self.attn_impl, self.mesh, cache)
            if step is not None:  # a step through the decode kernel: what its walk fetches
                T = cache.k.shape[3]
                counters = counters.at[2:].add(jnp.stack(
                    [decode_slots_streamed(kv_start, kv_len, T, step), B * T]).astype(counters.dtype))

        ScanBlocks = nn.scan(
            Block,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast,) * 7,
            out_axes=0,
            length=c.num_layers,
            # the block returns no broadcast output, and the check traces the
            # body a second time through partial evaluation: every branch of
            # a live-suffix prefill again, in every executable's set-up. An
            # init keeps it: without it the same key draws other parameters
            check_constancy_invariants=self.is_initializing(),
        )
        if self.kv_quant == "int8":
            assert cache.k_scale is not None, (
                "kv_quant='int8' needs an int8 cache — build it with "
                "make_kv_cache(..., quant='int8')"
            )
            kv_in = (cache.k, cache.v, cache.k_scale, cache.v_scale)
        else:
            kv_in = (cache.k, cache.v)
        (h, new_kv, _, _), _ = ScanBlocks(
            c, dt, self.attn_impl, self.mesh, self.chunked, self.row_frontier,
            self.fused_qkv, self.quantized, self.kv_quant, self.paged,
            name="layers",
        )(
            (h, kv_in, jnp.int32(0), bufs), kv_start, kv_len, cos, sin, write_index,
            block_tables, live,
        )
        new_cache = KVCache(*new_kv).replace(counters=counters)

        with phase_scope("norm_rope"):
            h = RMSNorm(c.rms_norm_eps, dt, name="final_norm")(h)
        with phase_scope("lm_head"):
            if logit_index is not None:
                # right-padded prefill (prefix-cache suffix chunks; the paged
                # engine's whole-prompt prefill): the LAST REAL token sits at a
                # dynamic position, not -1 — slice just it before the head
                # projection (same [B, S, V] avoidance as last_logit_only, but
                # at a traced index). A VECTOR index gathers per row — paged
                # admission groups rows of different real lengths in one bucket.
                B = h.shape[0]
                idx = jnp.clip(jnp.asarray(logit_index, jnp.int32), 0, h.shape[1] - 1)
                if idx.ndim == 0:
                    h = jax.lax.dynamic_slice(h, (0, idx, 0), (B, 1, h.shape[2]))
                else:
                    h = jnp.take_along_axis(h, idx.reshape(B, 1, 1), axis=1)
            elif last_logit_only:
                # prefill only consumes the final position — projecting just it
                # avoids a [B, S, V] fp32 intermediate (S x the FLOPs and HBM)
                h = h[:, -1:, :]
            if c.tie_word_embeddings:
                logits = jnp.einsum(
                    "bsd,vd->bsv", h, embedding.astype(dt.compute_dtype),
                    preferred_element_type=jnp.float32,
                )
                if self.quantized:
                    logits = logits * emb_scale[None, None, :]
            elif self.quantized:
                head = self.param(
                    "lm_head_q", nn.initializers.zeros,
                    (c.hidden_size, c.vocab_size), jnp.int8,
                )
                head_scale = self.param(
                    "lm_head_scale", nn.initializers.ones, (c.vocab_size,), jnp.float32
                )
                logits = (
                    jnp.einsum(
                        "bsd,dv->bsv", h, head.astype(dt.compute_dtype),
                        preferred_element_type=jnp.float32,
                    )
                    * head_scale[None, None, :]
                )
            else:
                head = self.param(
                    "lm_head",
                    nn.initializers.normal(stddev=0.02),
                    (c.hidden_size, c.vocab_size),
                    dt.param_dtype,
                )
                logits = jnp.einsum(
                    "bsd,dv->bsv", h, head.astype(dt.compute_dtype),
                    preferred_element_type=jnp.float32,
                )
        return logits.astype(dt.logits_dtype), new_cache


# ---------------------------------------------------------------------------
# masks + init
# ---------------------------------------------------------------------------


def mask_window(pad_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``[B, S]`` contiguous 0/1 pad mask → ``(kv_start, kv_len)`` ``[B]``.

    The whole system only ever produces contiguous valid windows (the engine
    left-pads, training right-pads), so a mask reduces to two scalars per row
    — replacing the reference-era materialized ``[B, 1, S, T]`` bias arrays.
    """
    m = pad_mask.astype(jnp.int32)
    start = jnp.argmax(m, axis=-1).astype(jnp.int32)  # first valid slot (0 if none)
    return start, start + jnp.sum(m, axis=-1).astype(jnp.int32)


def fuse_llama_params(params: dict) -> dict:
    """Fuse the per-layer projection weights for ``LlamaModel(fused_qkv=True)``:
    ``wq|wk|wv -> wqkv`` and ``w_gate|w_up -> w_gateup`` (one concat along the
    output dim, done ONCE on device at engine construction). Valid only
    unsharded / tp=1 — a tp split would cross the concat boundaries. The
    canonical (checkpoint / training / sharding) layout stays unfused.
    Deliberately NOT jitted: a jitted version would copy every pass-through
    leaf (embedding, lm_head, norms, wo, w_down) into fresh buffers —
    doubling peak weight memory at construction — whereas this rebuild
    reuses the original leaf references and allocates only the four
    concatenated kernels."""
    attn = params["layers"]["attn"]
    mlp = params["layers"]["mlp"]
    fused = dict(params)
    fused["layers"] = dict(params["layers"])
    fused["layers"]["attn"] = {
        "wqkv": {
            "kernel": jnp.concatenate(
                [attn["wq"]["kernel"], attn["wk"]["kernel"], attn["wv"]["kernel"]],
                axis=-1,
            )
        },
        "wo": attn["wo"],
    }
    fused["layers"]["mlp"] = {
        "w_gateup": {
            "kernel": jnp.concatenate(
                [mlp["w_gate"]["kernel"], mlp["w_up"]["kernel"]], axis=-1
            )
        },
        "w_down": mlp["w_down"],
    }
    return fused


def _quantize_leaf(w: jax.Array, axis: int, donate: bool):
    """Symmetric per-output-channel int8: reduce |w| over the contracted
    ``axis``, keep fp32 scales. Runs jitted on device so a multi-GB bf16
    tree never round-trips to host; int8 output is the only new buffer."""

    def q(w):
        wf = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=axis) / 127.0, 1e-8)
        kq = jnp.round(wf / jnp.expand_dims(scale, axis)).astype(jnp.int8)
        return kq, scale

    return jax.jit(q, donate_argnums=0 if donate else ())(w)


def quantize_llama_params(params: dict, donate: bool = False) -> dict:
    """bf16 tree → weight-only int8 tree (the ``LlamaModel(quantized=True)``
    layout): every projection kernel becomes ``{kernel_q int8, scale fp32}``;
    the tied embedding (re-read in full by each decode step's logit matmul)
    or the untied ``lm_head`` likewise; norms and an untied embedding (gather
    -only traffic) stay bf16. Composes with :func:`fuse_llama_params` in
    either order — per-output-channel scales are preserved by concatenation
    along the output axis. Like the fuser, pass-through leaves are reused,
    not copied; with ``donate=True`` the bf16 source kernels are donated
    (freed immediately — ONLY safe when the caller holds the sole reference
    and drops it; the engine deliberately passes donate=False because param
    trees are legitimately shared across engine instances).

    Serving-only (the reference never trains either — rag.py:172): int8
    params are not differentiable; keep the bf16 tree for training.
    """

    def q_group(group: dict, axis: int) -> dict:
        out = {}
        for name, sub in group.items():
            if isinstance(sub, dict) and "kernel" in sub:
                kq, scale = _quantize_leaf(sub["kernel"], axis, donate)
                out[name] = {"kernel_q": kq, "qscale": scale}
            else:
                out[name] = sub  # norms etc.
        return out

    quant = dict(params)
    layers = dict(params["layers"])
    # stacked [L, in, out] kernels contract over axis -2
    layers["attn"] = q_group(params["layers"]["attn"], axis=-2)
    layers["mlp"] = q_group(params["layers"]["mlp"], axis=-2)
    quant["layers"] = layers
    if "lm_head" in params:  # untied: [D, V], contract over D
        kq, scale = _quantize_leaf(params["lm_head"], axis=0, donate=donate)
        del quant["lm_head"]
        quant["lm_head_q"], quant["lm_head_scale"] = kq, scale
    else:  # tied: [V, D] rows are the logit output channels
        kq, scale = _quantize_leaf(params["embedding"], axis=1, donate=donate)
        del quant["embedding"]
        quant["embedding_q"], quant["embedding_scale"] = kq, scale
    return quant


def synth_leaf_kind(path, dtype) -> str:
    """Classify a Llama param leaf, given its ``path`` of string keys, for
    the synthetic weight builders (``utils/synth.synth_llama_params``,
    the benchmark's seeded trees): ``"kernel_q"`` (int8 kernels),
    ``"quant_scale"`` (per-channel dequant scales), ``"norm"`` (RMSNorm
    weights — MUST stay ~1), ``"embedding"`` (the token table) or
    ``"kernel"`` (bf16 projection kernels and output head). Norms go by
    their module's name, not by rank: the layers are stacked, so a norm
    weight is an ``[L, D]`` leaf. Quant scales match by EXACT leaf name:
    RMSNorm weights are ALSO called "scale" in the Flax tree, and a
    substring match once flattened every norm to ~1e-4 and collapsed the
    network to flat logits."""
    import numpy as np

    name = path[-1]
    if np.dtype(dtype) == np.int8:
        return "kernel_q"
    if name in ("qscale", "lm_head_scale", "embedding_scale"):
        return "quant_scale"
    if any("norm" in part for part in path):
        return "norm"
    return "embedding" if name == "embedding" else "kernel"


def init_llama_params(
    rng: jax.Array,
    config: LlamaConfig,
    dtypes: DTypePolicy = DTypePolicy(),
):
    """Random-init parameter pytree (tests, benchmarks; real weights come from
    the safetensors loader in ``models/loader.py``)."""
    model = LlamaModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_kv_cache(config, B, S, dtypes.compute_dtype)
    tokens = jnp.zeros((B, S), jnp.int32)
    positions = jnp.zeros((B, S), jnp.int32)
    window = jnp.zeros((B,), jnp.int32), jnp.full((B,), S, jnp.int32)
    variables = model.init(rng, tokens, positions, cache, *window, jnp.int32(0))
    return variables["params"]

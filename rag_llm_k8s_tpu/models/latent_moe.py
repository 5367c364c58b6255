"""The latent-attention, sparse-expert decoder (``LatentMoEConfig``).

The second decoder family beside ``models/llama.py``, with the same call
signature, so the engine's programs (bucketed prefill, the decode loop,
prompt-lookup verify, chunked prefill, the exact scorer) serve it unchanged:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

What differs from the Llama block:

- **Multi-head latent attention.** ``c_q = RMSNorm(x W_DQ)``, ``q = c_q W_UQ``
  (per head ``nope | rope``); ``[c | r] = x W_DKV``; the cache holds only
  ``c_kv = RMSNorm(c)`` and ``k_rope = RoPE(r)`` (one for all heads):
  ``LatentCache`` ``[L, B, T, C]`` + ``[L, B, T, R]``. Single-shot prefill
  expands the FRESH latents (``[k_nope | v] = c_kv W_UKV``) and runs flash
  attention with key width ``nope + rope`` against value width ``v``; decode,
  verify and chunked prefill use the absorbed form over the cache
  (``ops/mla.py``): the same numbers, never a per-head K/V over the cache.
  RoPE pairs dimension ``i`` with ``i + R/2`` (by halves, as the rest of this
  package; the publisher's interleaved pairing is a permutation of ``W_UQ``'s
  and ``W_DKV``'s rope columns). YaRN scales the frequencies and the softmax.
  Where the configuration says so, the queries and the normed latent carry
  the publisher's LoRA scales (``mla_scale_q_lora`` / ``mla_scale_kv_lora``);
  the latent is cached scaled, so both forms read it alike.
- **Layers.** ``first_k_dense`` leading dense layers OUTSIDE the layers' loop
  (``dense_<i>`` in the tree), then the MoE layers as ONE ``lax.scan`` over a
  stacked tree (``layers``). A layer is ``sublayers_per_layer`` attention
  sublayers (``Block``): with one, norm, attention, norm, expert layer; with
  two (shortcut-connected), each sublayer has a dense SwiGLU of its own, the
  ONE expert layer branches off sublayer 0's normed stream and joins the
  residual after the last sublayer's FFN. The cache holds a plane an
  attention sublayer (``num_cache_planes``).
- **Sparse experts** (``ops/moe.py``): the router scores all its outputs
  (sigmoid with group-limited choice, or softmax over routed and
  zero-computation experts), this chip's held range is computed by grouped
  matmuls over gathered assignments with no capacity limit; a shared expert
  and the zero-computation (identity) experts' term where the configuration
  has them. ``y = sum_{i selected and held} w_i E_i(x) [+ E_shared(x)]
  [+ (sum_{i selected and zero} w_i) x]``.

The cache carries the family's counters (``LatentCache.counters``), so they
ride every program the engine builds and come back with the answer.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import DTypePolicy, LatentMoEConfig, YarnScalingConfig
from rag_llm_k8s_tpu.models.llama import RMSNorm, apply_rope, rope_cos_sin
from rag_llm_k8s_tpu.models.llama import resolve_attn_impl as resolve_impl
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops import mla, moe
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed

# LatentCache.counters: [mode, what] int32, flattened. ``mode`` is how the
# model was called (single-shot prefill | one-token decode | a chunk over the
# cache: verify, chunked prefill, the scorer); ``what`` is ops.moe.ExpertCounts
# plus the layer-calls the mode made, and last the cache slots a step through
# the decode kernel fetched over the rows and rows x the slots allocated
# (``ops/attention.py decode_slots_streamed``; decode only), then the one-hot
# entries the experts' combine set, then the rows the down projection's
# grouped kernel multiplied (each appended last: the older fields keep their
# places).
COUNTER_MODES = ("prefill", "decode", "chunk")
COUNTER_FIELDS = ("tokens", "routed", "computed", "experts_hit", "layer_calls", "zero",
                  "slots_streamed", "slots_allocated", "combined", "tile_rows")
N_COUNTERS = len(COUNTER_MODES) * len(COUNTER_FIELDS)
# what ``/metrics`` calls them (``engine_<name>``) -> (mode, field) of the
# block; the first sums its field over the modes. Assignments to experts HELD
# here, assignment rows the grouped kernel stored and (last) one-hot entries
# the combine set, each by the kernel's own count, by how the model was
# called; held experts hit, summed over decode layer-steps, and those steps;
# assignments to zero-computation experts (none where a model has none); the
# rows the down projection's grouped kernel multiplied (its visits x its row
# tile: what ``computed`` is a share of is how full its tiles were)
COUNTER_STATS = {
    "moe_tokens_routed": (None, "tokens"),
    "moe_prefill_assignments_held": ("prefill", "routed"),
    "moe_prefill_assignments_computed": ("prefill", "computed"),
    "moe_decode_assignments_held": ("decode", "routed"),
    "moe_decode_assignments_computed": ("decode", "computed"),
    "moe_chunk_assignments_held": ("chunk", "routed"),
    "moe_chunk_assignments_computed": ("chunk", "computed"),
    "moe_decode_experts_hit": ("decode", "experts_hit"),
    "moe_decode_layer_steps": ("decode", "layer_calls"),
    "moe_prefill_layer_calls": ("prefill", "layer_calls"),
    "moe_prefill_assignments_zero": ("prefill", "zero"),
    "moe_decode_assignments_zero": ("decode", "zero"),
    "moe_chunk_assignments_zero": ("chunk", "zero"),
    "decode_slots_streamed": ("decode", "slots_streamed"),
    "decode_slots_allocated": ("decode", "slots_allocated"),
    "moe_prefill_assignments_combined": ("prefill", "combined"),
    "moe_decode_assignments_combined": ("decode", "combined"),
    "moe_chunk_assignments_combined": ("chunk", "combined"),
    "moe_prefill_tile_rows": ("prefill", "tile_rows"),
    "moe_decode_tile_rows": ("decode", "tile_rows"),
    "moe_chunk_tile_rows": ("chunk", "tile_rows"),
}


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_STATS`` from one fetched counter block."""
    import numpy as np

    c = np.asarray(row, np.int64).reshape(len(COUNTER_MODES), len(COUNTER_FIELDS))
    return {name: int(c[:, COUNTER_FIELDS.index(field)].sum() if mode is None
                      else c[COUNTER_MODES.index(mode), COUNTER_FIELDS.index(field)])
            for name, (mode, field) in COUNTER_STATS.items()}


@flax.struct.dataclass
class LatentCache:
    """``c_kv [L, B, T, C]`` normed latents and ``k_rope [L, B, T, R]`` rotated
    shared key slices (``L``: a plane an attention sublayer,
    ``num_cache_planes``), written at a shared index like ``KVCache``;
    ``counters [N_COUNTERS]`` int32 accumulate what the expert layers did."""

    c_kv: jax.Array
    k_rope: jax.Array
    counters: jax.Array


def make_latent_cache(config: LatentMoEConfig, batch_size: int, max_seq_len: int,
                      dtype: jnp.dtype = jnp.bfloat16) -> LatentCache:
    lead = (config.num_cache_planes, batch_size, max_seq_len)
    return LatentCache(
        c_kv=jnp.zeros(lead + (config.kv_lora_rank,), dtype),
        k_rope=jnp.zeros(lead + (config.qk_rope_head_dim,), dtype),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, s: Optional[YarnScalingConfig]) -> jax.Array:
    """Inverse frequencies ``[dim // 2]``: ``theta_i`` for the dimensions that
    turn more than ``beta_fast`` times in the original context, ``theta_i /
    factor`` for those that turn fewer than ``beta_slow`` times, a linear
    ramp over dimension index between the two."""
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if s is None:
        return freqs

    def turns_dim(turns: float) -> float:
        return dim * math.log(s.original_max_position_embeddings / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_dim(s.beta_fast)), 0)
    high = min(math.ceil(turns_dim(s.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / s.factor * ramp + freqs * (1.0 - ramp)


def softmax_scale(config: LatentMoEConfig) -> float:
    """``qk_head_dim ** -0.5 * m ** 2``, ``m`` YaRN's ``mscale_all_dim`` term."""
    s = config.rope_scaling
    m = 1.0 if s is None else yarn_mscale(s.factor, s.mscale_all_dim)
    return config.qk_head_dim ** -0.5 * m * m


def rope_amplitude(config: LatentMoEConfig) -> float:
    """What cos and sin are multiplied by (1 when mscale == mscale_all_dim)."""
    s = config.rope_scaling
    return 1.0 if s is None else yarn_mscale(s.factor, s.mscale) / yarn_mscale(s.factor, s.mscale_all_dim)


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------


class Kernel(nn.Module):
    """A bare ``[in, out]`` kernel under the same leaf name ``nn.Dense``
    uses, for the weights the caller multiplies itself."""

    shape: Tuple[int, ...]
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", nn.initializers.normal(stddev=0.02), self.shape,
                          self.dtypes.param_dtype)


def _dense(module: nn.Module, dt: DTypePolicy):
    return lambda feats, name: nn.Dense(
        feats, use_bias=False, dtype=dt.compute_dtype, param_dtype=dt.param_dtype,
        parent=module, name=name)


class LatentAttention(nn.Module):
    config: LatentMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False  # S > 1 calls attend over the cache (offset causality)
    q_direct: bool = False  # one query projection ``wq`` from the stream: no low-rank step, no norm
    rotate: bool = True  # False: the shared key slice and the queries' are not rotated (no position term)

    @nn.compact
    def __call__(self, x, planes, layer, kv_start, kv_len, cos, sin, write_index):
        c, dt = self.config, self.dtypes
        B, S, _ = x.shape
        H, C, R = c.num_heads, c.kv_lora_rank, c.qk_rope_head_dim
        dn, dv = c.qk_nope_head_dim, c.v_head_dim
        dense = _dense(self, dt)
        impl = resolve_impl(self.attn_impl)
        scale = softmax_scale(c)

        if self.q_direct:
            c_q, wq_b = x, dense(H * (dn + R), "wq")
        else:
            c_q = RMSNorm(c.rms_norm_eps, dt, name="q_norm")(dense(c.q_lora_rank, "wq_a")(x))
            wq_b = dense(H * (dn + R), "wq_b")
        wo = dense(c.hidden_size, "wo")
        rope = apply_rope if self.rotate else lambda x, cos, sin: x
        latent = dense(C + R, "wkv_a")(x)
        c_kv = RMSNorm(c.rms_norm_eps, dt, name="kv_norm")(latent[..., :C])
        if c.mla_scale_kv_lora:  # the latent is cached scaled: both forms read it alike
            c_kv = c_kv * jnp.asarray((c.hidden_size / C) ** 0.5, c_kv.dtype)
        q_scale = (c.hidden_size / c.q_lora_rank) ** 0.5 if c.mla_scale_q_lora else None
        k_rope = rope(latent[..., None, C:], cos, sin)[:, :, 0]  # [B, S, R]
        w_ukv = Kernel((C, H * (dn + dv)), dt, name="wkv_b")().astype(dt.compute_dtype)

        c_cache, r_cache = planes
        c_cache = jax.lax.dynamic_update_slice(
            c_cache, c_kv.astype(c_cache.dtype)[None], (layer, 0, write_index, 0))
        r_cache = jax.lax.dynamic_update_slice(
            r_cache, k_rope.astype(r_cache.dtype)[None], (layer, 0, write_index, 0))

        def queries(c_q, cos, sin):
            q = wq_b(c_q)
            if q_scale is not None:  # nope and rope slices alike, before the rotation
                q = q * jnp.asarray(q_scale, q.dtype)
            q = q.reshape(*c_q.shape[:2], H, dn + R)
            return q[..., :dn], rope(q[..., dn:], cos, sin)

        if S > 1 and not self.chunked:
            # single-shot prefill: the prompt's own latents, expanded
            def expanded(c_q, c_kv, k_rope, cos, sin, kv_start, kv_len):
                n = c_q.shape[0]
                q_nope, q_rope = queries(c_q, cos, sin)
                kv = jnp.dot(c_kv, w_ukv).reshape(n, S, H, dn + dv)
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :], (n, S, H, R))], axis=-1)
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                with phase_scope("latent"):
                    if impl == "xla":
                        o = mla.mla_prefill_attention_xla(
                            q, k, kv[..., dn:], kv_start, kv_len, scale=scale)
                    else:
                        o = mla.mla_flash_attention(
                            q, k, kv[..., dn:], kv_start, kv_len, scale=scale,
                            interpret=impl == "pallas_interpret")
                return wo(o.astype(dt.compute_dtype).reshape(n, S, H * dv))

            count_kernel_build(
                "prefill", "mla_prefill_attention_xla" if impl == "xla" else "mla_flash_attention")
            rows = by_rows if rowwise(c, B, S, dt.compute_dtype) else lambda fn, *a: fn(*a)
            out = rows(expanded, c_q, c_kv, k_rope, cos, sin, kv_start, kv_len)
        else:
            q_nope, q_rope = queries(c_q, cos, sin)
            with phase_scope("latent"):
                w = w_ukv.reshape(C, H, dn + dv)
                q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w[..., :dn])
                if S == 1 and impl != "xla":
                    count_kernel_build("decode", "mla_decode_attention")
                    o_lat = mla.mla_decode_attention(
                        q_lat, q_rope, c_cache, r_cache, kv_start, kv_len, layer,
                        scale=scale, interpret=impl == "pallas_interpret")
                else:
                    count_kernel_build("decode" if S == 1 else "chunk", "latent_attention_xla")
                    o_lat = mla.latent_attention_xla(
                        q_lat, q_rope, c_cache, r_cache, kv_start, kv_len, layer,
                        write_index, scale=scale)
                o = jnp.einsum("bshc,chv->bshv", o_lat, w[..., dn:])
            out = wo(o.astype(dt.compute_dtype).reshape(B, S, H * dv))
        return out, (c_cache, r_cache)


# a single-shot prefill whose expanded q, k and v (every head's, the whole
# batch's) reach this many bytes goes through attention and the dense FFN a
# row at a time. By SHAPE, whatever the kernels: at 128 heads a row of a 4096
# bucket expands to 0.5 GiB, and eight at once do not fit beside the weights
ROWWISE_BYTES = 1 << 30


def rowwise(config: LatentMoEConfig, batch: int, seq: int, dtype) -> bool:
    per_token = config.num_heads * (2 * config.qk_head_dim + config.v_head_dim) * jnp.dtype(dtype).itemsize
    return batch > 1 and batch * seq * per_token >= ROWWISE_BYTES


def by_rows(fn, *args):
    """``fn`` over the batch a row at a time, in order: each row starts when
    the one before it is written (an optimization barrier ties them), so what
    a row expands to is live once. Rows are written into one buffer (one a
    leaf, where ``fn`` returns a tuple) by updates traced HERE, under the
    caller's scope: joined by a concatenate, the compiler writes them with
    copies of its own that carry no scope."""
    out = None
    for b in range(args[0].shape[0]):
        row = tuple(a[b:b + 1] for a in args)
        if out is not None:
            row, out = jax.lax.optimization_barrier((row, out))
        y = fn(*row)
        if out is None:
            out = jax.tree.map(lambda leaf: jnp.zeros((args[0].shape[0],) + leaf.shape[1:], leaf.dtype), y)
        out = jax.tree.map(lambda buf, leaf: jax.lax.dynamic_update_slice(buf, leaf, (b,) + (0,) * (leaf.ndim - 1)),
                           out, y)
    return out


class SwiGLU(nn.Module):
    width: int
    hidden_size: int
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self, x):
        dense = _dense(self, self.dtypes)
        return dense(self.hidden_size, "w_down")(
            nn.silu(dense(self.width, "w_gate")(x)) * dense(self.width, "w_up")(x))


class Experts(nn.Module):
    """This chip's held routed experts of EVERY MoE layer, stacked ``[layers,
    held, in, out]``. They live at the model's level, outside the layers'
    loop, and each layer reads its own through the grouped kernel's index
    map: a loop that sliced them would copy a layer's 1.3 GB in front of
    every custom call."""

    config: LatentMoEConfig
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self):
        c, init = self.config, nn.initializers.normal(stddev=0.02)
        L, E, D, F = c.num_moe_layers, c.experts_held, c.hidden_size, c.moe_intermediate_size
        pd, cd = self.dtypes.param_dtype, self.dtypes.compute_dtype
        return tuple(self.param(name, init, shape, pd).astype(cd) for name, shape in (
            ("w_gate", (L, E, D, F)), ("w_up", (L, E, D, F)), ("w_down", (L, E, F, D))))


class SparseMLP(nn.Module):
    config: LatentMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, experts_stack, moe_layer):
        c, dt = self.config, self.dtypes
        B, S, D = x.shape
        flat = x.reshape(B * S, D)
        impl = resolve_impl(self.attn_impl)
        with phase_scope("router"):
            w_g = Kernel((D, c.router_width), dt, name="router")()
            bias = self.param("router_bias", nn.initializers.zeros, (c.router_width,), jnp.float32)
            # float32 scores. bf16 inputs multiply exactly into the float32
            # accumulator in one pass; float32 inputs (the fp32 policy) need
            # the highest precision said, or a TPU rounds them to bf16
            logits = jnp.dot(
                flat, w_g.astype(flat.dtype), preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST if flat.dtype == jnp.float32 else None)
            experts, weights = moe.route(
                logits, bias, top_k=c.num_experts_per_tok, n_group=c.n_group,
                topk_group=c.topk_group, scaling=c.routed_scaling_factor,
                normalize=c.norm_topk_prob, scoring=c.scoring_func, impl=impl, eps=c.norm_topk_eps)
        with phase_scope("experts"):
            y, counts = moe.held_expert_ffn(
                flat, experts, weights, *experts_stack, moe_layer, c.first_held,
                c.router_width, impl=impl)
        if c.zero_expert_num:
            with phase_scope("zero"):
                term, n_zero = moe.zero_expert_term(flat, experts, weights, c.n_routed_experts)
                y, counts = y + term, counts._replace(zero=n_zero)
        if c.n_shared_experts:
            with phase_scope("shared"):
                y = y + SwiGLU(c.moe_intermediate_size * c.n_shared_experts, D, dt, name="shared")(flat)
        return y.reshape(B, S, D), counts


def _count(counters, mode: str, counts: moe.ExpertCounts):
    base = COUNTER_MODES.index(mode) * len(COUNTER_FIELDS)
    add = {**counts._asdict(), "layer_calls": 1}  # the slots are the decode kernel's, counted a step
    add = jnp.stack([jnp.asarray(add.get(f, 0), jnp.int32) for f in COUNTER_FIELDS])
    return jax.lax.dynamic_update_slice(
        counters, jax.lax.dynamic_slice(counters, (base,), (add.shape[0],)) + add, (base,))


class Block(nn.Module):
    """One decoder layer: the scan body of the MoE layers (``sparse``) and,
    called directly, a leading dense layer. The carry threads ``(h, cache
    planes, counters, plane)`` like ``models/llama.py``'s; ``plane`` is the
    cache plane the layer's first attention writes.

    A sparse layer is ``sublayers_per_layer`` attention sublayers. With one,
    the expert layer IS the sublayer's FFN. With two (shortcut-connected)
    every sublayer has a dense SwiGLU, and the expert layer reads sublayer
    0's normed stream and joins the residual after the last sublayer's FFN:
    its value stays live through an attention and a dense FFN."""

    config: LatentMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False
    sparse: bool = True

    @nn.compact
    def __call__(self, carry, kv_start, kv_len, cos, sin, write_index, experts_stack=None):
        c, dt = self.config, self.dtypes
        h, planes, counters, plane = carry
        n = c.sublayers_per_layer if self.sparse else 1
        branch = None
        for i in range(n):
            sub = f"_{i}" if n > 1 else ""
            with phase_scope("norm_rope"):
                x = RMSNorm(c.rms_norm_eps, dt, name="input_norm" + sub)(h)
            with phase_scope("attn"):
                attn_out, planes = LatentAttention(c, dt, self.attn_impl, self.chunked, name="attn" + sub)(
                    x, planes, plane + i, kv_start, kv_len, cos, sin, write_index)
                h = h + attn_out
            with phase_scope("norm_rope"):
                x = RMSNorm(c.rms_norm_eps, dt, name="post_attn_norm" + sub)(h)
            with phase_scope("mlp"):
                if self.sparse and i == 0:
                    branch, counts = SparseMLP(c, dt, self.attn_impl, name="mlp")(
                        x, experts_stack, (plane - c.first_k_dense) // n)
                    mode = "decode" if x.shape[1] == 1 else "chunk" if self.chunked else "prefill"
                    counters = _count(counters, mode, counts)
                if not self.sparse or n > 1:
                    with phase_scope("dense"):
                        mlp = SwiGLU(c.intermediate_size, c.hidden_size, dt,
                                     name="ffn" + sub if self.sparse else "mlp")
                        big = not self.chunked and rowwise(c, x.shape[0], x.shape[1], dt.compute_dtype)
                        h = h + (by_rows(mlp, x) if big else mlp(x))
                if branch is not None and i == n - 1:
                    h = h + branch
        return (h, planes, counters, plane + n), None


class LatentMoEModel(nn.Module):
    config: LatentMoEConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # see LatentAttention.chunked

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array,
        cache: LatentCache,
        kv_start: jax.Array,
        kv_len: jax.Array,
        write_index: jax.Array,
        last_logit_only: bool = False,
        logit_index: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, LatentCache]:
        c, dt = self.config, self.dtypes
        with phase_scope("embed"):
            embedding = self.param("embedding", nn.initializers.normal(stddev=0.02),
                                   (c.vocab_size, c.hidden_size), dt.param_dtype)
            h = jnp.take(embedding, tokens, axis=0).astype(dt.compute_dtype)
        with phase_scope("norm_rope"):
            cos, sin = rope_cos_sin(
                positions, yarn_frequencies(c.qk_rope_head_dim, c.rope_theta, c.rope_scaling))
            amp = rope_amplitude(c)
            if amp != 1.0:
                cos, sin = cos * amp, sin * amp

        counters = cache.counters
        if tokens.shape[1] == 1 and resolve_impl(self.attn_impl) != "xla":
            # a step through ``mla_decode_attention``: what its walk fetches of
            # a plane (every plane's call fetches the same, so a step counts once)
            B, T = cache.c_kv.shape[1:3]
            step = mla.latent_decode_step(T, c.num_heads, c.kv_lora_rank, cache.c_kv.dtype)
            at = COUNTER_MODES.index("decode") * len(COUNTER_FIELDS) + COUNTER_FIELDS.index("slots_streamed")
            counters = counters.at[at:at + 2].add(jnp.stack(
                [decode_slots_streamed(kv_start, kv_len, T, step), B * T]).astype(counters.dtype))
        carry = (h, (cache.c_kv, cache.k_rope), counters, jnp.int32(0))
        window = (kv_start, kv_len, cos, sin, write_index)
        for i in range(c.first_k_dense):  # outside the layers' loop
            carry, _ = Block(c, dt, self.attn_impl, self.chunked, sparse=False,
                             name=f"dense_{i}")(carry, *window)
        if c.num_moe_layers:
            experts_stack = Experts(c, dt, name="experts")()
            scan = nn.scan(
                Block, variable_axes={"params": 0}, split_rngs={"params": True},
                in_axes=(nn.broadcast,) * 6, out_axes=0, length=c.num_moe_layers)
            carry, _ = scan(c, dt, self.attn_impl, self.chunked, name="layers")(
                carry, *window, experts_stack)
        h, (c_kv, k_rope), counters, _ = carry

        with phase_scope("norm_rope"):
            h = RMSNorm(c.rms_norm_eps, dt, name="final_norm")(h)
        with phase_scope("lm_head"):
            if logit_index is not None:
                B = h.shape[0]
                idx = jnp.clip(jnp.asarray(logit_index, jnp.int32), 0, h.shape[1] - 1)
                if idx.ndim == 0:
                    h = jax.lax.dynamic_slice(h, (0, idx, 0), (B, 1, h.shape[2]))
                else:
                    h = jnp.take_along_axis(h, idx.reshape(B, 1, 1), axis=1)
            elif last_logit_only:
                h = h[:, -1:, :]
            head = self.param("lm_head", nn.initializers.normal(stddev=0.02),
                              (c.hidden_size, c.vocab_size), dt.param_dtype)
            logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dt.compute_dtype),
                                preferred_element_type=jnp.float32)
        return logits.astype(dt.logits_dtype), LatentCache(c_kv, k_rope, counters)


def init_latent_moe_params(rng: jax.Array, config: LatentMoEConfig,
                           dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = LatentMoEModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_latent_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

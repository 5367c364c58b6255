"""The windowed-attention, sparse-expert decoder (``WindowedMoEConfig``).

The third decoder family, with the call signature of the other two, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it unchanged:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

It is made of the other two's parts and adds what neither has:

- **Attention over per-head K/V planes** (``models/llama.py``'s seam:
  ``KVCache`` ``[L, B, K, T, hd]``, ``attend``, ``rope_cos_sin``,
  ``apply_rope``) whose layers differ in KIND. A ``full_attention`` layer
  attends causally over the row's window ``[kv_start, kv_len)``; a
  ``sliding_attention`` layer over its last ``sliding_window`` slots: ``k >
  q - W`` in prefill (``flash_attention_window``), ``[max(kv_start, kv_len -
  W), kv_len)`` in a decode step (the decode kernel's walk, handed the
  shorter window), the XLA form with the bound in its mask in a chunk over
  the cache. Every plane is ``T`` slots long in both kinds: a sliding layer
  does not yet keep a ring of ``W``.
- **Query heads by layer** (``num_attention_heads_per_layer``), over the same
  ``num_kv_heads``: the group size a KV head differs by layer kind.
- **Two rotary tables a call** (``rope_parameters``, one a layer kind; a
  kind may rotate only the first part of a head, and scale cos and sin by
  YaRN's attention factor), chosen by the layer's kind inside the trip.
- **A per-head gate** on attention's output: ``g = softplus(x W_g)``, one
  scalar a head, ``o_h <- g_h o_h`` in front of the output projection.
- **Sparse experts behind them** (``models/latent_moe.py``'s ``SparseMLP``,
  ``Experts``, ``SwiGLU`` over ``ops/moe.py``): sigmoid scores with a
  selection-only bias, the top ``num_experts_per_tok`` of ALL published
  experts, weights normalised over the chosen and scaled, this chip's held
  range computed, a shared expert beside them.

Parameter SHAPES differ by layer kind, so one ``lax.scan`` over layers
cannot stack them: a trip of the layers' loop is one PERIOD (``config.period``
layers, ``l0`` .. in the tree under ``periods``), the leading dense layers
outside it (``lead_<i>``), as the latent family runs two sublayers a trip.

The cache carries the family's counters (``KVCache.counters``): the latent
family's block (the experts' assignments by mode; a FULL layer's decode
slots, a step counted once) and, appended last, the slots the SLIDING layers'
walks fetched and were allocated, summed over those layers, then the
query-key pairs their prefill kernel's steps multiplied and the live ones.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import DTypePolicy, RopeParameters, WindowedMoEConfig, YarnScalingConfig
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.models.llama import (
    KVCache, RMSNorm, apply_rope, attend, resolve_attn_impl, rope_cos_sin,
)
from rag_llm_k8s_tpu.obs.tracing import phase_scope
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed, flash_window_pairs, gqa_decode_step

# KVCache.counters: the latent family's [mode, what] block (``lm.COUNTER_FIELDS``;
# its decode slots are a FULL layer's, counted once a step), then the slots the
# sliding layers' decode walks fetched and rows x the slots allocated to them,
# summed over the sliding layers (a step through the decode kernel at a time),
# then one query head's query-key pairs that the steps of those layers' prefill
# kernel multiplied and the pairs of them that were live (a single-shot prefill
# through ``flash_attention_window`` at a time: ``ops/attention.py flash_window_pairs``)
WINDOW_STATS = ("decode_slots_streamed_window", "decode_slots_allocated_window",
                "prefill_window_pairs_multiplied", "prefill_window_pairs_live")
_WINDOW_PAIRS = lm.N_COUNTERS + WINDOW_STATS.index("prefill_window_pairs_multiplied")
N_COUNTERS = lm.N_COUNTERS + len(WINDOW_STATS)
COUNTER_NAMES = tuple(lm.COUNTER_STATS) + WINDOW_STATS
SLIDING = "sliding_attention"
_DECODE_SLOTS = lm.COUNTER_MODES.index("decode") * len(lm.COUNTER_FIELDS) + lm.COUNTER_FIELDS.index(
    "slots_streamed")


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    out = lm.fold_counters(row[:lm.N_COUNTERS])
    out.update({name: int(n) for name, n in zip(WINDOW_STATS, row[lm.N_COUNTERS:])})
    return out


def make_windowed_cache(config: WindowedMoEConfig, batch_size: int, max_seq_len: int,
                        dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
    """``KVCache`` planes ``[L, B, K, T, hd]``, a plane a layer at its depth,
    one length for both layer kinds, with this family's counters."""
    shape = (config.num_layers, batch_size, config.num_kv_heads, max_seq_len, config.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def rotary_dim(config: WindowedMoEConfig, rope: RopeParameters) -> int:
    return int(config.head_dim * rope.partial_rotary_factor)


def rope_table(positions: jax.Array, config: WindowedMoEConfig, rope: RopeParameters):
    """``cos, sin [B, S, rotary_dim // 2]`` of one layer kind's table."""
    yarn = None if rope.rope_type != "yarn" else YarnScalingConfig(
        factor=rope.factor, beta_fast=rope.beta_fast, beta_slow=rope.beta_slow,
        original_max_position_embeddings=rope.original_max_position_embeddings)
    cos, sin = rope_cos_sin(positions, lm.yarn_frequencies(rotary_dim(config, rope), rope.rope_theta, yarn))
    if rope.rope_type == "yarn" and rope.attention_factor != 1.0:
        cos, sin = cos * rope.attention_factor, sin * rope.attention_factor
    return cos, sin


def rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """The first ``2 * cos.shape[-1]`` dimensions of every head by halves,
    the rest untouched."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate([apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def rowwise(batch: int, seq: int, width: int, dtype) -> bool:
    """Whether a batch's ``[batch, seq, width]`` intermediates reach
    ``lm.ROWWISE_BYTES`` (and there is more than one row to take in turn)."""
    return batch > 1 and batch * seq * width * jnp.dtype(dtype).itemsize >= lm.ROWWISE_BYTES


class GatedAttention(nn.Module):
    """GQA of ``heads`` query heads over the layer's K/V plane, ``kind`` full
    or sliding, each head's output gated before the output projection."""

    config: WindowedMoEConfig
    dtypes: DTypePolicy
    heads: int
    kind: str
    attn_impl: str = "auto"
    chunked: bool = False  # S > 1 calls attend over the cache (offset causality)

    @nn.compact
    def __call__(self, x, planes, layer, kv_start, kv_len, cos, sin, write_index):
        c, dt = self.config, self.dtypes
        B, S, _ = x.shape
        H, K, hd = self.heads, c.num_kv_heads, c.head_dim
        dense = lm._dense(self, dt)
        window = c.sliding_window if self.kind == SLIDING else None
        wq, wg, wo = dense(H * hd, "wq"), dense(H, "wg"), dense(c.hidden_size, "wo")
        k = rotate(dense(K * hd, "wk")(x).reshape(B, S, K, hd), cos, sin)
        v = dense(K * hd, "wv")(x).reshape(B, S, K, hd)
        k_cache, v_cache = planes
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.transpose(0, 2, 1, 3).astype(k_cache.dtype)[None], (layer, 0, 0, write_index, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype)[None], (layer, 0, 0, write_index, 0))
        seam = dict(impl=resolve_attn_impl(self.attn_impl), window=window)
        fresh = S > 1 and not self.chunked

        def heads_of(x, k, v, cos, sin, kv_start, kv_len):
            """Queries, attention, gate and output projection of ``x``'s rows
            (``k`` / ``v``: their fresh keys in a single-shot prefill, else the planes)."""
            n = x.shape[0]
            q = rotate(wq(x).reshape(n, S, H, hd), cos, sin)
            with phase_scope("window" if window else "global"):
                if S == 1:
                    o = attend(q, k, v, kv_start, kv_len, layer, mode="decode", **seam)
                elif self.chunked:
                    o = attend(q, k, v, kv_start, kv_len, layer, mode="chunk", write_index=write_index, **seam)
                else:  # writes at slot 0: the fresh K/V are the populated prefix
                    o = attend(q, k, v, kv_start, kv_len, layer, mode="prefill", **seam)
            with phase_scope("gate"):
                gate = jax.nn.softplus(wg(x).astype(jnp.float32))  # [n, S, H]
                o = (o.astype(jnp.float32) * gate[..., None]).astype(dt.compute_dtype)
            return wo(o.reshape(n, S, H * hd))

        # a single-shot prefill whose queries, outputs and gated outputs
        # (every head's, the whole batch's) reach ROWWISE_BYTES goes a row at
        # a time, by SHAPE, as the latent family's (``lm.by_rows``)
        big = fresh and rowwise(B, S, 4 * H * hd, dt.compute_dtype)
        args = (x, k, v, cos, sin, kv_start, kv_len) if fresh else (x, k_cache, v_cache, cos, sin, kv_start, kv_len)
        return (lm.by_rows(heads_of, *args) if big else heads_of(*args)), (k_cache, v_cache)


class Layer(nn.Module):
    """One decoder layer at depth ``plane``: norm, gated attention of its
    kind, norm, then a dense SwiGLU or the expert layer."""

    config: WindowedMoEConfig
    dtypes: DTypePolicy
    heads: int
    kind: str
    sparse: bool
    attn_impl: str = "auto"
    chunked: bool = False

    @nn.compact
    def __call__(self, carry, kv_start, kv_len, tables, write_index, experts_stack=None):
        c, dt = self.config, self.dtypes
        h, planes, counters, plane = carry
        with phase_scope("norm_rope"):
            x = RMSNorm(c.rms_norm_eps, dt, name="input_norm")(h)
        with phase_scope("attn"):
            out, planes = GatedAttention(c, dt, self.heads, self.kind, self.attn_impl, self.chunked,
                                         name="attn")(
                x, planes, plane, kv_start, kv_len, *tables[self.kind], write_index)
            h = h + out
        with phase_scope("norm_rope"):
            x = RMSNorm(c.rms_norm_eps, dt, name="post_attn_norm")(h)
        with phase_scope("mlp"):
            if self.sparse:
                y, counts = lm.SparseMLP(c, dt, self.attn_impl, name="mlp")(
                    x, experts_stack, plane - c.num_lead)
                mode = "decode" if x.shape[1] == 1 else "chunk" if self.chunked else "prefill"
                counters = lm._count(counters, mode, counts)  # the latent family's block leads the vector
            else:
                with phase_scope("dense"):
                    mlp = lm.SwiGLU(c.intermediate_size, c.hidden_size, dt, name="mlp")
                    big = not self.chunked and rowwise(x.shape[0], x.shape[1], 2 * c.intermediate_size,
                                                       dt.compute_dtype)
                    y = lm.by_rows(mlp, x) if big else mlp(x)
            h = h + y
        return (h, planes, counters, plane + 1), None


class Period(nn.Module):
    """The scan body: ``config.period`` sparse layers, each of its own kind
    and head count (``l<i>`` in the tree)."""

    config: WindowedMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False

    @nn.compact
    def __call__(self, carry, kv_start, kv_len, tables, write_index, experts_stack):
        c = self.config
        for i in range(c.period):
            at = c.num_lead + i
            carry, _ = Layer(c, self.dtypes, c.num_attention_heads_per_layer[at], c.layer_types[at], True,
                             self.attn_impl, self.chunked, name=f"l{i}")(
                carry, kv_start, kv_len, tables, write_index, experts_stack)
        return carry, None


class WindowedMoEModel(nn.Module):
    config: WindowedMoEConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # see GatedAttention.chunked

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
    ) -> Tuple[jax.Array, KVCache]:
        c, dt = self.config, self.dtypes
        with phase_scope("embed"):
            embedding = self.param("embedding", nn.initializers.normal(stddev=0.02),
                                   (c.vocab_size, c.hidden_size), dt.param_dtype)
            h = jnp.take(embedding, tokens, axis=0).astype(dt.compute_dtype)
        with phase_scope("norm_rope"):  # both tables; a layer takes its kind's
            tables = {kind: rope_table(positions, c, c.rope_of(kind)) for kind in sorted(set(c.layer_types))}

        counters = cache.counters
        kernels = resolve_attn_impl(self.attn_impl) != "xla"
        n_win = c.num_sliding_layers
        heads = dict(zip(c.layer_types, c.num_attention_heads_per_layer))
        if tokens.shape[1] > 1 and not self.chunked and n_win and kernels:
            # a single-shot prefill through ``flash_attention_window``: what
            # the steps of a sliding layer's call multiply, over those layers
            pairs = flash_window_pairs(kv_start, kv_len, tokens.shape[1], heads[SLIDING] // c.num_kv_heads,
                                       c.head_dim, c.head_dim, c.sliding_window,
                                       jnp.dtype(dt.compute_dtype).itemsize)
            counters = counters.at[_WINDOW_PAIRS:_WINDOW_PAIRS + 2].add(
                (n_win * jnp.stack(pairs)).astype(counters.dtype))
        if tokens.shape[1] == 1 and kernels:
            # a step through ``decode_attention``: what a full layer's walk
            # fetches of its plane (a step counts once), and what the sliding
            # layers' walks fetch of theirs, over those layers
            B, K, T = cache.k.shape[1:4]

            def step(n_heads):
                return gqa_decode_step(T, K, n_heads // K, c.head_dim, cache.k.dtype)

            add = jnp.zeros_like(counters)
            if "full_attention" in heads:
                add = add.at[_DECODE_SLOTS:_DECODE_SLOTS + 2].set(jnp.stack(
                    [decode_slots_streamed(kv_start, kv_len, T, step(heads["full_attention"])),
                     B * T]).astype(counters.dtype))
            if n_win:
                add = add.at[lm.N_COUNTERS:lm.N_COUNTERS + 2].set(jnp.stack(
                    [n_win * decode_slots_streamed(jnp.maximum(kv_start, kv_len - c.sliding_window),
                                                   kv_len, T, step(heads[SLIDING])),
                     n_win * B * T]).astype(counters.dtype))
            counters = counters + add
        carry = (h, (cache.k, cache.v), counters, jnp.int32(0))
        window = (kv_start, kv_len, tables, write_index)
        for i in range(c.num_lead):  # outside the layers' loop
            carry, _ = Layer(c, dt, c.num_attention_heads_per_layer[i], c.layer_types[i], False,
                             self.attn_impl, self.chunked, name=f"lead_{i}")(carry, *window)
        if c.num_periods:
            experts_stack = lm.Experts(c, dt, name="experts")()
            scan = nn.scan(
                Period, variable_axes={"params": 0}, split_rngs={"params": True},
                in_axes=(nn.broadcast,) * 5, out_axes=0, length=c.num_periods)
            carry, _ = scan(c, dt, self.attn_impl, self.chunked, name="periods")(
                carry, *window, experts_stack)
        h, (k, v), counters, _ = carry

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
        return logits.astype(dt.logits_dtype), KVCache(k=k, v=v, counters=counters)


def init_windowed_moe_params(rng: jax.Array, config: WindowedMoEConfig,
                             dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = WindowedMoEModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_windowed_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

"""The gated short-convolution, sparse-expert decoder (``ConvMoEConfig``).

The sixth decoder family, with the call signature of the other five, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

**The layer** (``x`` the residual stream): ``h = x + Op(RMS(x))``, ``y = h +
FFN(RMS(h))``. It is made of the other families' parts and adds one operator:

- *The gated short convolution* (``ShortConv``, ``layer_types`` ``conv``):
  ``[B, C, u] = n W_in``, three vectors of the hidden size; ``z = B * u``;
  ``c_t = sum_j w_j * z_{t - (L - 1) + j}`` over ``L = conv_L_cache`` taps,
  depthwise and causal (``ops/ssm.py causal_conv`` without its ``silu`` and
  without a bias); ``W_out (C * c)``. What a decode step needs of the past is
  ``z_{t-L+1} .. z_{t-1}``: ``L - 1`` vectors a row-layer, no position axis.
- *Attention* (``QKNormAttention``, ``full_attention``): grouped-query over
  ``models/llama.py``'s seam (``attend``, planes ``[attention layers, B, K,
  T, hd]`` written at the shared ``write_index``); queries and keys are
  RMS-normed over the head (one ``[hd]`` scale shared by the heads) BEFORE the
  rotation, which covers the whole head by halves.
- *The FFN*: a dense SwiGLU in the first ``num_dense_layers`` layers, then
  ``models/latent_moe.py``'s ``SparseMLP`` over ``Experts`` (``ops/moe.py``):
  sigmoid scores, the top ``num_experts_per_tok`` of score plus bias, weights
  normalised over the chosen with the published ``1e-6``, this chip's held
  range (all of them at ``ep_size`` 1) computed with no capacity limit.

**The cache holds two kinds of state** (``ConvCache``, as
``models/hybrid_ssm.py HybridCache``). The attention layers' keys and values
are by position. A conv layer keeps ``conv [L - 1, hidden]`` a row, channels
last (the device pads a last axis of 2 to 128 lanes), overwritten in place by
every call: nothing the engine does to a frontier reaches it. So:

- *Left padding.* At a pad slot (``slot < kv_start[row]``) the gated input
  ``z`` is forced to 0: the first real token sees the zero history a row alone
  starts from, and a row of nothing but pads leaves its state exactly zero.
- *A verify step keeps some of what it fed.* The model built with
  ``keep_steps`` (the verify loop's) leaves the run of gated inputs in
  ``conv_steps``; ``commit(cache, kept)`` (``Family.commit``) puts the ``L -
  1`` in front of the first rejected position in place and drops the rest.

Operator SHAPES differ by kind, so one ``lax.scan`` over layers cannot stack
them: a trip of the layers' loop is one PERIOD (``config.period`` layers,
``l<i>`` under ``periods``), the dense layers in front of it (``lead_<i>``)
and a last cut period behind it (``tail_<i>``), as ``models/windowed_moe.py``.

**A decode step at a head of 64.** ``decode_attention``'s walk copies its
steps out of the planes in HBM itself, and Mosaic refuses a slice of a plane
whose last axis is not whole 128-lane tiles (PERF.md section 7). A step of
such a head is served as a chunk of ONE position by the grouped chunk kernel
(``attend(mode="chunk")``), whose blocks ride BlockSpecs: the same numbers,
every slot of the row's plane fetched (what the counters then say).

The cache carries the family's counters: the latent family's block (the
experts' assignments by mode; an attention layer's decode slots, a step
counted once), then the prefill token rows computed and bucketed
(``models/llama.py``'s names) and what ``commit`` was told.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import ConvMoEConfig, DTypePolicy
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.models.llama import RMSNorm, apply_rope, attend, resolve_attn_impl, rope_cos_sin
from rag_llm_k8s_tpu.models.windowed_moe import rowwise
from rag_llm_k8s_tpu.obs.tracing import phase_scope
from rag_llm_k8s_tpu.ops import ssm as ssm_ops
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed, gqa_decode_step

EXTRA_STATS = ("prefill_tokens_computed", "prefill_tokens_bucketed",
               "verify_positions_fed", "verify_positions_kept")
N_COUNTERS = lm.N_COUNTERS + len(EXTRA_STATS)
COUNTER_NAMES = tuple(lm.COUNTER_STATS) + EXTRA_STATS
_AT = {name: lm.N_COUNTERS + i for i, name in enumerate(EXTRA_STATS)}
_DECODE_SLOTS = lm.COUNTER_MODES.index("decode") * len(lm.COUNTER_FIELDS) + lm.COUNTER_FIELDS.index(
    "slots_streamed")
LANES = 128  # the decode walk copies whole lane tiles of a plane's last axis
ATTENTION = "full_attention"


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    out = lm.fold_counters(row[:lm.N_COUNTERS])
    out.update({name: int(n) for name, n in zip(EXTRA_STATS, row[lm.N_COUNTERS:])})
    return out


@flax.struct.dataclass
class ConvCache:
    """``k``, ``v`` ``[attention layers, B, K, T, hd]``; ``conv [conv layers,
    B, conv_L_cache - 1, hidden]`` in the compute type, oldest first (channels
    last). ``conv_steps [conv layers, B, conv_L_cache - 1 + n, hidden]`` only
    between a ``keep_steps`` call of ``n`` positions and its ``commit``."""

    k: jax.Array
    v: jax.Array
    conv: jax.Array
    counters: jax.Array
    conv_steps: Optional[jax.Array] = None


def make_conv_cache(config: ConvMoEConfig, batch_size: int, max_seq_len: int,
                    dtype: jnp.dtype = jnp.bfloat16) -> ConvCache:
    c = config
    kv = (c.num_attention_layers, batch_size, c.num_kv_heads, max_seq_len, c.head_dim)
    return ConvCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
        conv=jnp.zeros((c.num_conv_layers, batch_size, c.conv_L_cache - 1, c.hidden_size), dtype),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def commit(cache: ConvCache, kept: jax.Array) -> ConvCache:
    """After a verify step that fed ``n`` positions (a ``keep_steps`` call)
    and kept the first ``kept`` of them (1 <= kept <= n; one count for every
    row: the verify loop is batch 1), the cache whose conv state is the gated
    inputs in front of position ``kept``. The attention layers' planes need
    nothing: their frontier does the job."""
    taps = cache.conv.shape[2]
    fed = cache.conv_steps.shape[2] - taps
    kept = jnp.clip(jnp.asarray(kept, jnp.int32).reshape(()), 1, fed)
    counters = cache.counters.at[_AT["verify_positions_fed"]].add(fed)
    counters = counters.at[_AT["verify_positions_kept"]].add(kept)
    return cache.replace(conv=jax.lax.dynamic_slice_in_dim(cache.conv_steps, kept, taps, axis=2),
                         counters=counters, conv_steps=None)


def _put(stacked: jax.Array, index, value: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(stacked, value.astype(stacked.dtype), index, 0)


class ShortConv(nn.Module):
    """The gated short convolution of conv layer ``index`` on ``x [B, S, D]``
    from the state in ``conv[index]``; ``start [B]``: indices of ``x`` in
    front of it are pads. Returns the operator's output and the state
    ``(conv, conv_steps)`` with this layer's rows written."""

    config: ConvMoEConfig
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self, x, state, index, start):
        c, dt = self.config, self.dtypes
        S, D, taps = x.shape[1], c.hidden_size, c.conv_L_cache - 1
        dense = lm._dense(self, dt)
        w_out = dense(D, "out_proj")
        gate_in, gate_out, u = jnp.split(dense(3 * D, "in_proj")(x), 3, axis=-1)
        weight = self.param("conv_w", nn.initializers.normal(stddev=0.02), (taps + 1, D), dt.param_dtype)
        conv, steps = state
        with phase_scope("conv"):
            live = (jnp.arange(S, dtype=jnp.int32)[None, :] >= start[:, None])[..., None]
            z = jnp.where(live, gate_in * u, 0)
            history = jax.lax.dynamic_index_in_dim(conv, index, 0, keepdims=False)
            mixed, run = ssm_ops.causal_conv(z, history, weight, None, activate=False)
            conv = _put(conv, index, jax.lax.slice_in_dim(run, S, S + taps, axis=1))
            if steps is not None:
                steps = _put(steps, index, run)
            y = gate_out * mixed
        return w_out(y), (conv, steps)


class QKNormAttention(nn.Module):
    """GQA over attention layer ``index``'s K/V plane, queries and keys
    RMS-normed over the head and then rotated."""

    config: ConvMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False  # S > 1 calls attend over the cache (offset causality)

    @nn.compact
    def __call__(self, x, planes, index, kv_start, kv_len, cos, sin, write_index):
        c, dt = self.config, self.dtypes
        B, S, _ = x.shape
        H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
        dense = lm._dense(self, dt)
        q = dense(H * hd, "wq")(x).reshape(B, S, H, hd)
        k = dense(K * hd, "wk")(x).reshape(B, S, K, hd)
        v = dense(K * hd, "wv")(x).reshape(B, S, K, hd)
        q = apply_rope(RMSNorm(c.norm_eps, dt, name="q_norm")(q), cos, sin)
        k = apply_rope(RMSNorm(c.norm_eps, dt, name="k_norm")(k), cos, sin)
        k_cache, v_cache = planes
        at = (index, 0, 0, write_index, 0)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.transpose(0, 2, 1, 3).astype(k_cache.dtype)[None], at)
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype)[None], at)
        impl = resolve_attn_impl(self.attn_impl)
        with phase_scope("global"):
            if S == 1 and walks(impl, hd):
                o = attend(q, k_cache, v_cache, kv_start, kv_len, index, mode="decode", impl=impl)
            elif S == 1 or self.chunked:  # a step at a head the walk refuses: a chunk of one position
                o = attend(q, k_cache, v_cache, kv_start, kv_len, index, mode="chunk", impl=impl,
                           write_index=write_index)
            else:  # writes at slot 0: the fresh K/V are the populated prefix
                o = attend(q, k, v, kv_start, kv_len, index, mode="prefill", impl=impl)
        return dense(c.hidden_size, "wo")(o.reshape(B, S, H * hd)), (k_cache, v_cache)


def walks(impl: str, head_dim: int) -> bool:
    """Whether a single-token step takes the decode form (the XLA one, or the
    kernel's walk where a head is whole lane tiles)."""
    return impl == "xla" or head_dim % LANES == 0


class Layer(nn.Module):
    """One decoder layer: norm, its operator (``kind``), norm, then a dense
    SwiGLU or the expert layer. The carry threads ``(h, (k, v), (conv,
    conv_steps), counters, (layer, attention layer, conv layer))``."""

    config: ConvMoEConfig
    dtypes: DTypePolicy
    kind: str
    sparse: bool
    attn_impl: str = "auto"
    chunked: bool = False

    @nn.compact
    def __call__(self, carry, kv_start, kv_len, cos, sin, write_index, start, experts_stack=None):
        c, dt = self.config, self.dtypes
        h, planes, state, counters, (depth, ai, ci) = carry
        with phase_scope("norm_rope"):
            x = RMSNorm(c.norm_eps, dt, name="operator_norm")(h)
        with phase_scope("attn"):
            if self.kind == ATTENTION:
                out, planes = QKNormAttention(c, dt, self.attn_impl, self.chunked, name="attn")(
                    x, planes, ai, kv_start, kv_len, cos, sin, write_index)
                ai = ai + 1
            else:  # not named "conv": a module's name is in its operations' scope paths, the projections' too
                out, state = ShortConv(c, dt, name="shortconv")(x, state, ci, start)
                ci = ci + 1
            h = h + out
        with phase_scope("norm_rope"):
            x = RMSNorm(c.norm_eps, dt, name="ffn_norm")(h)
        with phase_scope("mlp"):
            if self.sparse:
                y, counts = lm.SparseMLP(c, dt, self.attn_impl, name="mlp")(x, experts_stack, depth - c.num_lead)
                mode = "decode" if x.shape[1] == 1 else "chunk" if self.chunked else "prefill"
                counters = lm._count(counters, mode, counts)  # the latent family's block leads the vector
            else:
                with phase_scope("dense"):
                    mlp = lm.SwiGLU(c.intermediate_size, c.hidden_size, dt, name="mlp")
                    big = not self.chunked and rowwise(x.shape[0], x.shape[1], 2 * c.intermediate_size,
                                                       dt.compute_dtype)
                    y = lm.by_rows(mlp, x) if big else mlp(x)
            h = h + y
        return (h, planes, state, counters, (depth + 1, ai, ci)), None


class Period(nn.Module):
    """The scan body: ``config.period`` sparse layers, each of its own kind
    (``l<i>`` in the tree)."""

    config: ConvMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False

    @nn.compact
    def __call__(self, carry, *window):
        c = self.config
        for i in range(c.period):
            carry, _ = Layer(c, self.dtypes, c.layer_types[c.num_lead + i], True, self.attn_impl,
                             self.chunked, name=f"l{i}")(carry, *window)
        return carry, None


class ConvMoEModel(nn.Module):
    config: ConvMoEConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # S > 1 calls run over the cache as it is (a verify step, a prompt chunk, the scorer)
    keep_steps: bool = False  # leave the run of gated inputs for ``commit`` (the verify loop's calls)

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array,
        cache: ConvCache,
        kv_start: jax.Array,
        kv_len: jax.Array,
        write_index: jax.Array,
        last_logit_only: bool = False,
        logit_index: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, ConvCache]:
        c, dt = self.config, self.dtypes
        B, S = tokens.shape
        impl = resolve_attn_impl(self.attn_impl)
        wi = jnp.asarray(write_index, jnp.int32).reshape(())
        start = jnp.maximum(kv_start.astype(jnp.int32) - wi, 0)  # [B]: indices of this call in front of it are pads
        with phase_scope("embed"):
            embedding = self.param("embedding", nn.initializers.normal(stddev=0.02),
                                   (c.vocab_size, c.hidden_size), dt.param_dtype)
            h = jnp.take(embedding, tokens, axis=0).astype(dt.compute_dtype)
        with phase_scope("norm_rope"):
            inv = 1.0 / c.rope_theta ** (jnp.arange(0, c.head_dim, 2, dtype=jnp.float32) / c.head_dim)
            cos, sin = rope_cos_sin(positions, inv)

        add = jnp.zeros_like(cache.counters)
        if S == 1 and impl != "xla" and c.num_attention_layers:
            # a step through a kernel: what an attention layer's call fetches
            # of its plane (every layer fetches the same: a step counts once);
            # the chunk form of a head the walk refuses fetches every slot
            T = cache.k.shape[3]
            streamed = B * T
            if walks(impl, c.head_dim):
                step = gqa_decode_step(T, c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, cache.k.dtype)
                streamed = decode_slots_streamed(kv_start, kv_len, T, step)
            add = add.at[_DECODE_SLOTS:_DECODE_SLOTS + 2].set(jnp.stack(
                [jnp.asarray(streamed, jnp.int32), jnp.asarray(B * T, jnp.int32)]))
        elif S > 1 and not self.chunked:
            add = add.at[_AT["prefill_tokens_computed"]].set(B * S)
            add = add.at[_AT["prefill_tokens_bucketed"]].set(B * S)

        steps = None
        if self.keep_steps and S > 1:
            steps = jnp.zeros(cache.conv.shape[:2] + (c.conv_L_cache - 1 + S, c.hidden_size), cache.conv.dtype)
        zero = jnp.int32(0)
        carry = (h, (cache.k, cache.v), (cache.conv, steps), cache.counters + add, (zero, zero, zero))
        window = (kv_start, kv_len, cos, sin, wi, start)
        for i in range(c.num_lead):  # outside the layers' loop
            carry, _ = Layer(c, dt, c.layer_types[i], False, self.attn_impl, self.chunked,
                             name=f"lead_{i}")(carry, *window)
        if c.num_moe_layers:
            experts_stack = lm.Experts(c, dt, name="experts")()
        if c.num_periods:
            scan = nn.scan(
                Period, variable_axes={"params": 0}, split_rngs={"params": True},
                in_axes=(nn.broadcast,) * 7, out_axes=0, length=c.num_periods)
            carry, _ = scan(c, dt, self.attn_impl, self.chunked, name="periods")(
                carry, *window, experts_stack)
        for i in range(c.num_tail):  # a last, cut period
            at = c.num_layers - c.num_tail + i
            carry, _ = Layer(c, dt, c.layer_types[at], True, self.attn_impl, self.chunked,
                             name=f"tail_{i}")(carry, *window, experts_stack)
        h, (k, v), (conv, steps), counters, _ = carry

        with phase_scope("norm_rope"):
            h = RMSNorm(c.norm_eps, dt, name="embedding_norm")(h)
        with phase_scope("lm_head"):
            if logit_index is not None:
                idx = jnp.clip(jnp.asarray(logit_index, jnp.int32), 0, h.shape[1] - 1)
                if idx.ndim == 0:
                    h = jax.lax.dynamic_slice(h, (0, idx, 0), (B, 1, h.shape[2]))
                else:
                    h = jnp.take_along_axis(h, idx.reshape(B, 1, 1), axis=1)
            elif last_logit_only:
                h = h[:, -1:, :]
            if c.tie_word_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", h, embedding.astype(dt.compute_dtype),
                                    preferred_element_type=jnp.float32)
            else:
                head = self.param("lm_head", nn.initializers.normal(stddev=0.02),
                                  (c.hidden_size, c.vocab_size), dt.param_dtype)
                logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dt.compute_dtype),
                                    preferred_element_type=jnp.float32)
        return logits.astype(dt.logits_dtype), ConvCache(k, v, conv, counters, steps)


def init_conv_moe_params(rng: jax.Array, config: ConvMoEConfig, dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = ConvMoEModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_conv_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

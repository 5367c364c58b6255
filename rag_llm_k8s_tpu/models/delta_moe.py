"""The gated delta-rule, sparse-expert decoder (``DeltaMoEConfig``).

The seventh decoder family, with the call signature of the other six, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

**The layer** (``x`` the residual stream): ``h = x + Mixer(RMS(x))``, ``y = h
+ FFN(RMS(h))``. It adds one mixer and borrows the rest:

- *Linear attention by the gated delta rule* (``DeltaAttention``, the
  configuration's ``kda_layers``): ``[q | k | v] = n W_qkv``, the three
  through ONE depthwise causal convolution of ``short_conv_kernel_size`` taps
  (``ops/ssm.py causal_conv``, no bias) and a SiLU; ``q`` and ``k`` L2-normed
  over the head (``q`` times ``head_dim ** -0.5``); a log decay for every key
  channel ``g = -exp(A_log) * softplus(W_fb (W_fa n) + dt_bias)``, ``beta =
  sigmoid(W_b n)``; the recurrence of ``ops/delta_rule.py`` over a float32
  ``[head_dim, head_dim]`` state a head; the output RMS-normed over the head,
  times ``sigmoid(W_gb (W_ga n))``, through ``W_o``.
- *Latent attention* (``full_attn_layers``): ``models/latent_moe.py
  LatentAttention`` with a direct query projection and, under
  ``mla_use_nope``, nothing rotated: the model has no position term, and
  ``positions`` is then unused.
- *The FFN*: a dense SwiGLU in the first ``first_k_dense_replace`` layers,
  then ``models/latent_moe.py``'s ``SparseMLP`` over ``Experts``.

**The cache holds three kinds of state** (``DeltaCache``). The full layers'
latent planes are by position (``LatentCache``'s). A linear layer keeps
``state [heads, head_dim, head_dim]`` float32 a row, and the convolution's
last ``taps - 1`` inputs ``conv [taps - 1, 3 * heads * head_dim]`` in the
compute type: no position axis, overwritten in place, so nothing the engine
does to a frontier reaches them. So:

- *Left padding.* A pad position (``slot < kv_start[row]``) is an identity of
  the recurrence: the convolution's input is forced to 0 there and so are
  ``g`` and ``beta``; a row of nothing but pads leaves its state exactly
  zero. A fresh prompt's recurrence starts at the first chunk that holds a
  real token (``first_chunk``): the live suffix, not the bucket. Where the
  program builds kernels, a prefill's and a prompt chunk's recurrence is ONE
  call of the kernel ``ops/delta_rule.py delta_rule_chunked`` a layer-row
  (the state in VMEM for the row's whole walk); a verify step's one chunk
  and ``commit``'s replay stay XLA's chunk form.
- *A verify step keeps some of what it fed*, and a state cannot be taken
  back. Keeping every fed position's state (as ``models/hybrid_ssm.py`` keeps
  its 327 kB) would write ``n`` x 2.1 MB a row-layer; the model built with
  ``keep_steps`` (the verify loop's) leaves the state AS IT WAS and keeps the
  step's ``k, v, g, beta`` (``n`` x 33 kB a row-layer) and the convolution's
  run of inputs in ``steps``; ``commit(cache, kept)`` (``Family.commit``)
  replays the first ``kept`` as one chunk of the recurrence from the state in
  front of the step (``ops/delta_rule.py delta_rule_replay``).

**The layers' loop.** The published pattern (three linear layers, one full)
starts and ends on a cut period, so a trip is ONE LAYER: the sparse layers
are one ``lax.scan`` whose trip chooses its mixer by ``lax.cond`` and reads
that mixer's leaves, stacked by kind (``kda_layers``, ``mla_layers``), at the
kind's own index inside the branch (as ``models/hybrid_ssm.py``; sliced in
front of a conditional, a layer's weights are copied first). Norms, router,
shared expert and the expert kernels stand outside the branch and are traced
once a program whatever the depth. The dense layers sit in front of the loop
(``lead_<i>``) and read their mixer from the same stacks.

The cache carries the family's counters: the latent family's block, then the
row-layer positions the recurrence advanced by how the model was called, what
a bucket-wide prefill would have advanced, and what ``commit`` kept.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import DeltaMoEConfig, DTypePolicy
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.models.llama import RMSNorm, resolve_attn_impl, rope_cos_sin
from rag_llm_k8s_tpu.models.windowed_moe import rowwise
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops import delta_rule, mla
from rag_llm_k8s_tpu.ops import ssm as ssm_ops
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed

EXTRA_STATS = ("kda_prefill_positions", "kda_prefill_positions_bucketed", "kda_decode_positions",
               "kda_verify_positions", "kda_verify_positions_kept")
N_COUNTERS = lm.N_COUNTERS + len(EXTRA_STATS)
COUNTER_NAMES = tuple(lm.COUNTER_STATS) + EXTRA_STATS
_AT = {name: lm.N_COUNTERS + i for i, name in enumerate(EXTRA_STATS)}
_DECODE_SLOTS = lm.COUNTER_MODES.index("decode") * len(lm.COUNTER_FIELDS) + lm.COUNTER_FIELDS.index(
    "slots_streamed")
L2_EPS = 1e-6  # under the root of q's and k's squared norm
# bytes a token of a linear layer's temporaries (q, k, v, the decay, the gate:
# a channel of each) from which a batch goes through the mixer a row at a time
_MIXER_BYTES_PER_CHANNEL = 32


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    out = lm.fold_counters(row[:lm.N_COUNTERS])
    out.update({name: int(n) for name, n in zip(EXTRA_STATS, row[lm.N_COUNTERS:])})
    return out


@flax.struct.dataclass
class DeltaCache:
    """``c_kv [full layers, B, T, C]`` and ``k_rope [full layers, B, T, R]``
    (``LatentCache``'s planes); ``conv [linear layers, B, taps - 1, 3 * W]``
    in the compute type, oldest first, and ``state [linear layers, B, heads,
    head_dim, head_dim]`` float32 (key channels, then value channels).
    ``steps``: ``(conv_run [.., taps - 1 + n, 3 * W], k, v, g [.., n, heads,
    head_dim], beta [.., n, heads])`` only between a ``keep_steps`` call of
    ``n`` positions and its ``commit``."""

    c_kv: jax.Array
    k_rope: jax.Array
    conv: jax.Array
    state: jax.Array
    counters: jax.Array
    steps: Optional[Tuple[jax.Array, ...]] = None


def make_delta_cache(config: DeltaMoEConfig, batch_size: int, max_seq_len: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> DeltaCache:
    c = config
    lead = (c.num_mla_layers, batch_size, max_seq_len)
    H, hd = c.kda_num_heads, c.kda_head_dim
    return DeltaCache(
        c_kv=jnp.zeros(lead + (c.kv_lora_rank,), dtype),
        k_rope=jnp.zeros(lead + (c.qk_rope_head_dim,), dtype),
        conv=jnp.zeros((c.num_kda_layers, batch_size, c.short_conv_kernel_size - 1, 3 * c.kda_width), dtype),
        state=jnp.zeros((c.num_kda_layers, batch_size, H, hd, hd), jnp.float32),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def commit(cache: DeltaCache, kept: jax.Array) -> DeltaCache:
    """After a verify step that fed ``n`` positions (a ``keep_steps`` call)
    and kept the first ``kept`` of them (0 <= kept <= n; one count for every
    row: the verify loop is batch 1), the cache whose state is the one behind
    position ``kept - 1``: the step's first ``kept`` rank-one corrections
    replayed from the state in front of it, and the convolution's inputs in
    front of position ``kept``. The latent planes need nothing: their
    frontier does the job."""
    run, k, v, g, beta = cache.steps
    layers, B, n = k.shape[:3]
    taps = cache.conv.shape[2]
    kept = jnp.clip(jnp.asarray(kept, jnp.int32).reshape(()), 0, n)

    def rows(a):  # a layer's rows are rows of one batch
        return a.reshape((layers * B,) + a.shape[2:])

    with phase_scope("attn/kda/delta"):
        state = delta_rule.delta_rule_replay(rows(k), rows(v), rows(g), rows(beta), rows(cache.state), kept)
    counters = cache.counters.at[_AT["kda_verify_positions_kept"]].add(layers * B * kept)
    return cache.replace(state=state.reshape(cache.state.shape),
                         conv=jax.lax.dynamic_slice_in_dim(run, kept, taps, axis=2),
                         counters=counters, steps=None)


def _at(stacked: jax.Array, index) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(stacked, index, 0, keepdims=False)


def _put(stacked: jax.Array, index, value: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(stacked, value.astype(stacked.dtype), index, 0)


def mixer_by_rows(config: DeltaMoEConfig, batch: int, seq: int) -> bool:
    """Whether a linear layer's mixer takes a batch a row at a time (by shape:
    eight rows of a 4096 bucket hold 4 GB of q, k, v, decay and gate)."""
    return batch > 1 and batch * seq * config.kda_width * _MIXER_BYTES_PER_CHANNEL >= lm.ROWWISE_BYTES


def first_chunk(start: jax.Array) -> jax.Array:
    """The first chunk of a fresh prompt's recurrence that holds a real token
    of some row in ``start [B]`` (indices in front of it are pads)."""
    return jnp.min(start) // delta_rule.CHUNK


def positions_advanced(config: DeltaMoEConfig, start: jax.Array, seq: int) -> jax.Array:
    """Row positions a fresh ``seq``-token call's recurrence visits in ONE
    linear layer: the rows' own live suffixes where the mixer goes by rows,
    else every row from the batch's first live chunk."""
    C = delta_rule.CHUNK
    if mixer_by_rows(config, start.shape[0], seq):
        return jnp.sum(seq - start // C * C)
    return start.shape[0] * (seq - first_chunk(start) * C)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of ``exp U(log 0.001, log 0.1)`` (the published draw)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(0.001), math.log(0.1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class DeltaAttention(nn.Module):
    """A linear-attention layer's mixer on ``x [B, S, D]`` from ITS rows of
    the cache: ``history [B, taps - 1, 3 * W]`` (the convolution's kept
    inputs) and ``s0 [B, heads, head_dim, head_dim]``; ``start [B]``: indices
    of ``x`` in front of it are pads. Returns ``(out, history, state, kept)``:
    the rows to write back, and under ``keep_steps`` (a verify step: history
    and state are then the ones handed in) the step's ``(conv run, k, v, g,
    beta)`` for ``commit``, else ``()``."""

    config: DeltaMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False  # S > 1 calls run from the state the cache holds
    keep_steps: bool = False  # leave the state as it was and the step's inputs for ``commit``

    @nn.compact
    def __call__(self, x, history, s0, start):
        c, dt = self.config, self.dtypes
        B, S, D = x.shape
        impl = resolve_attn_impl(self.attn_impl)
        H, hd, W, R = c.kda_num_heads, c.kda_head_dim, c.kda_width, c.kda_gate_rank
        taps, f32 = c.short_conv_kernel_size - 1, jnp.float32
        dense = lm._dense(self, dt)
        w_qkv, w_out, w_beta = dense(3 * W, "wqkv"), dense(D, "wo"), dense(H, "b_proj")
        f_a, f_b, g_a, g_b = dense(R, "f_a"), dense(W, "f_b"), dense(R, "g_a"), dense(W, "g_b")
        normal = nn.initializers.normal(stddev=0.02)
        conv_w = self.param("conv_w", normal, (taps + 1, 3 * W), dt.param_dtype)
        a_log = self.param("A_log", _a_log_init, (H,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H, hd), f32)
        o_scale = self.param("o_norm", nn.initializers.ones, (hd,), dt.param_dtype)
        keep = self.keep_steps and S > 1
        fresh = S > 1 and not self.chunked

        def unit(a):
            a = a.astype(f32)
            return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

        def mixer(x, start, history, s0):
            rows = x.shape[0]
            live = jnp.arange(S, dtype=jnp.int32)[None, :] >= start[:, None]  # [rows, S]
            qkv = jnp.where(live[..., None], w_qkv(x), 0)
            with phase_scope("conv"):
                mixed, run = ssm_ops.causal_conv(qkv, history, conv_w, None)
            q, k, v = (a.reshape(rows, S, H, hd) for a in jnp.split(mixed, 3, axis=-1))
            q, k = unit(q) * hd ** -0.5, unit(k)
            with phase_scope("gate"):
                decay = jax.nn.softplus(f_b(f_a(x)).astype(f32).reshape(rows, S, H, hd) + dt_bias)
                g = jnp.where(live[..., None, None], -jnp.exp(a_log)[:, None] * decay, 0.0)
                beta = jnp.where(live[..., None], jax.nn.sigmoid(w_beta(x).astype(f32)), 0.0)
                gate = jax.nn.sigmoid(g_b(g_a(x)).astype(f32)).reshape(rows, S, H, hd)
            with phase_scope("delta"):
                if S == 1:
                    o, s1 = delta_rule.delta_rule_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
                    o = o[:, None]
                elif keep:  # one chunk from the state, which stays: ``commit`` replays what was kept
                    o, s1 = delta_rule.delta_rule_chunked_xla(q, k, v, g, beta, s0, chunk=S)[0], s0
                else:  # the kernel where ``impl`` builds kernels: a walk from the first live chunk
                    o, s1 = delta_rule.delta_rule_chunked(
                        q, k, v, g, beta, s0, first_chunk=first_chunk(start) if fresh else None, impl=impl)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_norm_eps)
            y = (o * o_scale.astype(f32) * gate).astype(dt.compute_dtype).reshape(rows, S, W)
            if keep:
                return w_out(y), history, s0, run, k, v.astype(f32), g, beta
            return w_out(y), jax.lax.slice_in_dim(run, S, S + taps, axis=1).astype(history.dtype), s1

        with phase_scope("kda"):
            rows = lm.by_rows if mixer_by_rows(c, B, S) else lambda fn, *a: fn(*a)
            out, history, s1, *kept = rows(mixer, x, start, history, s0)
        return out, history, s1, tuple(kept)


def _stacked(module: nn.Module, name: str, layer: nn.Module, count: int, *args):
    """``count`` layers' parameters of the detached ``layer``, stacked on a
    leading axis as ONE entry ``name`` of ``module``'s tree (a trip reads its
    own at the kind's index); ``args``: a call's arguments, for the shapes."""
    return module.param(name, lambda rng: jax.vmap(lambda key: layer.init(key, *args)["params"])(
        jax.random.split(rng, count)))


class Layer(nn.Module):
    """One decoder layer: norm, its mixer, norm, then a dense SwiGLU or the
    expert layer. A trip of the loop (``sparse``) reads its mixer's kind from
    the configuration's table at the layer's index and takes it as a branch; a
    layer in front of the loop (``lead_index``) knows it statically. The carry
    threads ``(h, latent planes, (conv, state, steps),
    counters, (layer, linear layer, full layer))``."""

    config: DeltaMoEConfig
    dtypes: DTypePolicy
    attn_impl: str = "auto"
    chunked: bool = False
    keep_steps: bool = False
    sparse: bool = True
    lead_index: int = 0  # which layer a layer in front of the loop is (its mixer's kind is static)

    @nn.compact
    def __call__(self, carry, kv_start, kv_len, cos, sin, write_index, start, mixers, experts_stack=None):
        c, dt = self.config, self.dtypes
        h, planes, state, counters, (depth, ki, mi) = carry
        linear_stack, full_stack = mixers
        linear_mixer, full_mixer = mixer_modules(c, dt, self.attn_impl, self.chunked, self.keep_steps)

        # a linear layer's rows of the stacks are read in front of the branch and
        # written behind it, whichever mixer the trip takes (a full layer's trip
        # writes the NEXT linear layer's rows back as it read them: a stack that
        # a branch hands through untouched is COPIED by the compiler, 42 MB a
        # row a full layer), and the mixers' leaves are read at the kind's
        # index INSIDE the branch (sliced in front of it, they are copied first)
        conv, states, steps = state
        rows = (_at(conv, ki), _at(states, ki), tuple(_at(buf, ki) for buf in steps or ()))

        def linear(x, planes, rows):
            p = jax.tree_util.tree_map(lambda a: _at(a, ki), linear_stack)
            out, history, s1, kept = linear_mixer.apply({"params": p}, x, rows[0], rows[1], start)
            return out, planes, (history, s1, kept)

        def full(x, planes, rows):
            p = jax.tree_util.tree_map(lambda a: _at(a, mi), full_stack)
            out, planes = full_mixer.apply({"params": p}, x, planes, mi, kv_start, kv_len, cos, sin, write_index)
            return out, planes, rows

        with phase_scope("norm_rope"):
            x = RMSNorm(c.rms_norm_eps, dt, name="input_norm")(h)
        with phase_scope("attn"):
            if self.sparse:
                is_full = jnp.asarray([c.is_full(i) for i in range(c.num_layers)])[depth]
                out, planes, rows = jax.lax.cond(is_full, full, linear, x, planes, rows)
            else:
                is_full = c.is_full(self.lead_index)
                out, planes, rows = (full if is_full else linear)(x, planes, rows)
            state = (_put(conv, ki, rows[0]), _put(states, ki, rows[1]),
                     tuple(_put(buf, ki, row) for buf, row in zip(steps, rows[2])) if steps else steps)
            h = h + out
        with phase_scope("norm_rope"):
            x = RMSNorm(c.rms_norm_eps, dt, name="post_attn_norm")(h)
        with phase_scope("mlp"):
            if self.sparse:
                y, counts = lm.SparseMLP(c, dt, self.attn_impl, name="mlp")(x, experts_stack, depth - c.first_k_dense)
                mode = "decode" if x.shape[1] == 1 else "chunk" if self.chunked else "prefill"
                counters = lm._count(counters, mode, counts)  # the latent family's block leads the vector
            else:
                with phase_scope("dense"):
                    mlp = lm.SwiGLU(c.intermediate_size, c.hidden_size, dt, name="mlp")
                    big = not self.chunked and rowwise(x.shape[0], x.shape[1], 2 * c.intermediate_size,
                                                       dt.compute_dtype)
                    y = lm.by_rows(mlp, x) if big else mlp(x)
            h = h + y
        is_full = jnp.asarray(is_full, jnp.int32)
        return (h, planes, state, counters, (depth + 1, ki + 1 - is_full, mi + is_full)), None


def mixer_modules(config, dtypes, attn_impl: str, chunked: bool, keep_steps: bool):
    """The two mixers, detached: their parameters are the model's stacks."""
    return (DeltaAttention(config, dtypes, attn_impl, chunked, keep_steps, parent=None),
            lm.LatentAttention(config, dtypes, attn_impl, chunked, q_direct=True,
                               rotate=not config.mla_use_nope, parent=None))


class DeltaMoEModel(nn.Module):
    config: DeltaMoEConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # S > 1 calls run over the cache as it is (a verify step, a prompt chunk, the scorer)
    keep_steps: bool = False  # leave the step's inputs for ``commit`` (the verify loop's calls)

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array,
        cache: DeltaCache,
        kv_start: jax.Array,
        kv_len: jax.Array,
        write_index: jax.Array,
        last_logit_only: bool = False,
        logit_index: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, DeltaCache]:
        c, dt = self.config, self.dtypes
        B, S = tokens.shape
        impl = resolve_attn_impl(self.attn_impl)
        wi = jnp.asarray(write_index, jnp.int32).reshape(())
        start = jnp.maximum(kv_start.astype(jnp.int32) - wi, 0)  # [B]: indices of this call in front of it are pads
        keep = self.keep_steps and S > 1
        mode = "decode" if S == 1 else "chunk" if self.chunked else "prefill"
        # a verify step is ONE chunk from the state, in XLA's form whatever ``impl``
        count_kernel_build(mode, "delta_rule_step" if S == 1 else delta_rule.chunk_form("xla" if keep else impl))
        with phase_scope("embed"):
            embedding = self.param("embedding", nn.initializers.normal(stddev=0.02),
                                   (c.vocab_size, c.hidden_size), dt.param_dtype)
            h = jnp.take(embedding, tokens, axis=0).astype(dt.compute_dtype)
        with phase_scope("norm_rope"):
            cos, sin = rope_cos_sin(positions, lm.yarn_frequencies(c.qk_rope_head_dim, c.rope_theta, None))

        Lk = c.num_kda_layers
        add = jnp.zeros_like(cache.counters)
        if S == 1:
            add = add.at[_AT["kda_decode_positions"]].set(Lk * B)
            if impl != "xla" and c.num_mla_layers:
                # a step through ``mla_decode_attention``: what its walk fetches of
                # a plane (every plane's call fetches the same, so a step counts once)
                T = cache.c_kv.shape[2]
                step = mla.latent_decode_step(T, c.num_heads, c.kv_lora_rank, cache.c_kv.dtype)
                add = add.at[_DECODE_SLOTS:_DECODE_SLOTS + 2].set(jnp.stack(
                    [decode_slots_streamed(kv_start, kv_len, T, step), B * T]).astype(add.dtype))
        elif keep:
            add = add.at[_AT["kda_verify_positions"]].set(Lk * B * S)
        else:
            advanced = B * S if self.chunked else positions_advanced(c, start, S)
            add = add.at[_AT["kda_prefill_positions"]].set(Lk * advanced)
            add = add.at[_AT["kda_prefill_positions_bucketed"]].set(Lk * B * S)

        steps = None
        if keep:
            H, hd, f32 = c.kda_num_heads, c.kda_head_dim, jnp.float32
            steps = (jnp.zeros((Lk, B, cache.conv.shape[2] + S, 3 * c.kda_width), cache.conv.dtype),
                     jnp.zeros((Lk, B, S, H, hd), f32), jnp.zeros((Lk, B, S, H, hd), f32),
                     jnp.zeros((Lk, B, S, H, hd), f32), jnp.zeros((Lk, B, S, H), f32))
        zero = jnp.int32(0)
        planes, state = (cache.c_kv, cache.k_rope), (cache.conv, cache.state, steps)
        carry = (h, planes, state, cache.counters + add, (zero, zero, zero))

        # the mixers' leaves, stacked by kind: a layer reads its own at the kind's index
        linear_mixer, full_mixer = mixer_modules(c, dt, self.attn_impl, self.chunked, self.keep_steps)
        mixers = (_stacked(self, "kda_layers", linear_mixer, Lk, h, cache.conv[0], cache.state[0], start),
                  _stacked(self, "mla_layers", full_mixer, c.num_mla_layers, h, planes, zero, kv_start,
                           kv_len, cos, sin, wi))
        window = (kv_start, kv_len, cos, sin, wi, start, mixers)
        layer = (c, dt, self.attn_impl, self.chunked, self.keep_steps)
        for i in range(c.first_k_dense):  # outside the layers' loop
            carry, _ = Layer(*layer, sparse=False, lead_index=i, name=f"lead_{i}")(carry, *window)
        if c.num_moe_layers:
            experts_stack = lm.Experts(c, dt, name="experts")()
            scan = nn.scan(
                Layer, variable_axes={"params": 0}, split_rngs={"params": True},
                in_axes=(nn.broadcast,) * 8, out_axes=0, length=c.num_moe_layers)
            carry, _ = scan(*layer, name="layers")(carry, *window, experts_stack)
        h, (c_kv, k_rope), (conv, states, steps), counters, _ = carry

        with phase_scope("norm_rope"):
            h = RMSNorm(c.rms_norm_eps, dt, name="final_norm")(h)
        with phase_scope("lm_head"):
            if logit_index is not None:
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
        return logits.astype(dt.logits_dtype), DeltaCache(c_kv, k_rope, conv, states, counters, steps)


def init_delta_moe_params(rng: jax.Array, config: DeltaMoEConfig, dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = DeltaMoEModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_delta_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

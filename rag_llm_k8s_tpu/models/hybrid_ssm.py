"""The hybrid state-space decoder (``HybridSSMConfig``).

The fifth decoder family, with the call signature of the other four, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

**The layer** (``x`` the residual stream): ``x += mixer(RMS(x))``, then ``x
+= SwiGLU(RMS(x))``. The mixer of layer ``i`` is attention where ``i %
attn_layer_period == attn_layer_offset``, else the state-space mixer.

- *Attention*: grouped-query over ``models/llama.py``'s seam (``attend``, the
  planes ``[layers, B, K, T, hd]`` written at the shared ``write_index``),
  with NO rotation and no position term of any kind: ``positions`` is unused.
- *State space*: ``[u, z] = x W_in``; ``u`` through a depthwise causal
  convolution and ``silu``; ``[delta, B, C] = u W_x``, each RMS-normed with
  its own scale; ``delta W_dt`` and the recurrence of ``ops/ssm.py`` (the
  time step's ``softplus``, the state in float32, the ``D`` skip and the
  ``silu(z)`` gate are in it); ``W_out``.

**The cache holds two kinds of state** (``HybridCache``). The attention
layers' keys and values are by position, as everywhere else. A state layer
keeps ``conv [K - 1, d_inner]`` (the convolution's last inputs) and ``ssm [N,
d_inner]`` float32 a row: no position axis, overwritten in place by every
call, so nothing the engine does to a frontier (``kv_len``, ``write_index``)
reaches it. Two rules follow.

- *Left padding.* A softmax masks a pad's key; a recurrence has no mask. At a
  pad slot (``slot < kv_start[row]``) the convolution's input is forced to 0
  and the time step to 0 (``exp(0 A) = 1``, ``0 u B = 0``), so the first real
  token sees the zero history and zero state a row alone starts from.
  A fresh prompt call of which only the last position's logits leave does
  not compute the pads that EVERY row has: the state layers' matmuls, norms
  and convolution and every SwiGLU run on the token suffix ``[off, S)``,
  ``off`` the largest rung of ``live_rungs(S)`` (nothing, or a quarter of the
  bucket) that is <= every row's ``kv_start``, chosen on the device
  (``live_trip`` below says why the result is the same; the scan kernel,
  which passes a pad's chunk already, and the few attention layers keep the
  bucket's shape). Every other call computes what it is fed.
- *A verify step keeps some of what it fed.* The frontier takes back a
  rejected position's keys by not advancing; a state cannot be taken back.
  The model built with ``keep_steps`` (the verify loop's) leaves the state
  after EVERY fed position in ``ssm_steps`` and the convolution's run of
  inputs in ``conv_steps``; ``commit(cache, kept)`` (``Family.commit``) puts
  the state behind the ``kept``-th position in place and drops the rest.

Layers of both kinds run in ONE ``lax.scan`` over the depth (a trip chooses
its mixer by ``lax.cond``; the mixers' leaves are stacked by kind and read at
the trip's index inside the matmul that streams them), so an operation of a
layer is one loop beneath its phase whatever the pattern of kinds.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import DTypePolicy, HybridSSMConfig
from rag_llm_k8s_tpu.models.llama import (
    _rows_from,
    _set_rows_from,
    attend,
    live_offsets,
    resolve_attn_impl,
    rms_norm,
)
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops import ssm as ssm_ops
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed, gqa_decode_step

# HybridCache.counters. A fresh multi-token call at a time: token rows the
# state layers' and the SwiGLUs' matmuls ran on (rows x the live suffix where
# the call took a rung of ``live_rungs``, else rows x the bucket) and token
# rows of the padded batch (models/llama.py's names). A single-token step
# through the decode kernel at a time: the slots an attention layer's walk
# fetches over the rows, and rows x the slots allocated (both attention
# layers fetch the same: a step counts once). A multi-token call at a time:
# row-positions the scan was handed, pads included (it passes their chunks).
# A single-token step at a time: states written, rows x state layers. And
# what ``commit`` was told: positions a verify step fed, and kept.
COUNTER_NAMES = ("prefill_tokens_computed", "prefill_tokens_bucketed",
                 "decode_slots_streamed", "decode_slots_allocated",
                 "ssm_positions_scanned", "ssm_state_updates",
                 "verify_positions_fed", "verify_positions_kept")
N_COUNTERS = len(COUNTER_NAMES)
_AT = {name: i for i, name in enumerate(COUNTER_NAMES)}


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    return {name: int(n) for name, n in zip(COUNTER_NAMES, row)}


@flax.struct.dataclass
class HybridCache:
    """``k``, ``v`` ``[attention layers, B, K, T, hd]``; ``conv [state layers,
    B, d_conv - 1, d_inner]`` in the compute type and ``ssm [state layers, B,
    d_state, d_inner]`` float32 (channels last: the device pads a last axis
    to 128 lanes). ``ssm_steps [state layers, B, n, d_state, d_inner]`` and
    ``conv_steps [state layers, B, d_conv - 1 + n, d_inner]`` only between a
    ``keep_steps`` call of ``n`` positions and its ``commit``."""

    k: jax.Array
    v: jax.Array
    conv: jax.Array
    ssm: jax.Array
    counters: jax.Array
    ssm_steps: Optional[jax.Array] = None
    conv_steps: Optional[jax.Array] = None


def make_hybrid_cache(config: HybridSSMConfig, batch_size: int, max_seq_len: int,
                      dtype: jnp.dtype = jnp.bfloat16) -> HybridCache:
    c = config
    kv = (c.num_attention_layers, batch_size, c.num_kv_heads, max_seq_len, c.head_dim)
    M = c.num_state_layers
    return HybridCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
        conv=jnp.zeros((M, batch_size, c.mamba_d_conv - 1, c.d_inner), dtype),
        ssm=jnp.zeros((M, batch_size, c.mamba_d_state, c.d_inner), jnp.float32),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def commit(cache: HybridCache, kept: jax.Array) -> HybridCache:
    """After a verify step that fed ``n`` positions (a ``keep_steps`` call)
    and kept the first ``kept`` of them (1 <= kept <= n; one count for every
    row: the verify loop is batch 1), the cache whose state is the one behind
    position ``kept - 1``. The attention layers' planes need nothing: their
    frontier does the job."""
    taps = cache.conv.shape[2]
    fed = cache.ssm_steps.shape[2]
    kept = jnp.clip(jnp.asarray(kept, jnp.int32).reshape(()), 1, fed)
    counters = cache.counters.at[_AT["verify_positions_fed"]].add(fed)
    counters = counters.at[_AT["verify_positions_kept"]].add(kept)
    return cache.replace(
        ssm=jax.lax.dynamic_index_in_dim(cache.ssm_steps, kept - 1, axis=2, keepdims=False),
        conv=jax.lax.dynamic_slice_in_dim(cache.conv_steps, kept, taps, axis=2),
        counters=counters, ssm_steps=None, conv_steps=None)


def _at(stacked: jax.Array, index) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(stacked, index, 0, keepdims=False)


_SMALL = ("conv_w", "conv_b", "dt_norm", "b_norm", "c_norm", "dt_bias", "A_log", "D")


def _small_leaves(sp: dict, mi) -> dict:
    """State layer ``mi``'s leaves that no matmul streams (a few KB each)."""
    return {name: _at(sp[name], mi) for name in _SMALL}


def live_rungs(S: int) -> Tuple[int, ...]:
    """The leading tokens a fresh ``S``-token call of this family may skip:
    every second rung of ``models/llama.py live_offsets`` (nothing, or a
    quarter of the bucket). Quarters and not eighths by measurement (PERF.md,
    PR 41): a rung is a copy of a trip's matmuls, norms and convolution in
    every fresh-prompt executable, nine of them a deployment, and warm on one
    machine their tracing, lowering and loading cost ``setup_s`` 2 s of 67 at
    quarters and 7.5 s at eighths, against a bound of a tenth. (Of a four-way
    ``lax.switch`` the compiler also copies the residual stream in the third
    branch, twice a trip; a two-way conditional writes in place.)"""
    return live_offsets(S)[::2]


def _switch_rung(rung: jax.Array, offsets: Tuple[int, int], fn, *operands):
    """``fn(off, *operands)`` for the rung's ``off`` of the two ``offsets``,
    behind the barrier of ``models/llama.py _switch_live`` (it keeps what
    reads the result out of the branches)."""
    whole, suffix = (functools.partial(fn, off) for off in offsets)
    return jax.lax.optimization_barrier(jax.lax.cond(rung > 0, suffix, whole, *operands))


def _mm(x, w, out=None):
    """``x [..., d] @ w [d, f]``, accumulated in float32, returned in ``out``
    (``x``'s type)."""
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(out or x.dtype)


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


class HybridSSMModel(nn.Module):
    config: HybridSSMConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # S > 1 calls run over the cache as it is (a verify step, a prompt chunk, the scorer)
    keep_steps: bool = False  # leave every position's state for ``commit`` (the verify loop's calls)

    def _params(self):
        c, dt = self.config, self.dtypes
        D, F, L, Di, N = c.hidden_size, c.intermediate_size, c.num_layers, c.d_inner, c.mamba_d_state
        R, Kc, M, Na = c.mamba_dt_rank, c.mamba_d_conv, c.num_state_layers, c.num_attention_layers
        H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
        normal, ones, zeros = nn.initializers.normal(stddev=0.02), nn.initializers.ones, nn.initializers.zeros

        def p(name, shape, init=normal, dtype=dt.param_dtype):
            return self.param(name, init, shape, dtype)

        f32 = jnp.float32
        params = {
            "embedding": p("embedding", (c.vocab_size, D)),
            "final_norm": p("final_norm", (D,), ones),
            # every layer: its two norms and its SwiGLU, stacked over the depth
            "layers": {
                "input_norm": p("layers_input_norm", (L, D), ones),
                "ff_norm": p("layers_ff_norm", (L, D), ones),
                "w_gate": p("layers_w_gate", (L, D, F)), "w_up": p("layers_w_up", (L, D, F)),
                "w_down": p("layers_w_down", (L, F, D)),
            },
            # the state layers' mixers, stacked over those layers. ``A_log``
            # is [d_state, d_inner], the published leaf transposed (channels
            # last); it, ``D`` and the time step's bias stay float32
            "ssm": {
                "in_proj": p("ssm_in_proj", (M, D, 2 * Di)),
                "conv_w": p("ssm_conv_w", (M, Kc, Di)), "conv_b": p("ssm_conv_b", (M, Di), zeros),
                "x_proj": p("ssm_x_proj", (M, Di, R + 2 * N)),
                "dt_norm": p("ssm_dt_norm", (M, R), ones), "b_norm": p("ssm_b_norm", (M, N), ones),
                "c_norm": p("ssm_c_norm", (M, N), ones),
                "dt_proj": p("ssm_dt_proj", (M, R, Di)), "dt_bias": p("ssm_dt_bias", (M, Di), zeros, f32),
                "A_log": p("ssm_A_log", (M, N, Di), zeros, f32), "D": p("ssm_D", (M, Di), ones, f32),
                "out_proj": p("ssm_out_proj", (M, Di, D)),
            },
            "attn": {
                "wq": p("attn_wq", (Na, D, H * hd)), "wk": p("attn_wk", (Na, D, K * hd)),
                "wv": p("attn_wv", (Na, D, K * hd)), "wo": p("attn_wo", (Na, H * hd, D)),
            },
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = p("lm_head", (D, c.vocab_size))
        return params

    def _attention(self, ap, ai, x, planes, kv_start, kv_len, write_index, impl):
        c = self.config
        B, S, _ = x.shape
        H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
        k_plane, v_plane = planes
        q = _mm(x, _at(ap["wq"], ai)).reshape(B, S, H, hd)
        k = _mm(x, _at(ap["wk"], ai)).reshape(B, S, K, hd)
        v = _mm(x, _at(ap["wv"], ai)).reshape(B, S, K, hd)
        at = (ai, 0, 0, write_index, 0)
        k_plane = jax.lax.dynamic_update_slice(k_plane, k.transpose(0, 2, 1, 3).astype(k_plane.dtype)[None], at)
        v_plane = jax.lax.dynamic_update_slice(v_plane, v.transpose(0, 2, 1, 3).astype(v_plane.dtype)[None], at)
        with phase_scope("global"):
            if S == 1:
                o = attend(q, k_plane, v_plane, kv_start, kv_len, ai, mode="decode", impl=impl)
            elif self.chunked:
                o = attend(q, k_plane, v_plane, kv_start, kv_len, ai, mode="chunk", impl=impl,
                           write_index=write_index)
            else:  # writes at slot 0: the fresh K/V are the populated prefix
                o = attend(q, k, v, kv_start, kv_len, ai, mode="prefill", impl=impl)
        return _mm(o.reshape(B, S, H * hd), _at(ap["wo"], ai)), (k_plane, v_plane)

    def _scan_operands(self, sp, small, mi, x, history, start):
        """What the selective scan of state layer ``mi`` reads of ``x [B, S,
        D]``: ``(u, delta, z, B, C)``, and the convolution's run of inputs
        from ``history`` on (its last ``d_conv - 1`` rows are the new
        history); ``start [B]``: indices of ``x`` in front of it are pads.
        ``small``: the layer's ``_small_leaves``."""
        c, dt = self.config, self.dtypes
        Di, N, R = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
        S = x.shape[1]
        xz = _mm(x, _at(sp["in_proj"], mi))
        live = (jnp.arange(S, dtype=jnp.int32)[None, :] >= start[:, None])[..., None]
        u, z = jnp.where(live, xz[..., :Di], 0), xz[..., Di:]
        with phase_scope("conv"):
            u, run = ssm_ops.causal_conv(u, history, small["conv_w"], small["conv_b"])
        dbc = _mm(u, _at(sp["x_proj"], mi), jnp.float32)
        eps = c.rms_norm_eps
        delta = _rms(dbc[..., :R], small["dt_norm"], eps).astype(dt.compute_dtype)
        Bm = _rms(dbc[..., R:R + N], small["b_norm"], eps)
        Cm = _rms(dbc[..., R + N:], small["c_norm"], eps)
        return (u, _mm(delta, _at(sp["dt_proj"], mi)), z, Bm, Cm), run

    def _scan(self, small, operands, h0, start, impl, keep=False):
        """``(y, last state, every position's under keep)`` of a state
        layer's recurrence over ``_scan_operands``' five, from ``h0``."""
        u, delta, z, Bm, Cm = operands
        A = -jnp.exp(small["A_log"])
        args = (u, delta, z, A, Bm, Cm, small["D"], small["dt_bias"], h0, start)
        with phase_scope("scan"):
            if keep:
                return ssm_ops.selective_scan_xla(*args, keep_steps=True)
            return ssm_ops.selective_scan(*args, impl=impl) + (None,)

    def _state_mixer(self, sp, mi, x, conv, ssm, start, impl, keep):
        """The state-space mixer of state layer ``mi`` on ``x [B, S, D]``,
        from the state in ``conv[mi]`` / ``ssm[mi]``; ``start [B]``: indices
        of ``x`` in front of it are pads. Returns the mixer's output, the
        convolution's run of inputs (its last ``d_conv - 1`` rows are the new
        history), the last state and (``keep``) every position's."""
        small = _small_leaves(sp, mi)
        operands, run = self._scan_operands(sp, small, mi, x, _at(conv, mi), start)
        y, last, steps = self._scan(small, operands, _at(ssm, mi), start, impl, keep)
        return _mm(y, _at(sp["out_proj"], mi)), run, last, steps

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array,
        cache: HybridCache,
        kv_start: jax.Array,
        kv_len: jax.Array,
        write_index: jax.Array,
        last_logit_only: bool = False,
        logit_index: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, HybridCache]:
        c, dt = self.config, self.dtypes
        params = self._params()
        impl = resolve_attn_impl(self.attn_impl)
        B, S = tokens.shape
        P, off = c.attn_layer_period, c.attn_layer_offset
        wi = jnp.asarray(write_index, jnp.int32).reshape(())
        start = jnp.maximum(kv_start.astype(jnp.int32) - wi, 0)  # [B]: indices of this call in front of it are pads
        keep = self.keep_steps and S > 1
        mode = "decode" if S == 1 else "chunk" if self.chunked else "prefill"
        count_kernel_build(mode, "selective_scan_xla" if keep else ssm_ops.scan_form(S, impl))
        # a fresh prompt call of which one position's logits leave runs its
        # layers on the live suffix (``live_trip``); the rung is the batch's
        # smallest left pad, read here on the device
        fresh = mode == "prefill" and not self.keep_steps and last_logit_only
        offsets = live_rungs(S) if fresh else ()
        rung, skipped = None, 0
        if offsets:
            rung = jnp.minimum(jnp.min(start) // offsets[1], 1).astype(jnp.int32)
            skipped = rung * offsets[1]

        counters = cache.counters
        add = jnp.zeros_like(counters)
        if S == 1:
            add = add.at[_AT["ssm_state_updates"]].set(B * c.num_state_layers)
            if impl != "xla" and c.num_attention_layers:
                T = cache.k.shape[3]
                step = gqa_decode_step(T, c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, cache.k.dtype)
                add = add.at[_AT["decode_slots_streamed"]].set(decode_slots_streamed(kv_start, kv_len, T, step))
                add = add.at[_AT["decode_slots_allocated"]].set(B * T)
        else:
            add = add.at[_AT["ssm_positions_scanned"]].set(B * S)
            if not self.chunked:
                add = add.at[_AT["prefill_tokens_computed"]].set(B * (S - skipped))
                add = add.at[_AT["prefill_tokens_bucketed"]].set(B * S)
        counters = counters + add

        with phase_scope("embed"):
            h = jnp.take(params["embedding"], tokens, axis=0).astype(dt.compute_dtype)

        state = (cache.k, cache.v, cache.conv, cache.ssm)
        if keep:
            M, taps = c.num_state_layers, c.mamba_d_conv - 1
            state += (jnp.zeros((M, B, S) + cache.ssm.shape[2:], jnp.float32),
                      jnp.zeros((M, B, taps + S, c.d_inner), cache.conv.dtype))

        def put(stacked, index, value):
            return jax.lax.dynamic_update_index_in_dim(stacked, value.astype(stacked.dtype), index, 0)

        def layer(carry, xs):
            h, state = carry
            lp, i = xs
            before = (i - off + P - 1) // P  # attention layers in front of layer i
            with phase_scope("norm_rope"):
                x = rms_norm(h, lp["input_norm"], c.rms_norm_eps, dt)

            def attention(x, state):
                out, planes = self._attention(params["attn"], before, x, state[:2], kv_start, kv_len, wi, impl)
                return out, planes + state[2:]

            def state_space(x, state):
                mi = i - before
                out, run, last, steps = self._state_mixer(
                    params["ssm"], mi, x, state[2], state[3], start, impl, keep)
                history = jax.lax.slice_in_dim(run, S, S + c.mamba_d_conv - 1, axis=1)
                new = state[:2] + (put(state[2], mi, history), put(state[3], mi, last))
                if keep:
                    new += (put(state[4], mi, steps), put(state[5], mi, run))
                return out, new

            with phase_scope("attn"):
                if c.num_attention_layers:
                    out, state = jax.lax.cond(i % P == off, attention, state_space, x, state)
                else:
                    out, state = state_space(x, state)
                h = h + out
            with phase_scope("norm_rope"):
                x = rms_norm(h, lp["ff_norm"], c.rms_norm_eps, dt)
            with phase_scope("mlp"):
                y = nn.silu(_mm(x, lp["w_gate"])) * _mm(x, lp["w_up"])
                h = h + _mm(y, lp["w_down"])
            return (h, state), None

        def live_trip(carry, i):
            """``layer`` with the state layers' matmuls, norms and convolution
            and every SwiGLU on ``h[:, o:]``, ``o`` the rung's offset: indices
            in front of it are pads in EVERY row, and nothing reads a pad's
            output (``last_logit_only``), so the result is ``layer``'s. A
            state layer forces a pad's convolution input and time step to 0:
            the convolution's history at ``o`` is zeros (``o`` > ``d_conv -
            1`` pads stand in front of it), and the scan, which runs at the
            bucket's shape on buffers whose rows in front of ``o`` stay the
            zeros they start as, passes the pads' chunks as it always did.
            The two attention layers run the bucket.

            What PR 28 found in ``models/llama.py`` holds here (PERF.md): a
            branch reads the STACKED leaves at the trip's index inside the
            matmul that streams them (sliced by the scan in front of a
            conditional, a layer's weights are copied first); the residual
            stream and the scan's operands keep the bucket's shape through
            the loop and a branch writes its suffix rows in place; the
            kernels stand outside the rungs' branches, so a program traces
            and lowers each ONCE (in a branch a rung, the scan kernel alone
            cost every fresh-prompt program 4 s of set-up: PERF.md, PR 41);
            and the writes into the stacked ``conv`` / ``ssm`` stand outside
            every branch, where ``benchmark/lib/phases.py`` counts a
            prefill's rows: by the median executions of the instructions
            whose scope path holds no branch. The trip's small leaves are
            sliced there too, behind a barrier: sliced in a branch, the
            compiler's relayouts of them carry the operand's path, which
            holds no branch either, and two of those beside the two writes
            read 23.1 rows for 24 (PERF.md, PR 41)."""
            h, (k_plane, v_plane, conv, ssm), operands = carry
            layers, sp = params["layers"], params["ssm"]
            before = (i - off + P - 1) // P
            mi = i - before  # on an attention trip the NEXT state layer's: its slices are written back as read
            taps = c.mamba_d_conv - 1
            small, norm_in, norm_ff = jax.lax.optimization_barrier(
                (_small_leaves(sp, mi), _at(layers["input_norm"], i), _at(layers["ff_norm"], i)))

            def project(o, h, *operands):
                x = rms_norm(_rows_from(h, o), norm_in, c.rms_norm_eps, dt)
                history = jnp.zeros((B, taps, c.d_inner), x.dtype) if o else _at(conv, mi)
                new, run = self._scan_operands(sp, small, mi, x, history, start - o)
                history = jax.lax.slice_in_dim(run, S - o, S - o + taps, axis=1)
                return tuple(_set_rows_from(buf, o, rows.astype(buf.dtype)) for buf, rows in zip(operands, new)), history

            def project_out(o, h, y):
                return _set_rows_from(h, o, _rows_from(h, o) + _mm(_rows_from(y, o), _at(sp["out_proj"], mi)))

            def state_space(h, planes, operands):
                operands, history = _switch_rung(rung, offsets, project, h, *operands)
                y, last, _ = self._scan(small, operands, _at(ssm, mi), start, impl)
                h = _switch_rung(rung, offsets, project_out, h, y)
                return h, planes, history.astype(conv.dtype), last, operands

            def attention(h, planes, operands):
                x = rms_norm(h, norm_in, c.rms_norm_eps, dt)
                out, planes = self._attention(params["attn"], before, x, planes, kv_start, kv_len, wi, impl)
                return h + out, planes, _at(conv, mi), _at(ssm, mi), operands

            def add_ffn(o, h):
                hs = _rows_from(h, o)
                x = rms_norm(hs, norm_ff, c.rms_norm_eps, dt)
                y = nn.silu(_mm(x, _at(layers["w_gate"], i))) * _mm(x, _at(layers["w_up"], i))
                return _set_rows_from(h, o, hs + _mm(y, _at(layers["w_down"], i)))

            with phase_scope("attn"):
                if c.num_attention_layers:
                    h, planes, history, last, operands = jax.lax.cond(
                        i % P == off, attention, state_space, h, (k_plane, v_plane), operands)
                else:
                    h, planes, history, last, operands = state_space(h, (k_plane, v_plane), operands)
                conv, ssm = put(conv, mi, history), put(ssm, mi, last)
            with phase_scope("mlp"):
                h = _switch_rung(rung, offsets, add_ffn, h)
            return (h, planes + (conv, ssm), operands), None

        trips = jnp.arange(c.num_layers, dtype=jnp.int32)
        if offsets:
            cd, f32 = dt.compute_dtype, jnp.float32
            operands = tuple(jnp.zeros((B, S, n), t) for n, t in (
                (c.d_inner, cd), (c.d_inner, cd), (c.d_inner, cd), (c.mamba_d_state, f32), (c.mamba_d_state, f32)))
            (h, state, _), _ = jax.lax.scan(live_trip, (h, state, operands), trips)
        else:
            (h, state), _ = jax.lax.scan(layer, (h, state), (params["layers"], trips))
        new_cache = HybridCache(*state[:4], counters, *state[4:])

        with phase_scope("norm_rope"):
            h = rms_norm(h, params["final_norm"], c.rms_norm_eps, dt)
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
                logits = jnp.einsum("bsd,vd->bsv", h, params["embedding"].astype(dt.compute_dtype),
                                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dt.compute_dtype),
                                    preferred_element_type=jnp.float32)
        return logits.astype(dt.logits_dtype), new_cache


def init_hybrid_ssm_params(rng: jax.Array, config: HybridSSMConfig, dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = HybridSSMModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_hybrid_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

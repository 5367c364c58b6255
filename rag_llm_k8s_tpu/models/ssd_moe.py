"""The state-space-duality, latent-expert decoder (``SSDMoEConfig``).

The ninth decoder family, with the call signature of the other eight, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

**A layer is ONE thing** (``x`` the residual stream): ``x + F_k(RMS(x))``,
``k`` the layer's letter of ``hybrid_override_pattern``. No layer pairs a
mixer with a feed-forward part, so a layer has one norm and one residual.

- ``M``, *Mamba-2*: ``[z | xBC | dt] = n W_in``; ``xBC`` through a depthwise
  causal convolution (``ops/ssm.py causal_conv``, with a bias) and a SiLU,
  split ``x [heads, head_dim] | B [groups, N] | C [groups, N]``; ``dt =
  softplus(dt + dt_bias)`` a head (0 at a pad), the recurrence of
  ``ops/ssd.py`` over a float32 ``[head_dim, N]`` state a head with ONE decay
  a head; the output gated by ``silu(z)`` FIRST and RMS-normed a GROUP of
  ``d_inner / n_groups`` channels SECOND; ``W_out``.
- ``*``, *attention*: grouped-query over ``models/llama.py``'s seam
  (``attend``; planes ``[attention layers, B, K, T, hd]``) with NO position
  term of any kind: ``positions`` is unused.
- ``E``, *latent experts*: the router scores the STREAM (``ops/moe.py
  route``), the stream goes down to ``moe_latent_size``, this chip's held
  experts (two matrices, ``relu`` squared between: ``held_expert_ffn`` with
  no gate) work THERE on the tokens routed to them, their weighted partial
  sum goes back up through ``W_up``; a shared expert (``relu`` squared too)
  works on the stream. What experts held elsewhere would add is left out;
  nothing stands in for their chips.

**The cache holds three kinds of state** (``SSDCache``): the ``*`` layers'
K/V planes by position; a Mamba-2 layer's ``state [heads, head_dim, N]``
float32 a row (the last axis the 128 lanes) and the convolution's last
``conv_kernel - 1`` inputs in the compute type: no position axis, overwritten
in place, so nothing the engine does to a frontier reaches them.

- *Left padding.* At a pad (``slot < kv_start[row]``) the convolution's input
  and the time step are forced to 0: an identity of the recurrence. A fresh
  prompt's recurrence starts at the first chunk that holds a real token.
- *A verify step keeps some of what it fed*, and a state cannot be taken
  back. Keeping every fed position's state would write ``n`` x 4.2 MB a
  row-layer; the model built with ``keep_steps`` (the verify loop's) leaves
  the state AS IT WAS and keeps the step's ``x, B, dt`` and the convolution's
  run of inputs in ``steps``; ``commit(cache, kept)`` (``Family.commit``)
  replays the first ``kept`` as one chunk (``ops/ssd.py ssd_replay``).

**The layers' loop.** The published pattern is not periodic, so a trip is ONE
LAYER of one ``lax.scan``: the trip reads its norm, takes its kind as a branch
(``lax.switch`` over the kinds the pattern holds) and adds the branch's
output. Leaves are stacked by kind and read at the kind's own index inside
the branch (sliced in front of a conditional, a layer's weights are copied
first); a Mamba-2 layer's rows of ``conv`` and ``state`` are read in front of
the branch and written behind it whichever branch the trip takes (a stack
that a branch hands through untouched is copied by the compiler). The held
experts live outside the loop and a layer reads its own through the grouped
kernel's index map.

The cache carries the family's counters: ``models/latent_moe.py``'s block
(what the expert layers did, the decode walk's slots), then what the
recurrence advanced, by how the model was called.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import DTypePolicy, SSDMoEConfig
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.models.llama import attend, resolve_attn_impl, rms_norm
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops import moe, ssd
from rag_llm_k8s_tpu.ops import ssm as ssm_ops
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed, gqa_decode_step

# row-layer positions the recurrence advanced in a fresh prompt (live ones,
# not pads), what a bucket-wide walk would have advanced, the chunks it
# walked; positions a decode step advanced, a verify step fed, ``commit`` kept
EXTRA_STATS = ("ssd_prefill_positions", "ssd_prefill_positions_bucketed", "ssd_prefill_chunks",
               "ssd_decode_positions", "ssd_verify_positions", "ssd_verify_positions_kept")
N_COUNTERS = lm.N_COUNTERS + len(EXTRA_STATS)
COUNTER_NAMES = tuple(lm.COUNTER_STATS) + EXTRA_STATS
_AT = {name: lm.N_COUNTERS + i for i, name in enumerate(EXTRA_STATS)}
_DECODE_SLOTS = lm.COUNTER_MODES.index("decode") * len(lm.COUNTER_FIELDS) + lm.COUNTER_FIELDS.index(
    "slots_streamed")
# bytes a token of a layer's temporaries (the Mamba-2 projection's output and
# what the recurrence reads of it in float32; an expert layer's gathered rows
# at 22 choices a token) from which a batch goes through a layer a row at a time
_LAYER_BYTES_PER_CHANNEL = 16
_SMALL = ("conv_w", "conv_b", "dt_bias", "A_log", "D", "norm")  # a Mamba-2 layer's leaves that no matmul streams


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    out = lm.fold_counters(row[:lm.N_COUNTERS])
    out.update({name: int(n) for name, n in zip(EXTRA_STATS, row[lm.N_COUNTERS:])})
    return out


@flax.struct.dataclass
class SSDCache:
    """``k``, ``v`` ``[attention layers, B, K, T, hd]``; ``conv [Mamba-2
    layers, B, conv_kernel - 1, conv_width]`` in the compute type, oldest
    first, and ``state [Mamba-2 layers, B, heads, head_dim, N]`` float32.
    ``steps``: ``(conv_run [.., taps + n, conv_width], x [.., n, heads,
    head_dim], B [.., n, groups, N], dt and the log decay dt A [.., n,
    heads])`` only between a ``keep_steps`` call of ``n`` positions and its
    ``commit``."""

    k: jax.Array
    v: jax.Array
    conv: jax.Array
    state: jax.Array
    counters: jax.Array
    steps: Optional[Tuple[jax.Array, ...]] = None


def make_ssd_cache(config: SSDMoEConfig, batch_size: int, max_seq_len: int,
                   dtype: jnp.dtype = jnp.bfloat16) -> SSDCache:
    c = config
    kv = (c.num_attention_layers, batch_size, c.num_kv_heads, max_seq_len, c.head_dim)
    M = c.num_mamba_layers
    return SSDCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
        conv=jnp.zeros((M, batch_size, c.conv_kernel - 1, c.conv_width), dtype),
        state=jnp.zeros((M, batch_size, c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size), jnp.float32),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def commit(cache: SSDCache, kept: jax.Array) -> SSDCache:
    """After a verify step that fed ``n`` positions (a ``keep_steps`` call)
    and kept the first ``kept`` of them (0 <= kept <= n; one count for every
    row: the verify loop is batch 1), the cache whose state is the one behind
    position ``kept - 1``: the step's first ``kept`` updates replayed as one
    chunk from the state in front of it, and the convolution's inputs in front
    of position ``kept``. The K/V planes need nothing: their frontier does
    the job."""
    run, x, Bm, dt, a = cache.steps
    layers, B, n = x.shape[:3]
    taps = cache.conv.shape[2]
    kept = jnp.clip(jnp.asarray(kept, jnp.int32).reshape(()), 0, n)

    def rows(t):  # a layer's rows are rows of one batch
        return t.reshape((layers * B,) + t.shape[2:])

    with phase_scope("attn/ssd"):
        state = ssd.ssd_replay(rows(x), rows(dt), rows(a), rows(Bm), rows(cache.state), kept)
    counters = cache.counters.at[_AT["ssd_verify_positions_kept"]].add(layers * B * kept)
    return cache.replace(state=state.reshape(cache.state.shape), conv=jax.lax.dynamic_slice_in_dim(run, kept, taps, axis=2),
                         counters=counters, steps=None)


def _at(stacked: jax.Array, index) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(stacked, index, 0, keepdims=False)


def _put(stacked: jax.Array, index, value: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(stacked, value.astype(stacked.dtype), index, 0)


def _mm(x, w, out=None):
    """``x [..., d] @ w [d, f]``, accumulated in float32, returned in ``out``
    (``x``'s type). float32 operands (the fp32 policy) say the highest
    precision, or a TPU rounds them to bf16."""
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype), preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
                      ).astype(out or x.dtype)


def layer_by_rows(config: SSDMoEConfig, batch: int, seq: int) -> bool:
    """Whether a layer takes a batch a row at a time (by shape: eight rows of
    a 4096 bucket hold 9 GB of the Mamba-2 projection's output and what the
    recurrence reads of it, and as much of an expert layer's gathered rows)."""
    return batch > 1 and batch * seq * config.in_proj_width * _LAYER_BYTES_PER_CHANNEL >= lm.ROWWISE_BYTES


def chunks_walked(config: SSDMoEConfig, start: jax.Array, seq: int) -> jax.Array:
    """Chunks a fresh ``seq``-token call's recurrence walks in ONE Mamba-2
    layer, over the rows of ``start [B]`` (indices in front of it are pads):
    each row from its own first live chunk where a layer goes by rows, else
    every row from the batch's first."""
    Q, n = ssd.chunks_of(seq, config.chunk_size)
    if layer_by_rows(config, start.shape[0], seq):
        return jnp.sum(n - start // Q)
    return start.shape[0] * (n - jnp.min(start) // Q)


def a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def dt_bias_init(config: SSDMoEConfig):
    """The inverse softplus of ``exp U(log time_step_min, log time_step_max)``
    floored at ``time_step_floor`` (the published draw)."""
    lo, hi = math.log(config.time_step_min), math.log(config.time_step_max)

    def init(key, shape, dtype):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)), config.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


class SSDMoEModel(nn.Module):
    config: SSDMoEConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # S > 1 calls run over the cache as it is (a verify step, a prompt chunk, the scorer)
    keep_steps: bool = False  # leave the state as it was and the step's inputs for ``commit`` (the verify loop's calls)

    def _params(self):
        c, dt = self.config, self.dtypes
        D, L, Z = c.hidden_size, c.num_layers, c.moe_latent_size
        M, Na, Ne = c.num_mamba_layers, c.num_attention_layers, c.num_moe_layers
        Di, Cw, Hm = c.d_inner, c.conv_width, c.mamba_num_heads
        H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
        E, F, Fs = c.n_routed_experts, c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
        normal, ones, zeros = nn.initializers.normal(stddev=0.02), nn.initializers.ones, nn.initializers.zeros
        f32 = jnp.float32

        def p(name, shape, init=normal, dtype=dt.param_dtype):
            return self.param(name, init, shape, dtype)

        return {
            "embedding": p("embedding", (c.vocab_size, D)),
            "final_norm": p("final_norm", (D,), ones),
            "lm_head": p("lm_head", (D, c.vocab_size)),
            "norms": p("norms", (L, D), ones),  # every layer's one norm
            # the Mamba-2 mixers, stacked over those layers; ``A_log``, ``D``
            # and the time step's bias are a head's scalars and stay float32
            "mamba": {
                "in_proj": p("mamba_in_proj", (M, D, c.in_proj_width)),
                "conv_w": p("mamba_conv_w", (M, c.conv_kernel, Cw)), "conv_b": p("mamba_conv_b", (M, Cw), zeros),
                "dt_bias": p("mamba_dt_bias", (M, Hm), dt_bias_init(c), f32),
                "A_log": p("mamba_A_log", (M, Hm), a_log_init, f32), "D": p("mamba_D", (M, Hm), ones, f32),
                "norm": p("mamba_norm", (M, Di), ones), "out_proj": p("mamba_out_proj", (M, Di, D)),
            },
            "attn": {
                "wq": p("attn_wq", (Na, D, H * hd)), "wk": p("attn_wk", (Na, D, K * hd)),
                "wv": p("attn_wv", (Na, D, K * hd)), "wo": p("attn_wo", (Na, H * hd, D)),
            },
            # an expert layer's leaves on the stream; the held experts, in the
            # latent, are one stack of every expert layer's for the grouped kernel
            "moe": {
                "router": p("moe_router", (Ne, D, E)), "router_bias": p("moe_router_bias", (Ne, E), zeros, f32),
                "latent_down": p("moe_latent_down", (Ne, D, Z)), "latent_up": p("moe_latent_up", (Ne, Z, D)),
                "shared_up": p("moe_shared_up", (Ne, D, c.n_shared_experts * Fs)),
                "shared_down": p("moe_shared_down", (Ne, c.n_shared_experts * Fs, D)),
            },
            "experts": {"w_up": p("experts_w_up", (Ne, c.experts_held, Z, F)),
                        "w_down": p("experts_w_down", (Ne, c.experts_held, F, Z))},
        }

    def _mamba(self, mp, small, ki, x, history, s0, start, fresh: bool, keep: bool):
        """The Mamba-2 mixer of Mamba-2 layer ``ki`` on ``x [R, S, D]`` from ITS
        rows of the cache (``history [R, taps, conv_width]``, ``s0 [R, heads,
        head_dim, N]``); ``small``: the layer's ``_SMALL`` leaves; ``start
        [R]``: indices of ``x`` in front of it are pads. Returns ``(out,
        history, state, kept)``: the rows to write back and, under ``keep``
        (history and state are then the ones handed in), the step's ``(conv
        run, x, B, dt, dt A)`` for ``commit``, else ``()``."""
        c, dt = self.config, self.dtypes
        R, S, _ = x.shape
        H, P, G, N, Di = c.mamba_num_heads, c.mamba_head_dim, c.n_groups, c.ssm_state_size, c.d_inner
        taps, f32 = c.conv_kernel - 1, jnp.float32
        live = jnp.arange(S, dtype=jnp.int32)[None, :] >= start[:, None]  # [R, S]
        zxd = _mm(x, _at(mp["in_proj"], ki))
        z, xbc, dtr = zxd[..., :Di], zxd[..., Di:Di + c.conv_width], zxd[..., Di + c.conv_width:]
        with phase_scope("conv"):
            xbc, run = ssm_ops.causal_conv(jnp.where(live[..., None], xbc, 0), history,
                                           small["conv_w"], small["conv_b"])
        xs = xbc[..., :Di].reshape(R, S, H, P)
        Bm = xbc[..., Di:Di + G * N].reshape(R, S, G, N)
        Cm = xbc[..., Di + G * N:].reshape(R, S, G, N)
        step = jnp.where(live[..., None], jax.nn.softplus(dtr.astype(f32) + small["dt_bias"]), 0.0)
        A, skip = -jnp.exp(small["A_log"]), small["D"]
        with phase_scope("ssd"):
            if S == 1:
                y, s1 = ssd.ssd_step(xs[:, 0], step[:, 0], A, Bm[:, 0], Cm[:, 0], skip, s0)
                y = y[:, None]
            elif keep:  # one chunk from the state, which stays: ``commit`` replays what was kept
                y, s1 = ssd.ssd_chunked(xs, step, A, Bm, Cm, skip, s0, chunk=S)[0], s0
            else:  # a walk from the first chunk that holds a live position
                Q = ssd.chunks_of(S, c.chunk_size)[0]
                y, s1 = ssd.ssd_chunked(xs, step, A, Bm, Cm, skip, s0, chunk=c.chunk_size,
                                        first_chunk=jnp.min(start) // Q if fresh else None)
        with phase_scope("gate"):  # the gate FIRST, the norm a group SECOND
            y = (y.reshape(R, S, Di) * jax.nn.silu(z.astype(f32))).reshape(R, S, G, Di // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c.layer_norm_epsilon)
            y = (y.reshape(R, S, Di) * small["norm"].astype(f32)).astype(dt.compute_dtype)
        out = _mm(y, _at(mp["out_proj"], ki))
        if keep:
            return out, history, s0, (run, xs, Bm, step, step * A)
        return out, jax.lax.slice_in_dim(run, S, S + taps, axis=1).astype(history.dtype), s1, ()

    def _attention(self, ap, ki, x, planes, kv_start, kv_len, write_index, impl):
        c = self.config
        B, S, _ = x.shape
        H, K, hd = c.num_heads, c.num_kv_heads, c.head_dim
        k_plane, v_plane = planes
        q = _mm(x, _at(ap["wq"], ki)).reshape(B, S, H, hd)
        k = _mm(x, _at(ap["wk"], ki)).reshape(B, S, K, hd)
        v = _mm(x, _at(ap["wv"], ki)).reshape(B, S, K, hd)
        at = (ki, 0, 0, write_index, 0)
        k_plane = jax.lax.dynamic_update_slice(k_plane, k.transpose(0, 2, 1, 3).astype(k_plane.dtype)[None], at)
        v_plane = jax.lax.dynamic_update_slice(v_plane, v.transpose(0, 2, 1, 3).astype(v_plane.dtype)[None], at)
        with phase_scope("global"):
            if S == 1:
                o = attend(q, k_plane, v_plane, kv_start, kv_len, ki, mode="decode", impl=impl)
            elif self.chunked:
                o = attend(q, k_plane, v_plane, kv_start, kv_len, ki, mode="chunk", impl=impl,
                           write_index=write_index)
            else:  # writes at slot 0: the fresh K/V are the populated prefix
                o = attend(q, k, v, kv_start, kv_len, ki, mode="prefill", impl=impl)
        return _mm(o.reshape(B, S, H * hd), _at(ap["wo"], ki)), (k_plane, v_plane)

    def _experts(self, ep, bias, stacks, ki, x, impl):
        """The latent expert layer ``ki`` on ``x [R, S, D]`` (``bias``: its
        correction bias): ``(y, counts)``, ``counts`` what the call adds to its
        mode's fields of ``models/latent_moe.py``'s counter block
        (``COUNTER_FIELDS``: an ``ops.moe.ExpertCounts`` and the layer call)."""
        c = self.config
        R, S, D = x.shape
        flat = x.reshape(R * S, D)
        with phase_scope("router"):
            # float32 scores (bf16 inputs multiply exactly into the float32 accumulator)
            logits = jnp.dot(flat, _at(ep["router"], ki).astype(flat.dtype), preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST if flat.dtype == jnp.float32 else None)
            chosen, weights = moe.route(
                logits, bias, top_k=c.num_experts_per_tok, n_group=c.n_group,
                topk_group=c.topk_group, scaling=c.routed_scaling_factor, normalize=c.norm_topk_prob, impl=impl)
        with phase_scope("latent"):
            low = _mm(flat, _at(ep["latent_down"], ki))
        with phase_scope("experts"):
            part, counts = moe.held_expert_ffn(
                low, chosen, weights, None, stacks["w_up"].astype(low.dtype), stacks["w_down"].astype(low.dtype),
                ki, c.first_held, c.n_routed_experts, impl=impl)
        with phase_scope("latent"):  # this chip's partial sum goes on through W_up
            y = _mm(part, _at(ep["latent_up"], ki))
        if c.n_shared_experts:
            with phase_scope("shared"):
                y = y + _mm(jnp.square(jax.nn.relu(_mm(flat, _at(ep["shared_up"], ki)))), _at(ep["shared_down"], ki))
        add = {**counts._asdict(), "layer_calls": 1}
        return y.reshape(R, S, D), jnp.stack([jnp.asarray(add.get(f, 0), jnp.int32) for f in lm.COUNTER_FIELDS])

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array,
        cache: SSDCache,
        kv_start: jax.Array,
        kv_len: jax.Array,
        write_index: jax.Array,
        last_logit_only: bool = False,
        logit_index: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, SSDCache]:
        c, dt = self.config, self.dtypes
        params = self._params()
        impl = resolve_attn_impl(self.attn_impl)
        B, S = tokens.shape
        M, taps = c.num_mamba_layers, c.conv_kernel - 1
        wi = jnp.asarray(write_index, jnp.int32).reshape(())
        start = jnp.maximum(kv_start.astype(jnp.int32) - wi, 0)  # [B]: indices of this call in front of it are pads
        keep = self.keep_steps and S > 1
        mode = "decode" if S == 1 else "chunk" if self.chunked else "prefill"
        fresh = mode == "prefill"
        by_rows = layer_by_rows(c, B, S)
        count_kernel_build(mode, "ssd_step" if S == 1 else "ssd_chunked_xla")

        add = jnp.zeros_like(cache.counters)
        if S == 1:
            add = add.at[_AT["ssd_decode_positions"]].set(M * B)
            if impl != "xla" and c.num_attention_layers:
                # a step through ``decode_attention``: what its walk fetches of a
                # plane (every plane's call fetches the same, so a step counts once)
                T = cache.k.shape[3]
                step = gqa_decode_step(T, c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, cache.k.dtype)
                add = add.at[_DECODE_SLOTS:_DECODE_SLOTS + 2].set(jnp.stack(
                    [decode_slots_streamed(kv_start, kv_len, T, step), B * T]).astype(add.dtype))
        elif keep:
            add = add.at[_AT["ssd_verify_positions"]].set(M * B * S)
        else:
            chunks = chunks_walked(c, start, S) if fresh else B * ssd.chunks_of(S, c.chunk_size)[1]
            add = add.at[_AT["ssd_prefill_positions"]].set(M * jnp.sum(S - jnp.minimum(start, S)))
            add = add.at[_AT["ssd_prefill_positions_bucketed"]].set(M * B * S)
            add = add.at[_AT["ssd_prefill_chunks"]].set(M * chunks)

        with phase_scope("embed"):
            h = jnp.take(params["embedding"], tokens, axis=0).astype(dt.compute_dtype)

        steps = None
        if keep:
            H, P, f32 = c.mamba_num_heads, c.mamba_head_dim, jnp.float32
            steps = (jnp.zeros((M, B, taps + S, c.conv_width), cache.conv.dtype),
                     jnp.zeros((M, B, S, H, P), dt.compute_dtype),
                     jnp.zeros((M, B, S, c.n_groups, c.ssm_state_size), dt.compute_dtype),
                     jnp.zeros((M, B, S, H), f32), jnp.zeros((M, B, S, H), f32))

        # the kinds the pattern holds are the branches of a trip; a layer's
        # index among its kind, and the Mamba-2 and the expert layer whose rows
        # and small leaves a trip reads in front of the branch (its own, or the
        # next one's where the trip is none: what it reads it writes back)
        kinds = c.layer_kinds
        present = sorted(set(kinds))
        own, mamba_of, experts_of, seen = [], [], [], [0, 0, 0]
        for k in kinds:
            own.append(seen[k])
            mamba_of.append(min(seen[0], max(M - 1, 0)))
            experts_of.append(min(seen[2], max(c.num_moe_layers - 1, 0)))
            seen[k] += 1
        table = tuple(jnp.asarray(t, jnp.int32) for t in (
            [present.index(k) for k in kinds], own, mamba_of, experts_of))
        mp, ap, ep, stacks = params["mamba"], params["attn"], params["moe"], params["experts"]
        block = lm.COUNTER_MODES.index(mode) * len(lm.COUNTER_FIELDS)  # this mode's fields of the counter block
        no_counts = jnp.zeros((len(lm.COUNTER_FIELDS),), jnp.int32)  # what a layer that is no expert layer adds

        def mamba(x, planes, rows, small, ki):
            history, s0, _ = rows

            leaves = small[0] if small else {name: _at(mp[name], ki) for name in _SMALL}

            def mixer(x, start, history, s0):
                return self._mamba(mp, leaves, ki, x, history, s0, start, fresh, keep)

            with phase_scope("attn"):
                run = lm.by_rows if by_rows else lambda fn, *a: fn(*a)
                out, history, s1, kept = run(mixer, x, start, history, s0)
            kept = tuple(new.astype(old.dtype) for new, old in zip(kept, rows[2]))
            return out, planes, (history.astype(rows[0].dtype), s1, kept), no_counts

        def attention(x, planes, rows, small, ki):
            with phase_scope("attn"):
                out, planes = self._attention(ap, ki, x, planes, kv_start, kv_len, wi, impl)
            return out, planes, rows, no_counts

        def experts(x, planes, rows, small, ki):
            bias = small[1] if small else _at(ep["router_bias"], ki)
            with phase_scope("mlp"):
                if by_rows:  # a row's counts are a row of the buffer ``by_rows`` writes
                    def one(x):
                        y, counts = self._experts(ep, bias, stacks, ki, x, impl)
                        return y, counts[None]

                    y, counts = lm.by_rows(one, x)
                    counts = jnp.sum(counts, axis=0)
                else:
                    y, counts = self._experts(ep, bias, stacks, ki, x, impl)
            return y, planes, rows, counts

        branches = [(mamba, attention, experts)[k] for k in present]

        def layer(carry, xs):
            """A trip: the layer's norm, its kind's branch, the residual. What
            stands OUTSIDE the branch is what ``benchmark/lib/phases.py`` counts
            a prefill's rows by (the median executions of the instructions
            whose path holds no branch): the norm, the add, the reads and
            writes of the state's rows, the counters' update and, in a fresh
            prompt's call, the slices of the trip's small leaves (a few KB;
            sliced in a branch, the compiler's relayouts of them carry a path
            that holds no branch either: ``models/hybrid_ssm.py``'s finding.
            Every other call slices them in the branch that reads them: a
            decode step has no tiny operation to spare)."""
            h, planes, (conv, states, steps), counters = carry
            scale, branch, ki, mi, ei = xs
            rows = (_at(conv, mi), _at(states, mi), tuple(_at(buf, mi) for buf in steps or ()))
            small = ()
            if fresh and M and c.num_moe_layers:
                small = jax.lax.optimization_barrier(
                    ({name: _at(mp[name], mi) for name in _SMALL}, _at(ep["router_bias"], ei)))
            with phase_scope("norm_rope"):
                x = rms_norm(h, scale, c.layer_norm_epsilon, dt)
            if len(branches) == 1:
                out, planes, rows, counts = branches[0](x, planes, rows, small, ki)
            else:
                out, planes, rows, counts = jax.lax.switch(branch, branches, x, planes, rows, small, ki)
            state = (_put(conv, mi, rows[0]), _put(states, mi, rows[1]),
                     tuple(_put(buf, mi, row) for buf, row in zip(steps, rows[2])) if steps else steps)
            counters = jax.lax.dynamic_update_slice(
                counters, jax.lax.dynamic_slice(counters, (block,), counts.shape) + counts, (block,))
            return (h + out.astype(h.dtype), planes, state, counters), None

        carry = (h, (cache.k, cache.v), (cache.conv, cache.state, steps), cache.counters + add)
        (h, (k_plane, v_plane), (conv, states, steps), counters), _ = jax.lax.scan(
            layer, carry, (params["norms"],) + table)

        with phase_scope("norm_rope"):
            h = rms_norm(h, params["final_norm"], c.layer_norm_epsilon, dt)
        with phase_scope("lm_head"):
            if logit_index is not None:
                idx = jnp.clip(jnp.asarray(logit_index, jnp.int32), 0, h.shape[1] - 1)
                if idx.ndim == 0:
                    h = jax.lax.dynamic_slice(h, (0, idx, 0), (B, 1, h.shape[2]))
                else:
                    h = jnp.take_along_axis(h, idx.reshape(B, 1, 1), axis=1)
            elif last_logit_only:
                h = h[:, -1:, :]
            logits = _mm(h, params["lm_head"], jnp.float32)
        return logits.astype(dt.logits_dtype), SSDCache(k_plane, v_plane, conv, states, counters, steps)


def init_ssd_moe_params(rng: jax.Array, config: SSDMoEConfig, dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = SSDMoEModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_ssd_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

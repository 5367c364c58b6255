"""The decoder-hybrid-decoder (``CrossDecoderConfig``).

The eighth decoder family, with the call signature of the others, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

**The layer** (``x`` the residual stream): ``x += mixer(LN(x))``, then ``x +=
SwiGLU(LN(x))``; ``LN`` a LayerNorm with mean, scale and bias. No layer has a
position term: ``positions`` is unused. Five mixers, by the layer's depth
(``CrossDecoderConfig.kind_of``), in two halves:

- the SELF-decoder, layers ``0 .. L/2 + 1``: (Mamba, window attention) pairs,
  then the Mamba layer whose scan output IN FRONT OF its gate is the memory
  ``m``, then the one full-attention layer, whose keys and values are the
  shared plane. These layers own every state the cache holds.
- the CROSS-decoder, layers ``L/2 + 2 .. L - 1``: (gated memory unit,
  cross-attention) pairs. A memory unit is ``W_out (silu(W_in h) * m_t)``, a
  cross layer projects queries only and attends to the shared plane. They
  write nothing, and at position ``t`` read only their own stream at ``t``,
  ``m_t`` and the plane up to ``t``.

*Mamba-1* is ``models/hybrid_ssm.py``'s state layer without the norms on the
time step, ``B`` and ``C`` (``ops/ssm.py``: the scan, its float32 state, the
``D`` skip and the gate; ``ungated`` hands back ``m``).

*Differential attention* (window, full and cross alike) pairs heads up: query
pair ``p`` = heads ``(2p, 2p + 1)``, key pair ``r = p // 2`` = heads ``(2r, 2r
+ 1)``; two softmaxes, ``q1 k1`` and ``q2 k2``, each over the value pair ``[v1
| v2]``, the second subtracted at ``lambda``, a 128-wide RMS norm behind. The
planes hold it as GROUPED-QUERY attention at twice the head width, which needs
no kernel of its own: a key pair is one key head ``[k1 | k2]`` (the
projection's output, reshaped), a value pair one value head, and a query head
is zero-padded on the other half (``[q1 | 0]``, ``[0 | q2]``), so its score is
its own half's and every plane is whole 128-lane tiles. 40 query heads over 10
pair heads go through ``models/llama.py attend`` (the flash kernel, its
windowed form, the decode walk, the chunk kernels) at scale ``hd^-1/2`` of the
PUBLISHED head (the queries carry the ``sqrt 2``). The subtraction, ``lambda``
and the norm are ``attn/diff``.

**The cache holds three kinds of state** (``HybridCache``, as the hybrid
state-space family's): K/V planes ``[L/4 + 1, B, pair heads, T, 128]`` for
the window layers and the full layer (every plane ``T`` long: a window layer
keeps no ring yet), and ``conv`` / ``ssm`` for the ``L/4 + 1`` state layers.
The ``L/2 - 2`` layers of the cross-decoder own nothing; the memory is never
cached (it lives within one forward pass). Left pads and ``keep_steps`` /
``commit`` follow ``models/hybrid_ssm.py``'s two rules to the letter.

**A fresh prompt's prefill stops half way.** Of a fresh prompt call only the
last position's logits leave (``last_logit_only``), and the cross-decoder at
that position reads nothing of its own at any other. So that call runs the
self-decoder over the prompt and the cross-decoder AT THE LAST POSITION ONLY:
its cross layers are single-query walks of the shared plane (the decode
kernel). Every other call (a verify step, a prompt chunk, the scorer, a
decode step) runs all layers on what it is fed. It changes no logit that
leaves.

Loops: a ``lax.scan`` over the (Mamba, window) pairs, the memory's layer and
the full layer written out behind it, a ``lax.scan`` over the (memory unit,
cross) pairs. The one-position pass of a fresh prompt is written out instead
(no loop): ``benchmark/lib/phases.py`` counts a prefill's rows by the median
executions of the operations one loop beneath ``prefill``, so a prefill holds
ONE loop, of ``L/4`` trips. Leaves are stacked by kind and read at the layer's
index inside the matmul that streams them (the written-out layers' indices
stand behind a barrier: sliced at a constant, a layer's weights are copied
first).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import CrossDecoderConfig, DTypePolicy
from rag_llm_k8s_tpu.models import hybrid_ssm as hs
from rag_llm_k8s_tpu.models.hybrid_ssm import HybridCache, _at, _mm, commit  # noqa: F401  (``commit``: the row's)
from rag_llm_k8s_tpu.models.llama import attend, resolve_attn_impl
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops import ssm as ssm_ops
from rag_llm_k8s_tpu.ops.attention import decode_slots_streamed, flash_window_pairs, gqa_decode_step

# HybridCache.counters. The hybrid state-space family's eight lead (``commit``
# counts at their places; ``decode_slots_*`` are the FULL layer's own walk, a
# step counted once). Then the windowed family's four, summed over the window
# layers: the slots their decode walks fetched and were allocated, and one
# query head's query-key pairs their prefill kernel's steps multiplied and
# the live ones. Then this family's: row-positions the cross-decoder ran on
# and row-positions of the call (a fresh multi-token call at a time: 1 of S
# where the prefill stops half way), and the slots of the shared plane a
# decode step fetched, summed over the layers that read it (the full layer
# and every cross layer).
COUNTER_NAMES = hs.COUNTER_NAMES + (
    "decode_slots_streamed_window", "decode_slots_allocated_window",
    "prefill_window_pairs_multiplied", "prefill_window_pairs_live",
    "cross_positions_computed", "cross_positions_fed", "shared_plane_slots_streamed")
N_COUNTERS = len(COUNTER_NAMES)
_AT = {name: i for i, name in enumerate(COUNTER_NAMES)}


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    return {name: int(n) for name, n in zip(COUNTER_NAMES, row)}


def make_cross_cache(config: CrossDecoderConfig, batch_size: int, max_seq_len: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> HybridCache:
    """``k``, ``v`` ``[window layers + 1, B, pair heads, T, 2 hd]`` (the full
    layer's plane last); ``conv`` and ``ssm`` as ``make_hybrid_cache``."""
    c = config
    kv = (c.num_plane_layers, batch_size, c.num_pair_heads, max_seq_len, c.pair_dim)
    M = c.num_state_layers
    return HybridCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
        conv=jnp.zeros((M, batch_size, c.mamba_d_conv - 1, c.d_inner), dtype),
        ssm=jnp.zeros((M, batch_size, c.mamba_d_state, c.d_inner), jnp.float32),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def layer_norm(x, g, b, eps: float, dtypes: DTypePolicy):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(dtypes.compute_dtype)


def lambda_init(layer):
    """The published start of a differential layer's ``lambda``, by its depth."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def pad_query_pairs(q):
    """``[..., H, hd] -> [..., H, 2 hd]``: an even head (the pair's ``q1``)
    keeps the first half, an odd head (``q2``) the second, zeros on the other:
    against a key head ``[k1 | k2]`` each scores its own half."""
    zero = jnp.zeros_like(q)
    even = (jnp.arange(q.shape[-2]) % 2 == 0)[:, None]
    return jnp.concatenate([jnp.where(even, q, zero), jnp.where(even, zero, q)], axis=-1)


def differential(o, lam, lam_init, g, eps: float, dtype):
    """``o [B, S, H, 2 hd]``, the softmaxes of a layer's padded query heads
    over the value pairs -> ``[B, S, H / 2 * 2 hd]``: a pair's second
    subtracted from its first at ``lam``, RMS-normed over the ``2 hd`` at
    scale ``g``, times ``1 - lam_init``; float32 inside."""
    B, S, H, W = o.shape
    pairs = o.astype(jnp.float32).reshape(B, S, H // 2, 2, W)
    d = pairs[..., 0, :] - lam * pairs[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(jnp.square(d), axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)
    return (d * (1.0 - lam_init)).astype(dtype).reshape(B, S, H // 2 * W)


_SMALL = ("conv_w", "conv_b", "dt_bias", "A_log", "D")
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


class CrossDecoderModel(nn.Module):
    config: CrossDecoderConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # S > 1 calls run over the cache as it is (a verify step, a prompt chunk, the scorer)
    keep_steps: bool = False  # leave every position's state for ``commit`` (the verify loop's calls)

    def _params(self):
        c, dt = self.config, self.dtypes
        D, F, L, Di, N = c.hidden_size, c.intermediate_size, c.num_layers, c.d_inner, c.mamba_d_state
        R, Kc, M = c.mamba_dt_rank, c.mamba_d_conv, c.num_state_layers
        Na, Nc = c.num_plane_layers, c.num_cross_layers
        hd, W = c.head_dim, c.pair_dim
        KV = c.num_pair_heads * W
        normal, ones, zeros = nn.initializers.normal(stddev=0.02), nn.initializers.ones, nn.initializers.zeros
        f32 = jnp.float32

        def p(name, shape, init=normal, dtype=dt.param_dtype):
            return self.param(name, init, shape, dtype)

        def diff(kind, n):  # what a differential-attention mixer has whatever it attends to
            leaves = {"wq": p(f"{kind}_wq", (n, D, D)), "bq": p(f"{kind}_bq", (n, D), zeros),
                      "wo": p(f"{kind}_wo", (n, D, D)), "bo": p(f"{kind}_bo", (n, D), zeros),
                      "subln": p(f"{kind}_subln", (n, W), ones)}
            leaves.update({name: p(f"{kind}_{name}", (n, hd), nn.initializers.normal(stddev=0.1), f32)
                           for name in _LAMBDAS})
            return leaves

        params = {
            "embedding": p("embedding", (c.vocab_size, D)),
            "final_norm": p("final_norm", (D,), ones), "final_norm_b": p("final_norm_b", (D,), zeros),
            # every layer: its two norms and its SwiGLU, stacked over the depth
            "layers": {
                "input_norm": p("layers_input_norm", (L, D), ones),
                "input_norm_b": p("layers_input_norm_b", (L, D), zeros),
                "ff_norm": p("layers_ff_norm", (L, D), ones), "ff_norm_b": p("layers_ff_norm_b", (L, D), zeros),
                "w_gate": p("layers_w_gate", (L, D, F)), "w_up": p("layers_w_up", (L, D, F)),
                "w_down": p("layers_w_down", (L, F, D)),
            },
            # the state layers' mixers (the memory's last), as hybrid_ssm's
            # without the inner norms
            "ssm": {
                "in_proj": p("ssm_in_proj", (M, D, 2 * Di)),
                "conv_w": p("ssm_conv_w", (M, Kc, Di)), "conv_b": p("ssm_conv_b", (M, Di), zeros),
                "x_proj": p("ssm_x_proj", (M, Di, R + 2 * N)),
                "dt_proj": p("ssm_dt_proj", (M, R, Di)), "dt_bias": p("ssm_dt_bias", (M, Di), zeros, f32),
                "A_log": p("ssm_A_log", (M, N, Di), zeros, f32), "D": p("ssm_D", (M, Di), ones, f32),
                "out_proj": p("ssm_out_proj", (M, Di, D)),
            },
            # the layers that own a plane: the window layers, the full one last
            "attn": dict(diff("attn", Na), wk=p("attn_wk", (Na, D, KV)), bk=p("attn_bk", (Na, KV), zeros),
                         wv=p("attn_wv", (Na, D, KV)), bv=p("attn_bv", (Na, KV), zeros)),
            "cross": diff("cross", Nc),
            "gmu": {"in_proj": p("gmu_in_proj", (Nc, D, Di)), "out_proj": p("gmu_out_proj", (Nc, Di, D))},
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = p("lm_head", (D, c.vocab_size))
        return params

    def _project(self, x, ap, ai, name, bias, scale: float = 1.0):
        """``(x W + b) * scale`` of the stacked leaf ``name`` at ``ai``, rounded once."""
        y = _mm(x, _at(ap[name], ai), jnp.float32) + _at(ap[bias], ai).astype(jnp.float32)
        return (y * scale if scale != 1.0 else y).astype(self.dtypes.compute_dtype)

    def _queries(self, x, ap, ai):
        c = self.config
        B, S, _ = x.shape
        q = self._project(x, ap, ai, "wq", "bq", math.sqrt(2.0))  # attend's scale is the pair's width's
        return pad_query_pairs(q.reshape(B, S, c.num_heads, c.head_dim))

    def _behind(self, o, ap, ai, i):
        """``attn/diff`` and the output projection of a differential mixer
        (leaves ``ap`` at ``ai``, layer ``i``) behind its softmaxes ``o``."""
        c = self.config
        with phase_scope("diff"):
            lam_init = lambda_init(i)
            l = {name: _at(ap[name], ai).astype(jnp.float32) for name in _LAMBDAS}
            lam = (jnp.exp(jnp.sum(l["lambda_q1"] * l["lambda_k1"]))
                   - jnp.exp(jnp.sum(l["lambda_q2"] * l["lambda_k2"])) + lam_init)
            o = differential(o, lam, lam_init, _at(ap["subln"], ai), c.layer_norm_eps, self.dtypes.compute_dtype)
        return self._project(o, ap, ai, "wo", "bo")

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
        layers, sp, ap, xp, gp = (params[k] for k in ("layers", "ssm", "attn", "cross", "gmu"))
        impl = resolve_attn_impl(self.attn_impl)
        B, S = tokens.shape
        Di, N, R, taps = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv - 1
        H, Kp, W = c.num_heads, c.num_pair_heads, c.pair_dim
        n_win, n_cross, shared = c.num_window_layers, c.num_cross_layers, c.num_window_layers
        wi = jnp.asarray(write_index, jnp.int32).reshape(())
        start = jnp.maximum(kv_start.astype(jnp.int32) - wi, 0)  # [B]: indices of this call in front of it are pads
        keep = self.keep_steps and S > 1
        mode = "decode" if S == 1 else "chunk" if self.chunked else "prefill"
        count_kernel_build(mode, "selective_scan_xla" if keep else ssm_ops.scan_form(S, impl))
        # a fresh prompt call of which the last position's logits leave runs
        # the cross-decoder at that position only
        fresh = mode == "prefill" and not self.keep_steps and last_logit_only and logit_index is None

        add = jnp.zeros_like(cache.counters)
        T = cache.k.shape[3]
        if S == 1:
            add = add.at[_AT["ssm_state_updates"]].set(B * c.num_state_layers)
            if impl != "xla":
                step = gqa_decode_step(T, Kp, H // Kp, W, cache.k.dtype)
                full = decode_slots_streamed(kv_start, kv_len, T, step)
                window = decode_slots_streamed(jnp.maximum(kv_start, kv_len - c.sliding_window), kv_len, T, step)
                for name, n in (("decode_slots_streamed", full), ("decode_slots_allocated", B * T),
                                ("shared_plane_slots_streamed", (1 + n_cross) * full),
                                ("decode_slots_streamed_window", n_win * window),
                                ("decode_slots_allocated_window", n_win * B * T)):
                    add = add.at[_AT[name]].set(n)
        else:
            add = add.at[_AT["ssm_positions_scanned"]].set(B * S)
            if not self.chunked:
                add = add.at[_AT["prefill_tokens_computed"]].set(B * S)
                add = add.at[_AT["prefill_tokens_bucketed"]].set(B * S)
                add = add.at[_AT["cross_positions_computed"]].set(B if fresh else B * S)
                add = add.at[_AT["cross_positions_fed"]].set(B * S)
                if impl != "xla":
                    pairs = flash_window_pairs(kv_start, kv_len, S, H // Kp, W, W, c.sliding_window,
                                               jnp.dtype(dt.compute_dtype).itemsize)
                    at = _AT["prefill_window_pairs_multiplied"]
                    add = add.at[at:at + 2].set((n_win * jnp.stack(pairs)).astype(add.dtype))
        counters = cache.counters + add

        with phase_scope("embed"):
            h = jnp.take(params["embedding"], tokens, axis=0).astype(dt.compute_dtype)

        state = (cache.k, cache.v, cache.conv, cache.ssm)
        if keep:
            M = c.num_state_layers
            state += (jnp.zeros((M, B, S) + cache.ssm.shape[2:], jnp.float32),
                      jnp.zeros((M, B, taps + S, Di), cache.conv.dtype))

        def put(stacked, index, value):
            return jax.lax.dynamic_update_index_in_dim(stacked, value.astype(stacked.dtype), index, 0)

        def norm(h, i, which):
            with phase_scope("norm_rope"):
                return layer_norm(h, _at(layers[which], i), _at(layers[which + "_b"], i), c.layer_norm_eps, dt)

        def ffn(h, i):
            x = norm(h, i, "ff_norm")
            with phase_scope("mlp"):
                y = nn.silu(_mm(x, _at(layers["w_gate"], i))) * _mm(x, _at(layers["w_up"], i))
                return h + _mm(y, _at(layers["w_down"], i))

        def mamba(h, state, i, mi, ungated=False):
            """Layer ``i``, state layer ``mi``: ``(h, state, m or None)``."""
            x = norm(h, i, "input_norm")
            with phase_scope("attn"):
                small = {name: _at(sp[name], mi) for name in _SMALL}
                xz = _mm(x, _at(sp["in_proj"], mi))
                live = (jnp.arange(S, dtype=jnp.int32)[None, :] >= start[:, None])[..., None]
                u, z = jnp.where(live, xz[..., :Di], 0), xz[..., Di:]
                with phase_scope("conv"):
                    u, run = ssm_ops.causal_conv(u, _at(state[2], mi), small["conv_w"], small["conv_b"])
                dbc = _mm(u, _at(sp["x_proj"], mi), jnp.float32)
                delta = _mm(dbc[..., :R].astype(dt.compute_dtype), _at(sp["dt_proj"], mi))
                args = (u, delta, z, -jnp.exp(small["A_log"]), dbc[..., R:R + N], dbc[..., R + N:],
                        small["D"], small["dt_bias"], _at(state[3], mi), start)
                with phase_scope("scan"):
                    if keep:
                        y, last, steps, *m = ssm_ops.selective_scan_xla(*args, keep_steps=True, ungated=ungated)
                    else:
                        y, last, *m = ssm_ops.selective_scan(*args, impl=impl, ungated=ungated)
                h = h + _mm(y, _at(sp["out_proj"], mi))
                history = jax.lax.slice_in_dim(run, S, S + taps, axis=1)
                new = state[:2] + (put(state[2], mi, history), put(state[3], mi, last))
                if keep:
                    new += (put(state[4], mi, steps), put(state[5], mi, run))
            return ffn(h, i), new, (m[0] if m else None)

        def self_attention(h, state, i, ai, window):
            """Layer ``i``, the owner of plane ``ai``: ``(h, state, its fresh k and v)``."""
            x = norm(h, i, "input_norm")
            with phase_scope("attn"):
                q = self._queries(x, ap, ai)
                k = self._project(x, ap, ai, "wk", "bk").reshape(B, S, Kp, W)
                v = self._project(x, ap, ai, "wv", "bv").reshape(B, S, Kp, W)
                at = (ai, 0, 0, wi, 0)
                k_plane = jax.lax.dynamic_update_slice(
                    state[0], k.transpose(0, 2, 1, 3).astype(state[0].dtype)[None], at)
                v_plane = jax.lax.dynamic_update_slice(
                    state[1], v.transpose(0, 2, 1, 3).astype(state[1].dtype)[None], at)
                with phase_scope("window" if window else "global"):
                    if S == 1:
                        o = attend(q, k_plane, v_plane, kv_start, kv_len, ai, mode="decode", impl=impl, window=window)
                    elif self.chunked:
                        o = attend(q, k_plane, v_plane, kv_start, kv_len, ai, mode="chunk", impl=impl,
                                   write_index=wi, window=window)
                    else:  # writes at slot 0: the fresh K/V are the populated prefix
                        o = attend(q, k, v, kv_start, kv_len, ai, mode="prefill", impl=impl, window=window)
                h = h + self._behind(o, ap, ai, i)
            return ffn(h, i), (k_plane, v_plane) + state[2:], (k, v)

        def memory_unit(h, i, gi, m):
            x = norm(h, i, "input_norm")
            with phase_scope("attn/gmu"):
                y = nn.silu(_mm(x, _at(gp["in_proj"], gi))) * m
                h = h + _mm(y, _at(gp["out_proj"], gi))
            return ffn(h, i)

        def cross_attention(h, i, ci, planes, fresh_kv):
            """Layer ``i``, cross layer ``ci``, on ``h [B, s, D]``: queries of
            its own over the shared plane (``s`` = 1 of a longer call: the
            fresh prompt's last position, a single-query walk)."""
            x = norm(h, i, "input_norm")
            with phase_scope("attn"):
                q = self._queries(x, xp, ci)
                with phase_scope("cross"):
                    if h.shape[1] == 1:
                        o = attend(q, *planes, kv_start, kv_len, shared, mode="decode", impl=impl)
                    elif self.chunked:
                        o = attend(q, *planes, kv_start, kv_len, shared, mode="chunk", impl=impl, write_index=wi)
                    else:
                        o = attend(q, *fresh_kv, kv_start, kv_len, shared, mode="prefill", impl=impl)
                h = h + self._behind(o, xp, ci, i)
            return ffn(h, i)

        # ---- the self-decoder: every position it is fed ----------------------
        def self_pair(carry, j):
            h, state = carry
            h, state, _ = mamba(h, state, 2 * j, j)
            h, state, _ = self_attention(h, state, 2 * j + 1, j, c.sliding_window)
            return (h, state), None

        (h, state), _ = jax.lax.scan(self_pair, (h, state), jnp.arange(n_win, dtype=jnp.int32))
        # the written-out layers' indices, behind a barrier (the module docstring)
        i_mem, i_shared, at_shared, first_cross, pair_at = jax.lax.optimization_barrier((
            jnp.int32(c.memory_layer), jnp.int32(c.shared_layer), jnp.int32(shared),
            jnp.int32(c.shared_layer + 1), jnp.arange(n_cross, dtype=jnp.int32)))
        h, state, m = mamba(h, state, i_mem, at_shared, ungated=True)
        h, state, fresh_kv = self_attention(h, state, i_shared, at_shared, None)

        # ---- the cross-decoder: one position of a fresh prompt, else all -----
        def cross_pair(h, m, j):
            i = first_cross + 2 * j
            h = memory_unit(h, i, j, m)
            return cross_attention(h, i + 1, j, state[:2], fresh_kv)

        with phase_scope("cross"):
            if fresh:
                h, m = h[:, -1:, :], m[:, -1:, :]
                for j in range(n_cross):
                    h = cross_pair(h, m, pair_at[j])
            else:
                h, _ = jax.lax.scan(lambda h, j: (cross_pair(h, m, j), None), h,
                                    jnp.arange(n_cross, dtype=jnp.int32))
        new_cache = HybridCache(*state[:4], counters, *state[4:])

        with phase_scope("norm_rope"):
            h = layer_norm(h, params["final_norm"], params["final_norm_b"], c.layer_norm_eps, dt)
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


def init_cross_decoder_params(rng: jax.Array, config: CrossDecoderConfig, dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = CrossDecoderModel(config, dtypes, attn_impl="xla")
    B, S = 1, 8
    cache = make_cross_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

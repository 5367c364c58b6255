"""The block-window, pooled-summary decoder (``BlockWindowConfig``).

The fourth decoder family, with the call signature of the other three, so the
engine's one-shot programs (bucketed prefill, the decode loop, prompt-lookup
verify, chunked prefill, the exact scorer) serve it:

``(tokens [B,S], positions [B,S], cache, kv_start [B], kv_len [B],
write_index)`` -> ``(logits [B,S,V] fp32, new_cache)``.

**The layer** (``x`` the residual stream, float32 throughout): a unit-offset
RMS norm (``x / rms(x) * (1 + g)``); 32 heads of q, k, v, RoPE by halves on q
and k; attention over the query's own window exactly and over one pooled key
and value for every chunk of an earlier window (``ops/block_window.py``);
``x += o W_o``; the norm again and a SwiGLU. After the last layer the norm
once more and ``num_pred_heads`` next-position heads in float32, head-major
(``lm_head [hidden, num_pred_heads * vocab]``); the served logits are head
0's (``all_heads=True`` returns every head's: tests, and what a drafter of
several positions would read).

**The cache is of two kinds of state, in one plane a layer.** ``KVCache.k``
and ``.v`` are ``[L, B, H, NS + W, hd]``: the upper ``W`` slots are a RING
of the window's exact keys (position ``t`` at slot ``NS + t % W``); the lower
``NS`` slots hold the SUMMARIES, stored downward from the seam (chunk ``c``
at slot ``NS - 1 - c``). A query at ``t`` then reads the summaries of every
earlier window and its own window's keys as ONE contiguous range, ``[NS -
(W // C) * (t // W), NS + t % W]``: the decode kernel's walk
(``ops/attention.py decode_attention``) takes the range as a row's window and
fetches nothing else, with no kernel of this family's own. Nothing is copied
or cleared when a window ends: the range's two ends move. No plane is as long
as the context.

Positions are a row's own (``slot - kv_start``: the engine left-pads), so
windows and chunks are by POSITION. Every call that writes the ring also
pools the chunks it touched (a chunk's summary is pooled again whenever one
of its positions is written, so the last write leaves it whole; it is not
visible before its window has closed). A prefill computes a row at a time
through ALL layers, shifted left so that index = position: what a prompt row
expands to (its K/V of a layer, its FFN) is live once, not a batch of them.
The shift puts a row's pads BEHIND its real positions, and attention is
causal, so a layer of a row runs over the blocks of ``LIVE_BLOCK`` positions
its real ones fill and no further: loops whose trip count is read from
``kv_start``, the window kernel told the same bound, no branch.

A chunk that is VERIFIED (speculative proposals, some of which are thrown
away) must not write the ring past its window's end: slot ``t % W`` of the
next window is slot ``t % W`` of this one, still to be read if the proposal
is rejected. The engine asks ``verify_span`` how many fed positions may live
and says so through ``kv_len``; positions at or past ``kv_len`` write nothing.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from rag_llm_k8s_tpu.core.config import BlockWindowConfig, DTypePolicy
from rag_llm_k8s_tpu.models.llama import KVCache, apply_rope, resolve_attn_impl, rope_cos_sin
from rag_llm_k8s_tpu.obs.tracing import count_kernel_build, phase_scope
from rag_llm_k8s_tpu.ops import block_window as bw
from rag_llm_k8s_tpu.ops.attention import (
    DECODE_ALIGN, _fit_block, decode_attention, decode_attention_xla, decode_block_plan, gqa_decode_step,
)

# KVCache.counters, a decode step through the kernel at a time (one layer's
# call: every layer of a step fetches the same): the ring and the summary
# slots the walk fetches over the rows, and the positions they stand for
# (ring + chunk_size x summaries). And, a call that writes the cache at a
# time: positions written that end a chunk and that end a window (a verify
# step counts what it writes, kept or not). And, a single-shot prefill call at
# a time: positions its rows' layers ran over (whole blocks of ``LIVE_BLOCK``)
# and rows x the bucket (models/llama.py's names); and, through the kernel, the
# softmax steps ONE head's live query blocks took and those blocks, summed over
# the rows and the layers (``ops/block_window.py window_summary_steps``: the
# rule the kernel's own bounds follow).
COUNTER_NAMES = ("decode_ring_slots_fetched", "decode_summary_slots_fetched",
                 "decode_slots_attended_positions", "chunks_closed", "windows_closed",
                 "prefill_tokens_computed", "prefill_tokens_bucketed",
                 "prefill_window_softmax_steps", "prefill_window_query_blocks")
N_COUNTERS = len(COUNTER_NAMES)
DECODE_KERNEL = "ring_summary_decode_attention"  # what a trace calls the decode walk here
# Positions a trip of a prompt row's loops takes (the window, where it is
# shorter): it tiles the window in whole query blocks of the prefill kernel
# and whole chunks; a row's layers run over ceil(real positions / this) of
# them. The kernel's query block, the finest such: on the chip a row of 17.5 k
# positions in the 20480 bucket took 409.8 ms at 512, 439.6 at 1024 and 423.4
# at 2048, a full row 468-488 at all three (PERF.md section 6, PR 42).
LIVE_BLOCK = 512


def live_block(config: BlockWindowConfig) -> int:
    """Positions a trip of a prompt row's loops takes: see ``LIVE_BLOCK``."""
    return _fit_block(config.window_size, LIVE_BLOCK)


def fold_counters(row) -> dict:
    """``{name: increment}`` of ``COUNTER_NAMES`` from one fetched counter row."""
    return {name: int(n) for name, n in zip(COUNTER_NAMES, row)}


def summary_slots(config: BlockWindowConfig, max_seq_len: int) -> int:
    """``NS``: summary slots a plane holds for a context of ``max_seq_len``
    positions, whole steps of the decode walk where the window is."""
    n = -(-max_seq_len // config.chunk_size)
    align = DECODE_ALIGN if config.window_size % DECODE_ALIGN == 0 else 8
    return -(-n // align) * align


def make_block_window_cache(config: BlockWindowConfig, batch_size: int, max_seq_len: int,
                            dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
    """Planes ``[L, B, H, NS + W, hd]`` (see the module's docstring) and this
    family's counters."""
    shape = (config.num_layers, batch_size, config.num_heads,
             summary_slots(config, max_seq_len) + config.window_size, config.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   counters=jnp.zeros((N_COUNTERS,), jnp.int32))


def ring_of(plane: jax.Array, config: BlockWindowConfig) -> jax.Array:
    """``[..., W, hd]``: the ring, slot ``t % W`` at index ``t % W``."""
    return plane[..., plane.shape[-2] - config.window_size:, :]


def summaries_of(plane: jax.Array, config: BlockWindowConfig) -> jax.Array:
    """``[..., NS, hd]``: the summaries, chunk ``c`` at index ``c``."""
    return plane[..., :plane.shape[-2] - config.window_size, :][..., ::-1, :]


def live_range(t: jax.Array, config: BlockWindowConfig, NS: int) -> Tuple[jax.Array, jax.Array]:
    """``[first, end)`` of the plane's slots a query at position ``t`` reads."""
    W = config.window_size
    return NS - config.chunks_per_window * (t // W), NS + t % W + 1


def verify_span(config: BlockWindowConfig, position: jax.Array, fed: int) -> jax.Array:
    """How many of ``fed`` consecutive positions from ``position`` on a verify
    step may write: those of ``position``'s window (at least one)."""
    W = config.window_size
    return jnp.minimum(fed, W - position % W).astype(jnp.int32)


def _write_run(plane, layer, row, first, vals, valid):
    """``vals [H, n, hd]`` into ``plane[layer, row, :, first : first + n]``
    where ``valid [n]``, in place. ``first`` may lie outside the plane (the
    run is clamped into it and the slots it does not name keep their values)."""
    H, n, hd = vals.shape
    start = jnp.clip(first, 0, plane.shape[3] - n).astype(jnp.int32)
    old = jax.lax.dynamic_slice(plane, (layer, row, 0, start, 0), (1, 1, H, n, hd))
    j = start + jnp.arange(n, dtype=jnp.int32) - first  # which of vals a slot takes
    at = jnp.clip(j, 0, n - 1)
    ok = (j >= 0) & (j < n) & jnp.take(valid, at)
    new = jnp.where(ok[None, :, None], jnp.take(vals, at, axis=1).astype(plane.dtype), old[0, 0])
    return jax.lax.dynamic_update_slice(plane, new[None, None], (layer, row, 0, start, 0))


def _norm(x, g, eps, dtype):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(dtype)


def _mm(x, w, out=jnp.float32):
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype), preferred_element_type=out)


def _block(x, at, n):
    """``x[:, at : at + n]``, ``at`` traced."""
    return jax.lax.dynamic_slice_in_dim(x, at, n, axis=1)


def _put_block(x, block, at):
    """``x`` with ``block`` at ``[:, at : at + block.shape[1]]``, in place and
    row-major (the layout a kernel takes: left to the compiler, a loop may
    carry ``x`` in the layout its block was computed in, and re-lay ALL of
    ``x`` behind the loop)."""
    x = jax.lax.dynamic_update_slice_in_dim(x, block.astype(x.dtype), at, axis=1)
    return with_layout_constraint(x, Layout(major_to_minor=tuple(range(x.ndim))))


class BlockWindowModel(nn.Module):
    config: BlockWindowConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "pallas" | "pallas_interpret" | "xla"
    chunked: bool = False  # S > 1 calls attend over the cache (a verify step, a prompt chunk, the scorer)
    all_heads: bool = False  # logits of every next-position head, not head 0's

    def _qkv(self, lp, x, positions):
        """``x [B, S, D]`` normed -> q, k, v ``[B, H, S, hd]``, q and k rotated."""
        c, dt = self.config, self.dtypes
        B, S, _ = x.shape
        H, hd = c.num_heads, c.head_dim
        inv = 1.0 / (c.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        cos, sin = rope_cos_sin(positions, inv)
        q, k, v = (_mm(x, lp[n], dt.compute_dtype).reshape(B, S, H, hd) for n in ("wq", "wk", "wv"))
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        return tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v))

    def _mlp(self, lp, h):
        c, dt = self.config, self.dtypes
        with phase_scope("norm_rope"):
            x = _norm(h, lp["post_attn_norm"], c.rms_norm_eps, dt.compute_dtype)
        with phase_scope("mlp"):
            y = nn.silu(_mm(x, lp["w_gate"], dt.compute_dtype)) * _mm(x, lp["w_up"], dt.compute_dtype)
            return h + _mm(y, lp["w_down"])

    def _through_layers(self, params, layer, h, planes):
        """``layer((h, k_plane, v_plane), (a layer's leaves, its index))`` over the stacked layers."""
        (h, *planes), _ = jax.lax.scan(
            layer, (h, *planes), (params["layers"], jnp.arange(self.config.num_layers, dtype=jnp.int32)))
        return h, tuple(planes)

    def _out(self, lp, h, o):
        """``o [B, H, S, hd]`` through the output projection onto the stream."""
        B, H, S, hd = o.shape
        return h + _mm(o.transpose(0, 2, 1, 3).reshape(B, S, H * hd), lp["wo"])

    # -- a bucket of prompt rows: index = position ----------------------------
    def _prefill_layer_row(self, lp, li, h, bufs, n, live, row, planes, impl):
        """One layer of one row: ``h [1, Sp, D]`` the row's stream (shifted
        left: index = position; ``n`` real positions, in the first ``live``
        blocks of ``LIVE_BLOCK``); ``bufs`` the row's head-major q, k, v ``[H,
        Sp, hd]``; writes the row's ring and summaries of plane ``li``;
        returns the stream, ``bufs`` and the planes. At and past ``live``
        blocks the stream and ``bufs`` keep what they held (finite: a layer
        before's, a row before's, zeros), the pooling pools that, and the
        window kernel's result holds ANYTHING: the loop behind it stops where
        the kernel does."""
        c, dt = self.config, self.dtypes
        W, C = c.window_size, c.chunk_size
        k_plane, v_plane = planes
        Sp = h.shape[1]
        NS = k_plane.shape[3] - W
        kept = min(Sp // C, NS)  # summaries the plane has room for
        first = (jnp.maximum(n - 1, 0) // W * W).astype(jnp.int32)  # the last window's first position
        size = live_block(c)

        def front(i, bufs):  # a block's q, k, v into the row's buffers
            at = i * size
            with phase_scope("norm_rope"):
                x = _norm(_block(h, at, size), lp["input_norm"], c.rms_norm_eps, dt.compute_dtype)
            with phase_scope("attn"):
                qkv = self._qkv(lp, x, at + jnp.arange(size, dtype=jnp.int32)[None])
                return tuple(_put_block(buf, a[0], at) for buf, a in zip(bufs, qkv))

        q, k, v = bufs = jax.lax.fori_loop(0, live, front, bufs)
        with phase_scope("attn"):
            with phase_scope("pool"):
                sk, sv = bw.pool_chunks(k, v, lp["mu"], lp["phi"], C, impl)
            with phase_scope("ring"):  # the kernel's one softmax holds the summaries' part too
                if impl == "xla":
                    o = bw.window_summary_attention_xla(q, k, v, sk, sv, window=W, chunk=C)
                else:
                    o = bw.window_summary_flash_attention(
                        q, k, v, sk, sv, live * size, window=W, chunk=C, interpret=impl == "pallas_interpret")
            new_k = jnp.concatenate([sk[:, :kept][:, ::-1], _block(k, first, W)], axis=1)
            new_v = jnp.concatenate([sv[:, :kept][:, ::-1], _block(v, first, W)], axis=1)
            at = (li, row, 0, NS - kept, 0)
            k_plane = jax.lax.dynamic_update_slice(k_plane, new_k.astype(k_plane.dtype)[None, None], at)
            v_plane = jax.lax.dynamic_update_slice(v_plane, new_v.astype(v_plane.dtype)[None, None], at)

        def back(i, h):  # a block's attention output onto the stream, and its FFN
            at = i * size
            with phase_scope("attn"):
                x = self._out(lp, _block(h, at, size), _block(o, at, size)[None])
            return _put_block(h, self._mlp(lp, x), at)

        return jax.lax.fori_loop(0, live, back, h), bufs, (k_plane, v_plane)

    def _prefill(self, params, tokens, kv_start, planes, impl, last_logit_only, logit_index):
        """The rows of a bucket, ONE AT A TIME through all the layers: what a
        row expands to (its stream, its q, k, v of a layer, its FFN) is live
        once, never a batch of them. The rows are unrolled here (each its own
        trip through the layers' loop, tied to the row before it through the
        planes it wrote), so an operation of the layers' loop is one loop deep
        in every row; a layer's matmuls, norms and rotation are a loop deeper,
        over the row's live blocks. Returns the final stream at the positions
        asked for, the planes and what to count."""
        c = self.config
        B, S = tokens.shape
        W = c.window_size
        Sp = -(-S // W) * W
        size = live_block(c)
        n_rows = S - kv_start  # [B] real lengths
        live = jnp.clip(-(-n_rows // size), 1, Sp // size).astype(jnp.int32)  # [B] blocks that hold them
        # shift every row left by its pad: index = position (the tail is
        # whatever wraps around; no layer computes it)
        toks = jax.vmap(lambda row, by: jnp.roll(row, by))(jnp.pad(tokens, ((0, 0), (0, Sp - S))), -kv_start)
        one = last_logit_only or logit_index is not None  # the stream at one slot a row, not at all of them
        if one:
            at = S - 1 if logit_index is None else jnp.clip(jnp.asarray(logit_index, jnp.int32), 0, S - 1)
            at = jnp.maximum(jnp.broadcast_to(at, (B,)) - kv_start, 0)  # slot -> position
        count_kernel_build(
            "prefill", "window_summary_attention_xla" if impl == "xla" else "window_summary_flash_attention")
        row = (c.num_heads, Sp, c.head_dim)
        count_kernel_build("prefill", "chunk_pool" if bw.pool_blocks(
            row, c.chunk_size, self.dtypes.compute_dtype, impl) else "pool_chunks")
        # one shape for both kernels' operands; a row's dead blocks keep the row before's
        bufs = (jnp.zeros(row, self.dtypes.compute_dtype),) * 3
        outs = []
        for b in range(B):
            if b:  # this row starts when the one before it is in the planes
                planes, toks = jax.lax.optimization_barrier((planes, toks))
            with phase_scope("embed"):
                h = jnp.take(params["embedding"], toks[b], axis=0).astype(jnp.float32)[None]

            def layer(carry, xs, b=b):
                h, k_plane, v_plane, *bufs = carry
                h, bufs, planes = self._prefill_layer_row(
                    xs[0], xs[1], h, tuple(bufs), n_rows[b], live[b], b, (k_plane, v_plane), impl)
                return (h, *planes, *bufs), None

            h, carried = self._through_layers(params, layer, h, (*planes, *bufs))
            planes, bufs = carried[:2], carried[2:]
            outs.append(_block(h, at[b], 1) if one else jnp.roll(h, kv_start[b], axis=1)[:, :S])
        counted = {"chunks_closed": jnp.sum(n_rows // c.chunk_size), "windows_closed": jnp.sum(n_rows // W),
                   "prefill_tokens_computed": jnp.sum(live) * size, "prefill_tokens_bucketed": B * S}
        if impl != "xla":
            steps, blocks = bw.window_summary_steps(
                live * size, Sp, W, c.chunk_size, c.head_dim, jnp.dtype(self.dtypes.compute_dtype).itemsize)
            counted.update(prefill_window_softmax_steps=c.num_layers * steps,
                           prefill_window_query_blocks=c.num_layers * blocks)
        return jnp.concatenate(outs, axis=0), planes, counted

    # -- one position a row over the planes -----------------------------------
    def _decode(self, params, tokens, positions, t, planes, impl):
        c, dt = self.config, self.dtypes
        W, C = c.window_size, c.chunk_size
        B = tokens.shape[0]
        NS = planes[0].shape[3] - W
        first, end = live_range(t, c, NS)
        chunk_at, summary_at = NS + t % W // C * C, NS - 1 - t // C  # [B]: a row's chunk in the ring, its summary
        count_kernel_build("decode", "chunk_pool_in_place" if bw.in_place_pool_serves(
            planes[0].shape, C, planes[0].dtype, impl) else "pool_chunks")
        with phase_scope("embed"):
            h = jnp.take(params["embedding"], tokens, axis=0).astype(jnp.float32)

        def layer(carry, xs):
            h, k_plane, v_plane = carry
            lp, li = xs
            with phase_scope("norm_rope"):
                x = _norm(h, lp["input_norm"], c.rms_norm_eps, dt.compute_dtype)
            with phase_scope("attn"):
                q, k, v = self._qkv(lp, x, positions)  # [B, H, 1, hd]
                for b in range(B):  # every row's own ring slot
                    at = (li, b, 0, NS + t[b] % W, 0)
                    k_plane = jax.lax.dynamic_update_slice(k_plane, k[b].astype(k_plane.dtype)[None, None], at)
                    v_plane = jax.lax.dynamic_update_slice(v_plane, v[b].astype(v_plane.dtype)[None, None], at)

                with phase_scope("pool"):  # the chunk each row's position is of, from the ring: one call
                    k_plane, v_plane = bw.pool_ring_chunks(
                        k_plane, v_plane, lp["mu"], lp["phi"], li, chunk_at, summary_at, C, impl)
                with phase_scope("ring"):  # one walk over the live summaries and the live ring
                    qd = q.transpose(0, 2, 1, 3)  # [B, 1, H, hd]
                    if impl == "xla":
                        o = decode_attention_xla(qd, k_plane, v_plane, first, end, li)
                    else:
                        o = decode_attention(qd, k_plane, v_plane, first, end, li,
                                             interpret=impl == "pallas_interpret", name=DECODE_KERNEL)
                h = self._out(lp, h, o.transpose(0, 2, 1, 3))
            return (self._mlp(lp, h), k_plane, v_plane), None

        h, planes = self._through_layers(params, layer, h, planes)
        return h, planes, {"chunks_closed": jnp.sum(t % C == C - 1), "windows_closed": jnp.sum(t % W == W - 1)}

    # -- a chunk of positions a row over the planes ---------------------------
    def _chunk(self, params, tokens, positions, t, live, planes, impl):
        c, dt = self.config, self.dtypes
        W, C = c.window_size, c.chunk_size
        B, n = tokens.shape
        NS = planes[0].shape[3] - W
        t0 = t[:, 0]
        c0 = jnp.maximum(t0, 0) // C  # the first chunk a row's positions touch
        nch = (n - 1) // C + 2  # chunks n consecutive positions can touch
        span = c0[:, None] * C + jnp.arange(nch * C, dtype=jnp.int32)[None]  # [B, nch * C] positions
        src = span - t0[:, None]  # which fresh position, where it is one
        fresh = (src >= 0) & (src < n) & jnp.take_along_axis(live, jnp.clip(src, 0, n - 1), axis=1)
        r0, lane, every = t0 % W, jnp.arange(n, dtype=jnp.int32), jnp.ones((nch,), bool)
        count_kernel_build("chunk", "ring_summary_chunk_attention_xla")
        touched = (B, c.num_heads, nch * C, c.head_dim)
        count_kernel_build("chunk", "chunk_pool" if bw.pool_blocks(touched, C, planes[0].dtype, impl) else "pool_chunks")
        with phase_scope("embed"):
            h = jnp.take(params["embedding"], tokens, axis=0).astype(jnp.float32)

        def layer(carry, xs):
            h, k_plane, v_plane = carry
            lp, li = xs
            with phase_scope("norm_rope"):
                x = _norm(h, lp["input_norm"], c.rms_norm_eps, dt.compute_dtype)
            with phase_scope("attn"):
                q, k, v = self._qkv(lp, x, positions)  # [B, H, n, hd]
                rows_k = jax.lax.dynamic_index_in_dim(k_plane, li, 0, keepdims=False)  # [B, H, P, hd]
                rows_v = jax.lax.dynamic_index_in_dim(v_plane, li, 0, keepdims=False)
                with phase_scope("pool"):
                    # the chunks this call touches, from the ring as it was and the fresh
                    # keys: a window's last chunk must be pooled before a query of the
                    # next window (in this same call) reads it
                    def merged(rows, new):
                        at = (NS + span % W)[:, None, :, None]
                        old = jnp.take_along_axis(rows, at, axis=2)  # [B, H, nch * C, hd]
                        put = jnp.take_along_axis(new, jnp.clip(src, 0, n - 1)[:, None, :, None], axis=2)
                        return jnp.where(fresh[:, None, :, None], put.astype(rows.dtype), old)

                    sk, sv = bw.pool_chunks(merged(rows_k, k), merged(rows_v, v), lp["mu"], lp["phi"], C, impl)
                    for b in range(B):
                        k_plane = _write_run(k_plane, li, b, NS - c0[b] - nch, sk[b, :, ::-1], every)
                        v_plane = _write_run(v_plane, li, b, NS - c0[b] - nch, sv[b, :, ::-1], every)
                with phase_scope("ring"):  # the summaries as just written, the ring as it still is
                    o = bw.ring_summary_chunk_attention_xla(
                        q, k, v, jax.lax.dynamic_index_in_dim(k_plane, li, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(v_plane, li, 0, keepdims=False),
                        t, live, window=W, chunk=C)
                for b in range(B):
                    # position t0 + j lands at ring slot (t0 + j) % W: from t0 % W on,
                    # and from slot 0 on behind the window's end (or behind a leading pad)
                    here = live[b] & (r0[b] + lane < W)
                    for first, valid in ((NS + r0[b], here), (NS + r0[b] - W, live[b] & ~here)):
                        k_plane = _write_run(k_plane, li, b, first, k[b], valid)
                        v_plane = _write_run(v_plane, li, b, first, v[b], valid)
                h = self._out(lp, h, o)
            return (self._mlp(lp, h), k_plane, v_plane), None

        h, planes = self._through_layers(params, layer, h, planes)
        return h, planes, {"chunks_closed": jnp.sum(live & (t % C == C - 1)),
                           "windows_closed": jnp.sum(live & (t % W == W - 1))}

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
        D, F, L, H, hd = c.hidden_size, c.intermediate_size, c.num_layers, c.num_heads, c.head_dim
        init = nn.initializers.normal(stddev=0.02)

        def stacked(name, shape, fn=init):
            return self.param(name, fn, (L,) + shape, dt.param_dtype)

        params = {
            "embedding": self.param("embedding", init, (c.vocab_size, D), dt.param_dtype),
            "final_norm": self.param("final_norm", nn.initializers.zeros, (D,), dt.param_dtype),
            "lm_head": self.param("lm_head", init, (D, c.num_pred_heads * c.vocab_size), dt.param_dtype),
            "layers": {
                "input_norm": stacked("layers_input_norm", (D,), nn.initializers.zeros),
                "post_attn_norm": stacked("layers_post_attn_norm", (D,), nn.initializers.zeros),
                "wq": stacked("layers_wq", (D, D)), "wk": stacked("layers_wk", (D, D)),
                "wv": stacked("layers_wv", (D, D)), "wo": stacked("layers_wo", (D, D)),
                "mu": stacked("layers_mu", (H, hd)), "phi": stacked("layers_phi", (H, hd)),
                "w_gate": stacked("layers_w_gate", (D, F)), "w_up": stacked("layers_w_up", (D, F)),
                "w_down": stacked("layers_w_down", (F, D)),
            },
        }
        impl = resolve_attn_impl(self.attn_impl)
        B, S = tokens.shape
        wi = jnp.asarray(write_index, jnp.int32).reshape(())
        planes = (cache.k, cache.v)
        NS = cache.k.shape[3] - c.window_size
        counters = cache.counters
        # a row's positions are its own: slot - kv_start (the engine left-pads)
        t = wi + jnp.arange(S, dtype=jnp.int32)[None] - kv_start[:, None]  # [B, S]

        if S == 1:
            h, planes, counted = self._decode(params, tokens, positions, t[:, 0], planes, impl)
            if impl != "xla":
                P = cache.k.shape[3]
                step = gqa_decode_step(P, H, 1, hd, cache.k.dtype)
                first, end = live_range(t[:, 0], c, NS)
                origin, steps = decode_block_plan(first, end, P, step)
                pooled = jnp.sum(NS - jnp.minimum(origin, NS))
                ring = jnp.sum(steps) * step - pooled
                counted.update(decode_ring_slots_fetched=ring, decode_summary_slots_fetched=pooled,
                               decode_slots_attended_positions=ring + c.chunk_size * pooled)
        elif self.chunked:
            live = (t >= 0) & (wi + jnp.arange(S, dtype=jnp.int32)[None] < kv_len[:, None])
            # a call over the ring takes at most window - chunk positions (what it
            # writes may not reach what it still reads): a longer one (a prompt
            # chunk as long as a bucket) goes in pieces, each through every layer
            room = c.window_size - c.chunk_size
            m = max(d for d in range(1, min(S, room) + 1) if S % d == 0)
            if m == S:
                h, planes, counted = self._chunk(params, tokens, positions, t, live, planes, impl)
            else:
                def piece(planes, xs):
                    h, planes, counted = self._chunk(params, *xs, planes, impl)
                    return planes, (h, counted)

                cut = lambda a: a.reshape(B, S // m, m).swapaxes(0, 1)  # noqa: E731
                planes, (h, counted) = jax.lax.scan(
                    piece, planes, tuple(cut(a) for a in (tokens, positions, t, live)))
                h, counted = h.swapaxes(0, 1).reshape(B, S, -1), {name: x.sum(0) for name, x in counted.items()}
        else:  # a single-shot prefill: the rows' prompts end at the bucket's end
            h, planes, counted = self._prefill(
                params, tokens, kv_start, planes, impl, last_logit_only, logit_index)
            last_logit_only, logit_index = False, None  # taken where the row was computed
        if counters is not None:
            counters = counters + jnp.stack(
                [jnp.asarray(counted.get(name, 0), counters.dtype) for name in COUNTER_NAMES])

        with phase_scope("norm_rope"):
            h = _norm(h, params["final_norm"], c.rms_norm_eps, jnp.float32)
        with phase_scope("lm_head"):
            if logit_index is not None:
                idx = jnp.clip(jnp.asarray(logit_index, jnp.int32), 0, h.shape[1] - 1)
                if idx.ndim == 0:
                    h = jax.lax.dynamic_slice(h, (0, idx, 0), (B, 1, h.shape[2]))
                else:
                    h = jnp.take_along_axis(h, idx.reshape(B, 1, 1), axis=1)
            elif last_logit_only:
                h = h[:, -1:, :]
            # float32 logits (fp32_logits); the heads a caller does not read
            # are not computed, except by a chunk call (the scorer's)
            head = params["lm_head"]
            if not (self.all_heads or self.chunked):
                head = head[:, :c.vocab_size]
            logits = jnp.einsum("bsd,dv->bsv", h, head.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            if not self.all_heads:
                logits = logits[..., :c.vocab_size]
        return logits.astype(dt.logits_dtype), KVCache(k=planes[0], v=planes[1], counters=counters)


def init_block_window_params(rng: jax.Array, config: BlockWindowConfig,
                             dtypes: DTypePolicy = DTypePolicy()):
    """Random-init parameter pytree (tests; a benchmark draws its own)."""
    model = BlockWindowModel(config, dtypes, attn_impl="xla")
    B, S = 1, config.window_size
    cache = make_block_window_cache(config, B, S, dtypes.compute_dtype)
    zeros = jnp.zeros((B, S), jnp.int32)
    variables = model.init(rng, zeros, zeros, cache, jnp.zeros((B,), jnp.int32),
                           jnp.full((B,), S, jnp.int32), jnp.int32(0))
    return variables["params"]

"""Block-window attention with pooled summaries (``models/block_window.py``).

A query at position ``t`` sees, under ONE softmax,

- the exact keys of its own WINDOW ``w(t) = t // W``, causally, and
- for every CHUNK of ``C`` positions in an EARLIER window one pooled key and
  one pooled value (``pool_chunks``): chunk ``c`` is visible iff
  ``c // (W // C) < w(t)``. A query never sees a summary of its own window.

Three forms, one rule:

- ``window_summary_flash_attention``: the prefill kernel, over one prompt
  row whose index IS its position (the model shifts a left-padded row to the
  left first). A query block walks the causal part of its window's K/V strip
  (resident: a window is ``W`` keys, whatever the prompt's length) and then
  the summary plane up to ``(W // C) * w``, under one running max and sum.
  ``window_summary_attention_xla`` is its dense oracle and CPU form.
- a decode step reads the cache's joined plane (``models/block_window.py``:
  summaries stored DOWNWARD from a seam, the ring upward, so that the live
  summaries and the live ring slots are one contiguous range) through
  ``ops/attention.py decode_attention`` and its walk, unchanged.
- ``ring_summary_chunk_attention_xla``: a chunk of fresh positions over the
  plane as it was BEFORE the chunk's writes plus the chunk's own keys (a
  verify step, a chunk of a chunked prefill, the exact scorer): a mask by
  the position every slot holds.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rag_llm_k8s_tpu.ops.attention import NEG_INF, _fit_block


def pool_chunks(k: jax.Array, v: jax.Array, mu: jax.Array, phi: jax.Array, chunk: int
                ) -> Tuple[jax.Array, jax.Array]:
    """One pooled key and value a chunk: ``k, v [..., H, n * chunk, hd]``
    (rotated keys) and ``mu, phi [H, hd]`` -> ``[..., H, n, hd]`` each, in
    ``k``'s type. ``k~ = sum_j softmax_j(k_j . mu) k_j``, ``v~ = sum_j
    softmax_j(k_j . phi) v_j``: both weightings read the KEYS, logits
    unscaled, float32 throughout."""
    *lead, H, S, hd = k.shape
    f32 = jnp.float32
    # elementwise products and small reductions (16 positions a chunk): the
    # float32 copies of k and v fuse into them and never exist in memory
    kc = k.reshape(*lead, H, S // chunk, chunk, hd).astype(f32)
    vc = v.reshape(*lead, H, S // chunk, chunk, hd).astype(f32)

    def weights(vec):  # [..., H, n, chunk]
        return jax.nn.softmax(jnp.sum(kc * vec.astype(f32)[:, None, None, :], axis=-1), axis=-1)

    sk = jnp.sum(weights(mu)[..., None] * kc, axis=-2)
    sv = jnp.sum(weights(phi)[..., None] * vc, axis=-2)
    return sk.astype(k.dtype), sv.astype(v.dtype)


def _softmax_av(s: jax.Array, ok: jax.Array, v: jax.Array) -> jax.Array:
    """``softmax(s where ok) v`` with float32 scores, rows with no live key -> 0."""
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("...qt,...td->...qd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def window_summary_attention_xla(q, k, v, sk, sv, *, window: int, chunk: int) -> jax.Array:
    """Dense form over rows whose index is their position: ``q, k, v [..., H,
    S, hd]``, ``sk, sv [..., H, >= S // chunk, hd]`` -> ``[..., H, S, hd]``."""
    S, hd = q.shape[-2:]
    i = jnp.arange(S)
    c = jnp.arange(sk.shape[-2])
    ok = jnp.concatenate([
        (i[None, :] <= i[:, None]) & (i[None, :] // window == i[:, None] // window),
        c[None, :] // (window // chunk) < i[:, None] // window,
    ], axis=1)
    keys = jnp.concatenate([k, sk], axis=-2)
    s = jnp.einsum("...qd,...td->...qt", q, keys, preferred_element_type=jnp.float32) * hd**-0.5
    return _softmax_av(s, ok, jnp.concatenate([v, sv], axis=-2)).astype(q.dtype)


def ring_summary_chunk_attention_xla(q, k_new, v_new, k_plane, v_plane, t, live, *,
                                     window: int, chunk: int) -> jax.Array:
    """A chunk of fresh positions over a row's plane AS IT WAS BEFORE the
    chunk's ring writes, and the chunk's own keys.

    ``q, k_new, v_new [B, H, n, hd]``; ``k_plane, v_plane [B, H, NS + W,
    hd]`` (summary ``c`` at slot ``NS - 1 - c``, position ``p`` at ``NS + p %
    W``); ``t [B, n]`` the positions (``t[:, 0]`` may be negative: a chunk
    that starts in a row's left pad); ``live [B, n]`` which of them exist.
    The summaries of every window earlier than a query's must already be in
    the plane (the model pools the chunks this call completes first). A ring
    slot ``s`` holds the newest position below ``t[:, 0]`` that is ``s``
    modulo ``W``; a query sees it iff it is of the query's window."""
    hd = q.shape[-1]
    W, per = window, window // chunk
    NS = k_plane.shape[2] - W
    t0 = t[:, :1]  # [B, 1]
    slot = jnp.arange(W)[None, :]
    held = (t0 - 1) - jnp.mod(t0 - 1 - slot, W)  # [B, W]: the position ring slot s holds
    wq = (t // W)[:, :, None]  # [B, n, 1]
    ok_ring = (held >= 0)[:, None, :] & ((held // W)[:, None, :] == wq)
    c = (NS - 1 - jnp.arange(NS))[None, None, :]  # the chunk summary slot i holds
    ok_sum = (c // per) < wq
    j = jnp.arange(t.shape[1])
    ok_new = (j[None, None, :] <= j[None, :, None]) & live[:, None, :] & ((t // W)[:, None, :] == wq)
    ok = jnp.concatenate([ok_sum, ok_ring, ok_new], axis=2)[:, None]  # [B, 1, n, NS + W + n]
    keys = jnp.concatenate([k_plane, k_new.astype(k_plane.dtype)], axis=2)
    vals = jnp.concatenate([v_plane, v_new.astype(v_plane.dtype)], axis=2)
    # dead slots may hold anything (a fresh cache is zeros, but a plane is
    # never cleared): zero what no query of this call may see
    seen = jnp.any(ok, axis=2)[..., None]  # [B, 1, T, 1]
    keys, vals = jnp.where(seen, keys, 0), jnp.where(seen, vals, 0)
    s = jnp.einsum("bhqd,bhtd->bhqt", q, keys, preferred_element_type=jnp.float32) * hd**-0.5
    return _softmax_av(s, ok, vals).astype(q.dtype)


def _window_summary_kernel(q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref, *, window: int, per: int,
                           bq: int, bs: int, scale: float):
    """One query block of one head: the causal part of its window's strip
    (``k_ref``/``v_ref [1, W, hd]``, key blocks of ``bq``: the last one is
    the diagonal), then the summaries ``[0, per * w)`` of ``sk_ref``/``sv_ref
    [1, NS, hd]`` in blocks of ``bs``, one running softmax."""
    qi = pl.program_id(1)
    w = qi * bq // window
    at = qi * bq - w * window  # the block's first query, within its window
    q = q_ref[0]
    hd = q.shape[-1]

    def fold(carry, k, v, ok):
        m, l, acc = carry
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l, acc

    def exact(j, carry):  # an interior key block of the window: no mask
        off = pl.multiple_of(j * bq, bq)
        return fold(carry, k_ref[0, pl.ds(off, bq), :], v_ref[0, pl.ds(off, bq), :], None)

    carry = (jnp.full((bq, 1), NEG_INF, jnp.float32), jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, hd), jnp.float32))
    carry = jax.lax.fori_loop(0, at // bq, exact, carry)
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
    off = pl.multiple_of(at, bq)
    carry = fold(carry, k_ref[0, pl.ds(off, bq), :], v_ref[0, pl.ds(off, bq), :], col <= row)

    n_sum = per * w  # live summaries: those of the windows before this one

    def pooled(j, carry):
        off = pl.multiple_of(j * bs, bs)
        ok = off + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) < n_sum
        # a summary past the live ones may be pooled from a row's junk tail:
        # zero it before any matmul (0 * inf)
        okc = off + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0) < n_sum
        return fold(carry, jnp.where(okc, sk_ref[0, pl.ds(off, bs), :], 0),
                    jnp.where(okc, sv_ref[0, pl.ds(off, bs), :], 0), ok)

    m, l, acc = jax.lax.fori_loop(0, (n_sum + bs - 1) // bs, pooled, carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)  # the diagonal always holds a live key


@functools.partial(jax.jit, static_argnames=("window", "chunk", "interpret"))
def window_summary_flash_attention(q, k, v, sk, sv, *, window: int, chunk: int,
                                   interpret: bool = False) -> jax.Array:
    """The prefill kernel over rows whose index is their position: ``q, k, v
    [N, S, hd]`` (``N`` = rows x heads), ``sk, sv [N, S // chunk, hd]`` (the
    rows' pooled chunks, ``pool_chunks``) -> ``[N, S, hd]``. ``S`` is padded
    to whole windows here; a query past a row's end computes on whatever the
    row holds there, and nobody reads it."""
    N, S, hd = q.shape
    W, per = window, window // chunk
    Sp = -(-S // W) * W
    bq = _fit_block(W, 512)
    bs = _fit_block(Sp // chunk, 512)
    if Sp != S:
        pad = lambda x, n: jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0)))  # noqa: E731
        q, k, v = pad(q, Sp), pad(k, Sp), pad(v, Sp)
        sk, sv = pad(sk[:, :S // chunk], Sp // chunk), pad(sv[:, :S // chunk], Sp // chunk)
    NS = sk.shape[1]

    def q_index(h, qi):
        return (h, qi, 0)

    def window_index(h, qi):
        return (h, qi * bq // W, 0)

    def summary_index(h, qi):
        return (h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_window_summary_kernel, window=W, per=per, bq=bq, bs=bs, scale=hd**-0.5),
        grid=(N, Sp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_index),
            pl.BlockSpec((1, W, hd), window_index),
            pl.BlockSpec((1, W, hd), window_index),
            pl.BlockSpec((1, NS, hd), summary_index),
            pl.BlockSpec((1, NS, hd), summary_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_index),
        out_shape=jax.ShapeDtypeStruct((N, Sp, hd), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="window_summary_flash_attention",
    )(q, k, v, sk, sv)
    return out[:, :S]

"""Attention over a latent cache (multi-head latent attention, MLA).

The cache of the latent-attention family (``models/latent_moe.py``) holds, a
token and a layer, one normed latent ``c_kv [C]`` and one rotated key slice
``k_rope [R]`` shared by every head. Two forms compute the same numbers:

- **expanded** (single-shot prefill): per-head ``k = [k_nope | k_rope]`` and
  ``v`` are rebuilt from the FRESH latents of the prompt itself, and attention
  is plain multi-head attention with a key width (``nope + rope``) that
  differs from the value width. ``mla_flash_attention`` is the flash
  recurrence of ``ops/attention.py`` (the same kernel body) over those
  shapes; ``mla_prefill_attention_xla`` is its dense oracle.
- **absorbed** (decode, verify, chunked prefill): ``W_UK`` is folded into the
  query (``q_lat = q_nope · W_UK``), scores are taken against the cache rows
  themselves (``q_lat · c_kv + q_rope · k_rope``), the weighted sum of
  ``c_kv`` comes back through ``W_UV`` outside. All 128 query heads share the
  one latent "KV head": no per-head K/V is ever rebuilt over the cache.
  ``mla_decode_attention`` streams the cache once a row for S = 1;
  ``latent_attention_xla`` is the dense form for any S (verify's S = 17, a
  prompt chunk, the oracle).

Masking is the serving engine's: a per-row window ``[kv_start, kv_len)`` of
valid cache slots, causal over slots on top.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rag_llm_k8s_tpu.ops.attention import (
    NEG_INF, _decode_step_or, _decode_walk, _flash_call, _softmax_fold, decode_step,
)

# a dense score plane is [B, H, S, T] fp32: queries beyond this many go
# through it a block at a time
_XLA_QUERY_BLOCK = 512


# ---------------------------------------------------------------------------
# expanded form: fresh per-head keys and values (single-shot prefill)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "bq", "bk", "interpret"))
def mla_flash_attention(
    q: jax.Array,  # [B, S, H, dq]   dq = nope + rope
    k: jax.Array,  # [B, S, H, dq]
    v: jax.Array,  # [B, S, H, dv]
    kv_start: jax.Array,  # [B] int32
    kv_len: jax.Array,  # [B] int32
    *,
    scale: float,
    bq: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal flash attention with a key width that differs from the value
    width, and a caller-given softmax scale (YaRN's correction rides in it).
    Returns ``[B, S, H, dv]``. The kernel is ``ops/attention.py``'s
    ``_flash_kernel`` with one head a group: it takes its widths from the
    blocks it is handed, and its default blocks from the same rule."""
    B, S, H, dq = q.shape
    dv = v.shape[-1]
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, dq)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, dq)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, dv)
    out = _flash_call(
        qt, kt, vt, kv_start, kv_len, scale=scale, causal=True,
        bq=bq, bk=bk, interpret=interpret, name="mla_flash_attention",
    )
    return out.reshape(B, H, S, dv).transpose(0, 2, 1, 3)


def mla_prefill_attention_xla(q, k, v, kv_start, kv_len, *, scale: float) -> jax.Array:
    """Dense oracle of ``mla_flash_attention`` (and the path off the TPU)."""
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)
    ok = (pos[None, None, :] >= kv_start[:, None, None]) & (pos[None, None, :] < kv_len[:, None, None])
    ok = ok & (pos[None, None, :] <= pos[None, :, None])  # [B, Sq, Sk]
    s = jnp.where(ok[:, None], s, NEG_INF)
    p = jnp.where(ok[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# absorbed form: scores against the cache rows themselves
# ---------------------------------------------------------------------------


def _latent_block_xla(q_lat, q_rope, c, r, kv_start, kv_len, q_pos, scale):
    """``q_lat [B, S, H, C]``, ``q_rope [B, S, H, R]`` against one layer's
    ``c [B, T, C]``, ``r [B, T, R]``; ``q_pos [S]`` are the queries' slots."""
    T = c.shape[1]
    s = jnp.einsum("bqhc,btc->bhqt", q_lat, c, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bqhr,btr->bhqt", q_rope, r, preferred_element_type=jnp.float32)
    s = s * scale
    t_pos = jnp.arange(T)
    ok = (t_pos[None, None, :] >= kv_start[:, None, None]) & (t_pos[None, None, :] < kv_len[:, None, None])
    ok = ok & (t_pos[None, None, :] <= q_pos[None, :, None])  # [B, S, T]
    s = jnp.where(ok[:, None], s, NEG_INF)
    p = jnp.where(ok[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    # slots past the frontier may hold anything: a zero weight times a NaN is a NaN
    c = jnp.where((t_pos[None, :] < kv_len[:, None])[..., None], c, 0)
    o = jnp.einsum("bhqt,btc->bhqc", p.astype(c.dtype), c, preferred_element_type=jnp.float32)
    return o.astype(q_lat.dtype).transpose(0, 2, 1, 3)


def latent_attention_xla(
    q_lat: jax.Array,  # [B, S, H, C]: q_nope with W_UK folded in
    q_rope: jax.Array,  # [B, S, H, R]: rotated
    c_cache: jax.Array,  # [L, B, T, C]
    r_cache: jax.Array,  # [L, B, T, R]
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] int32
    write_index: jax.Array,  # [] int32: the cache slot of query 0
    *,
    scale: float,
) -> jax.Array:
    """Dense absorbed attention: ``[B, S, H, C]``, the softmax-weighted sum
    of ``c_kv`` rows (the caller takes it through ``W_UV``). Query ``t`` sits
    at cache slot ``write_index + t``. More than ``_XLA_QUERY_BLOCK`` queries
    go a block at a time, so the score plane stays bounded."""
    S = q_lat.shape[1]
    lay = jnp.asarray(layer, jnp.int32).reshape(())
    c = jax.lax.dynamic_index_in_dim(c_cache, lay, 0, keepdims=False)
    r = jax.lax.dynamic_index_in_dim(r_cache, lay, 0, keepdims=False)
    q_pos = jnp.asarray(write_index, jnp.int32).reshape(()) + jnp.arange(S, dtype=jnp.int32)
    bq = _XLA_QUERY_BLOCK
    if S <= bq or S % bq:
        return _latent_block_xla(q_lat, q_rope, c, r, kv_start, kv_len, q_pos, scale)

    def blocks(x):  # [B, S, ...] -> [S / bq, B, bq, ...]
        return jnp.moveaxis(x.reshape(x.shape[0], S // bq, bq, *x.shape[2:]), 1, 0)

    out = jax.lax.map(
        lambda a: _latent_block_xla(a[0], a[1], c, r, kv_start, kv_len, a[2], scale),
        (blocks(q_lat), blocks(q_rope), q_pos.reshape(S // bq, bq)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(q_lat.shape)


def latent_decode_step(T: int, heads: int, rank: int, dtype):
    """``ops/attention.py decode_step`` on the latent cache's shapes: a slot
    is one latent of ``rank`` values (the rotated key slices ride whole a
    row), attended by ``heads`` query rows. The kernel's wrapper and the
    model's counters (``models/latent_moe.py``) ask this one function."""
    return decode_step(T, rank * jnp.dtype(dtype).itemsize, heads, rank)


def _mla_decode_kernel(
    layer_ref,  # SMEM [1]
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    ql_ref,  # [1, H, C]
    qr_ref,  # [1, H, R]
    c_hbm,  # [L, B, T, C], left in HBM
    r_ref,  # [1, 1, T, R]: the row's whole plane of rotated key slices
    o_ref,  # [1, H, C]
    c_buf,  # VMEM [2, step, C]
    sem,  # DMA [2, 1]
    turn_ref,  # SMEM [1]
    m_scr,  # VMEM [H, 1]
    l_scr,  # VMEM [H, 1]
    acc_scr,  # VMEM [H, C]
    *,
    T: int,
    step: int,
    scale: float,
):
    def copies(row, first, buf):
        src = c_hbm.at[layer_ref[0], row, pl.ds(first, step), :]
        return (pltpu.make_async_copy(src, c_buf.at[buf], sem.at[buf, 0]),)

    def consume(buf, first, lo, hi):
        c = c_buf[buf]  # [step, C]
        r = r_ref[0, 0, pl.ds(first, step), :]  # [step, R]
        # zero rows outside the live slots BEFORE any matmul (see _decode_kernel)
        rpos = first + jax.lax.broadcasted_iota(jnp.int32, (step, 1), 0)
        rok = (rpos >= lo) & (rpos < hi)
        c = jnp.where(rok, c, 0)
        r = jnp.where(rok, r, 0)
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(ql_ref[0], c, dims, preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], r, dims, preferred_element_type=jnp.float32)
        s = s * scale  # [H, step]
        k_pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _softmax_fold(
            s, (k_pos >= lo) & (k_pos < hi), m_scr, l_scr, acc_scr,
            lambda p: jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32),
        )

    o_ref[0] = _decode_walk(
        kv_start_ref, kv_len_ref, turn_ref, m_scr, l_scr, acc_scr,
        T=T, step=step, copies=copies, consume=consume,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def mla_decode_attention(
    q_lat: jax.Array,  # [B, 1, H, C]
    q_rope: jax.Array,  # [B, 1, H, R]
    c_cache: jax.Array,  # [L, B, T, C]
    r_cache: jax.Array,  # [L, B, T, R]
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
    *,
    scale: float,
    bk: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token absorbed attention over the latent cache: one grid cell a
    row, all H query heads against the row's one latent "KV head" as the rows
    of one matmul, so the cache streams once a row. The walk is
    ``ops/attention.py``'s (``decode_block_plan``, ``_decode_walk``): the
    latents stay in HBM, the layer and the row's window ride scalar prefetch
    into the copies' addresses, and only the steps the window
    ``[kv_start, kv_len)`` touches are fetched (no per-layer slice of the
    cache is materialized, no dead step of latents is read). The rotated key
    slices (a ninth of a slot's bytes) ride whole a row, a block of the
    pipeline's: a plane ``R`` = 64 wide is padded to the 128-lane tile in
    HBM, and Mosaic refuses to slice such a plane for a copy of the kernel's
    own (compiled for a v5e, PR 32). Returns ``[B, 1, H, C]``."""
    B, S, H, C = q_lat.shape
    assert S == 1, f"mla_decode_attention is single-token (got S={S})"
    R = q_rope.shape[-1]
    T = c_cache.shape[2]
    step = _decode_step_or(bk, latent_decode_step(T, H, C, c_cache.dtype), T, 16, interpret)

    def row_block(b, *s_):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, T=T, step=step, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, C), row_block),
                pl.BlockSpec((1, H, R), row_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, 1, T, R), lambda b, layer_ref, *s_: (layer_ref[0], b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, C), row_block),
            scratch_shapes=[
                pltpu.VMEM((2, step, C), c_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 1)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, C), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_lat.dtype),
        interpret=interpret,
        name="mla_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        kv_start.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        q_lat.reshape(B, H, C),
        q_rope.reshape(B, H, R),
        c_cache,
        r_cache,
    )
    return out.reshape(B, 1, H, C)

"""Block-window attention with pooled summaries (``models/block_window.py``).

A query at position ``t`` sees, under ONE softmax,

- the exact keys of its own WINDOW ``w(t) = t // W``, causally, and
- for every CHUNK of ``C`` positions in an EARLIER window one pooled key and
  one pooled value (``pool_chunks``): chunk ``c`` is visible iff
  ``c // (W // C) < w(t)``. A query never sees a summary of its own window.

Three forms, one rule:

- ``window_summary_flash_attention``: the prefill kernel, over one prompt
  row whose index IS its position (the model shifts a left-padded row to the
  left first). A query block takes the causal part of its window's K/V strip
  (resident: a window is ``W`` keys, whatever the prompt's length) and then
  the summary plane up to ``(W // C) * w``, under one softmax: each as one
  slice where the shape lets VMEM hold it, else in blocks under a running max
  and sum (``window_summary_plan``).
  ``window_summary_attention_xla`` is its dense oracle and CPU form.
- a decode step reads the cache's joined plane (``models/block_window.py``:
  summaries stored DOWNWARD from a seam, the ring upward, so that the live
  summaries and the live ring slots are one contiguous range) through
  ``ops/attention.py decode_attention`` and its walk, unchanged.
- ``ring_summary_chunk_attention_xla``: a chunk of fresh positions over the
  plane as it was BEFORE the chunk's writes plus the chunk's own keys (a
  verify step, a chunk of a chunked prefill, the exact scorer): a mask by
  the position every slot holds.

The pooling is one rule at two call shapes: ``pool_chunks`` (rows of keys ->
their summaries: a prompt row, a chunk call's touched chunks; the kernel
``chunk_pool`` where the shape tiles, else the jnp body, which is the oracle)
and ``pool_ring_chunks`` (a decode step: every row's current chunk, from the
ring into its summary's slot, by ONE call of ``chunk_pool_in_place``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rag_llm_k8s_tpu.ops.attention import _FLASH_VMEM, NEG_INF, _fit_block


POOL_PIECE = 16  # chunks ``chunk_pool`` pools at once: 256 positions of k and of v, 64 float32 registers
POOL_STEP = 2048  # positions of k and of v a grid step of ``chunk_pool`` holds (0.5 MB of bf16 each)


def _chunk_weights(kc: jax.Array, vec: jax.Array) -> jax.Array:
    """``softmax_j(k_j . vec)`` over a chunk's positions, logits unscaled:
    ``kc [..., chunk, hd]`` float32, ``vec [..., 1, hd]`` -> ``[..., chunk, 1]``."""
    s = jnp.sum(kc * vec.astype(jnp.float32), axis=-1, keepdims=True)
    e = jnp.exp(s - jnp.max(s, axis=-2, keepdims=True))
    return e / jnp.sum(e, axis=-2, keepdims=True)


def _whole_tiles(rows: int, hd: int, dtype, impl: str) -> bool:
    """Whether Mosaic can hold ``[rows, hd]`` of ``dtype`` as whole tiles (16
    rows of bf16, 8 of float32, 128 lanes); the interpreter takes any shape."""
    return impl == "pallas_interpret" or (rows % (32 // jnp.dtype(dtype).itemsize) == 0 and hd % 128 == 0)


def pool_blocks(shape, chunk: int, dtype, impl: str) -> Optional[Tuple[int, int]]:
    """``(positions a grid step, chunks a piece)`` by which ``chunk_pool`` takes
    keys of ``shape [..., S, hd]``, or None where the jnp body serves: under
    ``"xla"``, and, compiled, where a row's chunks are no whole number of
    pieces of whole tiles (a chunk is read, ``[piece, hd]`` stored)."""
    S, hd = shape[-2:]
    per = S // chunk
    piece = _fit_block(per, POOL_PIECE)
    if impl == "xla" or not (_whole_tiles(piece, hd, dtype, impl) and _whole_tiles(chunk, hd, dtype, impl)):
        return None
    return chunk * piece * _fit_block(per // piece, max(1, POOL_STEP // (chunk * piece))), piece


def pool_chunks(k: jax.Array, v: jax.Array, mu: jax.Array, phi: jax.Array, chunk: int,
                impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    """One pooled key and value a chunk: ``k, v [..., H, n * chunk, hd]``
    (rotated keys) and ``mu, phi [H, hd]`` -> ``[..., H, n, hd]`` each, in
    ``k``'s type. ``k~ = sum_j softmax_j(k_j . mu) k_j``, ``v~ = sum_j
    softmax_j(k_j . phi) v_j``: both weightings read the KEYS, logits
    unscaled, float32 throughout. Through the kernel ``chunk_pool`` where
    ``pool_blocks`` gives it blocks; else the jnp body below (the oracle, the
    CPU's form), whose float32 copies of ``k`` and ``v`` XLA WRITES OUT (``kc``
    has three consumers): fit for a call's few chunks, not for a prompt row."""
    *lead, H, S, hd = k.shape
    n = S // chunk
    blocks = pool_blocks(k.shape, chunk, k.dtype, impl)
    if blocks is not None:
        sk, sv = chunk_pool(k.reshape(-1, S, hd), v.reshape(-1, S, hd), mu, phi, chunk=chunk, blocks=blocks,
                            interpret=impl == "pallas_interpret")
        return sk.reshape(*lead, H, n, hd), sv.reshape(*lead, H, n, hd)
    f32 = jnp.float32
    kc = k.reshape(*lead, H, n, chunk, hd).astype(f32)
    vc = v.reshape(*lead, H, n, chunk, hd).astype(f32)
    mu, phi = mu[:, None, None, :], phi[:, None, None, :]
    sk = jnp.sum(_chunk_weights(kc, mu) * kc, axis=-2)
    sv = jnp.sum(_chunk_weights(kc, phi) * vc, axis=-2)
    return sk.astype(k.dtype), sv.astype(v.dtype)


def _chunk_pool_kernel(k_ref, v_ref, mu_ref, phi_ref, sk_ref, sv_ref, *, chunk: int, piece: int):
    """One grid step: ``k_ref, v_ref [1, bs, hd]`` of one row, ``mu_ref,
    phi_ref [1, 1, hd]`` its head's -> ``sk_ref, sv_ref [1, bs // chunk,
    hd]``, a piece of ``piece`` chunks at a time: float32 exists only of a
    piece, in registers and VMEM."""
    bs, hd = k_ref.shape[1:]
    n = piece * chunk

    def pooled(j, carry):
        at, to = pl.multiple_of(j * n, n), pl.multiple_of(j * piece, piece)
        kc = k_ref[0, pl.ds(at, n), :].astype(jnp.float32).reshape(piece, chunk, hd)
        vc = v_ref[0, pl.ds(at, n), :].astype(jnp.float32).reshape(piece, chunk, hd)
        sk_ref[0, pl.ds(to, piece), :] = jnp.sum(_chunk_weights(kc, mu_ref[...]) * kc, axis=-2).astype(sk_ref.dtype)
        sv_ref[0, pl.ds(to, piece), :] = jnp.sum(_chunk_weights(kc, phi_ref[...]) * vc, axis=-2).astype(sv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bs // n, pooled, None)


@functools.partial(jax.jit, static_argnames=("chunk", "blocks", "interpret"))
def chunk_pool(k, v, mu, phi, *, chunk: int, blocks: Tuple[int, int], interpret: bool = False):
    """The pooling kernel: ``k, v [N, S, hd]`` (``N`` = rows x heads,
    head-minor: the prefill kernel's own operands), ``mu, phi [H, hd]`` ->
    ``sk, sv [N, S // chunk, hd]`` in ``k``'s type. One pass over ``k`` and
    ``v`` as they are stored; ``blocks`` are ``pool_blocks``'s."""
    N, S, hd = k.shape
    H = mu.shape[0]
    bs, piece = blocks
    kv = pl.BlockSpec((1, bs, hd), lambda n, j: (n, j, 0))
    vec = pl.BlockSpec((1, 1, hd), lambda n, j: (n % H, 0, 0))
    out = pl.BlockSpec((1, bs // chunk, hd), lambda n, j: (n, j, 0))
    return pl.pallas_call(
        functools.partial(_chunk_pool_kernel, chunk=chunk, piece=piece),
        grid=(N, S // bs),
        in_specs=[kv, kv, vec, vec],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((N, S // chunk, hd), k.dtype), jax.ShapeDtypeStruct((N, S // chunk, hd), v.dtype)],
        compiler_params=None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="chunk_pool",
    )(k, v, mu[:, None], phi[:, None])


def _chunk_pool_in_place_kernel(layer_ref, src_ref, dst_ref, mu_ref, phi_ref, k_in, v_in, k_plane, v_plane,
                                kbuf, vbuf, skbuf, svbuf, sem, *, chunk: int):
    """Every row ``b`` of layer ``layer_ref[0]``'s planes ``[L, B, H, P, hd]``
    (left in HBM; ``k_in, v_in`` are the same buffers): the chunk at slots
    ``[src, src + chunk)`` pooled into slot ``dst``. A slot of bf16 shares a
    word with its neighbour, so what is written back is the aligned run of
    ``chunk`` slots around ``dst`` with the one row replaced. All the rows'
    reads are in flight before the first is pooled."""
    del k_in, v_in
    B = kbuf.shape[0]
    P = k_plane.shape[3]
    layer = layer_ref[0]
    planes = ((k_plane, kbuf, skbuf), (v_plane, vbuf, svbuf))

    def slots(b):  # the ring chunk's first slot, the run's, and the summary's place in the run
        dst = jnp.clip(dst_ref[b], 0, P - 1)
        ring = jnp.clip(src_ref[b], 0, P - chunk) // chunk * chunk
        return pl.multiple_of(ring, chunk), pl.multiple_of(dst // chunk * chunk, chunk), dst % chunk

    def reads(b):
        ring, run, _ = slots(b)
        return [pltpu.make_async_copy(plane.at[layer, b, :, pl.ds(at, chunk), :], buf.at[b], sem.at[2 * i + j, b])
                for i, (plane, *bufs) in enumerate(planes) for j, (at, buf) in enumerate(zip((ring, run), bufs))]

    def writes(b):
        run = slots(b)[1]
        return [pltpu.make_async_copy(runbuf.at[b], plane.at[layer, b, :, pl.ds(run, chunk), :], sem.at[4 + i, b])
                for i, (plane, _, runbuf) in enumerate(planes)]

    for b in range(B):
        for c in reads(b):
            c.start()
    for b in range(B):
        for c in reads(b):
            c.wait()
        kc, vc = kbuf[b].astype(jnp.float32), vbuf[b].astype(jnp.float32)  # [H, chunk, hd]
        sk = jnp.sum(_chunk_weights(kc, mu_ref[...]) * kc, axis=-2, keepdims=True)
        sv = jnp.sum(_chunk_weights(kc, phi_ref[...]) * vc, axis=-2, keepdims=True)
        row = jax.lax.broadcasted_iota(jnp.int32, kc.shape, 1) == slots(b)[2]
        skbuf[b] = jnp.where(row, sk, skbuf[b].astype(jnp.float32)).astype(skbuf.dtype)
        svbuf[b] = jnp.where(row, sv, svbuf[b].astype(jnp.float32)).astype(svbuf.dtype)
        for c in writes(b):
            c.start()
    for b in range(B):
        for c in writes(b):
            c.wait()


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunk_pool_in_place(k_plane, v_plane, mu, phi, layer, src, dst, *, chunk: int, interpret: bool = False):
    """The pooling kernel of a decode step: planes ``[L, B, H, P, hd]``, ``mu,
    phi [H, hd]``, ``src, dst [B]`` -> the planes with, in every row of
    ``layer``, slot ``dst`` holding the pooled chunk ``[src, src + chunk)``
    (``src`` and ``P`` multiples of ``chunk``). One call for all the rows, in
    place: the planes alias the outputs."""
    L, B, H, P, hd = k_plane.shape
    vec = pl.BlockSpec((H, 1, hd), lambda i, *_: (0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((B, H, chunk, hd), k_plane.dtype)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_chunk_pool_in_place_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,), in_specs=[vec, vec, hbm, hbm], out_specs=[hbm, hbm],
            scratch_shapes=[buf, buf, buf, buf, pltpu.SemaphoreType.DMA((6, B))]),
        out_shape=[jax.ShapeDtypeStruct(k_plane.shape, k_plane.dtype), jax.ShapeDtypeStruct(v_plane.shape, v_plane.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        name="chunk_pool_in_place",
    )(i32(layer).reshape(1), i32(src), i32(dst), mu[:, None], phi[:, None], k_plane, v_plane)


def in_place_pool_serves(shape, chunk: int, dtype, impl: str) -> bool:
    """Whether ``chunk_pool_in_place`` takes planes of ``shape [L, B, H, P,
    hd]``: not under ``"xla"``; runs of ``chunk`` slots must tile the plane
    and, compiled, be whole tiles; the rows' four buffers must fit VMEM."""
    L, B, H, P, hd = shape
    return (impl != "xla" and P % chunk == 0 and _whole_tiles(chunk, hd, dtype, impl)
            and 4 * B * H * chunk * hd * jnp.dtype(dtype).itemsize <= 8 << 20)


def pool_ring_chunks(k_plane, v_plane, mu, phi, layer, src, dst, chunk: int, impl: str = "xla"):
    """A decode step's pooling: in every row ``b`` of ``layer``'s planes ``[L,
    B, H, P, hd]``, the chunk at slots ``[src[b], src[b] + chunk)`` (the ring's,
    with the position just written) pooled into slot ``dst[b]`` (its summary's).
    One kernel call for all the rows where ``in_place_pool_serves``; else the
    rows' chunks gathered, pooled once by the jnp body and written a row at a time."""
    if in_place_pool_serves(k_plane.shape, chunk, k_plane.dtype, impl):
        return chunk_pool_in_place(k_plane, v_plane, mu, phi, layer, src, dst, chunk=chunk,
                                   interpret=impl == "pallas_interpret")
    L, B, H, P, hd = k_plane.shape
    rows = range(B)
    size = (1, 1, H, chunk, hd)
    sk, sv = pool_chunks(
        jnp.concatenate([jax.lax.dynamic_slice(k_plane, (layer, b, 0, src[b], 0), size)[0] for b in rows]),
        jnp.concatenate([jax.lax.dynamic_slice(v_plane, (layer, b, 0, src[b], 0), size)[0] for b in rows]),
        mu, phi, chunk)
    for b in rows:
        k_plane = jax.lax.dynamic_update_slice(k_plane, sk[b][None, None], (layer, b, 0, dst[b], 0))
        v_plane = jax.lax.dynamic_update_slice(v_plane, sv[b][None, None], (layer, b, 0, dst[b], 0))
    return k_plane, v_plane


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


WINDOW_PIECE = 512  # summaries a piece (the query block's rows: a piece is a key block's size)
# Key blocks of straight-line code the sliced form's bodies may hold together:
# the cell's 34 (43 k bundles) run at the schedule's speed on a v5e, 64 (77 k:
# window and summaries in ONE step, sixteen bodies) nine times slower, the
# program no longer resident (PERF.md section 6, PR 54); nothing between was run
WINDOW_CODE_BLOCKS = 40


def window_summary_plan(S: int, window: int, chunk: int, hd: int, itemsize: int = 2) -> Tuple[int, int, bool]:
    """``(bq, bs, sliced)`` of the prefill kernel over rows of ``S`` positions,
    from the shape alone (no option, no model's name): ``bq`` queries a grid
    step, ``bs`` summaries a piece (the operands are padded to whole pieces),
    and whether a query block takes its keys as SLICES: the window's part, the
    ``at + bq`` keys in front of its last query, as one slice of the resident
    strip in ONE softmax step (the mask on its last ``bq`` columns only,
    nothing carried between the window's key blocks), and its summaries as one
    slice of whole pieces in a second. Else it walks key blocks of ``bq`` and
    pieces of ``bs`` under a running softmax.

    A step's cost is its keys' and, a ROW, its bookkeeping (the row max, the
    correction's ``exp``, sum and accumulator re-scaled; a walk carries the
    three through every trip of its loops): ``ops/attention.py flash_blocks``;
    PERF.md section 6, PRs 30, 47, 54. So the fewest steps the scoped VMEM
    holds win. Slices are taken where the operands' blocks (q, o, the window's
    strips, the summaries; twice: the pipeline's buffers) and the wider step's
    scores fit ``_flash_fits``'s budget at its prices: 1.25 KB a row, 12 bytes
    a key under a mask, 7.25 of an unmasked one; and where the bodies (one a
    value of ``at``, in each one a count of pieces) stay under
    ``WINDOW_CODE_BLOCKS``. A window of 2048 at 128 lanes fits (13.7 of 15.5
    MiB beside 1536 summaries, 34 blocks of code); a window of 4096, 2048
    summaries, heads of 256 or float32 operands keep the walk."""
    W = window
    NS = -(-S // W) * W // chunk
    bq = _fit_block(W, 512)
    bs = min(WINDOW_PIECE, NS)
    NS = -(-NS // bs) * bs
    lanes = -(-hd // 128) * 128
    blocks = 2 * 2 * itemsize * lanes * (bq + W + NS)
    masked = max(bq, bs)  # the diagonal's key block, the summaries' last piece
    step = bq * (1280 + 12 * masked + 7.25 * (max(W, NS) - masked))
    nb, pieces = W // bq, NS // bs
    code = nb * (nb + 1) // 2 + nb * pieces * (pieces + 1) // 2
    return bq, bs, blocks + step <= _FLASH_VMEM and code <= WINDOW_CODE_BLOCKS


def window_summary_block_plan(qi, bq: int, bs: int, window: int, per: int):
    """What query block ``qi`` of a row sees: ``(at, pieces, n_sum)``. Its first
    query is at ``at`` within its window ``w = qi * bq // window``, so the
    window's part is keys ``[0, at + bq)`` of the strip, the last ``bq`` under
    the diagonal. Its summaries are the first ``n_sum = per * w`` (the windows
    before its own), in ``pieces`` pieces of ``bs``, of which only the last may
    hold dead ones. Integers only: the kernel's scalars, the model's counters
    and the tests read this one rule."""
    w = qi * bq // window
    n_sum = per * w
    return qi * bq - w * window, (n_sum + bs - 1) // bs, n_sum


def window_summary_steps(live, S: int, window: int, chunk: int, hd: int, itemsize: int = 2):
    """``(steps, blocks)``: softmax steps ONE head's call of the prefill kernel
    takes over rows whose first ``live`` positions (``[rows]``) are computed,
    and the query blocks that take them; in the form the shape takes
    (``window_summary_plan``): a live block's window in one step and its
    summaries, where it has any, in another; or ``at // bq + 1`` key blocks and
    a step a piece."""
    bq, bs, sliced = window_summary_plan(S, window, chunk, hd, itemsize)
    Sp = -(-S // window) * window
    qi = jnp.arange(Sp // bq, dtype=jnp.int32)[None, :]
    at, pieces, _ = window_summary_block_plan(qi, bq, bs, window, window // chunk)
    steps = 1 + jnp.minimum(pieces, 1) if sliced else at // bq + 1 + pieces
    alive = qi * bq < jnp.clip(jnp.asarray(live, jnp.int32).reshape(-1, 1), 1, Sp)
    return jnp.sum(jnp.where(alive, steps, 0)), jnp.sum(alive)


def _window_summary_kernel(live_ref, q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref, *, window: int, per: int,
                           bq: int, bs: int, sliced: bool, scale: float):
    """One query block of one head, one softmax (``window_summary_block_plan``):
    the keys ``[0, at + bq)`` of its window's strip (``k_ref``/``v_ref [1, W,
    hd]``), the last ``bq`` under the diagonal, then the summaries ``[0,
    n_sum)`` of ``sk_ref``/``sv_ref [1, NS, hd]``. ``sliced``: the window's part
    is ONE step and the summaries' another, each a static slice (a body for
    each of the ``W // bq`` values of ``at`` and for each count of pieces), the
    mask on the diagonal's key block and the last piece only; else key blocks
    of ``bq`` and pieces of ``bs``, every piece under the mask, through loops
    that carry the running max, sum and accumulator. A block whose first query
    is at or past ``live_ref[0]`` does nothing."""
    qi = pl.program_id(1)
    at, pieces, n_sum = window_summary_block_plan(qi, bq, bs, window, per)

    def scores(q, k):
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale

    def fold(state, parts):
        """``parts`` (scores and their values, masked scores at ``NEG_INF``)
        into the running ``(max, sum, accumulator)``; ``None``: nothing came
        before, so nothing is re-scaled. Every row has seen a live key by
        then (its own, on the diagonal), so its max is a score's and a
        masked entry's ``exp`` an exact zero: no select on the probabilities."""
        m = functools.reduce(jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s, _ in parts])
        l = acc = None
        if state is not None:
            m_old, l, acc = state
            m = jnp.maximum(m_old, m)
            alpha = jnp.exp(m_old - m)
            l, acc = l * alpha, acc * alpha
        for s, v in parts:
            p = jnp.exp(s - m)
            row = jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            l, acc = (row, pv) if l is None else (l + row, acc + pv)
        return m, l, acc

    def run(k_ref, v_ref, q, off, n):  # keys (or summaries) ``[off, off + n)``, all live: no mask
        return scores(q, k_ref[0, pl.ds(off, n), :]), v_ref[0, pl.ds(off, n), :]

    def diagonal(q, off):  # the key block the queries themselves are in
        row = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
        s, v = run(k_ref, v_ref, q, off, bq)
        return jnp.where(col <= row, s, NEG_INF), v

    def ragged(q, off):  # a piece the live summaries may end in
        # a summary past the live ones may be pooled from a row's junk tail:
        # zero it before any matmul (0 * inf)
        okc = off + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0) < n_sum
        ok = off + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) < n_sum
        k = jnp.where(okc, sk_ref[0, pl.ds(off, bs), :], 0)
        return jnp.where(ok, scores(q, k), NEG_INF), jnp.where(okc, sv_ref[0, pl.ds(off, bs), :], 0)

    def emit(state):
        _, l, acc = state
        o_ref[0] = (acc / l).astype(o_ref.dtype)

    @pl.when(qi * bq < live_ref[0])
    def _():
        q = q_ref[0]
        if not sliced:  # the diagonal first: the state is never empty
            state = fold(None, [diagonal(q, pl.multiple_of(at, bq))])
            state = jax.lax.fori_loop(
                0, at // bq, lambda j, state: fold(state, [run(k_ref, v_ref, q, pl.multiple_of(j * bq, bq), bq)]), state)
            emit(jax.lax.fori_loop(
                0, pieces, lambda j, state: fold(state, [ragged(q, pl.multiple_of(j * bs, bs))]), state))
            return
        for j in range(window // bq):
            @pl.when(at == j * bq)
            def _(j=j):
                state = fold(None, ([run(k_ref, v_ref, q, 0, j * bq)] if j else []) + [diagonal(q, j * bq)])
                pl.when(pieces == 0)(lambda: emit(state))
                for n in range(1, sk_ref.shape[1] // bs + 1):
                    @pl.when(pieces == n)
                    def _(n=n):
                        head = [run(sk_ref, sv_ref, q, 0, (n - 1) * bs)] if n > 1 else []
                        emit(fold(state, head + [ragged(q, (n - 1) * bs)]))


@functools.partial(jax.jit, static_argnames=("window", "chunk", "interpret"))
def window_summary_flash_attention(q, k, v, sk, sv, live=None, *, window: int, chunk: int,
                                   interpret: bool = False) -> jax.Array:
    """The prefill kernel over rows whose index is their position: ``q, k, v
    [N, S, hd]`` (``N`` = rows x heads), ``sk, sv [N, S // chunk, hd]`` (the
    rows' pooled chunks, ``pool_chunks``) -> ``[N, S, hd]``. ``S`` is padded
    to whole windows here and the summaries to whole pieces
    (``window_summary_plan``, which also says in how many steps a query block
    takes its window); a query past a row's end computes on whatever the
    row holds there, and nobody reads it. ``live`` (traced; None: all): only
    the query blocks that start under it are computed, and the result past
    them is UNWRITTEN (anything, NaN included): what a caller that knows its
    rows end by then need not pay for."""
    N, S, hd = q.shape
    W, per = window, window // chunk
    Sp = -(-S // W) * W
    bq, bs, sliced = window_summary_plan(S, W, chunk, hd, q.dtype.itemsize)
    NS = -(-(Sp // chunk) // bs) * bs
    pad = lambda x, n: x if n == x.shape[1] else jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0)))  # noqa: E731
    q, k, v = pad(q, Sp), pad(k, Sp), pad(v, Sp)
    sk, sv = pad(sk[:, :S // chunk], NS), pad(sv[:, :S // chunk], NS)

    def live_block(qi, live):
        # a dead query block names the last live one, so its step fetches and
        # writes nothing (``ops/attention.py``'s dead blocks)
        return jnp.minimum(qi, (live[0] - 1) // bq)

    def q_index(h, qi, live):
        return (h, live_block(qi, live), 0)

    def window_index(h, qi, live):
        return (h, live_block(qi, live) * bq // W, 0)

    def summary_index(h, qi, live):
        return (h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_window_summary_kernel, window=W, per=per, bq=bq, bs=bs, sliced=sliced, scale=hd**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, Sp // bq),
            in_specs=[
                pl.BlockSpec((1, bq, hd), q_index),
                pl.BlockSpec((1, W, hd), window_index),
                pl.BlockSpec((1, W, hd), window_index),
                pl.BlockSpec((1, NS, hd), summary_index),
                pl.BlockSpec((1, NS, hd), summary_index),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), q_index)),
        out_shape=jax.ShapeDtypeStruct((N, Sp, hd), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="window_summary_flash_attention",
    )(jnp.clip(jnp.asarray(Sp if live is None else live, jnp.int32), 1, Sp).reshape(1), q, k, v, sk, sv)
    return out[:, :S]

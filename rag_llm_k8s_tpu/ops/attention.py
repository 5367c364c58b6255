"""Flash attention (prefill) as a Pallas TPU kernel.

The torch path this replaces materializes full [S, S] attention matrices on
CPU inside ``model.generate`` (/root/reference/llm/rag.py:172). Here the
prefill attention runs blockwise: a KV head's K/V strip stays in VMEM (where
it fits; its blocks are streamed where not) while the query blocks of its G
query heads pass over it, each with a running (max, sum, accumulator)
softmax — the flash-attention recurrence, written for the MXU/VPU split
(matmuls on the MXU via ``jax.lax.dot_general`` with fp32 accumulation,
renormalization on the VPU).

Masking model matches the serving engine's left-padded batches: causal over
global positions plus a per-row valid window ``[kv_start, kv_len)`` delivered
through scalar prefetch (SMEM) — no [S, S] bias array ever exists. The work
follows the live causal triangle of each row (``flash_block_plan``): a query
block visits the key blocks that hold a live pair and no others, and only
the blocks a mask can touch pay for one.

GQA is a fold: the G query heads of a KV head are G·bq rows of ONE matmul
against that head's K/V block; K/V are read once a KV head and never
repeated in memory.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NO_KEY = 2**31 - 1  # the position of a key no query may see


def _fit_block(n: int, pref: int) -> int:
    """Largest block ≤ ``pref`` that tiles ``n`` exactly (halves until it
    divides; terminates at 1)."""
    b = min(pref, n)
    while n % b:
        b //= 2
    return b


def flash_block_plan(qi, kv_start, kv_len, S: int, bq: int, bk: int, causal: bool,
                     window: Optional[int] = None):
    """Which key blocks query block ``qi`` of a row visits, and which of them
    no mask can touch: ``(lo, hi, int_lo, int_hi)``, every bound inclusive.

    The live pairs of a row are ``kv_start <= k < kv_len`` and, under
    ``causal``, ``k <= q``; under ``window`` (causal only) also ``k > q -
    window``. Key blocks ``lo..hi`` are exactly those that hold
    a live pair with a query of the block (none when ``hi < lo``: a query
    block wholly in the left pad, or an empty window). Of those, blocks
    ``int_lo..int_hi`` are INTERIOR: wholly inside the window and wholly
    under the block's diagonal (and wholly behind its window edge), so every
    pair in them is live; the others (on the diagonal, on the window's edge,
    or straddling ``kv_start`` or ``kv_len``) are EDGE blocks (a window that
    ONE step holds walks none: ``flash_window_step``). Integers only, so it serves Python ints, numpy arrays
    and the kernel's traced scalars alike: the kernel's loop bounds and the
    tests read this one rule. ``S`` is the key length (bounds stay inside it).
    ``window=None`` is the plan without one, bit for bit."""
    nk = S // bk
    q_lo, q_hi = qi * bq, qi * bq + bq - 1  # the block's first and last query
    if window is None:
        lo = jnp.maximum(kv_start // bk, 0)
        int_lo = (kv_start + bk - 1) // bk
    else:
        assert causal, "a window bound is causal"
        # the oldest key the block's FIRST query sees; every key of an
        # interior block is seen by its LAST query too
        lo = jnp.maximum(jnp.maximum(kv_start, q_lo - window + 1) // bk, 0)
        int_lo = (jnp.maximum(kv_start, q_hi - window + 1) + bk - 1) // bk
    hi = jnp.minimum((kv_len - 1) // bk, nk - 1)
    int_hi = kv_len // bk - 1
    empty = kv_len <= kv_start
    if causal:
        hi = jnp.minimum(hi, q_hi // bk)
        int_hi = jnp.minimum(int_hi, (q_lo + 1) // bk - 1)
        empty = empty | (q_hi < kv_start)
    if window is not None:
        empty = empty | (kv_len <= q_lo - window + 1)
    hi = jnp.where(empty, lo - 1, hi)
    return lo, hi, int_lo, int_hi


def _flash_kernel(
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [G, bq, dq]: the G query heads of one KV head
    k_ref,  # [1, Sk, dq]: that KV head's whole strip, resident across q blocks
    v_ref,  # [1, Sk, dv]   (streamed: [1, bk, .], the block the grid step names)
    o_ref,  # [G, bq, dv]
    m_scr,  # VMEM [G*bq, 1]
    l_scr,  # VMEM [G*bq, 128]: the sum a LANE, folded once at the end
    acc_scr,  # VMEM [G*bq, dv]
    *,
    sk: int,
    bq: int,
    bk: int,
    wide: int,
    scale: float,
    causal: bool,
    kv_heads: int,
    resident: bool,
    window: Optional[int] = None,
):
    G = q_ref.shape[0]
    rows = G * bq
    b = pl.program_id(0) // kv_heads
    qi = pl.program_id(1)
    start, end = kv_start_ref[b], kv_len_ref[b]
    lo, hi, int_lo, int_hi = flash_block_plan(qi, start, end, sk, bq, bk, causal, window)

    # the G heads fold into one matmul's rows: row r is (head r // bq, query
    # qi*bq + r % bq) against ONE K/V block
    q = q_ref[:].reshape(rows, q_ref.shape[2])
    lanes = l_scr.shape[1]
    if causal:
        # row r is query r % bq: peeled off by G - 1 selects on one column
        # (no vector division)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        t = r
        for g in range(1, G):
            t = jnp.where(r >= g * bq, r - g * bq, t)
        q_pos = qi * bq + t  # [rows, 1]
    else:
        # every live key is allowed: the mask stays a ROW of keys, and never
        # becomes a [rows, width] compare (the encoder's hd 64 is VPU-bound)
        q_pos = _NO_KEY - 1

    def block(kj, n: int, masked: bool, first: bool = False):
        """Key blocks ``kj .. kj + n - 1`` into the running softmax, as one
        step. ``first``: the state is still empty, so nothing is rescaled."""
        width = n * bk
        off = pl.multiple_of(kj * bk, bk)
        at = off if resident else 0  # streamed: the block IS the ref
        k = k_ref[0, pl.ds(at, width), :]
        v = v_ref[0, pl.ds(at, width), :]
        if masked:
            # zero K/V rows outside the valid window BEFORE any matmul: cache
            # slots past the frontier may be uninitialized device memory, and a
            # NaN there survives even a zero-weight product (0 * NaN = NaN)
            cpos = off + jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
            cok = (cpos >= start) & (cpos < end)
            k = jnp.where(cok, k, 0)
            v = jnp.where(cok, v, 0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, width]
        if masked:
            # a key outside the window sits past every query: the window and
            # the diagonal are then ONE compare over the block, of a row of
            # key positions against a column of query positions
            k_pos = off + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
            k_pos = jnp.where((k_pos >= start) & (k_pos < end), k_pos, _NO_KEY)
            ok = k_pos <= q_pos  # [rows, width] under ``causal``, else [1, width]
            if window is not None:  # a key outside the live slots is past every query already
                ok = ok & (k_pos > q_pos - window)
            s = jnp.where(ok, s, NEG_INF)

        m_new = jnp.max(s, axis=1, keepdims=True)  # [rows, 1]
        if not first:
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, m_new)
            alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [rows, width]
        if masked:
            # explicit zero for masked entries: when a whole row is masked both
            # s and m_new sit at NEG_INF and exp(s - m_new) would be 1,
            # polluting l/acc with mean(V); such rows emit zeros
            p = jnp.where(ok, p, 0.0)
        # the row sum stays a sum a lane (whole-vreg adds) until the end: one
        # cross-lane reduction a query block, not one a key block
        if width % lanes == 0:
            part = p[:, :lanes]
            for c in range(1, width // lanes):
                part = part + p[:, c * lanes:(c + 1) * lanes]
        else:  # blocks under a vreg's lanes (tests, tiny shapes): lane 0 holds it
            col = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
            part = jnp.where(col == 0, jnp.sum(p, axis=1, keepdims=True), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_scr[:] = part if first else l_scr[:] * alpha + part
        acc_scr[:] = pv if first else acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    def emit():
        @pl.when(hi < lo)
        def _dead():  # wholly in the left pad (or an empty window): zeros
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(hi >= lo)
        def _live():
            l = jnp.sum(l_scr[:], axis=1, keepdims=True)
            l = jnp.maximum(l, 1e-30)  # fully-masked rows -> 0, not NaN
            out = (acc_scr[:] / l).astype(o_ref.dtype)
            for g in range(G):
                o_ref[g] = out[g * bq:(g + 1) * bq]

    # which body a block takes is a matter of scalars. The first block visited
    # finds the state empty and goes under the mask (it holds ``kv_start``
    # behind a left pad, and where it holds no masked pair the mask changes
    # nothing). Of the rest, interior blocks hold no masked pair: no iota, no
    # compare, no select; edge blocks keep the mask.
    last_int = jnp.minimum(int_hi, hi)
    first_int = jnp.maximum(int_lo, lo + 1)
    n_int = jnp.maximum(last_int - first_int + 1, 0)

    if not resident:
        # one key block a grid step: step j holds block lo + j, and the steps
        # past ``hi`` re-name the block already there (``_flash_call``'s index
        # map), so they fetch nothing and do nothing
        j = pl.program_id(2)
        kj = lo + j
        interior = (kj >= first_int) & (kj <= last_int)
        pl.when((j == 0) & (hi >= lo))(lambda: block(kj, 1, True, first=True))
        pl.when((j > 0) & (kj <= hi) & interior)(lambda: block(kj, 1, False))
        pl.when((j > 0) & (kj <= hi) & ~interior)(lambda: block(kj, 1, True))
        pl.when(j == pl.num_programs(2) - 1)(emit)
        return

    # the strip is resident: an inner loop with the plan's bounds. ``wide``
    # interior blocks in a row go as ONE step, because what a step costs is
    # mostly its bookkeeping a row (max, rescale), not its keys; the odd ones
    # left go one by one, then the edge blocks behind them (the diagonal, the
    # block that straddles ``kv_len``).
    odd = first_int + n_int // wide * wide  # the interior blocks no wide step takes

    def step(at, n: int, masked: bool):
        def body(t, carry):
            block(at + t * n, n, masked)
            return carry

        return body

    @pl.when(hi >= lo)
    def _visit():
        block(lo, 1, True, first=True)
        # (without a window an edge block between ``lo`` and the interior
        # cannot be: ``int_lo`` is ``lo`` or ``lo + 1``; a window's edge is a
        # second diagonal; one too wide for ``flash_window_step`` walks here)
        if window is not None:
            jax.lax.fori_loop(0, jnp.minimum(first_int, hi + 1) - (lo + 1), step(lo + 1, 1, True), 0)
        jax.lax.fori_loop(0, n_int // wide, step(first_int, wide, False), 0)
        if wide > 1:
            jax.lax.fori_loop(0, first_int + n_int - odd, step(odd, 1, False), 0)
        jax.lax.fori_loop(0, hi + 1 - (first_int + n_int), step(first_int + n_int, 1, True), 0)

    emit()


FLASH_WIDE = 2  # interior key blocks a step of the causal flash prefill kernels
_FLASH_VMEM = 31 * 2**19  # what a kernel's blocks may hold of the 16 MiB scoped limit


def _flash_fits(S: int, rows: int, bk: int, wide: int, dq: int, dv: int, itemsize: int) -> bool:
    """Whether a KV head's K/V strips (twice: the pipeline's buffers) fit
    VMEM beside a query block of ``rows`` = G·bq rows. A row holds its q, o
    and accumulators (≈ 1.25 KB) and a step's scores and probabilities: 12
    bytes a key of a masked block, 7.25 of an unmasked step of ``wide``
    blocks (as compiled for a v5e: 1024 rows of 1024 masked keys fit beside
    strips of 2 MiB and not of 3)."""
    pad = lambda d: -(-d // 128) * 128  # noqa: E731 — lanes of a VMEM tile
    row = 1280 + bk * max(12, 7.25 * wide)
    return 2 * itemsize * S * (pad(dq) + pad(dv)) + row * rows <= _FLASH_VMEM


def flash_blocks(S: int, G: int, dq: int, dv: int, causal: bool = True, itemsize: int = 2) -> Tuple[int, int]:
    """The default ``(bq, bk)`` of the flash prefill kernels, from the shape
    alone (no option, no model's name).

    Swept on a v5e (PR 30; PERF.md §6 has the tables). What a step of the key
    loop costs is mostly its bookkeeping a ROW (running max, rescaling sum
    and accumulator through VMEM: ≈ 1.2 µs a step at 1024 rows, whatever the
    keys), so a step takes 1024 keys and the G-fold keeps G·bq = 1024 rows a
    step (bq 256 at G = 4; where G is no power of two, the power of two
    nearest ``1024 / G``, since plain halving of 113 or 170 queries ends at a
    block of one or two: G = 9 takes 128 queries, 1152 rows, 7-8% faster than
    64 on this walk and 8% in ``flash_window_step``'s one step (PR 47); G = 6
    takes 128, 768 rows: PRs 33, 47, PERF.md §6). Under ``causal`` the 1024 keys are ``FLASH_WIDE``
    interior blocks of ``bk`` = 512, so that the blocks a mask can touch are
    512 wide, and bq stops at 512: a taller query block only widens the
    diagonal's waste (MLA, G = 1: 1024 × 512 lost 11% to 512 × 512). Finer
    blocks LOSE: 256 × 256 by 12%, bq 128 by 8%, one block a step by 8%; the
    1024 × 1024 this replaces (a per-head grid that masked every block) was
    60% slower at the serving prefill. Without a diagonal (the encoder) there
    is nothing to cut finer: ONE block of 1024 keys a step, which is also the
    order the kernel before PR 30 summed in, so an index's embeddings stay
    what they were (7 in a million outputs differ, in bf16's last place).

    The K/V strips of a KV head are resident, so a long sequence or a wide
    head leaves less for the query block: bq halves while that lets the strips
    fit. Where they do not fit beside 256 rows (S ≥ 16384 at hd 128) the
    K/V blocks are streamed a grid step (``_flash_call``) and bq stays."""
    bk = _fit_block(S, 512 if causal else 1024)
    wide = FLASH_WIDE if causal else 1
    # 1024 rows a step, as the nearest power of two of queries so that the
    # block still tiles a bucket at a group size that is none (G = 9: 128
    # queries, 1152 rows; G = 6: 128, 768; at a power of two nothing changes)
    full = min(512, 1 << round(math.log2(1024 / G))) if causal else 1024
    bq = full
    while G * bq > 256 and not _flash_fits(S, G * bq, bk, wide, dq, dv, itemsize):
        bq //= 2
    if not _flash_fits(S, G * bq, bk, wide, dq, dv, itemsize):
        bq = full
    return _fit_block(S, bq), bk


def _flash_call(qt, kt, vt, kv_start, kv_len, *, scale, causal, bq, bk, interpret, name, resident=None,
                window=None):
    """The one flash prefill ``pallas_call``: ``qt [B*H, Sq, dq]`` against
    ``kt [B*K, Sk, dq]`` / ``vt [B*K, Sk, dv]`` (the G = H // K query heads of
    a KV head are consecutive rows of ``qt``); returns ``[B*H, Sq, dv]``.
    ``bq`` / ``bk`` left ``None`` and ``resident`` (``None``: where the strips
    fit) are ``flash_walk_blocks``' to fill. ``window`` (causal only) bounds
    every query to its last ``window`` keys; where the shape lets a query block
    take its window in ONE step (``flash_window_step``) that kernel is built,
    not this one (``bk`` names the WALK's key block: a call that gives one, or
    asks for streamed blocks, keeps the walk)."""
    (BH, Sq, dq), (BK, Sk, dv) = qt.shape, vt.shape
    G = BH // BK
    kv_heads = BK // kv_start.shape[0]
    wide = FLASH_WIDE if causal else 1
    if window and bk is None and resident is not False and Sq == Sk:
        step = flash_window_step(Sk, G, dq, dv, window, kt.dtype.itemsize, bq)
        if step:
            return _window_call(qt, kt, vt, kv_start, kv_len, *step, scale=scale, window=window,
                                interpret=interpret, name=name)
    bq, bk, resident = flash_walk_blocks(Sq, Sk, G, dq, dv, causal, kt.dtype.itemsize, bq, bk, resident)

    if resident:
        grid, kv_block = (BK, Sq // bq), Sk

        def kv_index(h, qi, *s_):
            return (h, 0, 0)
    else:
        grid, kv_block = (BK, Sq // bq, Sk // bk), bk

        def kv_index(h, qi, j, start_ref, len_ref):
            b = h // kv_heads
            lo, hi, _, _ = flash_block_plan(qi, start_ref[b], len_ref[b], Sk, bq, bk, causal, window)
            return (h, jnp.minimum(lo + j, jnp.clip(hi, lo, Sk // bk - 1)), 0)

    def q_index(h, qi, *s_):
        return (h, qi, 0)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, sk=Sk, bq=bq, bk=bk, wide=min(wide, Sk // bk),
            scale=scale, causal=causal, kv_heads=kv_heads, resident=resident,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((G, bq, dq), q_index),
                pl.BlockSpec((1, kv_block, dq), kv_index),
                pl.BlockSpec((1, kv_block, dv), kv_index),
            ],
            out_specs=pl.BlockSpec((G, bq, dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 128), jnp.float32),
                pltpu.VMEM((G * bq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dv), qt.dtype),
        interpret=interpret,
        name=name,
    )(kv_start.astype(jnp.int32), kv_len.astype(jnp.int32), qt, kt, vt)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "window")
)
def flash_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Sk, K, hd]
    v: jax.Array,  # [B, Sk, K, hd]
    kv_start: Optional[jax.Array] = None,  # [B] int32 (left-pad offset)
    kv_len: Optional[jax.Array] = None,  # [B] int32 (valid frontier)
    causal: bool = True,
    bq: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Blockwise fused attention; returns ``[B, Sq, H, hd]`` in q's dtype.
    ``bq`` / ``bk`` default to ``flash_blocks``' rule on the shape; blocks
    shrink (halving) until they tile the sequence exactly, and a sequence
    whose K/V strips outgrow VMEM has its key blocks streamed
    (``_flash_call``), so any power-of-two length works. ``window`` (causal
    only): a query sees its last ``window`` keys, itself among them; that
    call is built under the name ``flash_attention_window``, so a trace
    tells the two apart."""
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    if kv_start is None:
        kv_start = jnp.zeros((B,), jnp.int32)
    if kv_len is None:
        kv_len = jnp.full((B,), Sk, jnp.int32)

    # [B, S, H, hd] -> [B*H, S, hd] rows; kv head for query head h is h // G
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    out = _flash_call(
        qt, kt, vt, kv_start, kv_len, scale=hd**-0.5, causal=causal,
        bq=bq, bk=bk, interpret=interpret,
        name="flash_attention" if window is None else "flash_attention_window", window=window,
    )
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# single-token decode over the dense cache: a row's live window, in a few
# large steps (decode_attention, decode_attention_q8, ops/mla.py's
# mla_decode_attention)
# ---------------------------------------------------------------------------

# a step starts on a multiple of this many slots: the lane tile of the int8
# cache's scale planes ([.., K, T] float32), and a whole number of sublane
# tiles of every payload (32 rows of int8, 16 of bf16)
DECODE_ALIGN = 128
# what one step of the walk moves at least (bytes), how many times the rows'
# float32 accumulator, and what a step may take of VMEM (its two buffers, the
# unpacked or masked copy the matmuls read, 12 bytes a score): the sweep
# behind all three is in PERF.md §6, PR 32
_DECODE_STEP_BYTES = 1 << 20
_DECODE_ACC_STEPS = 16
_DECODE_VMEM = 12 << 20


def decode_step(T: int, slot_bytes: int, rows: int, width: int) -> int:
    """Slots a step of the decode kernels' walk over a cache of ``T`` slots
    fetches for a row and folds into its softmax at once, from the shape
    alone (no option, no model's name).

    ``slot_bytes`` is what a slot costs to read (every local KV head's K and
    V and their scales; or a latent), ``rows`` the query rows that attend to
    it (K·G; or H) and ``width`` the accumulator's columns a row (``hd``; or
    the latent rank). Swept on a v5e (PR 32): a step costs ≈ 0.25 µs whatever
    it moves, so it moves at least 1 MiB; and folding a step into the running
    max, sum and accumulator costs by the ROW, not by the slot (PR 30 found
    the same law in the prefill kernel), so a step also moves 16 times the
    accumulator's bytes: 128 heads over a rank-512 latent want steps of 2048
    slots (69.6 µs a call against 96.3 at 512), 32 rows of 128 want 512
    (90.0 against 97.6 at 1024: a longer step only fetches more dead slots
    behind the window's end). A power-of-two count of ``DECODE_ALIGN`` slots,
    at most half the cache's; it need not divide ``T`` (the last step of a
    row is fetched from ``T - step``: ``decode_step_bounds``). Cutting a
    fetched step into pieces for the matmuls never won (it is how the latent
    kernel lost a factor of two), so there are none."""
    if T % DECODE_ALIGN or T <= DECODE_ALIGN:
        return T  # one step: the whole (short, or oddly sized) cache
    want = max(_DECODE_STEP_BYTES, _DECODE_ACC_STEPS * rows * width * 4)
    step = DECODE_ALIGN
    while (4 * step <= T and step * slot_bytes < want
           and 2 * step * (4 * slot_bytes + 12 * rows) <= _DECODE_VMEM):
        step *= 2
    return step


def decode_block_plan(kv_start, kv_len, T: int, step: int):
    """Where a row's decode walk starts and how many steps it takes:
    ``(origin, n)``. Step ``j < n`` covers the slots ``[origin + j·step,
    origin + (j + 1)·step)``; together they are exactly the steps from
    ``origin`` that intersect the live window ``[kv_start, kv_len)``, and
    nothing else of ``[0, T)`` is fetched. ``origin`` is ``kv_start`` rounded
    down to ``DECODE_ALIGN`` (to the step, where that is finer). An empty
    window still takes one step (everything in it is masked; the output is
    zeros), so a walk always has a first fetch. Integer arithmetic only: the
    kernels' traced scalars, the model's counters and the tests' numpy arrays
    read this one rule."""
    align = math.gcd(step, DECODE_ALIGN)
    origin = jnp.clip(kv_start, 0, T - 1) // align * align
    n = jnp.maximum(-(-(jnp.minimum(kv_len, T) - origin) // step), 1)
    return origin, n


def decode_step_bounds(origin, j, T: int, step: int):
    """``(first, nominal)`` of step ``j``: it covers the slots from
    ``nominal`` on and is fetched from ``first``. They differ only where a
    step would pass the end of the cache (``T`` need not be a multiple of the
    step): that step is fetched from ``T - step`` and the slots in front of
    ``nominal``, which the step before it covered, are masked."""
    nominal = origin + j * step
    return jnp.minimum(nominal, T - step), nominal


def decode_slots_streamed(kv_start, kv_len, T: int, step: int):
    """Cache slots the walk fetches for the rows ``kv_start`` / ``kv_len``
    describe, summed over them (one layer's call): what the model's
    ``decode_slots_streamed`` counter adds a step, next to rows × ``T``."""
    _, n = decode_block_plan(kv_start, kv_len, T, step)
    return jnp.sum(n) * step


def _decode_walk(kv_start_ref, kv_len_ref, turn_ref, m_scr, l_scr, acc_scr, *, T: int, step: int, copies, consume):
    """One grid cell of a decode kernel: batch row ``b``'s window, fetched a
    step at a time into one of two buffers while the step before it is
    consumed; returns the row's attention output (float32, the
    accumulator's shape). ``copies(row, first, buf)`` names the async copies
    of the step that starts at slot ``first`` of ``row`` into buffer ``buf``;
    ``consume(buf, first, lo, hi)`` folds the slots ``[lo, hi)`` of it into
    the row's running softmax (``_softmax_fold`` over ``m_scr``, ``l_scr``,
    ``acc_scr``). The first step of the NEXT row is started under this row's
    last, so a row does not begin by waiting on HBM; ``turn_ref`` (SMEM)
    carries which buffer that is across grid cells."""
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    b, nb = pl.program_id(0), pl.num_programs(0)
    hint = math.gcd(math.gcd(step, DECODE_ALIGN), T - step)

    def fetch(row, j, buf):
        origin, _ = decode_block_plan(kv_start_ref[row], kv_len_ref[row], T, step)
        first, _ = decode_step_bounds(origin, j, T, step)
        return copies(row, pl.multiple_of(first, hint), buf)

    @pl.when(b == 0)
    def _first_row():
        turn_ref[0] = 0
        for c in fetch(0, 0, 0):
            c.start()

    turn = turn_ref[0]
    start, end = kv_start_ref[b], kv_len_ref[b]
    origin, n = decode_block_plan(start, end, T, step)

    def one_step(j, carry):
        buf = (turn + j) % 2
        more = j + 1 < n  # else the next row's first step, if there is a next row

        @pl.when(more | (b + 1 < nb))
        def _prefetch():
            row = jnp.where(more, b, jnp.minimum(b + 1, nb - 1))
            for c in fetch(row, jnp.where(more, j + 1, 0), 1 - buf):
                c.start()

        for c in fetch(b, j, buf):
            c.wait()
        first, nominal = decode_step_bounds(origin, j, T, step)
        consume(buf, pl.multiple_of(first, hint), jnp.maximum(start, nominal), end)
        return carry

    jax.lax.fori_loop(0, n, one_step, 0)
    turn_ref[0] = (turn + n) % 2
    return acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)


def _softmax_fold(s, ok, m_scr, l_scr, acc_scr, weigh):
    """Fold one step's scores ``s`` (float32, slots last; ``ok`` its live
    slots) into the running max / sum / accumulator; ``weigh(p)`` is the
    step's probability-weighted sum of values."""
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + weigh(p)
    m_scr[:] = m_new


def _decode_kernel(
    layer_ref,  # SMEM [1]
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, K, G, hd]
    k_hbm,  # [L, B, K, T, hd], left in HBM
    v_hbm,  # [L, B, K, T, hd]
    o_ref,  # [1, K, G, hd]
    k_buf,  # VMEM [2, K, step, hd]
    v_buf,  # VMEM [2, K, step, hd]
    sem,  # DMA [2, 2]
    turn_ref,  # SMEM [1]
    m_scr,  # VMEM [K, G, 1]
    l_scr,  # VMEM [K, G, 1]
    acc_scr,  # VMEM [K, G, hd]
    *,
    T: int,
    step: int,
    scale: float,
):
    def copies(row, first, buf):
        def src(ref):
            return ref.at[layer_ref[0], row, :, pl.ds(first, step), :]

        return (pltpu.make_async_copy(src(k_hbm), k_buf.at[buf], sem.at[buf, 0]),
                pltpu.make_async_copy(src(v_hbm), v_buf.at[buf], sem.at[buf, 1]))

    def consume(buf, first, lo, hi):
        q = q_ref[0]  # [K, G, hd]
        k = k_buf[buf]  # [K, step, hd]
        v = v_buf[buf]
        # zero K/V rows outside the live slots BEFORE any matmul: cache
        # slots past the frontier may be uninitialized device memory, and
        # a NaN there survives even a zero-weight product (0 * NaN = NaN)
        rpos = first + jax.lax.broadcasted_iota(jnp.int32, (k.shape[0], step, 1), 1)
        rok = (rpos >= lo) & (rpos < hi)
        k = jnp.where(rok, k, 0)
        v = jnp.where(rok, v, 0)
        # one batched dot over all kv heads: [K, G, hd] x [K, step, hd] -> [K, G, step]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale
        k_pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        _softmax_fold(
            s, (k_pos >= lo) & (k_pos < hi), m_scr, l_scr, acc_scr,
            lambda p: jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32),
        )

    o_ref[0] = _decode_walk(
        kv_start_ref, kv_len_ref, turn_ref, m_scr, l_scr, acc_scr,
        T=T, step=step, copies=copies, consume=consume,
    ).astype(o_ref.dtype)


def gqa_decode_step(T: int, kv_heads: int, group: int, head_dim: int, dtype) -> int:
    """``decode_step`` for ``decode_attention`` (a bf16 / float32 cache) and
    ``decode_attention_q8`` (int8, with two float32 scales a head-vector) on
    the shapes ONE device's kernel sees (``kv_heads`` local under tp): the
    wrappers and the model's counters (``models/llama.py``) ask this one
    function."""
    dtype = jnp.dtype(dtype)
    width = head_dim + 4 if dtype == jnp.int8 else head_dim * dtype.itemsize
    return decode_step(T, 2 * kv_heads * width, kv_heads * group, head_dim)


def _decode_step_or(bk: Optional[int], rule: int, T: int, tile: int, interpret: bool) -> int:
    """The rule's step, or a caller's (``bk``: tests, sweeps); on hardware a
    step is whole sublane tiles (``tile`` rows) of the payload."""
    step = rule if bk is None else min(bk, T)
    if not interpret and (step % tile or (T - step) % tile):
        raise ValueError(
            f"cache length T={T} walked in steps of {step}: both must be multiples of "
            f"{tile} slots — the engine rounds cache lengths to {DECODE_ALIGN} for this"
        )
    return step


def _decode_block(T: int, bk: int) -> int:
    """The chunk kernels' K/V block (``chunk_prefill_attention[_q8]``,
    ``_chunk_grouped``): the largest ≤ ``bk`` that tiles ``T`` exactly —
    prefer the coarse candidates (more MXU work per sequential grid step),
    else the largest divisor of ``T`` that fits, so any caller-supplied
    ``bk`` works. The single-token decode kernels left this rule in PR 32
    (``decode_step``: their steps need not divide ``T``)."""
    if T <= bk:
        return T
    for cand in (512, 384, 256, 128):
        if cand <= bk and T % cand == 0:
            return cand
    return max(d for d in range(1, min(bk, T) + 1) if T % d == 0)


@functools.partial(jax.jit, static_argnames=("bk", "interpret", "name"))
def decode_attention(
    q: jax.Array,  # [B, 1, H, hd] — the single fresh query token
    k_cache: jax.Array,  # [L, B, K, T, hd] — FULL stacked head-major cache
    v_cache: jax.Array,  # [L, B, K, T, hd]
    kv_start: jax.Array,  # [B] int32: first valid cache slot (left-pad offset)
    kv_len: jax.Array,  # [B] int32: valid frontier (exclusive)
    layer: jax.Array,  # [] or [1] int32: which layer's cache to attend over
    bk: Optional[int] = None,
    interpret: bool = False,
    name: str = "decode_attention",  # what a trace calls it (a family whose planes are of another kind)
) -> jax.Array:
    """Fused single-token decode attention over the KV cache.

    Replaces the reference's per-step torch attention inside ``model.generate``
    (/root/reference/llm/rag.py:172). The cache stays in HBM and the kernel
    copies what it reads itself: ``layer`` and the row's window
    ``[kv_start, kv_len)`` ride scalar prefetch into the copies' addresses,
    so no per-layer slice of the multi-GB cache is ever materialized and no
    slot outside the steps that window touches is ever FETCHED
    (``decode_block_plan``; bandwidth scales with the live tokens, not with
    the allocation). One grid cell per batch row: all K kv heads' slots of a
    step arrive in one double-buffered copy (``decode_step`` sizes it, ≈ 1 MiB;
    ``bk`` overrides the step), the next step — or the next row's first — in
    flight while this one is consumed, one batched MXU dot a step, with the
    flash recurrence across steps. The ``[.., K, T, hd]`` layout
    makes every step K contiguous ``(step, hd)`` slabs — tiled exactly for
    the VPU/MXU, no transposition of cache memory ever happens.
    """
    B, S, H, hd = q.shape
    assert S == 1, f"decode_attention is single-token (got S={S})"
    L, _, K, T, _ = k_cache.shape
    G = H // K
    step = _decode_step_or(bk, gqa_decode_step(T, K, G, hd, k_cache.dtype), T, 16, interpret)

    def row_block(b, *s_):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, T=T, step=step, scale=hd**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, K, G, hd), row_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, K, G, hd), row_block),
            scratch_shapes=[
                pltpu.VMEM((2, K, step, hd), k_cache.dtype),
                pltpu.VMEM((2, K, step, hd), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        kv_start.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        q.reshape(B, K, G, hd),
        k_cache,
        v_cache,
    )

    return out.reshape(B, 1, H, hd)


def _chunk_kernel(
    layer_ref,  # SMEM [1] (consumed by the index maps)
    wi_ref,  # SMEM [1]: write_index — global cache slot of query 0
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, bq, hd]
    k_ref,  # [1, 1, 1, bk, hd]
    v_ref,  # [1, 1, 1, bk, hd]
    o_ref,  # [1, bq, hd]
    m_scr,  # VMEM [bq, 1]
    l_scr,  # VMEM [bq, 1]
    acc_scr,  # VMEM [bq, hd]
    *,
    bq: int,
    bk: int,
    scale: float,
    num_heads: int,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    b = bh // num_heads
    wi = wi_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block skip: K blocks inside the pad region / past the frontier /
    # strictly above the OFFSET causal diagonal (query t sits at global
    # cache slot wi + t) do no work
    q_hi = wi + qi * bq + bq - 1  # last query slot of this q block
    overlap = (kj * bk + bk > kv_start_ref[b]) & (kj * bk < kv_len_ref[b])
    live = overlap & (kj * bk <= q_hi)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0, 0, 0]
        v = v_ref[0, 0, 0]
        # zero K/V rows outside the valid window BEFORE any matmul (cache
        # slots past the frontier may be uninitialized; 0 * NaN = NaN)
        cpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        cok = (cpos >= kv_start_ref[b]) & (cpos < kv_len_ref[b])
        k = jnp.where(cok, k, 0)
        v = jnp.where(cok, v, 0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        q_pos = wi + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = (k_pos >= kv_start_ref[b]) & (k_pos < kv_len_ref[b]) & (k_pos <= q_pos)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def chunk_prefill_attention(
    q: jax.Array,  # [B, S, H, hd] — one prompt chunk's fresh queries
    k_cache: jax.Array,  # [L, B, K, T, hd] — FULL stacked head-major cache
    v_cache: jax.Array,  # [L, B, K, T, hd]
    kv_start: jax.Array,  # [B] int32: first valid cache slot
    kv_len: jax.Array,  # [B] int32: valid frontier (= write_index + S)
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [] or [1] int32: cache slot of query 0
    bq: int = 512,  # swept on v5e: ~5% over 256; wider is flat (per-head grid)
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Cache-wide flash attention for CHUNKED prefill (``S > 1`` queries
    written at ``write_index > 0``): each query attends over the whole
    populated cache prefix — earlier chunks' slots AND its own chunk — under
    offset causality (query ``t`` lives at cache slot ``write_index + t``).

    Streams the head-major cache exactly like ``decode_attention`` (layer via
    scalar prefetch into the block index map — no per-layer cache slice is
    materialized) but with the blockwise flash recurrence of
    ``flash_attention`` across ``bq`` query rows. The reference has no
    equivalent: its torch path materializes full [S, T] score matrices and
    cannot prefill beyond what fits one forward (rag.py:172)."""
    B, S, H, hd = q.shape
    L, _, K, T, _ = k_cache.shape
    G = H // K
    bq = _fit_block(S, bq)
    bk = _decode_block(T, bk)
    if not interpret and bk % 16:
        raise ValueError(
            f"cache length T={T} only tiles into blocks of {bk}: pad T to a "
            "multiple of 128 — the engine rounds cache lengths for this"
        )

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    grid = (B * H, S // bq, T // bk)

    def kv_index(bh, qi, kj, layer_ref, *s_):
        return (layer_ref[0], bh // H, (bh % H) // G, kj, 0)

    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, bq=bq, bk=bk, scale=hd**-0.5, num_heads=H
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
                pl.BlockSpec((1, 1, 1, bk, hd), kv_index),
                pl.BlockSpec((1, 1, 1, bk, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
        name="chunk_prefill_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(write_index, jnp.int32).reshape(1),
        kv_start.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        qt,
        k_cache,
        v_cache,
    )

    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def chunk_attention_xla(
    q: jax.Array,  # [B, S, H, hd]
    k_cache: jax.Array,  # [L, B, K, T, hd]
    v_cache: jax.Array,  # [L, B, K, T, hd]
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [] int32
    window: Optional[int] = None,  # a query sees its last ``window`` slots
) -> jax.Array:
    """Dense XLA reference for ``chunk_prefill_attention`` (oracle; fallback
    off-TPU; the served form of a windowed layer's chunk calls, for which the
    chunk kernels have no bound)."""
    B, S, H, hd = q.shape
    _, _, K, T, _ = k_cache.shape
    G = H // K
    lay = jnp.asarray(layer, jnp.int32).reshape(())
    k = jax.lax.dynamic_index_in_dim(k_cache, lay, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(v_cache, lay, 0, keepdims=False)
    qg = q.reshape(B, S, K, G, hd)
    s = jnp.einsum("bqkgd,bktd->bkgqt", qg, k, preferred_element_type=jnp.float32)
    s = s * (hd**-0.5)
    q_pos = jnp.asarray(write_index, jnp.int32).reshape(()) + jnp.arange(S)
    t_pos = jnp.arange(T)
    ok = (t_pos[None, None, :] >= kv_start[:, None, None]) & (
        t_pos[None, None, :] < kv_len[:, None, None]
    )
    ok = ok & (t_pos[None, None, :] <= q_pos[None, :, None])  # [B, S, T]
    if window is not None:
        ok = ok & (t_pos[None, None, :] > q_pos[None, :, None] - window)
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok[:, None, None, :, :], p, 0.0)
    o = jnp.einsum(
        "bkgqt,bktd->bqkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, S, H, hd).astype(q.dtype)


def decode_attention_xla(
    q: jax.Array,  # [B, 1, H, hd]
    k_cache: jax.Array,  # [L, B, K, T, hd]
    v_cache: jax.Array,  # [L, B, K, T, hd]
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
    window: Optional[int] = None,  # the query (slot kv_len - 1) sees its last ``window`` slots
) -> jax.Array:
    """Dense XLA reference for ``decode_attention`` (oracle; fallback off-TPU)."""
    if window is not None:
        kv_start = jnp.maximum(kv_start, kv_len - window)
    B, S, H, hd = q.shape
    _, _, K, T, _ = k_cache.shape
    G = H // K
    lay = jnp.asarray(layer, jnp.int32).reshape(())
    k_cache = jax.lax.dynamic_index_in_dim(k_cache, lay, 0, keepdims=False)
    v_cache = jax.lax.dynamic_index_in_dim(v_cache, lay, 0, keepdims=False)
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum(
        "bkgd,bktd->bkgt", qg, k_cache, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    t_pos = jnp.arange(T)
    ok = (t_pos[None, :] >= kv_start[:, None]) & (t_pos[None, :] < kv_len[:, None])
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok[:, None, None, :], p, 0.0)
    o = jnp.einsum(
        "bkgt,bktd->bkgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_start: Optional[jax.Array] = None,
    kv_len: Optional[jax.Array] = None,
    causal: bool = True,
    window: Optional[int] = None,  # causal only: a query sees its last ``window`` keys
) -> jax.Array:
    """Dense XLA reference (oracle for the kernel; fallback off-TPU)."""
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    s = s * (hd**-0.5)
    q_pos = jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    ok = jnp.ones((B, Sq, Sk), bool)
    if kv_start is not None:
        ok = ok & (k_pos[None, None, :] >= kv_start[:, None, None])
    if kv_len is not None:
        ok = ok & (k_pos[None, None, :] < kv_len[:, None, None])
    if causal:
        ok = ok & (k_pos[None, None, :] <= q_pos[None, :, None])
    if window is not None:
        assert causal, "a window bound is causal"
        ok = ok & (k_pos[None, None, :] > q_pos[None, :, None] - window)
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key: softmax of all-NEG_INF is uniform — zero it so
    # pad rows contribute nothing downstream (matches the fused kernels)
    p = jnp.where(ok[:, None, None, :, :], p, 0.0)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# RoPE re-rotation of cached K planes (chunk-granular prefix reuse)
# ---------------------------------------------------------------------------
#
# RoPE is a per-position orthogonal rotation of each (i, i + hd/2) pair of
# the K vector: K computed at position p and reused at position p + delta
# differs ONLY by a further rotation of angle delta * inv_freq per pair — a
# closed form over bytes already in HBM, no re-prefill (SIFT's attention
# invariance: retrieved-chunk KV is largely position/composition-invariant,
# so a hot chunk's KV is computed ONCE at a canonical position and spliced
# anywhere by rotating the cached K planes by the position delta). V carries
# no positional encoding and splices untouched. delta == 0 is exactly the
# identity (cos 0 = 1, sin 0 = 0 — the multiply-by-one round trip is exact
# in every dtype), so a canonical-position hit stays bit-identical.


@jax.jit
def rope_rerotate(k: jax.Array, delta: jax.Array, inv_freqs: jax.Array) -> jax.Array:
    """Rotate cached K planes ``[..., hd]`` by a uniform position ``delta``
    (scalar int): the pairwise-by-halves rotation of ``apply_rope`` with
    phase ``delta * inv_freq`` — position-shifting every token of a cached
    segment in one VPU pass. Computes in fp32, returns ``k``'s dtype."""
    half = k.shape[-1] // 2
    phase = delta.astype(jnp.float32) * inv_freqs  # [hd/2]
    c, s = jnp.cos(phase), jnp.sin(phase)
    # halves as a [..., 2, hd/2] view, re-joined by stack + reshape: the
    # same arithmetic per element as slicing and concatenating the halves,
    # but the TPU compiler aborts (a fusion-emitter check on the
    # lane-unaligned concatenate) on that spelling for fp32 K at hd >= 64
    xr = k.astype(jnp.float32).reshape(*k.shape[:-1], 2, half)
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    return jnp.stack(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-2
    ).reshape(k.shape).astype(k.dtype)


@jax.jit
def rope_rerotate_q8(
    k_q: jax.Array,  # [..., hd] int8 payload
    k_scale: jax.Array,  # [...] fp32 per-(token, head) vector scale
    delta: jax.Array,
    inv_freqs: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """``rope_rerotate`` over the int8-quantized K layout (the warm tier /
    int8-KV engines): dequant → rotate → requant. The rotation pairs dims
    ``i`` and ``i + hd/2`` of the SAME token vector, which shares one
    symmetric scale — but it changes the vector's max-abs, so the scale is
    recomputed per vector (same grammar as :func:`quantize_kv`) instead of
    carried; drift stays bounded at max|x|/254 per element either way."""
    xf = k_q.astype(jnp.float32) * k_scale[..., None]
    half = xf.shape[-1] // 2
    phase = delta.astype(jnp.float32) * inv_freqs
    c, s = jnp.cos(phase), jnp.sin(phase)
    x1, x2 = xf[..., :half], xf[..., half:]
    rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    scale = jnp.maximum(jnp.max(jnp.abs(rot), axis=-1), 1e-8) / 127.0
    q = jnp.round(rot / scale[..., None]).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# weight-only-int8 KV cache (kv_quant="int8")
# ---------------------------------------------------------------------------
#
# At the engine's full cache budget the decode step's HBM traffic is weights
# PLUS the whole populated cache (e.g. 8B, B=8, T=4352: ~8 GiB int8 weights
# + ~4.6 GB bf16 cache per step). Storing K/V as int8 with one fp32 scale
# per (token, kv-head) vector halves the cache bytes streamed and the cache
# HBM footprint; dequantization happens in VMEM right after each block load,
# so the flash recurrence and masking below are IDENTICAL to the bf16
# kernel's. Per-vector symmetric scales bound the dequant error at
# max|x|/254 per element — the parity tests pin logits against the bf16
# cache path.


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``[..., hd] -> (int8 [..., hd], fp32 scale [...])`` — one symmetric
    scale per head-vector (the granularity the kernels dequantize at)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def dequantize_layer_slice(
    cache: jax.Array,  # [L, B, K, T, hd] int8
    scale: jax.Array,  # [L, B, K, T] fp32
    layer: jax.Array,  # [] or [1] int32
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    dtype: jnp.dtype,
) -> jax.Array:
    """``[1, B, K, T, hd]`` dequantized view of ONE layer — the shared
    slice-dequant used by the XLA q8 oracle and the chunked-prefill path
    (a layer slice is ~MBs; the stacked cache the q8 layout exists to avoid
    copying is GBs). Scales outside ``[kv_start, kv_len)`` zero out under
    the window mask: slots past the frontier can be uninitialized fp32
    memory (NaN), while the int8 payload is finite by construction, so
    zeroed scales alone make every invalid slot contribute exactly 0."""
    lay = jnp.asarray(layer, jnp.int32).reshape(())
    T = cache.shape[3]
    t_ok = (jnp.arange(T)[None, :] >= kv_start[:, None]) & (
        jnp.arange(T)[None, :] < kv_len[:, None]
    )
    c = jax.lax.dynamic_index_in_dim(cache, lay, 0, keepdims=False)
    s = jax.lax.dynamic_index_in_dim(scale, lay, 0, keepdims=False)
    s = jnp.where(t_ok[:, None, :], s, 0.0)
    return (c.astype(jnp.float32) * s[..., None]).astype(dtype)[None]


def _decode_kernel_q8(
    layer_ref,  # SMEM [1]
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, K, G, hd]
    k_hbm,  # [L, B, K, T, hd] int8, left in HBM
    v_hbm,  # [L, B, K, T, hd] int8
    ks_hbm,  # [L, B, K, T] fp32
    vs_hbm,  # [L, B, K, T] fp32
    o_ref,  # [1, K, G, hd]
    k_buf,  # VMEM [2, K, step, hd] int8
    v_buf,  # VMEM [2, K, step, hd] int8
    ks_buf,  # VMEM [2, K, step] fp32
    vs_buf,  # VMEM [2, K, step] fp32
    sem,  # DMA [2, 4]
    turn_ref,  # SMEM [1]
    m_scr,  # VMEM [K, G, 1]
    l_scr,  # VMEM [K, G, 1]
    acc_scr,  # VMEM [K, G, hd]
    *,
    T: int,
    step: int,
    scale: float,
):
    def copies(row, first, buf):
        lay, win = layer_ref[0], pl.ds(first, step)
        return (pltpu.make_async_copy(k_hbm.at[lay, row, :, win, :], k_buf.at[buf], sem.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[lay, row, :, win, :], v_buf.at[buf], sem.at[buf, 1]),
                pltpu.make_async_copy(ks_hbm.at[lay, row, :, win], ks_buf.at[buf], sem.at[buf, 2]),
                pltpu.make_async_copy(vs_hbm.at[lay, row, :, win], vs_buf.at[buf], sem.at[buf, 3]))

    def consume(buf, first, lo, hi):
        q = q_ref[0]  # [K, G, hd]
        # int8 payloads need NO validity masking: unlike bf16 (where an
        # uninitialized slot can hold NaN that survives 0-weighting), every
        # int8 bit pattern is a finite value, and dead columns are
        # eliminated by the score mask + zeroed scales below. The convert
        # to the matmul dtype is the only per-element op on the payload.
        k = k_buf[buf].astype(q.dtype)  # [K, step, hd]
        rpos = first + jax.lax.broadcasted_iota(jnp.int32, (k.shape[0], step), 1)
        rok = (rpos >= lo) & (rpos < hi)
        # scales CAN be NaN past the frontier (uninitialized fp32 memory):
        # zero them under the window mask — [K, step] work, not [K, step, hd]
        ks = jnp.where(rok, ks_buf[buf], 0.0)
        vs = jnp.where(rok, vs_buf[buf], 0.0)
        # dequantization rides the EPILOGUES: scores scale per key column,
        # probabilities fold the V scale — O(K*G*step) multiplies instead
        # of O(K*step*hd) on the payload (the whole point: the int8 win is
        # bandwidth, so the kernel must not spend it back in VPU flops)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale * ks[:, None, :]
        k_pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        _softmax_fold(
            s, (k_pos >= lo) & (k_pos < hi), m_scr, l_scr, acc_scr,
            lambda p: jax.lax.dot_general(
                (p * vs[:, None, :]).astype(q.dtype), v_buf[buf].astype(q.dtype),
                (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32),
        )

    o_ref[0] = _decode_walk(
        kv_start_ref, kv_len_ref, turn_ref, m_scr, l_scr, acc_scr,
        T=T, step=step, copies=copies, consume=consume,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_q8(
    q: jax.Array,  # [B, 1, H, hd] — the single fresh query token
    k_cache: jax.Array,  # [L, B, K, T, hd] int8
    v_cache: jax.Array,  # [L, B, K, T, hd] int8
    k_scale: jax.Array,  # [L, B, K, T] fp32
    v_scale: jax.Array,  # [L, B, K, T] fp32
    kv_start: jax.Array,  # [B] int32
    kv_len: jax.Array,  # [B] int32
    layer: jax.Array,  # [] or [1] int32
    bk: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """``decode_attention`` over an int8 KV cache (see module note above).

    Same walk over the row's live window, masking, and streaming layout as
    the bf16 kernel; the only addition is the two per-(token, head) scale
    planes, whose slots of a step ride their own copies beside the int8
    payload's."""
    B, S, H, hd = q.shape
    assert S == 1, f"decode_attention_q8 is single-token (got S={S})"
    L, _, K, T, _ = k_cache.shape
    G = H // K
    # int8 rows come in sublane tiles of 32; the scale planes' lanes in 128
    step = _decode_step_or(bk, gqa_decode_step(T, K, G, hd, k_cache.dtype), T, 32, interpret)

    def row_block(b, *s_):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel_q8, T=T, step=step, scale=hd**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, K, G, hd), row_block)] + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=pl.BlockSpec((1, K, G, hd), row_block),
            scratch_shapes=[
                pltpu.VMEM((2, K, step, hd), jnp.int8),
                pltpu.VMEM((2, K, step, hd), jnp.int8),
                pltpu.VMEM((2, K, step), jnp.float32),
                pltpu.VMEM((2, K, step), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 4)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="decode_attention_q8",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        kv_start.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        q.reshape(B, K, G, hd),
        k_cache,
        v_cache,
        k_scale,
        v_scale,
    )

    return out.reshape(B, 1, H, hd)



def _chunk_kernel_q8(
    layer_ref,  # SMEM [1] (consumed by the index maps)
    wi_ref,  # SMEM [1]: write_index — global cache slot of query 0
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, bq, hd]
    k_ref,  # [1, 1, 1, bk, hd] int8
    v_ref,  # [1, 1, 1, bk, hd] int8
    ks_ref,  # [1, 1, K, bk] fp32 — ALL kv heads' scales for this block range
    vs_ref,  # [1, 1, K, bk] fp32
    o_ref,  # [1, bq, hd]
    m_scr,  # VMEM [bq, 1]
    l_scr,  # VMEM [bq, 1]
    acc_scr,  # VMEM [bq, hd]
    *,
    bq: int,
    bk: int,
    scale: float,
    num_heads: int,
    group: int,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    b = bh // num_heads
    # Mosaic's tile rules reject a (1, bk) scale block ((1, 1, 1, bk) spec:
    # second-to-minor 1 neither divides 8 nor equals K), so the block carries
    # all K heads' scales — KBs — and the kernel row-selects its own kv head
    # with an iota mask (a [K, bk] VPU reduce, nothing on the payload path)
    kvh = (bh % num_heads) // group
    wi = wi_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_hi = wi + qi * bq + bq - 1  # last query slot of this q block
    overlap = (kj * bk + bk > kv_start_ref[b]) & (kj * bk < kv_len_ref[b])
    live = overlap & (kj * bk <= q_hi)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        # int8 payloads need NO validity masking (every bit pattern is
        # finite); invalid columns die via the score mask + zeroed scales —
        # dequantization rides the epilogues exactly as in _decode_kernel_q8
        k = k_ref[0, 0, 0].astype(q.dtype)  # [bk, hd]
        rows = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[2:], 0)  # [K, bk]
        ks_row = jnp.sum(jnp.where(rows == kvh, ks_ref[0, 0], 0.0), axis=0)
        vs_row = jnp.sum(jnp.where(rows == kvh, vs_ref[0, 0], 0.0), axis=0)
        cpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        cok = (cpos >= kv_start_ref[b]) & (cpos < kv_len_ref[b])
        # scales CAN be NaN past the frontier (uninitialized fp32 memory)
        ks = jnp.where(cok, ks_row[None, :], 0.0)  # [1, bk]
        vs = jnp.where(cok, vs_row[None, :], 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale * ks  # [bq, bk]; ks broadcasts over the bq rows

        q_pos = wi + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = (k_pos >= kv_start_ref[b]) & (k_pos < kv_len_ref[b]) & (k_pos <= q_pos)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = (p * vs).astype(q.dtype)  # V scale folded into the prob matrix
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pv, v_ref[0, 0, 0].astype(q.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def chunk_prefill_attention_q8(
    q: jax.Array,  # [B, S, H, hd] — one prompt chunk's fresh queries
    k_cache: jax.Array,  # [L, B, K, T, hd] int8
    v_cache: jax.Array,  # [L, B, K, T, hd] int8
    k_scale: jax.Array,  # [L, B, K, T] fp32
    v_scale: jax.Array,  # [L, B, K, T] fp32
    kv_start: jax.Array,  # [B] int32
    kv_len: jax.Array,  # [B] int32
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [] or [1] int32: cache slot of query 0
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``chunk_prefill_attention`` over an int8 KV cache: offset-causal
    flash attention where each query block streams the int8 cache blocks
    directly and dequantizes in the matmul EPILOGUES (score × k-scale,
    prob × v-scale) — the long-prompt int8 path never materializes a bf16
    layer slice, so chunked prefill keeps the bandwidth int8 bought.
    (Round 3 dequantized ``[1, B, K, T, hd]`` bf16 per layer per chunk.)

    Reached by chunks too long for one MXU pass a KV head (``G·S > 128``:
    prompt chunks over the largest bucket, the prefix cache's segment
    builder, dense chunked admission). A speculative verify step's
    ``spec_tokens + 1`` positions go to ``chunk_attention_grouped_q8``
    (``grouped_chunk_fits``): at S = 16 this grid is 544 steps of a 16-row
    matmul a call, each KV head's blocks streamed G times."""
    B, S, H, hd = q.shape
    L, _, K, T, _ = k_cache.shape
    G = H // K
    bq = _fit_block(S, bq)
    bk = _decode_block(T, bk)
    if not interpret and bk % 32:
        # int8 blocks need a 32-row second-to-minor tile on real hardware
        raise ValueError(
            f"cache length T={T} only tiles into blocks of {bk}: pad T to a "
            "multiple of 128 — the engine rounds cache lengths for this"
        )

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    grid = (B * H, S // bq, T // bk)

    def kv_index(bh, qi, kj, layer_ref, *s_):
        return (layer_ref[0], bh // H, (bh % H) // G, kj, 0)

    def sc_index(bh, qi, kj, layer_ref, *s_):
        return (layer_ref[0], bh // H, 0, kj)

    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel_q8, bq=bq, bk=bk, scale=hd**-0.5, num_heads=H, group=G
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
                pl.BlockSpec((1, 1, 1, bk, hd), kv_index),
                pl.BlockSpec((1, 1, 1, bk, hd), kv_index),
                pl.BlockSpec((1, 1, K, bk), sc_index),
                pl.BlockSpec((1, 1, K, bk), sc_index),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
        name="chunk_prefill_attention_q8",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(write_index, jnp.int32).reshape(1),
        kv_start.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        qt,
        k_cache,
        v_cache,
        k_scale,
        v_scale,
    )

    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# GQA-grouped chunk attention (small chunks over the dense cache)
# ---------------------------------------------------------------------------
#
# The per-head chunk kernels above were written for prompt chunks of hundreds
# of rows: grid (B·H, S//bq, T//bk), one query head a cell. Speculative
# verification hands the same path S = spec_tokens + 1 = 16 rows: every cell
# is then a [16, hd] × [bk, hd] matmul (16 of the MXU's 128 rows), the cache
# of a KV head is streamed once for each of its G query heads, and the grid
# steps alone (544 a call at 8B widths) outweigh the work twenty times over.
# The grouped kernel takes ``decode_attention``'s grid instead: (B, T//bk),
# every KV head's block in one cell, and the G query heads of a KV head
# folded with the S chunk positions into ONE matmul's G·S rows. The cache is
# streamed once, in T//bk steps. Arithmetic per (query, head) — payload,
# scales, fp32 accumulators, softmax recurrence, masks — is the per-head
# kernel's. The model step picks it by shape alone (``grouped_chunk_fits``).

GROUPED_CHUNK_ROWS = 128  # one pass of the MXU's rows
GROUPED_CELL_ROWS = 1024  # query rows of ALL kv heads a grid cell holds in VMEM


def grouped_chunk_fits(group: int, chunk: int, kv_heads: int) -> bool:
    """The shape rule ``LlamaModel._attend`` applies: the grouped kernel when
    a KV head's ``group · chunk`` query rows fit one MXU pass (and the cell's
    ``kv_heads`` of them fit VMEM: queries, output and the three accumulators
    stay resident), the per-head kernel for the long chunks it was written
    for. ``kv_heads`` is what one device holds (K/tp under ``shard_map``)."""
    rows = group * chunk
    return rows <= GROUPED_CHUNK_ROWS and kv_heads * rows <= GROUPED_CELL_ROWS


def _chunk_grouped_kernel(
    layer_ref,  # SMEM [1] (consumed by the index maps)
    wi_ref,  # SMEM [1]: write_index — global cache slot of query 0
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, K, G*S, hd] — row r of a kv head is (head r // S, query r % S)
    k_ref,  # [1, 1, K, bk, hd] (int8 when quantized)
    v_ref,  # [1, 1, K, bk, hd]
    *rest,  # quantized: ks_ref, vs_ref [1, 1, K, bk] fp32; then o_ref + scratch
    chunk: int,
    group: int,
    bk: int,
    scale: float,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    kj = pl.program_id(1)
    nk = pl.num_programs(1)
    wi = wi_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block skip, as in the per-head chunk kernel: inside the left pad, past
    # the frontier, or strictly above the last query's slot
    blk_lo = kj * bk
    overlap = (blk_lo + bk > kv_start_ref[b]) & (blk_lo < kv_len_ref[b])
    live = overlap & (blk_lo <= wi + chunk - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # [K, G*S, hd]
        cpos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        cok = (cpos >= kv_start_ref[b]) & (cpos < kv_len_ref[b])  # [1, bk]
        if quantized:
            # int8 payloads need no validity masking (every bit pattern is
            # finite); scales CAN be NaN past the frontier, so they are
            # zeroed under the window mask, used whole: [K, bk], no row-select
            k = k_ref[0, 0].astype(q.dtype)  # [K, bk, hd]
            v = v_ref[0, 0].astype(q.dtype)
            ks = jnp.where(cok, ks_ref[0, 0], 0.0)
            vs = jnp.where(cok, vs_ref[0, 0], 0.0)
        else:
            # zero K/V rows outside the valid window BEFORE any matmul (cache
            # slots past the frontier may be uninitialized; 0 * NaN = NaN)
            rpos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, (1, bk, 1), 1)
            rok = (rpos >= kv_start_ref[b]) & (rpos < kv_len_ref[b])
            k = jnp.where(rok, k_ref[0, 0], 0)
            v = jnp.where(rok, v_ref[0, 0], 0)
        # one batched dot over the kv heads: [K, G*S, hd] x [K, bk, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [K, G*S, bk]
        if quantized:
            s = s * ks[:, None, :]  # dequantization rides the epilogues

        # row r is query r % S: peeled off by G - 1 selects on one column
        # (no vector division); that query sits at cache slot wi + r % S
        r = jax.lax.broadcasted_iota(jnp.int32, (group * chunk, 1), 0)
        t = r
        for g in range(1, group):
            t = jnp.where(r >= g * chunk, r - g * chunk, t)
        ok = (cok & (cpos <= wi + t))[None]  # [1, G*S, bk], one mask for all K
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            p = p * vs[:, None, :]  # V scale folded into the prob matrix
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _chunk_grouped(q, k_cache, v_cache, scales, kv_start, kv_len, layer,
                   write_index, bk, interpret):
    """Both grouped kernels: ``scales`` is ``(k_scale, v_scale)`` over an
    int8 cache, ``None`` over a bf16 one."""
    B, S, H, hd = q.shape
    L, _, K, T, _ = k_cache.shape
    G = H // K
    rows = G * S
    quantized = scales is not None
    # what a cell holds in VMEM for each cache slot of its block: the fp32
    # score and probability columns [K, G*S], the K and V rows twice (the
    # pipeline's two buffers) and, over int8, their copies in q's dtype.
    # Keep that near 6 MiB whatever the head count.
    slot_bytes = K * (2 * rows * 4 + 4 * hd * k_cache.dtype.itemsize
                      + (2 * hd * q.dtype.itemsize if quantized else 0))
    while bk > 128 and slot_bytes * bk > 6 * 1024 * 1024:
        bk //= 2
    bk = _decode_block(T, bk)
    if not interpret and (bk % (32 if quantized else 16)
                          or (quantized and bk % 128 and bk != T)):
        # int8 blocks need a 32-row second-to-minor tile (bf16: 16), and the
        # scale block's minor dim is bk: lanes of 128, or the whole of T
        raise ValueError(
            f"cache length T={T} only tiles into blocks of {bk}: pad T to a "
            "multiple of 128 — the engine rounds cache lengths for this"
        )
    nk = T // bk

    # [B, S, K*G, hd] -> [B, K, G*S, hd]: head k*G + g, query s at row g*S + s
    qh = q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4).reshape(B, K, rows, hd)

    def live_block(b, kj, wi_ref, kv_start_ref, kv_len_ref):
        # dead blocks (left pad, past the frontier) name the nearest live
        # one, so the pipeline does not fetch what the body will not read
        lo = jnp.minimum(jax.lax.div(kv_start_ref[b], bk), nk - 1)
        end = jnp.minimum(kv_len_ref[b], wi_ref[0] + S)
        hi = jnp.minimum(jax.lax.div(jnp.maximum(end - 1, 0), bk), nk - 1)
        return jnp.clip(kj, lo, jnp.maximum(hi, lo))

    def kv_index(b, kj, layer_ref, *s_):
        return (layer_ref[0], b, 0, live_block(b, kj, *s_), 0)

    def sc_index(b, kj, layer_ref, *s_):
        return (layer_ref[0], b, 0, live_block(b, kj, *s_))

    def q_index(b, kj, *s_):
        return (b, 0, 0, 0)

    kv_spec = pl.BlockSpec((1, 1, K, bk, hd), kv_index)
    sc_specs = [pl.BlockSpec((1, 1, K, bk), sc_index)] * 2 if quantized else []
    out = pl.pallas_call(
        functools.partial(
            _chunk_grouped_kernel, chunk=S, group=G, bk=bk, scale=hd**-0.5,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nk),
            in_specs=[pl.BlockSpec((1, K, rows, hd), q_index), kv_spec, kv_spec,
                      *sc_specs],
            out_specs=pl.BlockSpec((1, K, rows, hd), q_index),
            scratch_shapes=[
                pltpu.VMEM((K, rows, 1), jnp.float32),
                pltpu.VMEM((K, rows, 1), jnp.float32),
                pltpu.VMEM((K, rows, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, rows, hd), q.dtype),
        interpret=interpret,
        name="chunk_attention_grouped_q8" if quantized else "chunk_attention_grouped",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(write_index, jnp.int32).reshape(1),
        kv_start.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        qh,
        k_cache,
        v_cache,
        *(scales or ()),
    )

    return out.reshape(B, K, G, S, hd).transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def chunk_attention_grouped(
    q: jax.Array,  # [B, S, H, hd] — a small chunk: G*S <= GROUPED_CHUNK_ROWS
    k_cache: jax.Array,  # [L, B, K, T, hd] — FULL stacked head-major cache
    v_cache: jax.Array,  # [L, B, K, T, hd]
    kv_start: jax.Array,  # [B] int32: first valid cache slot
    kv_len: jax.Array,  # [B] int32: valid frontier (= write_index + S)
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [] or [1] int32: cache slot of query 0
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``chunk_prefill_attention`` for SMALL chunks (see the note above):
    same arguments, same offset causality, same result; the cache is read
    once a KV head instead of once a query head."""
    return _chunk_grouped(q, k_cache, v_cache, None, kv_start, kv_len, layer,
                          write_index, bk, interpret)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def chunk_attention_grouped_q8(
    q: jax.Array,  # [B, S, H, hd] — a small chunk: G*S <= GROUPED_CHUNK_ROWS
    k_cache: jax.Array,  # [L, B, K, T, hd] int8
    v_cache: jax.Array,  # [L, B, K, T, hd] int8
    k_scale: jax.Array,  # [L, B, K, T] fp32
    v_scale: jax.Array,  # [L, B, K, T] fp32
    kv_start: jax.Array,  # [B] int32
    kv_len: jax.Array,  # [B] int32
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [] or [1] int32: cache slot of query 0
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``chunk_attention_grouped`` over an int8 KV cache: the speculative
    verify step's attention (S = spec_tokens + 1). Dequantization rides the
    two matmul epilogues as in ``chunk_prefill_attention_q8``."""
    return _chunk_grouped(q, k_cache, v_cache, (k_scale, v_scale), kv_start,
                          kv_len, layer, write_index, bk, interpret)


# ---------------------------------------------------------------------------
# paged KV cache (block-pool arena + per-row block tables)
# ---------------------------------------------------------------------------
#
# The dense kernels above read a [L, B, K, T, hd] cache whose T is the
# engine's FULL window for every row — at B=64 that is mostly pad (a
# 300-token prompt in a 4352-slot row): HBM ALLOCATED for every slot of it
# (the single-token kernels fetch only a row's live window since PR 32; the
# chunk kernels still fetch every block). The paged layout replaces the
# per-row T axis with a POOL of fixed-size blocks, [L, N, K, bs, hd], plus a
# per-row int32 block table mapping logical block j of row b to a physical
# pool block. The kernels below keep the BlockSpec pipeline: the K/V block
# index map reads the table (scalar prefetch, SMEM) — the flash recurrence,
# masking, and out-of-window block skip are the dense chunk kernels', and
# only a row's LIVE blocks are ever allocated or streamed, so decode
# bandwidth and footprint scale with real tokens, not the window.
#
# Geometry: paged rows are RIGHT-padded — logical positions start at 0, the
# valid window is [0, kv_len), and kv_start does not exist (this is also
# what makes prefix blocks shareable: a shared prompt head always occupies
# logical blocks 0..n at identical in-block offsets). Table entries for
# blocks a row has not reached point at the reserved null block 0
# (engine/kv_pool.py): the index map may prefetch it, but the block-skip
# predicate (kj * bs >= kv_len) guarantees it is never computed on.
#
# Tensor parallelism: on a tp>1 mesh the arena is HEAD-SHARDED — each device
# holds [L, N, K/tp, bs, hd], i.e. its K/tp kv heads of EVERY physical block
# (paged_partition_specs below; models/llama.py wraps these kernels in
# shard_map with exactly those rules). Block tables, kv_len, and the layer
# scalar stay replicated: allocation is per-ROW, never per-head, so one
# host-side table drives all shards and the free-list/ref-count allocator
# needs no tp awareness at all. Inside the shard each kernel is UNCHANGED —
# K in the shapes above is simply the local head count — and per-device
# decode bandwidth scales as live_tokens × K/tp; the cross-device reduce is
# the wo projection's row-parallel psum that XLA already inserts, identical
# to the dense tp path.


def _paged_decode_kernel(
    layer_ref,  # SMEM [1] (consumed by the index maps)
    tables_ref,  # SMEM [B * MB]: flattened block tables (index maps)
    kv_len_ref,  # SMEM [B]: valid logical frontier (exclusive)
    q_ref,  # [1, K, G, hd]
    k_ref,  # [1, 1, K, bs, hd] — the PHYSICAL block the table named
    v_ref,  # [1, 1, K, bs, hd]
    o_ref,  # [1, K, G, hd]
    m_scr,  # VMEM [K, G, 1]
    l_scr,  # VMEM [K, G, 1]
    acc_scr,  # VMEM [K, G, hd]
    *,
    bs: int,
    scale: float,
):
    b = pl.program_id(0)
    kj = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # logical block skip: blocks at/after the frontier were never allocated
    # (their table entries are the null block) — no work, no reads counted
    blk_lo = kj * bs
    live = blk_lo < kv_len_ref[b]

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # [K, G, hd]
        k = k_ref[0, 0]  # [K, bs, hd]
        v = v_ref[0, 0]
        # zero K/V rows past the frontier BEFORE any matmul: the frontier
        # block's tail slots may be uninitialized device memory, and a NaN
        # there survives even a zero-weight product (0 * NaN = NaN)
        rpos = blk_lo + jax.lax.broadcasted_iota(
            jnp.int32, (k.shape[0], k.shape[1], 1), 1
        )
        rok = rpos < kv_len_ref[b]
        k = jnp.where(rok, k, 0)
        v = jnp.where(rok, v, 0)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [K, G, bs]

        k_pos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        ok = k_pos < kv_len_ref[b]
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,  # [B, 1, H, hd] — the single fresh query token
    k_arena: jax.Array,  # [L, N, K, bs, hd] — the block-pool arena
    v_arena: jax.Array,  # [L, N, K, bs, hd]
    block_tables: jax.Array,  # [B, MB] int32: logical block -> physical block
    kv_len: jax.Array,  # [B] int32: valid logical frontier (exclusive)
    layer: jax.Array,  # [] or [1] int32
    interpret: bool = False,
) -> jax.Array:
    """``decode_attention`` over a paged arena: one grid cell per (row,
    logical block), the physical block resolved by the row's table inside
    the block index map (scalar prefetch — the table never leaves SMEM).
    Streaming layout, flash recurrence, and masking match the dense kernel;
    the only difference is WHICH ``(bs, hd)`` slabs get DMA'd."""
    B, S, H, hd = q.shape
    assert S == 1, f"paged_decode_attention is single-token (got S={S})"
    L, N, K, bs, _ = k_arena.shape
    G = H // K
    MB = block_tables.shape[1]
    if not interpret and bs % 16:
        raise ValueError(
            f"paged block_size={bs} must be a multiple of the Mosaic 16-row "
            "bf16 tile (EngineConfig.kv_block_size)"
        )

    qh = q.reshape(B, K, G, hd)
    grid = (B, MB)

    def kv_index(b, kj, layer_ref, tables_ref, *s_):
        return (layer_ref[0], tables_ref[b * MB + kj], 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs, scale=hd**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, K, G, hd), lambda b, kj, *s_: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, K, bs, hd), kv_index),
                pl.BlockSpec((1, 1, K, bs, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, K, G, hd), lambda b, kj, *s_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32).reshape(-1),
        kv_len.astype(jnp.int32),
        qh,
        k_arena,
        v_arena,
    )

    return out.reshape(B, 1, H, hd)


def _paged_decode_kernel_q8(
    layer_ref,  # SMEM [1]
    tables_ref,  # SMEM [B * MB]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, K, G, hd]
    k_ref,  # [1, 1, K, bs, hd] int8
    v_ref,  # [1, 1, K, bs, hd] int8
    ks_ref,  # [1, 1, K, bs] fp32
    vs_ref,  # [1, 1, K, bs] fp32
    o_ref,  # [1, K, G, hd]
    m_scr,  # VMEM [K, G, 1]
    l_scr,  # VMEM [K, G, 1]
    acc_scr,  # VMEM [K, G, hd]
    *,
    bs: int,
    scale: float,
):
    b = pl.program_id(0)
    kj = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    blk_lo = kj * bs
    live = blk_lo < kv_len_ref[b]

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # [K, G, hd]
        # int8 payloads need NO validity masking (every bit pattern is
        # finite); invalid columns die via the score mask + zeroed scales,
        # dequantization rides the epilogues exactly as in _decode_kernel_q8
        k = k_ref[0, 0].astype(q.dtype)  # [K, bs, hd]
        rpos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, (k.shape[0], bs), 1)
        rok = rpos < kv_len_ref[b]
        # scales CAN be NaN past the frontier (uninitialized fp32 memory)
        ks = jnp.where(rok, ks_ref[0, 0], 0.0)
        vs = jnp.where(rok, vs_ref[0, 0], 0.0)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale * ks[:, None, :]

        k_pos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        ok = k_pos < kv_len_ref[b]
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = (p * vs[:, None, :]).astype(q.dtype)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pv, v_ref[0, 0].astype(q.dtype), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_q8(
    q: jax.Array,  # [B, 1, H, hd]
    k_arena: jax.Array,  # [L, N, K, bs, hd] int8
    v_arena: jax.Array,  # [L, N, K, bs, hd] int8
    k_scale: jax.Array,  # [L, N, K, bs] fp32
    v_scale: jax.Array,  # [L, N, K, bs] fp32
    block_tables: jax.Array,  # [B, MB] int32
    kv_len: jax.Array,  # [B] int32
    layer: jax.Array,  # [] or [1] int32
    interpret: bool = False,
) -> jax.Array:
    """``paged_decode_attention`` over an int8 arena: the table indirection
    of the paged kernel + the epilogue dequantization of the q8 kernel."""
    B, S, H, hd = q.shape
    assert S == 1, f"paged_decode_attention_q8 is single-token (got S={S})"
    L, N, K, bs, _ = k_arena.shape
    G = H // K
    MB = block_tables.shape[1]
    if not interpret and bs % 32:
        # int8 blocks need a 32-row second-to-minor tile on real hardware
        raise ValueError(
            f"paged block_size={bs} must be a multiple of the Mosaic 32-row "
            "int8 tile under kv_quant='int8' (EngineConfig.kv_block_size)"
        )

    qh = q.reshape(B, K, G, hd)
    grid = (B, MB)

    def kv_index(b, kj, layer_ref, tables_ref, *s_):
        return (layer_ref[0], tables_ref[b * MB + kj], 0, 0, 0)

    def sc_index(b, kj, layer_ref, tables_ref, *s_):
        return (layer_ref[0], tables_ref[b * MB + kj], 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel_q8, bs=bs, scale=hd**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, K, G, hd), lambda b, kj, *s_: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, K, bs, hd), kv_index),
                pl.BlockSpec((1, 1, K, bs, hd), kv_index),
                pl.BlockSpec((1, 1, K, bs), sc_index),
                pl.BlockSpec((1, 1, K, bs), sc_index),
            ],
            out_specs=pl.BlockSpec((1, K, G, hd), lambda b, kj, *s_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention_q8",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32).reshape(-1),
        kv_len.astype(jnp.int32),
        qh,
        k_arena,
        v_arena,
        k_scale,
        v_scale,
    )

    return out.reshape(B, 1, H, hd)


def _paged_chunk_kernel(
    layer_ref,  # SMEM [1]
    wi_ref,  # SMEM [B]: per-row logical slot of query 0
    tables_ref,  # SMEM [B * MB]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, bq, hd]
    k_ref,  # [1, 1, 1, bs, hd]
    v_ref,  # [1, 1, 1, bs, hd]
    o_ref,  # [1, bq, hd]
    m_scr,  # VMEM [bq, 1]
    l_scr,  # VMEM [bq, 1]
    acc_scr,  # VMEM [bq, hd]
    *,
    bq: int,
    bs: int,
    scale: float,
    num_heads: int,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    b = bh // num_heads
    wi = wi_ref[b]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block skip: logical blocks past the frontier or strictly above the
    # OFFSET causal diagonal (query t sits at logical slot wi + t) do no work
    q_hi = wi + qi * bq + bq - 1
    live = (kj * bs < kv_len_ref[b]) & (kj * bs <= q_hi)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0, 0, 0]
        v = v_ref[0, 0, 0]
        # zero K/V rows past the frontier BEFORE any matmul (frontier-block
        # tail slots may be uninitialized; 0 * NaN = NaN)
        cpos = kj * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        cok = cpos < kv_len_ref[b]
        k = jnp.where(cok, k, 0)
        v = jnp.where(cok, v, 0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bs]

        q_pos = wi + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
        k_pos = kj * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        ok = (k_pos < kv_len_ref[b]) & (k_pos <= q_pos)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_chunk_attention(
    q: jax.Array,  # [B, S, H, hd] — one prompt chunk's fresh queries
    k_arena: jax.Array,  # [L, N, K, bs, hd]
    v_arena: jax.Array,  # [L, N, K, bs, hd]
    block_tables: jax.Array,  # [B, MB] int32
    kv_len: jax.Array,  # [B] int32: valid frontier (= write_index + chunk len)
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [B] int32: per-row logical slot of query 0
    bq: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``chunk_prefill_attention`` over a paged arena (the paged
    chunked-prefill path): each query block streams its row's LIVE blocks
    via the table with offset causality. The chunk's own K/V must already
    be scattered into the row's blocks (the model writes before attending,
    exactly like the dense chunk path). ``write_index`` is per-row — paged
    rows are right-padded, so rows at different depths chunk together.

    This is also THE multi-position paged DECODE kernel: the speculative
    verify step (``ContinuousEngine._build_verify_paged``) feeds every
    row ``last_tok`` + its K drafted tokens as one S = K+1 "chunk" at the
    row's own frontier (``write_index = kv_len``, per-row), so a verify
    window streams each row's live blocks ONCE for K+1 query lanes —
    decode is bandwidth-bound, which is exactly why a K+1-wide verify
    costs ~one decode step. Junk lanes past a row's real draft count are
    masked by its ``kv_len`` window, never by extra kernel logic.

    Its third consumer is the UNIFIED ragged sync window
    (``ContinuousEngine._build_mixed_step``, ISSUE 16): decode lanes
    (write_index = the row's frontier, one real query) and
    chunked-prefill lanes (write_index = the admission's progress
    offset, up to S real queries) ride the SAME S-wide call — the
    per-row ``write_index``/``kv_len`` vectors are what lets rows play
    different roles in one grid, with no new kernel logic."""
    B, S, H, hd = q.shape
    L, N, K, bs, _ = k_arena.shape
    G = H // K
    MB = block_tables.shape[1]
    bq = _fit_block(S, bq)
    if not interpret and bs % 16:
        raise ValueError(
            f"paged block_size={bs} must be a multiple of the Mosaic 16-row "
            "bf16 tile (EngineConfig.kv_block_size)"
        )

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    grid = (B * H, S // bq, MB)

    def kv_index(bh, qi, kj, layer_ref, wi_ref, tables_ref, *s_):
        return (
            layer_ref[0],
            tables_ref[(bh // H) * MB + kj],
            (bh % H) // G,
            0,
            0,
        )

    out = pl.pallas_call(
        functools.partial(
            _paged_chunk_kernel, bq=bq, bs=bs, scale=hd**-0.5, num_heads=H
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
                pl.BlockSpec((1, 1, 1, bs, hd), kv_index),
                pl.BlockSpec((1, 1, 1, bs, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
        name="paged_chunk_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.broadcast_to(jnp.asarray(write_index, jnp.int32), (B,)),
        block_tables.astype(jnp.int32).reshape(-1),
        kv_len.astype(jnp.int32),
        qt,
        k_arena,
        v_arena,
    )

    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _paged_chunk_kernel_q8(
    layer_ref,  # SMEM [1]
    wi_ref,  # SMEM [B]: per-row logical slot of query 0
    tables_ref,  # SMEM [B * MB]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [1, bq, hd]
    k_ref,  # [1, 1, 1, bs, hd] int8
    v_ref,  # [1, 1, 1, bs, hd] int8
    ks_ref,  # [1, 1, K, bs] fp32 — ALL kv heads' scales for this block
    vs_ref,  # [1, 1, K, bs] fp32
    o_ref,  # [1, bq, hd]
    m_scr,  # VMEM [bq, 1]
    l_scr,  # VMEM [bq, 1]
    acc_scr,  # VMEM [bq, hd]
    *,
    bq: int,
    bs: int,
    scale: float,
    num_heads: int,
    group: int,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    b = bh // num_heads
    # same Mosaic tile workaround as _chunk_kernel_q8: a (1, bs) scale
    # block is untileable, so the block carries all K heads' scales and
    # the kernel row-selects its own kv head with an iota mask
    kvh = (bh % num_heads) // group
    wi = wi_ref[b]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block skip: logical blocks past the frontier or strictly above the
    # OFFSET causal diagonal (query t sits at logical slot wi + t) do no work
    q_hi = wi + qi * bq + bq - 1
    live = (kj * bs < kv_len_ref[b]) & (kj * bs <= q_hi)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        # int8 payloads need NO validity masking (every bit pattern is
        # finite); invalid columns die via the score mask + zeroed scales —
        # dequantization rides the matmul EPILOGUES (score × k-scale,
        # prob × v-scale) exactly as in the dense q8 chunk kernel, so
        # warm-tier prefill keeps the bandwidth int8 bought instead of
        # paying the gather oracle's
        k = k_ref[0, 0, 0].astype(q.dtype)  # [bs, hd]
        rows = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[2:], 0)  # [K, bs]
        ks_row = jnp.sum(jnp.where(rows == kvh, ks_ref[0, 0], 0.0), axis=0)
        vs_row = jnp.sum(jnp.where(rows == kvh, vs_ref[0, 0], 0.0), axis=0)
        cpos = kj * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        cok = cpos < kv_len_ref[b]
        # scales CAN be NaN past the frontier (uninitialized fp32 memory)
        ks = jnp.where(cok, ks_row[None, :], 0.0)  # [1, bs]
        vs = jnp.where(cok, vs_row[None, :], 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale * ks  # [bq, bs]; ks broadcasts over the bq rows

        q_pos = wi + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
        k_pos = kj * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        ok = (k_pos < kv_len_ref[b]) & (k_pos <= q_pos)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = (p * vs).astype(q.dtype)  # V scale folded into the prob matrix
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pv, v_ref[0, 0, 0].astype(q.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_chunk_attention_q8(
    q: jax.Array,  # [B, S, H, hd] — one prompt chunk's fresh queries
    k_arena: jax.Array,  # [L, N, K, bs, hd] int8
    v_arena: jax.Array,  # [L, N, K, bs, hd] int8
    k_scale: jax.Array,  # [L, N, K, bs] fp32
    v_scale: jax.Array,  # [L, N, K, bs] fp32
    block_tables: jax.Array,  # [B, MB] int32
    kv_len: jax.Array,  # [B] int32
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [B] int32: per-row logical slot of query 0
    bq: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``paged_chunk_attention`` over an int8 arena: the table indirection
    of the paged chunk kernel + the epilogue dequantization of the q8
    kernels. PR 5 left this path on the gather XLA oracle — which
    materialized a dequantized logical view per layer, spending the
    bandwidth the int8 arena bought; fused, warm-tier (int8) chunked
    prefill streams the int8 blocks directly like every other q8 path."""
    B, S, H, hd = q.shape
    L, N, K, bs, _ = k_arena.shape
    G = H // K
    MB = block_tables.shape[1]
    bq = _fit_block(S, bq)
    if not interpret and bs % 32:
        # int8 blocks need a 32-row second-to-minor tile on real hardware
        raise ValueError(
            f"paged block_size={bs} must be a multiple of the Mosaic 32-row "
            "int8 tile under kv_quant='int8' (EngineConfig.kv_block_size)"
        )

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    grid = (B * H, S // bq, MB)

    def kv_index(bh, qi, kj, layer_ref, wi_ref, tables_ref, *s_):
        return (
            layer_ref[0],
            tables_ref[(bh // H) * MB + kj],
            (bh % H) // G,
            0,
            0,
        )

    def sc_index(bh, qi, kj, layer_ref, wi_ref, tables_ref, *s_):
        return (layer_ref[0], tables_ref[(bh // H) * MB + kj], 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _paged_chunk_kernel_q8, bq=bq, bs=bs, scale=hd**-0.5,
            num_heads=H, group=G,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
                pl.BlockSpec((1, 1, 1, bs, hd), kv_index),
                pl.BlockSpec((1, 1, 1, bs, hd), kv_index),
                pl.BlockSpec((1, 1, K, bs), sc_index),
                pl.BlockSpec((1, 1, K, bs), sc_index),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, kj, *s_: (bh, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
        name="paged_chunk_attention_q8",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.broadcast_to(jnp.asarray(write_index, jnp.int32), (B,)),
        block_tables.astype(jnp.int32).reshape(-1),
        kv_len.astype(jnp.int32),
        qt,
        k_arena,
        v_arena,
        k_scale,
        v_scale,
    )

    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def paged_partition_specs(mode: str, q8: bool = False):
    """``(in_specs, out_spec)`` for ``shard_map``-ing the paged kernels over
    the ``tp`` mesh axis — THE partition rules of the head-sharded arena
    layout (kept here, next to the kernels they describe, so the model and
    the parity tests lower the exact same specs):

    - q / output ``[B, S, H, hd]`` → heads over ``tp``;
    - arena planes ``[L, N, K, bs, hd]`` (and ``[L, N, K, bs]`` scales) →
      kv heads over ``tp``: every device holds K/tp heads of EVERY block;
    - block tables ``[B, MB]``, ``kv_len [B]``, ``layer [1]``, and the
      chunk path's per-row ``write_index [B]`` → replicated (allocation is
      per-row, so one host table serves all shards).

    ``mode``: ``"decode"`` (args ``q, k, v[, ks, vs], tables, kv_len,
    layer``) or ``"chunk"`` (args ``q, k, v[, ks, vs], tables, kv_len,
    layer, wi``)."""
    from jax.sharding import PartitionSpec as P

    hspec = P(None, None, "tp", None)  # q / o: [B, S, H, hd]
    aspec = P(None, None, "tp", None, None)  # arena: [L, N, K, bs, hd]
    sspec = P(None, None, "tp", None)  # scales: [L, N, K, bs]
    tspec = P(None, None)  # tables: [B, MB]
    vspec = P(None)  # kv_len / layer / write_index
    if mode == "decode":
        if q8:
            return (hspec, aspec, aspec, sspec, sspec, tspec, vspec, vspec), hspec
        return (hspec, aspec, aspec, tspec, vspec, vspec), hspec
    if mode == "chunk":
        if q8:
            return (
                (hspec, aspec, aspec, sspec, sspec, tspec, vspec, vspec,
                 vspec),
                hspec,
            )
        return (hspec, aspec, aspec, tspec, vspec, vspec, vspec), hspec
    raise ValueError(f"paged_partition_specs: unknown mode {mode!r}")


def _gather_paged_layer(
    arena: jax.Array,  # [L, N, K, bs, hd] (or [L, N, K, bs] for scales)
    block_tables: jax.Array,  # [B, MB] int32
    layer: jax.Array,  # [] or [1] int32
) -> jax.Array:
    """``[B, K, MB*bs(, hd)]`` logical view of ONE layer, assembled by
    gathering each row's blocks — the shared helper of the XLA oracles (a
    per-layer gather is MBs; CPU tests and the q8 chunk fallback use it,
    the Pallas kernels never materialize it)."""
    lay = jnp.asarray(layer, jnp.int32).reshape(())
    al = jax.lax.dynamic_index_in_dim(arena, lay, 0, keepdims=False)
    g = jnp.take(al, block_tables, axis=0)  # [B, MB, K, bs(, hd)]
    if g.ndim == 5:
        B, MB, K, bs, hd = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(B, K, MB * bs, hd)
    B, MB, K, bs = g.shape
    return g.transpose(0, 2, 1, 3).reshape(B, K, MB * bs)


def paged_decode_attention_xla(
    q: jax.Array,  # [B, 1, H, hd]
    k_arena: jax.Array,  # [L, N, K, bs, hd]
    v_arena: jax.Array,  # [L, N, K, bs, hd]
    block_tables: jax.Array,  # [B, MB]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
) -> jax.Array:
    """Dense XLA reference for ``paged_decode_attention`` (oracle; fallback
    off-TPU): gather each row's blocks into a logical [B, K, T', hd] view,
    then the dense decode math over the [0, kv_len) window. Gathered slots
    past the frontier zero out first — they can be null-block junk (and in
    tests deliberately NaN), and 0 * NaN = NaN survives the prob mask."""
    k = _zero_invalid(_gather_paged_layer(k_arena, block_tables, layer), kv_len)[None]
    v = _zero_invalid(_gather_paged_layer(v_arena, block_tables, layer), kv_len)[None]
    B = q.shape[0]
    zero = jnp.zeros((B,), jnp.int32)
    return decode_attention_xla(q, k, v, zero, kv_len, jnp.int32(0))


def _zero_invalid(x: jax.Array, kv_len: jax.Array) -> jax.Array:
    """Zero logical slots >= kv_len of a gathered ``[B, K, T'(, hd)]``
    view (the oracle-side mirror of the kernels' pre-matmul zeroing)."""
    T = x.shape[2]
    ok = jnp.arange(T)[None, None, :] < kv_len[:, None, None]
    if x.ndim == 4:
        ok = ok[..., None]
    return jnp.where(ok, x, 0)


def paged_decode_attention_xla_q8(
    q: jax.Array,  # [B, 1, H, hd]
    k_arena: jax.Array,  # [L, N, K, bs, hd] int8
    v_arena: jax.Array,  # [L, N, K, bs, hd] int8
    k_scale: jax.Array,  # [L, N, K, bs] fp32
    v_scale: jax.Array,  # [L, N, K, bs] fp32
    block_tables: jax.Array,  # [B, MB]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
) -> jax.Array:
    """Dense XLA reference for ``paged_decode_attention_q8``: gather +
    window-masked dequant of this layer's blocks, then the bf16 oracle."""
    kd, vd = _dequant_paged_layer(
        k_arena, v_arena, k_scale, v_scale, block_tables, kv_len, layer, q.dtype
    )
    B = q.shape[0]
    zero = jnp.zeros((B,), jnp.int32)
    return decode_attention_xla(q, kd, vd, zero, kv_len, jnp.int32(0))


def _dequant_paged_layer(
    k_arena, v_arena, k_scale, v_scale, block_tables, kv_len, layer, dtype
):
    """Gathered, dequantized ``[1, B, K, T', hd]`` K/V views of one layer
    of an int8 arena. Scales past the frontier zero out under the window
    mask (they can be uninitialized fp32 = NaN; the int8 payload is finite
    by construction), so invalid slots contribute exactly 0."""
    k = _gather_paged_layer(k_arena, block_tables, layer)
    v = _gather_paged_layer(v_arena, block_tables, layer)
    ks = _gather_paged_layer(k_scale, block_tables, layer)
    vs = _gather_paged_layer(v_scale, block_tables, layer)
    T = k.shape[2]
    t_ok = jnp.arange(T)[None, None, :] < kv_len[:, None, None]  # [B, 1, T]
    ks = jnp.where(t_ok, ks, 0.0)
    vs = jnp.where(t_ok, vs, 0.0)
    kd = (k.astype(jnp.float32) * ks[..., None]).astype(dtype)[None]
    vd = (v.astype(jnp.float32) * vs[..., None]).astype(dtype)[None]
    return kd, vd


def paged_chunk_attention_xla(
    q: jax.Array,  # [B, S, H, hd]
    k_arena: jax.Array,  # [L, N, K, bs, hd]
    v_arena: jax.Array,  # [L, N, K, bs, hd]
    block_tables: jax.Array,  # [B, MB]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [B] int32: per-row logical slot of query 0
) -> jax.Array:
    """Dense XLA reference for ``paged_chunk_attention`` (oracle; fallback
    off-TPU). Offset causality is PER-ROW (``write_index`` is a vector —
    paged rows are right-padded and chunk at their own depths)."""
    k = _zero_invalid(_gather_paged_layer(k_arena, block_tables, layer), kv_len)[None]
    v = _zero_invalid(_gather_paged_layer(v_arena, block_tables, layer), kv_len)[None]
    return _paged_chunk_on_views(q, k, v, kv_len, write_index)


def paged_chunk_attention_xla_q8(
    q: jax.Array,  # [B, S, H, hd]
    k_arena: jax.Array,  # [L, N, K, bs, hd] int8
    v_arena: jax.Array,  # [L, N, K, bs, hd] int8
    k_scale: jax.Array,  # [L, N, K, bs] fp32
    v_scale: jax.Array,  # [L, N, K, bs] fp32
    block_tables: jax.Array,  # [B, MB]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [B] int32
) -> jax.Array:
    """Dense XLA reference for ``paged_chunk_attention_q8`` (oracle; the
    off-TPU fallback): gather + dequantize ONE layer's blocks, then the
    bf16 oracle. Serving uses the fused kernel above — this path
    materializes a dequantized logical view per layer, spending the
    bandwidth the int8 arena bought."""
    kd, vd = _dequant_paged_layer(
        k_arena, v_arena, k_scale, v_scale, block_tables, kv_len, layer, q.dtype
    )
    return _paged_chunk_on_views(q, kd, vd, kv_len, write_index)


def _paged_chunk_on_views(q, kd, vd, kv_len, write_index):
    """Offset-causal attention over already-gathered [1, B, K, T, hd]
    views (the q8 oracle's tail — shares the masking math above)."""
    B, S, H, hd = q.shape
    K = kd.shape[2]
    G = H // K
    k = kd[0]
    v = vd[0]
    T = k.shape[2]
    qg = q.reshape(B, S, K, G, hd)
    s = jnp.einsum("bqkgd,bktd->bkgqt", qg, k, preferred_element_type=jnp.float32)
    s = s * (hd**-0.5)
    wi = jnp.broadcast_to(jnp.asarray(write_index, jnp.int32), (B,))
    q_pos = wi[:, None] + jnp.arange(S)[None, :]
    t_pos = jnp.arange(T)
    ok = t_pos[None, None, :] < kv_len[:, None, None]
    ok = ok & (t_pos[None, None, :] <= q_pos[:, :, None])
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok[:, None, None, :, :], p, 0.0)
    o = jnp.einsum(
        "bkgqt,bktd->bqkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, S, H, hd).astype(q.dtype)


def chunk_attention_xla_q8(
    q: jax.Array,  # [B, S, H, hd]
    k_cache: jax.Array,  # [L, B, K, T, hd] int8
    v_cache: jax.Array,  # [L, B, K, T, hd] int8
    k_scale: jax.Array,  # [L, B, K, T] fp32
    v_scale: jax.Array,  # [L, B, K, T] fp32
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
    write_index: jax.Array,  # [] int32
) -> jax.Array:
    """Dense XLA reference for ``chunk_prefill_attention_q8`` (oracle; CPU
    path). Dequantizes THIS layer's cache slice and reuses the bf16 oracle."""
    kd = dequantize_layer_slice(k_cache, k_scale, layer, kv_start, kv_len, q.dtype)
    vd = dequantize_layer_slice(v_cache, v_scale, layer, kv_start, kv_len, q.dtype)
    return chunk_attention_xla(q, kd, vd, kv_start, kv_len, jnp.int32(0), write_index)


def decode_attention_xla_q8(
    q: jax.Array,  # [B, 1, H, hd]
    k_cache: jax.Array,  # [L, B, K, T, hd] int8
    v_cache: jax.Array,  # [L, B, K, T, hd] int8
    k_scale: jax.Array,  # [L, B, K, T] fp32
    v_scale: jax.Array,  # [L, B, K, T] fp32
    kv_start: jax.Array,  # [B]
    kv_len: jax.Array,  # [B]
    layer: jax.Array,  # [] or [1] int32
) -> jax.Array:
    """Dense XLA reference for ``decode_attention_q8`` (oracle; CPU path).
    Dequantizes THIS layer's cache slice and reuses the bf16 oracle."""
    kd = dequantize_layer_slice(k_cache, k_scale, layer, kv_start, kv_len, q.dtype)
    vd = dequantize_layer_slice(v_cache, v_scale, layer, kv_start, kv_len, q.dtype)
    return decode_attention_xla(q, kd, vd, kv_start, kv_len, jnp.int32(0))


# ---------------------------------------------------------------------------
# a sliding layer's prefill where the window fits ONE step
# (``flash_attention_window``, built by ``_flash_call`` where
# ``flash_window_step`` names a span). At the end of the file so that the
# kernels above keep their lines, which the compile cache keys on.
# ---------------------------------------------------------------------------


def flash_walk_blocks(Sq: int, Sk: int, G: int, dq: int, dv: int, causal: bool, itemsize: int,
                      bq: Optional[int] = None, bk: Optional[int] = None,
                      resident: Optional[bool] = None) -> Tuple[int, int, bool]:
    """``(bq, bk, resident)`` of the block walk (``_flash_kernel``): what the
    caller gave, else ``flash_blocks``' rule on the shape; the strips resident
    where they fit beside the query block, and a streamed step one block of a
    wide step's keys."""
    wide = FLASH_WIDE if causal else 1
    rule_bq, rule_bk = flash_blocks(max(Sq, Sk), G, dq, dv, causal, itemsize)
    bq = _fit_block(Sq, bq or rule_bq)
    if resident is None:
        resident = _flash_fits(Sk, G * bq, min(bk or rule_bk, Sk), wide, dq, dv, itemsize)
    return bq, _fit_block(Sk, bk or (rule_bk if resident else wide * rule_bk)), resident


def flash_window_step(S: int, G: int, dq: int, dv: int, window: int, itemsize: int = 2,
                      bq: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """``(bq, span)`` where a causal windowed prefill over ``S`` keys takes a
    query block's window in ONE step, ``None`` where it keeps the block walk;
    from the shape alone (no option, no model's name).

    A block of ``bq`` queries under a window of ``W`` sees at most ``W + bq -
    1`` keys. The walk cuts them at multiples of ``bk`` = 512, so at ``W`` =
    512 they always straddle TWO blocks, both under the mask, and the second
    rescales sum and accumulator: 1024 keys multiplied for 512 live (1.405 ms
    a call at ``[72, 4096, 128]``: PERF.md §6, PR 47). One slice of ``span`` =
    ``W`` (rounded up to ``bq``, so that the slice starts on a query block's
    boundary) + ``bq`` keys, from ``bq·(qi + 1) - span``, holds them all: one
    softmax pass, no running state. It is taken where the slice is shorter
    than the strip (else the window bounds nothing worth a second kernel) and
    the resident strips and ``G·bq`` rows of ``span`` masked keys fit
    (``_flash_fits``: 1152 rows × 640 keys at G = 9, W = 512). A window in the
    thousands, or a strip that is streamed (S ≥ 16384), keeps the walk."""
    bq = _fit_block(S, bq or flash_blocks(S, G, dq, dv, True, itemsize)[0])
    span = -(-window // bq) * bq + bq
    if span >= S or not _flash_fits(S, G * bq, span, 1, dq, dv, itemsize):
        return None
    return bq, span


def flash_window_plan(qi, kv_start, kv_len, bq: int, span: int, window: int):
    """The one step of query block ``qi``: ``(off, live, steady)``. The step
    multiplies the block's queries by the ``span`` keys from ``off`` (a
    multiple of ``bq``, inside the strip), which hold every key a query of
    the block may see: ``k <= q``, ``k > q - window``. ``live``: some pair of
    them is (else the block's output is zeros and nothing is multiplied: a
    block in the left pad, an empty row). ``steady``: the slice lies wholly
    inside ``[kv_start, kv_len)`` and starts where the rule puts it, so the
    mask is the same two edges at every such block. Integer arithmetic only:
    the kernel's scalars, the model's counters and the tests read this rule."""
    q_lo = qi * bq
    at = q_lo + bq - span  # where the oldest key the block's first query sees is rounded down to
    off = jnp.maximum(at, 0)
    live = (kv_len > kv_start) & (q_lo + bq - 1 >= kv_start) & (kv_len > q_lo - window + 1)
    steady = (at >= 0) & (kv_start <= at) & (kv_len >= q_lo + bq)
    return off, live, steady


def flash_window_pairs(kv_start, kv_len, S: int, G: int, dq: int, dv: int, window: int, itemsize: int = 2):
    """``(multiplied, live)``: the query-key pairs of ONE query head that a
    windowed prefill call's steps multiply over the rows ``kv_start`` /
    ``kv_len`` describe, and the pairs of them that are live (``kv_start <= k
    < kv_len``, ``q - window < k <= q``). In the form the shape takes: a live
    block's ``bq × span`` under ``flash_window_step``, else the blocks the
    walk visits (``flash_block_plan``). int32: a row of a 4096 bucket is 2.6e6
    pairs, so 2^31 are 800 row-layers; a cache's counters start a call at 0."""
    kv_start, kv_len = kv_start[:, None], kv_len[:, None]
    step = flash_window_step(S, G, dq, dv, window, itemsize)
    if step:
        bq, span = step
        _, live, _ = flash_window_plan(jnp.arange(S // bq)[None, :], kv_start, kv_len, bq, span, window)
        multiplied = jnp.sum(live) * (bq * span)
    else:
        bq, bk, _ = flash_walk_blocks(S, S, G, dq, dv, True, itemsize)
        lo, hi, _, _ = flash_block_plan(jnp.arange(S // bq)[None, :], kv_start, kv_len, S, bq, bk, True, window)
        multiplied = jnp.sum(jnp.maximum(hi - lo + 1, 0)) * (bq * bk)
    q = jnp.arange(S)[None, :]
    keys = jnp.minimum(q, kv_len - 1) - jnp.maximum(kv_start, q - window + 1) + 1
    return multiplied, jnp.sum(jnp.maximum(keys, 0))


def _window_kernel(
    kv_start_ref,  # SMEM [B]
    kv_len_ref,  # SMEM [B]
    q_ref,  # [G, bq, dq]: the G query heads of one KV head
    k_ref,  # [1, S, dq]: that KV head's whole strip, resident across q blocks
    v_ref,  # [1, S, dv]
    o_ref,  # [G, bq, dv]
    *,
    bq: int,
    span: int,
    window: int,
    scale: float,
    kv_heads: int,
):
    """One grid cell = one step: the block's ``G·bq`` rows against the
    ``span`` keys ``flash_window_plan`` names, a whole softmax (no running
    max, sum or accumulator: nothing came before and nothing comes after)."""
    G = q_ref.shape[0]
    rows = G * bq
    qi = pl.program_id(1)
    b = pl.program_id(0) // kv_heads
    start, end = kv_start_ref[b], kv_len_ref[b]
    off, live, steady = flash_window_plan(qi, start, end, bq, span, window)
    off = pl.multiple_of(off, bq)
    slack = span - bq - window  # keys the rounding put in front of the oldest a first query sees
    # in a steady step a pair can be masked only by the window's edge, in the
    # first columns, or by the diagonal, in the last ``bq``: the columns
    # between are live whoever asks
    left = bq * (1 + (slack > 0))
    edges = left + bq < span

    def tile():
        # row r is query r % bq of the block: peeled off by G - 1 selects on
        # one column (no vector division)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        t = r
        for g in range(1, G):
            t = jnp.where(r >= g * bq, r - g * bq, t)
        return t

    def scores(k):
        q = q_ref[:].reshape(rows, q_ref.shape[2])
        return jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, span]

    def store(p, l, v):
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        out = (pv / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)  # a row with no live key: zeros
        for g in range(G):
            o_ref[g] = out[g * bq:(g + 1) * bq]

    @pl.when(~live)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live & ~(steady & edges))
    def _general():
        k = k_ref[0, pl.ds(off, span), :]
        v = v_ref[0, pl.ds(off, span), :]
        # zero K/V rows outside the valid window BEFORE any matmul: cache
        # slots past the frontier may be uninitialized device memory, and a
        # NaN there survives even a zero-weight product (0 * NaN = NaN)
        cpos = off + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        cok = (cpos >= start) & (cpos < end)
        k = jnp.where(cok, k, 0)
        v = jnp.where(cok, v, 0)
        s = scores(k)
        # a key outside the live slots sits past every query: the window, the
        # diagonal and the row's slots are then two compares over the step
        k_pos = off + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        k_pos = jnp.where((k_pos >= start) & (k_pos < end), k_pos, _NO_KEY)
        q_pos = qi * bq + tile()
        ok = (k_pos <= q_pos) & (k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)
        # the max is floored far above NEG_INF, so a masked entry is an exact
        # zero even where a whole row is masked (s and its max both at NEG_INF
        # would make exp(s - m) 1): no second select over the step
        m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), 0.5 * NEG_INF)
        p = jnp.exp(s - m)
        store(p, jnp.sum(p, axis=1, keepdims=True), v)

    if not edges:
        return

    @pl.when(live & steady)
    def _steady():
        # every key of the slice is a live slot and every query has its own
        # key: no zeroing, no dead row, and a mask on the two edges alone.
        # Column c is key ``off + c``, row r query ``off + span - bq + t``
        k = k_ref[0, pl.ds(off, span), :]
        v = v_ref[0, pl.ds(off, span), :]
        s = scores(k)
        t = tile()
        c = jax.lax.broadcasted_iota(jnp.int32, (1, left), 1)
        head = jnp.where(c > t + slack, s[:, :left], NEG_INF)  # k > q - window
        c = jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
        tail = jnp.where(c <= t, s[:, span - bq:], NEG_INF)  # k <= q
        s = jnp.concatenate([head, s[:, left:span - bq], tail], axis=1)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        store(p, jnp.sum(p, axis=1, keepdims=True), v)


def _window_call(qt, kt, vt, kv_start, kv_len, bq, span, *, scale, window, interpret, name):
    """The one-step windowed prefill ``pallas_call``: ``_flash_call``'s
    arrays, grid and result (so a trace reads it under the same name and
    result type), no scratch."""
    BH, S, dq = qt.shape
    BK, _, dv = vt.shape
    G = BH // BK

    def q_index(h, qi, *s_):
        return (h, qi, 0)

    def kv_index(h, qi, *s_):
        return (h, 0, 0)

    return pl.pallas_call(
        functools.partial(_window_kernel, bq=bq, span=span, window=window, scale=scale,
                          kv_heads=BK // kv_start.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BK, S // bq),
            in_specs=[
                pl.BlockSpec((G, bq, dq), q_index),
                pl.BlockSpec((1, S, dq), kv_index),
                pl.BlockSpec((1, S, dv), kv_index),
            ],
            out_specs=pl.BlockSpec((G, bq, dv), q_index),
        ),
        out_shape=jax.ShapeDtypeStruct((BH, S, dv), qt.dtype),
        interpret=interpret,
        name=name,
    )(kv_start.astype(jnp.int32), kv_len.astype(jnp.int32), qt, kt, vt)

"""Sparse experts: routing by the two published rules this family spans, and
this chip's share of the routed experts as grouped matmuls over the
assignments it holds.

``route`` scores ALL of the router's outputs (sigmoid, or softmax over the
routed experts and the zero-computation ones after them), chooses by score
plus a correction bias (group-limited where there are groups), and weighs by
the score itself. From a prefill's size on all of it after the router's dot is
ONE kernel over token tiles (``route_topk``: scores, the groups' mask,
``top_k`` rounds of largest-and-mask that take the score in the same round,
the normalising sum and the scaling; no sort of a row and no gather); a
decode step's few tokens keep the ``lax.top_k`` form, which is also the
``xla`` side and the oracle. ``zero_expert_term`` is what the chosen zero-computation
experts (identity) add, whole on every chip. ``held_expert_ffn`` computes
``sum_i w_i * E_i(x)`` over the selected experts
THIS chip holds (a contiguous range of the published experts): assignments
to held experts are gathered into rows sorted by expert, with **no capacity
limit**, and run through three grouped matmuls (gate, up, down) whose work
follows the rows that exist: an expert no token chose is not read, and any
imbalance only adds passes over a fixed-size row buffer. A pass's rows go
back into their tokens as a one-hot matmul (``combine``): one dense dot where
rows x tokens is small, the kernel ``expert_combine`` over token tiles where
it is large; no program scatters. What experts held elsewhere would add is
left out; nothing stands in for their chips.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rag_llm_k8s_tpu.ops.attention import _fit_block

# rows of the gathered buffer as a multiple of what a balanced router sends
# here: one pass serves up to twice the mean load, more load takes more passes
PASS_HEADROOM = 2
ROW_ALIGN = 128  # the grouped kernel's row tile; a buffer is a multiple of it


LANES = 128  # tokens a column of the router's kernel: one lane each
# tokens from which ``route`` runs its kernel: the sweep behind it is in
# PERF.md section 6, PR 37
ROUTE_KERNEL_TOKENS = 1024
_TAKEN = -(2**31)  # under every key ``_order_key`` makes of a float that is not a NaN


def route_blocks(N: int, E: int, n_group: int) -> Optional[int]:
    """Whether ``route_topk`` routes ``N`` tokens over ``E`` outputs, from the
    shape alone (no option, no model's name): None for the ``lax.top_k`` form,
    else the columns of ``LANES`` tokens a grid step takes.

    The kernel from ``ROUTE_KERNEL_TOKENS`` tokens on: every prefill of a
    bucket and the scorer's lengths. It wins from one column on (128 tokens:
    4.2-7.7 us against 21-94, PR 37's sweep on a v5e), so the bound is
    set-up's price and not speed's: a decode step (8 tokens, 8-13 us of
    ``lax.top_k``), a verify chunk (16 a row) and a 512-token prefill chunk
    keep the jnp body, and their programs do not trace and lower one more
    kernel. Outputs fill whole 128-lane tiles (the kernel transposes them), a
    group whole 8-sublane rows, the groups one such row: every served width
    does (256 outputs in 8 groups of 32; 768 and 256 in one). Tokens that fill
    no whole column are padded to one (no served shape: a bucket is whole
    columns). A step takes up to 1 MiB of logits (8 columns at 256 outputs, 2
    at 768, a ``[E, 128]`` float32 tile beside its keys in VMEM): 1 to 16
    columns a step read alike, within 4% (the same sweep)."""
    if N < ROUTE_KERNEL_TOKENS or E % LANES:
        return None
    if n_group > 1 and (n_group > 8 or E % n_group or (E // n_group) % 8):
        return None
    return _fit_block(-(-N // LANES), max(1, 2048 // E))


def _order_key(x):
    """float32 -> int32 that orders as the floats do (``-0.0`` made ``0.0``
    first), so that ``_TAKEN`` lies under ``-inf``'s key: what a round took
    can be told from what was ``-inf`` to begin with."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _largest(key, place, size: int):
    """``[rows, LANES]`` keys -> ``(place [1, LANES] of a lane's largest key,
    the lowest among equals; the mask [rows, LANES] of that one row)``."""
    best = jnp.max(key, axis=0, keepdims=True)
    at = jnp.min(jnp.where(key == best, place, size), axis=0, keepdims=True)
    return at, place == at


def _route_kernel(
    logits_ref,  # VMEM [cols, LANES, E] float32: a token a row
    bias_ref,  # VMEM [E, 1] float32
    experts_ref,  # VMEM [cols, top_k, LANES] int32
    weights_ref,  # VMEM [cols, top_k, LANES] float32
    key_scr,  # VMEM [E, LANES] int32: a column's choice keys, an expert a sublane row
    s_scr,  # VMEM [E, LANES] float32: its scores, the same way
    *,
    n_group: int,
    topk_group: int,
    scaling: float,
    normalize: bool,
    scoring: str, eps: float = 1e-20,
):
    """One grid step: ``cols`` columns of ``LANES`` tokens, one after another.
    A column's logits are transposed so that experts lie along the sublanes
    and tokens along the lanes: a reduction over experts is then elementwise
    ACROSS the ``E / 8`` vector registers of a column and one fold inside the
    last, and nothing crosses lanes. A round is the largest key with its
    lowest index, then that index's score summed out of the scores and its
    key set to ``_TAKEN``; the group-limited rule is the same
    largest-and-mask in front (twice inside each group, ``topk_group`` times
    over the groups' row). Every iota and mask is made here, inside the call."""
    cols, _, E = logits_ref.shape
    top_k = experts_ref.shape[1]
    out_rows = -(-top_k // 8) * 8
    out_row = jax.lax.broadcasted_iota(jnp.int32, (out_rows, LANES), 0)

    def one_column(c, carry):
        x = logits_ref[c].T  # [E, LANES]
        if scoring == "softmax":
            e = jnp.exp(x - jnp.max(x, axis=0, keepdims=True))
            s = e / jnp.sum(e, axis=0, keepdims=True)
        else:
            s = jax.nn.sigmoid(x)
        s_scr[...] = s
        choice = s + bias_ref[...]
        if n_group > 1:
            per = E // n_group
            sub = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
            place = jax.lax.broadcasted_iota(jnp.int32, (per, LANES), 0)
            group_key = jnp.full((8, LANES), _TAKEN, jnp.int32)
            for g in range(n_group):
                own = choice[g * per:(g + 1) * per]
                top = jnp.max(own, axis=0, keepdims=True)
                first = jnp.min(jnp.where(own == top, place, per), axis=0, keepdims=True)
                second = jnp.max(jnp.where(place == first, -jnp.inf, own), axis=0, keepdims=True)
                group_key = jnp.where(sub == g, _order_key(top + second), group_key)
            kept = jnp.zeros((8, LANES), jnp.bool_)
            for _ in range(topk_group):
                _, hit = _largest(group_key, sub, 8)
                kept, group_key = kept | hit, jnp.where(hit, _TAKEN, group_key)
            for g in range(n_group):
                keep = jnp.any(kept & (sub == g), axis=0, keepdims=True)
                key_scr[pl.ds(g * per, per), :] = _order_key(
                    jnp.where(keep, choice[g * per:(g + 1) * per], -jnp.inf))
        else:
            key_scr[...] = _order_key(choice)
        place = jax.lax.broadcasted_iota(jnp.int32, (E, LANES), 0)

        def one_round(r, carry):
            experts, weights, total = carry
            at, hit = _largest(key_scr[...], place, E)
            # zeros and the one score: exact, and no gather
            score = jnp.sum(jnp.where(hit, s_scr[...], 0.0), axis=0, keepdims=True)
            key_scr[...] = jnp.where(hit, _TAKEN, key_scr[...])
            return (jnp.where(out_row == r, at, experts), jnp.where(out_row == r, score, weights), total + score)

        experts, weights, total = jax.lax.fori_loop(0, top_k, one_round, (
            jnp.zeros((out_rows, LANES), jnp.int32), jnp.zeros((out_rows, LANES), jnp.float32),
            jnp.zeros((1, LANES), jnp.float32)))
        if normalize:
            weights = weights / (total + eps)
        experts_ref[c] = experts[:top_k]
        weights_ref[c] = weights[:top_k] * scaling
        return carry

    jax.lax.fori_loop(0, cols, one_column, 0)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "scaling", "normalize", "scoring", "cols", "interpret", "eps"))
def route_topk(
    logits: jax.Array,  # [N, E] float32
    bias: jax.Array,  # [E] float32
    *,
    top_k: int,
    n_group: int,
    topk_group: int,
    scaling: float,
    normalize: bool,
    scoring: str,
    cols: int,  # ``route_blocks``'s
    interpret: bool = False, eps: float = 1e-20,
) -> Tuple[jax.Array, jax.Array]:
    """``route`` as one kernel: the logits read once, ``experts`` and
    ``weights`` written once, and between them nothing leaves VMEM. What
    ``lax.top_k`` of ``s + bias`` (group-limited where ``n_group > 1``) and a
    gather of ``s`` return: descending, ties to the lower index, what was
    ``-inf`` behind every finite score in the order of its index; the
    normalising sum adds the ``top_k`` scores in that order."""
    tokens, E = logits.shape
    N = -(-tokens // LANES) * LANES
    if N != tokens:
        logits = jnp.pad(logits, ((0, N - tokens), (0, 0)))
    shape = (N // LANES, top_k, LANES)
    experts, weights = pl.pallas_call(
        functools.partial(_route_kernel, n_group=n_group, topk_group=topk_group, scaling=scaling,
                          normalize=normalize, scoring=scoring, eps=eps),
        grid=(N // LANES // cols,),
        in_specs=[
            pl.BlockSpec((cols, LANES, E), lambda i: (i, 0, 0)),
            pl.BlockSpec((E, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((cols, top_k, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((cols, top_k, LANES), lambda i: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((E, LANES), jnp.int32), pltpu.VMEM((E, LANES), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.int32), jax.ShapeDtypeStruct(shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="route_topk",
    )(logits.reshape(N // LANES, LANES, E), bias.reshape(E, 1))
    # held_expert_ffn stands on the order of experts.reshape(N * top_k): a token's choices side by side
    experts, weights = (x.transpose(0, 2, 1).reshape(N, top_k) for x in (experts, weights))
    return (experts, weights) if N == tokens else (experts[:tokens], weights[:tokens])


def route(
    logits: jax.Array,  # [N, E] float32: x . W_g
    bias: jax.Array,  # [E] float32: e_score_correction_bias
    *,
    top_k: int,
    n_group: int,
    topk_group: int,
    scaling: float,
    normalize: bool = True,
    scoring: str = "sigmoid", eps: float = 1e-20,  # in the normalising sum
    impl: str = "xla",  # "xla" (lax.top_k) | "pallas" | "pallas_interpret"
) -> Tuple[jax.Array, jax.Array]:
    """``(experts [N, top_k] int32, weights [N, top_k] float32)``.

    ``s = sigmoid(logits)``, or ``softmax`` over all ``E`` outputs (routed and
    zero-computation experts alike); the CHOICE is by ``s + bias``: a group's
    score is the sum of its top 2, the best ``topk_group`` groups stay, the
    ``top_k`` best experts inside them are chosen (ties to the lower index;
    ``n_group`` 1: the ``top_k`` best of all). The WEIGHT is ``s`` itself
    (never ``s + bias``) at the chosen experts, divided by their sum over all
    ``top_k`` (plus ``eps``) where ``normalize``, times ``scaling``.

    Where ``route_blocks`` says so the kernel ``route_topk`` chooses and
    weighs in one pass over a token tile; under ``impl`` "xla" and for fewer
    tokens than ``ROUTE_KERNEL_TOKENS``, ``lax.top_k`` and a gather do (the
    body below, also the kernel's oracle): the same experts in the same order."""
    N, E = logits.shape
    logits = logits.astype(jnp.float32)
    cols = None if impl == "xla" else route_blocks(N, E, n_group)
    if cols is not None:
        return route_topk(
            logits, bias.astype(jnp.float32), top_k=top_k, n_group=n_group, topk_group=topk_group,
            scaling=float(scaling), normalize=bool(normalize), scoring=scoring, cols=cols,
            interpret=impl == "pallas_interpret", eps=float(eps))
    s = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
    choice = s + bias.astype(jnp.float32)[None, :]
    if n_group > 1:
        per = E // n_group
        group_score = jnp.sum(jax.lax.top_k(choice.reshape(N, n_group, per), 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, topk_group)  # [N, topk_group]
        kept = jnp.zeros((N, n_group), bool).at[jnp.arange(N)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(s, experts, axis=1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return experts.astype(jnp.int32), weights * scaling


def zero_expert_term(
    x: jax.Array,  # [N, D]
    experts: jax.Array,  # [N, top_k] int32, over the router's outputs
    weights: jax.Array,  # [N, top_k] float32
    n_routed: int,
) -> Tuple[jax.Array, jax.Array]:
    """``(sum_{chosen e >= n_routed} w_e) * x`` for every token, ``[N, D]`` in
    ``x``'s dtype (float32 product, cast once like the held experts' combine),
    and the number of assignments to zero-computation experts. An identity
    expert gathers nothing, holds no weight and takes no row of the grouped
    buffer; every chip computes the term for its own tokens."""
    zero = experts >= n_routed
    w = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1, keepdims=True)
    return (w * x.astype(jnp.float32)).astype(x.dtype), jnp.sum(zero).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the grouped matmul (Mosaic)
# ---------------------------------------------------------------------------

# mean rows a group (``m / G``) under which the kernel's tiles follow the
# groups, and what a whole-K weight block may take of VMEM (it is double
# buffered): the sweep behind both is in PERF.md section 6, PR 46
SMALL_GROUP_ROWS = 1024
_WHOLE_K_BLOCK = 3 << 20


def _fit_width(width: int, pref: int) -> int:
    """``_fit_block`` for a weight's side: where halving ``pref`` lands under
    512 (a width that 512 does not divide: 2304 = 9 * 256 halves to 256), the
    widest multiple of ``LANES`` up to ``pref`` that tiles it (768). Every
    width 512 divides keeps its halving."""
    block = _fit_block(width, pref)
    if block >= 512:
        return block
    return max([b for b in range(LANES, min(pref, width) + 1, LANES) if width % b == 0] + [block])


def grouped_blocks(m: int, G: int, k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """``grouped_matmul``'s ``(tm, tk, tn)``, from the shape alone (no option,
    no model's name): the row tile, and the ``[tk, tn]`` block of a group's
    weights a grid step multiplies it by.

    A row tile that spans groups is visited once a group, so a buffer of
    ``m`` rows in ``G`` groups costs about ``m / tm + G - 1`` visits of ``tm``
    rows each, whatever the rows that exist. With ``k`` CUT (512 rows by 1024
    of ``k``: what fits at any width) the weight block's index changes at
    every grid step, so every visit fetches its group's weights again, and
    few large tiles win. That is the plan where groups are large (a batched
    prefill over a chip's share of the experts: ``m / G`` from
    ``SMALL_GROUP_ROWS`` on, few tiles straddle) and where a ``[k, tn]`` block
    is over ``_WHOLE_K_BLOCK``. Where groups are small and the block fits,
    ``k`` is WHOLE: consecutive visits of one group then have the same block
    index, the pipeline fetches a group's weights once a column of tiles, and
    a straddling tile costs FLOPs only; so the row tile FOLLOWS the mean group
    (its power of two, from ``ROW_ALIGN`` to 512), not the buffer. One row's
    prefill with every expert held (16384 rows in 64 groups of about 256) runs
    256-row tiles at 1.16 ms a call for 1.84 (128-row tiles fill better, 67%
    for 50, and run no faster: 1.20); a decode step's or a verify chunk's
    128-row buffer keeps its one tile and halves its grid steps, for 2% of a
    gate or up call. The sweep (v5e): PERF.md section 6, PR 46. A side that
    512 does not divide (2304) takes ``_fit_width``'s divisor, 768."""
    tm, tk, tn = _fit_block(m, 512), _fit_width(k, 1024), _fit_width(n, 1024)
    mean = m // G
    if mean >= SMALL_GROUP_ROWS or k * tn * itemsize > _WHOLE_K_BLOCK:
        return tm, tk, tn
    follow = ROW_ALIGN
    while follow < 512 and 2 * follow <= mean:
        follow *= 2
    return _fit_block(m, follow), k, tn


def _grouped_matmul_kernel(
    layer_ref,  # SMEM [1]: which layer's stack of groups (read by the index maps)
    group_offsets_ref,  # SMEM [G + 1]: row where each group starts
    group_ids_ref,  # SMEM [tiles]: the group a grid step works on
    m_tile_ids_ref,  # SMEM [tiles]: the row tile a grid step works on
    lhs_ref,  # [tm, tk]
    rhs_ref,  # [tk, tn] of the step's group
    out_ref,  # [tm, tn]
    stored_ref,  # SMEM [n tiles]: rows this column of tiles has stored so far
    acc_scr,  # VMEM [tm, tn] float32
    *,
    tm: int,
    tiles_k: int,
):
    del layer_ref
    n_i = pl.program_id(0)
    step = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when((step == 0) & (k_i == 0))
    def _reset():
        stored_ref[n_i] = 0

    @pl.when(k_i == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # a row tile that spans groups is visited once a group, consecutively:
        # each visit writes its own group's rows and keeps the others
        group = group_ids_ref[step]
        first = m_tile_ids_ref[step] * tm
        lo = jnp.maximum(group_offsets_ref[group], first)
        hi = jnp.minimum(group_offsets_ref[group + 1], first + tm)
        rows = first + jax.lax.broadcasted_iota(jnp.int32, acc_scr.shape, 0)
        mine = (rows >= lo) & (rows < hi)
        out_ref[...] = jnp.where(mine, acc_scr[...], out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)
        stored_ref[n_i] += jnp.maximum(hi - lo, 0)  # the rows ``mine`` holds


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(
    lhs: jax.Array,  # [m, k] rows sorted by group
    rhs: jax.Array,  # [L, G, k, n]: every layer's groups, stacked
    group_sizes: jax.Array,  # [G] int32, sum <= m
    layer: jax.Array,  # [] int32: the layer whose groups multiply
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``out[rows of group g] = lhs[rows of group g] @ rhs[layer, g]``, ``[m,
    n]`` in ``lhs``'s dtype; the number of rows the kernel STORED: summed
    by the kernel itself from the bounds of every store it made (the same
    two scalars its store mask is built from), so a (row tile, group) pair
    the grid never reached, or a bound that cut a group short, shows as fewer
    rows than ``sum(group_sizes)``; and the rows it MULTIPLIED, visits times
    the row tile (what ``stored`` is a share of says how full the tiles
    were). The grid's middle dimension is the number of (row
    tile, group) pairs that hold rows, computed from ``group_sizes`` on the
    device: a group with no rows is never read, and rows past
    ``sum(group_sizes)`` are never written (the caller masks them). The layer
    rides scalar prefetch into the block index, so the kernel reads its tiles
    straight out of the stacked weights: no per-layer slice is materialized
    (a slice handed to a custom call is a copy, 1.3 GB a layer at the served
    widths). The tiles are ``grouped_blocks``'s, which reads ``m``, the number
    of groups, ``k``, ``n`` and the item's size: the whole ``k`` and a row tile
    of the mean group's size where groups are small (a group's weights are
    then fetched once a column of tiles, not once a visit), 512 rows by 1024
    of ``k`` elsewhere. The tiling scheme is that of JAX's megablox ``gmm``, whose
    metadata builder this reuses."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    m, k = lhs.shape
    _, G, _, n = rhs.shape
    tm, tk, tn = grouped_blocks(m, G, k, n, lhs.dtype.itemsize)
    (offsets, group_ids, m_tile_ids), num_tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=G, visit_empty_groups=False)
    tiles_k = k // tk
    out, stored = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm=tm, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, num_tiles, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, s, k_i, lay, off, gid, mid: (mid[s], k_i)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda n_i, s, k_i, lay, off, gid, mid: (lay[0], gid[s], k_i, n_i)),
            ],
            out_specs=[
                pl.BlockSpec((tm, tn), lambda n_i, s, k_i, lay, off, gid, mid: (mid[s], n_i)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((m, n), lhs.dtype),
                   jax.ShapeDtypeStruct((n // tn,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul",
    )(jnp.asarray(layer, jnp.int32).reshape(1), offsets, group_ids, m_tile_ids, lhs, rhs)
    # with no rows at all the grid is empty and nothing was written
    return out, jnp.where(num_tiles > 0, stored[0], 0), num_tiles * tm


def _grouped_xla(lhs, rhs, group_sizes, layer):
    """``grouped_matmul`` as ``ragged_dot``, which zeroes the rows past the
    groups and reports nothing: the rows stored, and the rows multiplied, are
    those it was given."""
    w = jax.lax.dynamic_index_in_dim(rhs, jnp.asarray(layer, jnp.int32).reshape(()), 0, keepdims=False)
    sizes = group_sizes.astype(jnp.int32)
    given = jnp.sum(sizes)
    return jax.lax.ragged_dot(lhs, w, sizes), given, given


# ---------------------------------------------------------------------------
# the combine: a pass's rows summed back into their tokens (one-hot matmul)
# ---------------------------------------------------------------------------

# rows x tokens up to which the one-hot sum is ONE dense dot in XLA, and what
# a grid cell of the kernel may take of VMEM: the sweep behind both is in
# PERF.md section 6, PR 34
COMBINE_DENSE = 1 << 19
_COMBINE_VMEM = 12 << 20


def combine_blocks(N: int, C: int, D: int, itemsize: int) -> Optional[Tuple[int, int, int]]:
    """How ``C`` rows are summed into ``N`` tokens of width ``D``, from the
    shape alone (no option, no model's name): None for the dense dot, else
    ``expert_combine``'s ``(tn, R, dblk)``: a grid cell sums into ``tn``
    tokens by ``dblk`` columns, from rows fetched ``R`` at a time.

    Swept on a v5e at 7168 columns (PR 34): the dot wins up to 1024 tokens x
    512 rows (142 us against the kernel's 159) and loses from 1024 x 1024 on
    (188 against 161; 1536 against 405 at 4096 x 4096), so decode (8 x 128),
    a verify chunk (16 x 128) and a 512-token prefill chunk take the dot and
    every prefill of a bucket the kernel. ``tn`` is the largest halving of
    256 that tiles ``N``; ``R`` is the row tile (``ROW_ALIGN``: a tile's
    range starts anywhere, so a longer step fetches more rows of its
    neighbours; 256 lost 1-6%, 512 10-24%); ``dblk`` the widest
    multiple of 128 dividing ``D`` whose cell (the tile in and out, double
    buffered, its float32 sum, two row buffers) fits ``_COMBINE_VMEM``: a
    narrower block only repeats the walk (896 columns +5% on 1792). All of
    it moves a call by 3-6%: the call is its bytes."""
    if N * C <= COMBINE_DENSE:
        return None
    tn, R = _fit_block(N, 256), ROW_ALIGN
    if tn % 16 or C % R:
        return None  # no tile of whole sublanes: a shape no program serves at this size
    cols = [b for b in range(128, D + 1, 128) if D % b == 0] or [D]
    fit = [b for b in cols if b * (tn * (4 * itemsize + 4) + 2 * R * itemsize) <= _COMBINE_VMEM]
    return tn, R, max(fit or cols[:1])


def _expert_combine_kernel(
    first_ref,  # SMEM [tiles + 1]: the row where each token tile's rows start
    tok_ref,  # VMEM [C / R, R] int32: the rows' tokens, row by row (N: no token)
    acc_ref,  # VMEM [tn, dblk]: what the tile's tokens hold so far
    rows_hbm,  # [C, D] in the order of their token tiles, left in HBM
    out_ref,  # [tn, dblk] (the same memory as ``acc_ref``'s array)
    count_ref,  # SMEM [1]: one-hot entries set
    buf,  # VMEM [2, R, dblk]
    sem,  # DMA [2]
    turn_ref,  # SMEM [1]: which buffer the cell's first step is in
    sum_scr,  # VMEM [tn, dblk] float32
    *,
    tn: int,
    R: int,
    dblk: int,
    blocks: int,
    precision,
):
    """One grid cell: token tile ``t``, column block ``d``. The tile's rows
    are ONE range ``[first[t], first[t + 1])`` of the tile-ordered rows; the
    cell copies the ``R``-row blocks that range touches itself, one a step
    into one of two buffers while the step before is summed (``ops/attention
    .py _decode_walk``'s pattern), the next cell's first block in flight
    under this cell's last. A step's one-hot block is (row's token = tile's
    token): rows of other tiles in a fetched block, and rows of no token,
    match none, so no bound is compared. A tile with no rows still takes one
    step (its block matches nothing): every cell has a first fetch."""
    t, d = pl.program_id(0), pl.program_id(1)
    nt, nd = pl.num_programs(0), pl.num_programs(1)

    def first_block(tile):  # (whole numbers, none negative: ``lax.div``, not ``//`` and its signs)
        return jnp.minimum(jax.lax.div(first_ref[tile], R), blocks - 1)

    def copy(block, col, slot):
        src = rows_hbm.at[pl.ds(pl.multiple_of(block * R, R), R), pl.ds(pl.multiple_of(col * dblk, dblk), dblk)]
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[slot])

    @pl.when((t == 0) & (d == 0))
    def _first_cell():
        turn_ref[0] = 0
        count_ref[0] = 0
        copy(first_block(0), 0, 0).start()

    turn = turn_ref[0]
    b0 = first_block(t)
    n = jnp.maximum(jax.lax.div(first_ref[t + 1] + (R - 1), R) - b0, 1)
    sum_scr[...] = acc_ref[...].astype(jnp.float32)
    tile_tokens = t * tn + jax.lax.broadcasted_iota(jnp.int32, (tn, R), 0)
    wrap = d + 1 == nd  # the next cell is the next tile's first column block

    def one_step(j, carry):
        slot = (turn + j) & 1
        more = j + 1 < n

        @pl.when(more | ~(wrap & (t + 1 == nt)))
        def _prefetch():
            ahead = first_block(jnp.where(wrap, jnp.minimum(t + 1, nt - 1), t))
            col = jnp.where(more, d, jnp.where(wrap, 0, d + 1))
            copy(jnp.where(more, b0 + j + 1, ahead), col, 1 - slot).start()

        copy(b0 + j, d, slot).wait()
        hot = tok_ref[pl.ds(b0 + j, 1), :] == tile_tokens  # [tn, R]
        sum_scr[...] += jax.lax.dot_general(
            hot.astype(buf.dtype), buf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

        @pl.when(d == 0)
        def _count():
            count_ref[0] += jnp.sum(hot.astype(jnp.int32))

        return carry

    jax.lax.fori_loop(0, n, one_step, 0)
    turn_ref[0] = (turn + n) & 1
    out_ref[...] = sum_scr[...].astype(out_ref.dtype)


def _one_hot_precision(dtype):
    """A 0/1 matrix times bf16 rows is exact in one pass; float32 rows (the
    fp32 policy) need the highest precision said, or a TPU rounds them."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _weighted_rows(y, weight, has_token, dtype):
    """``weight_i * y_i`` formed in float32, zero for a row of no token (what
    the grouped kernel left unwritten there may be anything, NaN included)."""
    return jnp.where(has_token[:, None], y.astype(jnp.float32) * weight.astype(jnp.float32)[:, None],
                     0.0).astype(dtype)


def rows_by_token_tile(token, group, groups: int, N: int, tn: int):
    """Where a pass's rows go when they are put in the order of their token
    TILES: ``(src [C], first [N / tn + 1])``: row ``r`` of the new order is row
    ``src[r]`` of the pass, and tile ``t``'s rows are ``[first[t], first[t +
    1])`` of the new order (rows of no token stay behind them all).

    No sort (a one-dimensional sort of 32768 keys takes the TPU compiler 18 s
    a program, and 0.3 ms a call): a pass is ``groups`` runs, one an expert,
    inside each of which tokens only rise (``held_expert_ffn``'s stable
    argsort), so the rows of (run, tile) are a block of the pass whose bounds
    are counts: how many rows lie under ``(run, tile's first token)``. The
    new order is the blocks tile by tile, a row's source its own place plus
    what the blocks in front of it were moved by; both are compare-and-sum
    fusions over rows x blocks (2064 blocks at 16 experts and 128 tiles)."""
    C = token.shape[0]
    tiles, span = N // tn, N + 1
    assert (groups + 1) * span < 2**31, "the (run, token) key is an int32"
    key = jnp.minimum(group, groups).astype(jnp.int32) * span + jnp.minimum(token, N).astype(jnp.int32)
    bounds = (jnp.arange(groups, dtype=jnp.int32)[:, None] * span
              + jnp.arange(tiles + 1, dtype=jnp.int32)[None, :] * tn)  # [run, tile]
    under = jnp.sum(key[None, None, :] < bounds[:, :, None], axis=-1).astype(jnp.int32)  # rows in front of the block
    start = under[:, :-1].T.reshape(-1)  # blocks tile by tile: where each starts in the pass
    size = (under[:, 1:] - under[:, :-1]).T.reshape(-1)
    end = jnp.cumsum(size)  # ... and where each ends, and starts, in the new order
    new_start = end - size
    moved = start - new_start
    r = jnp.arange(C, dtype=jnp.int32)
    step = jnp.diff(moved, append=moved[-1:])
    src = r + moved[0] + jnp.sum(jnp.where(end[None, :] <= r[:, None], step[None, :], 0), axis=1)
    return jnp.where(r < end[-1], src, r), jnp.concatenate([new_start[::groups], end[-1:]])


@functools.partial(jax.jit, static_argnames=("groups", "blocks", "interpret"))
def expert_combine(
    acc: jax.Array,  # [N, D]
    y: jax.Array,  # [C, D]: a pass's rows, run by run, tokens rising inside a run
    weight: jax.Array,  # [C] float32
    token: jax.Array,  # [C] int32: the row's token; N for a row of no token
    group: jax.Array,  # [C] int32: the row's run (its expert); ``groups`` for a row of no token
    *,
    groups: int,
    blocks: Tuple[int, int, int],  # ``combine_blocks``'s (tn, R, dblk)
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``acc[n] + sum_i [token_i = n] * (weight_i * y_i)``, ``[N, D]`` in
    ``acc``'s dtype, summed in float32 and cast once; and the number of
    one-hot entries the kernel SET, summed by the kernel itself, so a row it
    never reached shows as fewer than the rows that have a token.

    The rows are put in the order of their token tiles first
    (``rows_by_token_tile``, and one row gather, the size of the dispatch
    gather; ``weight_i * y_i`` is formed on the way: ``_weighted_rows``).
    Then every token tile's rows are one contiguous range whose
    bounds ride scalar prefetch, and the kernel adds ``one_hot @ rows`` a
    tile on the MXU where a scatter-add walks its updates one at a time
    (0.9 us a row at 7168 columns: PERF.md section 6, PR 34). ``acc`` is read
    and written a tile at a time in place."""
    N, D = acc.shape
    C = y.shape[0]
    tn, R, dblk = blocks
    src, first = rows_by_token_tile(token, group, groups, N, tn)
    key = jnp.take(token, src).astype(jnp.int32)
    rows = _weighted_rows(jnp.take(y, src, axis=0), jnp.take(weight, src), key < N, acc.dtype)

    def tile_block(t, d, first):
        return (t, d)

    out, count = pl.pallas_call(
        functools.partial(_expert_combine_kernel, tn=tn, R=R, dblk=dblk, blocks=C // R,
                          precision=_one_hot_precision(acc.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // tn, D // dblk),
            in_specs=[
                pl.BlockSpec((C // R, R), lambda t, d, first: (0, 0)),
                pl.BlockSpec((tn, dblk), tile_block),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((tn, dblk), tile_block),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, R, dblk), acc.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((tn, dblk), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((N, D), acc.dtype), jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_combine",
    )(first, key.reshape(C // R, R), acc, rows)
    return out, count[0]


def _combine_dense(acc, y, weight, token):
    """``expert_combine`` as one dense dot of the ``[N, C]`` one-hot matrix:
    rows x tokens x columns FLOPs, so the form of small shapes (15 MFLOP a
    decode step's layer) and of the XLA path."""
    N = acc.shape[0]
    hot = token[None, :] == jnp.arange(N, dtype=token.dtype)[:, None]
    out = jnp.dot(hot.astype(acc.dtype), _weighted_rows(y, weight, token < N, acc.dtype),
                  preferred_element_type=jnp.float32, precision=_one_hot_precision(acc.dtype))
    return (acc.astype(jnp.float32) + out).astype(acc.dtype), jnp.sum(hot).astype(jnp.int32)


def combine(acc, y, weight, token, group, groups: int, *, impl: str = "xla"):
    """A pass's rows into their tokens, and the one-hot entries set: the
    dense dot or the kernel by ``combine_blocks`` (``impl`` "xla": always the
    dot, as ``ragged_dot`` stands in for ``grouped_matmul``)."""
    blocks = None if impl == "xla" else combine_blocks(acc.shape[0], y.shape[0], acc.shape[1], acc.dtype.itemsize)
    if blocks is None:
        return _combine_dense(acc, y, weight, token)
    return expert_combine(acc, y, weight, token, group, groups=groups, blocks=blocks,
                          interpret=impl == "pallas_interpret")


# ---------------------------------------------------------------------------
# this chip's share of the routed experts
# ---------------------------------------------------------------------------


class ExpertCounts(NamedTuple):
    """What one call did, as int32 scalars (the engine's counters)."""

    tokens: jax.Array  # rows that went through the router
    routed: jax.Array  # assignments to experts held here
    computed: jax.Array  # assignment rows the down projection's kernel stored, by its own count
    experts_hit: jax.Array  # held experts with at least one assignment
    zero: jax.Array = 0  # assignments to zero-computation experts (``zero_expert_term``)
    combined: jax.Array = 0  # one-hot entries the combine set, by its own count (== routed)
    tile_rows: jax.Array = 0  # rows the down projection's kernel multiplied: its visits x its row tile


def rows_per_pass(n_tokens: int, top_k: int, n_experts: int, held: int) -> int:
    """The gathered buffer's rows: ``PASS_HEADROOM`` times what a balanced
    router sends to ``held`` of ``n_experts``, in whole row tiles, and never
    more than every assignment there is."""
    assignments = n_tokens * min(top_k, held)
    mean = -(-n_tokens * top_k * held // n_experts)
    rows = min(PASS_HEADROOM * mean, assignments)
    return -(-max(rows, 1) // ROW_ALIGN) * ROW_ALIGN


def held_expert_ffn(
    x: jax.Array,  # [N, D]
    experts: jax.Array,  # [N, top_k] int32, over ALL the router's outputs
    weights: jax.Array,  # [N, top_k] float32
    w_gate: Optional[jax.Array],  # [L, held, D, F]: every MoE layer's held experts; None: an expert has no gate
    w_up: jax.Array,  # [L, held, D, F]
    w_down: jax.Array,  # [L, held, F, D]
    layer: jax.Array,  # [] int32: which of the L
    first_held: int,
    n_experts: int,  # the router's outputs (zero-computation ones too): the balanced load's divisor
    *,
    impl: str = "xla",  # "xla" (ragged_dot) | "pallas" | "pallas_interpret"
) -> Tuple[jax.Array, ExpertCounts]:
    """``sum_i w_i * E_i(x)`` over the chosen experts in ``[first_held,
    first_held + held)``, every ``E`` a SwiGLU, or under ``w_gate=None`` (a
    static choice: the gated callers trace what they always did) two matrices
    with a squared ``relu`` between, ``relu(x W_up)^2 W_down``; ``[N, D]`` in
    ``x``'s dtype (``D`` the width the experts work at: the stream's, or a
    latent's). A pass gathers its rows, runs the grouped matmuls and sums the
    weighted rows back into their tokens (``combine``); ``counts.computed``
    and ``counts.combined`` are the down projection's and the combine's own
    counts of what they did, both equal to ``counts.routed``."""
    N, D = x.shape
    top_k = experts.shape[1]
    held = w_up.shape[1]
    A = N * top_k
    local = experts.reshape(A) - first_held
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # held assignments first, by expert
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :], axis=0).astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    total = ends[-1]

    C = rows_per_pass(N, top_k, n_experts, held)
    passes = -(-A // C)
    order = jnp.pad(order, (0, passes * C - A))
    flat_w = weights.reshape(A)
    if impl == "xla":
        grouped = _grouped_xla
    else:
        grouped = functools.partial(grouped_matmul, interpret=impl == "pallas_interpret")

    def one_pass(carry):
        p, acc, computed, combined, tile_rows = carry
        lo = p * C
        a = jax.lax.dynamic_slice(order, (lo,), (C,))
        valid = lo + jnp.arange(C, dtype=jnp.int32) < total
        token = a // top_k
        rows = jnp.take(x, token, axis=0)
        sizes_here = jnp.clip(ends - lo, 0, C) - jnp.clip(starts - lo, 0, C)
        if w_gate is None:
            h = jnp.square(jax.nn.relu(grouped(rows, w_up, sizes_here, layer)[0]))
        else:
            h = jax.nn.silu(grouped(rows, w_gate, sizes_here, layer)[0]) * grouped(rows, w_up, sizes_here, layer)[0]
        y, stored, tiled = grouped(h, w_down, sizes_here, layer)
        acc, hot = combine(acc, y, jnp.take(flat_w, a), jnp.where(valid, token, N),
                           jnp.where(valid, jnp.take(key, a), held), held, impl=impl)
        return p + 1, acc, computed + stored, combined + hot, tile_rows + tiled

    n_pass = (total + C - 1) // C
    _, y, computed, combined, tile_rows = jax.lax.while_loop(
        lambda c: c[0] < n_pass, one_pass,
        (jnp.int32(0), jnp.zeros((N, D), x.dtype), jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    counts = ExpertCounts(
        tokens=jnp.int32(N), routed=total.astype(jnp.int32), computed=computed,
        experts_hit=jnp.sum(sizes > 0).astype(jnp.int32), combined=combined, tile_rows=tile_rows)
    return y, counts

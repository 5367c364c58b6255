"""Sparse experts: routing by the two published rules this family spans, and
this chip's share of the routed experts as grouped matmuls over the
assignments it holds.

``route`` scores ALL of the router's outputs (sigmoid, or softmax over the
routed experts and the zero-computation ones after them), chooses by score
plus a correction bias (group-limited where there are groups), and weighs by
the score itself. ``zero_expert_term`` is what the chosen zero-computation
experts (identity) add, whole on every chip. ``held_expert_ffn`` computes
``sum_i w_i * E_i(x)`` over the selected experts
THIS chip holds (a contiguous range of the published experts): assignments
to held experts are gathered into rows sorted by expert, with **no capacity
limit**, and run through three grouped matmuls (gate, up, down) whose work
follows the rows that exist: an expert no token chose is not read, and any
imbalance only adds passes over a fixed-size row buffer. What experts held
elsewhere would add is left out; nothing stands in for their chips.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rag_llm_k8s_tpu.ops.attention import _fit_block

# rows of the gathered buffer as a multiple of what a balanced router sends
# here: one pass serves up to twice the mean load, more load takes more passes
PASS_HEADROOM = 2
ROW_ALIGN = 128  # the grouped kernel's row tile; a buffer is a multiple of it


def route(
    logits: jax.Array,  # [N, E] float32: x . W_g
    bias: jax.Array,  # [E] float32: e_score_correction_bias
    *,
    top_k: int,
    n_group: int,
    topk_group: int,
    scaling: float,
    normalize: bool = True,
    scoring: str = "sigmoid",
) -> Tuple[jax.Array, jax.Array]:
    """``(experts [N, top_k] int32, weights [N, top_k] float32)``.

    ``s = sigmoid(logits)``, or ``softmax`` over all ``E`` outputs (routed and
    zero-computation experts alike); the CHOICE is by ``s + bias``: a group's
    score is the sum of its top 2, the best ``topk_group`` groups stay, the
    ``top_k`` best experts inside them are chosen (ties to the lower index;
    ``n_group`` 1: the ``top_k`` best of all). The WEIGHT is ``s`` itself
    (never ``s + bias``) at the chosen experts, divided by their sum over all
    ``top_k`` where ``normalize``, times ``scaling``."""
    N, E = logits.shape
    logits = logits.astype(jnp.float32)
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
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scaling


def zero_expert_term(
    x: jax.Array,  # [N, D]
    experts: jax.Array,  # [N, top_k] int32, over the router's outputs
    weights: jax.Array,  # [N, top_k] float32
    n_routed: int,
) -> Tuple[jax.Array, jax.Array]:
    """``(sum_{chosen e >= n_routed} w_e) * x`` for every token, ``[N, D]`` in
    ``x``'s dtype (float32 product, cast like the held experts' scatter-add),
    and the number of assignments to zero-computation experts. An identity
    expert gathers nothing, holds no weight and takes no row of the grouped
    buffer; every chip computes the term for its own tokens."""
    zero = experts >= n_routed
    w = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1, keepdims=True)
    return (w * x.astype(jnp.float32)).astype(x.dtype), jnp.sum(zero).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the grouped matmul (Mosaic)
# ---------------------------------------------------------------------------


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
) -> Tuple[jax.Array, jax.Array]:
    """``out[rows of group g] = lhs[rows of group g] @ rhs[layer, g]``, ``[m,
    n]`` in ``lhs``'s dtype, and the number of rows the kernel STORED: summed
    by the kernel itself from the bounds of every store it made (the same
    two scalars its store mask is built from), so a (row tile, group) pair
    the grid never reached, or a bound that cut a group short, shows as fewer
    rows than ``sum(group_sizes)``. The grid's middle dimension is the number of (row
    tile, group) pairs that hold rows, computed from ``group_sizes`` on the
    device: a group with no rows is never read, and rows past
    ``sum(group_sizes)`` are never written (the caller masks them). The layer
    rides scalar prefetch into the block index, so the kernel reads its tiles
    straight out of the stacked weights: no per-layer slice is materialized
    (a slice handed to a custom call is a copy, 1.3 GB a layer at the served
    widths). The tiling scheme is that of JAX's megablox ``gmm``, whose
    metadata builder this reuses."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    m, k = lhs.shape
    _, G, _, n = rhs.shape
    tm, tk, tn = _fit_block(m, 512), _fit_block(k, 1024), _fit_block(n, 1024)
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
    return out, jnp.where(num_tiles > 0, stored[0], 0)


def _grouped_xla(lhs, rhs, group_sizes, layer):
    """``grouped_matmul`` as ``ragged_dot``, which zeroes the rows past the
    groups and reports nothing: the rows stored are those it was given."""
    w = jax.lax.dynamic_index_in_dim(rhs, jnp.asarray(layer, jnp.int32).reshape(()), 0, keepdims=False)
    sizes = group_sizes.astype(jnp.int32)
    return jax.lax.ragged_dot(lhs, w, sizes), jnp.sum(sizes)


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
    w_gate: jax.Array,  # [L, held, D, F]: every MoE layer's held experts
    w_up: jax.Array,  # [L, held, D, F]
    w_down: jax.Array,  # [L, held, F, D]
    layer: jax.Array,  # [] int32: which of the L
    first_held: int,
    n_experts: int,  # the router's outputs (zero-computation ones too): the balanced load's divisor
    *,
    impl: str = "xla",  # "xla" (ragged_dot) | "pallas" | "pallas_interpret"
) -> Tuple[jax.Array, ExpertCounts]:
    """``sum_i w_i * E_i(x)`` over the chosen experts in ``[first_held,
    first_held + held)``, every ``E`` a SwiGLU; ``[N, D]`` in ``x``'s dtype."""
    N, D = x.shape
    top_k = experts.shape[1]
    held = w_gate.shape[1]
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
        p, acc, computed = carry
        lo = p * C
        a = jax.lax.dynamic_slice(order, (lo,), (C,))
        valid = lo + jnp.arange(C, dtype=jnp.int32) < total
        token = a // top_k
        rows = jnp.take(x, token, axis=0)
        sizes_here = jnp.clip(ends - lo, 0, C) - jnp.clip(starts - lo, 0, C)
        h = jax.nn.silu(grouped(rows, w_gate, sizes_here, layer)[0]) * grouped(rows, w_up, sizes_here, layer)[0]
        y, stored = grouped(h, w_down, sizes_here, layer)
        wa = jnp.take(flat_w, a).astype(jnp.float32)
        y = jnp.where(valid[:, None], y.astype(jnp.float32) * wa[:, None], 0.0)
        acc = acc.at[token].add(y.astype(acc.dtype))
        return p + 1, acc, computed + stored

    n_pass = (total + C - 1) // C
    _, y, computed = jax.lax.while_loop(
        lambda c: c[0] < n_pass, one_pass,
        (jnp.int32(0), jnp.zeros((N, D), x.dtype), jnp.int32(0)))
    counts = ExpertCounts(
        tokens=jnp.int32(N), routed=total.astype(jnp.int32), computed=computed,
        experts_hit=jnp.sum(sizes > 0).astype(jnp.int32))
    return y, counts

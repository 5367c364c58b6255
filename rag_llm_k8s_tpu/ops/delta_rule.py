"""The gated delta rule with a decay a CHANNEL: a linear-attention layer's
recurrence over a float32 matrix state a head.

For one head (``d_k`` key channels, ``d_v`` value channels), ``S`` the state
``[d_k, d_v]``, ``q_t``, ``k_t`` the normed query and key, ``v_t`` the value,
``g_t <= 0`` the log decay of each key channel and ``beta_t`` in (0, 1]:

    S'  = Diag(exp(g_t)) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)            what the state does not yet say of v_t
    S_t = S' + k_t u_t^T                     (= (I - beta k k^T) Diag(alpha) S + beta k v^T)
    o_t = S_t^T q_t

Four forms that agree (``tests/test_delta_rule.py``):

- ``delta_rule_steps``: a ``lax.scan`` over positions, every product an
  elementwise float32 one (no matmul unit, so the same numbers on every
  backend): the oracle inside the program, and with ``S == 1`` the
  single-token step (read the state, decay it, correct it by rank one, write
  it: ``2 * 4 * d_k * d_v`` bytes a head).
- ``delta_rule_chunked_xla``: the chunk form in XLA. Inside a chunk of ``C``
  positions with ``G_i = sum_{j <= i} g_j`` the running log decay, ``u``
  solves the unit lower-triangular system ``(I + tril(Diag(beta) A, -1)) U =
  Diag(beta) (V - (exp(G) * K) S_0)`` with ``A_ij = sum_c k_i[c] k_j[c]
  exp(G_i[c] - G_j[c])``, in float32; ``O = (exp(G) * Q) S_0 + tril(B) U``
  with ``B`` as ``A`` from ``q_i``; and the state moves by ``S_C =
  Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U``: ``[d_k, d_v]`` matmuls
  between chunks. Decay enters only as DIFFERENCES ``exp(G_i - G_j)``, ``j <=
  i``: every factor is at most 1 and ``1 / exp(G)`` is never formed (a
  channel whose ``alpha`` is near 0 overflows it within a chunk). Chunks in
  front of ``first_chunk`` (a bucket's left pads) are not visited. It serves
  the CPU, a verify step (ONE chunk of the step's length from the state) and
  ``delta_rule_replay``. (A second XLA form that makes ``A``, ``B`` and the
  system's inverse for every chunk at once, with the pairs of different
  16-position sub-blocks as matmuls, read no faster on the chip: XLA's cost
  is a launch and a round trip through HBM for each of thousands of tiny
  operations, whatever their algebra. The kernel has neither, which is why it
  won where that form did not: PERF.md section 6, PRs 49 and 50.)
- the kernel ``delta_rule_chunked`` (``delta_rule_chunked_pallas``;
  ``pl.pallas_call(name=...)``): the same chunk algebra at chunks of 64 with
  a group of eight heads' states in VMEM for a row's whole walk (grid ``(row,
  head group, chunk)``, the chunk axis sequential; the state read in front of
  the first chunk and written behind the last, never in between); chunks in
  front of ``first_chunk`` cost a grid step and no copy; ``q, k, v, g`` and
  ``o`` are read and written as ``[B, S, H, d]`` lies. Every pair of a chunk
  goes to the MXU with its decay split at a position BETWEEN the two
  (``_chunk_of_heads`` says how: six levels of half-blocks, every factor at
  most 1), the log decay's segment sums are a 0/1 matmul, the triangular
  system is inverted by blocks of 16 as matmuls (``N^16 = 0``: four
  squarings; then the blocks under the diagonal, ``X^4 = 0``). Every product
  is float32 with float32-accurate operands.
- ``delta_rule_replay``: the XLA chunk form over ONE chunk with the positions
  from ``kept`` on made identities (``g = 0``, ``beta = 0``): what a verify
  step's commit runs from the state in front of the step.

A pad position is an identity of the recurrence (``g = 0``, ``beta = 0``): the
caller masks both, and a row of nothing but pads leaves its state exactly
zero, since ``u = 0 * (...)`` adds ``k 0^T``.

Shapes: ``q, k [B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``g [B, S, H, d_k]``
float32, ``beta [B, S, H]`` float32, the state ``[B, H, d_k, d_v]`` float32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64  # positions a chunk of the prefill form (the usual; the kernel's)
KERNEL = "delta_rule_chunked"  # what a trace calls the kernel
HEAD_GROUP = 8  # heads a grid step of the kernel walks: the sublanes of a block ``[CHUNK, HEAD_GROUP, d]``
_HI = jax.lax.Precision.HIGHEST  # the state is float32: its products are not rounded to bf16


def _one_step(state, q, k, v, g, beta):
    """One position: ``state [B, H, dk, dv]``, ``q, k, g [B, H, dk]``, ``v [B,
    H, dv]``, ``beta [B, H]`` -> ``(state, o [B, H, dv])``, all float32."""
    state = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
    state = state + k[..., None] * u[..., None, :]
    return state, jnp.sum(state * q[..., None], axis=-2)


def delta_rule_steps(q, k, v, g, beta, state):
    """The recurrence a position at a time from ``state``: ``(o [B, S, H, dv]
    float32, the last state)``."""
    f32 = jnp.float32
    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    last, o = jax.lax.scan(lambda s, x: _one_step(s, *x), state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), last


def delta_rule_step(q, k, v, g, beta, state) -> Tuple[jax.Array, jax.Array]:
    """The single-token step: ``q, k, g [B, H, dk]``, ``v [B, H, dv]``, ``beta
    [B, H]`` -> ``(o [B, H, dv] float32, state)``."""
    f32 = jnp.float32
    state, o = _one_step(state.astype(f32), *(a.astype(f32) for a in (q, k, v, g, beta)))
    return o, state


def _chunk(qc, kc, vc, gc, bc, state):
    """One chunk from ``state``: ``qc, kc, gc [B, H, C, dk]``, ``vc [B, H, C,
    dv]``, ``bc [B, H, C]`` (float32) -> ``(o [B, H, C, dv], state)``."""
    C = qc.shape[2]
    G = jnp.cumsum(gc, axis=2)  # [B, H, C, dk], falling from g_0 to G_C
    # exp(G_i - G_j) for j <= i, the key channels in FRONT of the (i, j)
    # plane: the sum over them is then of whole planes, no reduction in a
    # lane; the mask goes in before the exp (an upper pair's difference is
    # positive and may overflow)
    Gt = jnp.swapaxes(G, 2, 3)  # [B, H, dk, C]
    at = jnp.arange(C)
    lower = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(lower, Gt[..., :, None] - Gt[..., None, :], -jnp.inf))  # [B, H, dk, C, C]
    kt, qt = jnp.swapaxes(kc, 2, 3), jnp.swapaxes(qc, 2, 3)
    kk = jnp.sum(decay * kt[..., :, None] * kt[..., None, :], axis=2)  # [B, H, C, C]: A, its diagonal |k_i|^2
    qk = jnp.sum(decay * qt[..., :, None] * kt[..., None, :], axis=2)  # B, the diagonal q_i . k_i
    system = jnp.where(at[:, None] > at[None, :], bc[..., None] * kk, 0.0) + jnp.eye(C, dtype=kk.dtype)
    gamma = jnp.exp(G)
    rhs = bc[..., None] * (vc - jnp.einsum("bhck,bhkv->bhcv", gamma * kc, state, precision=_HI))
    u = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhck,bhkv->bhcv", gamma * qc, state, precision=_HI) + jnp.einsum(
        "bhij,bhjv->bhiv", qk, u, precision=_HI)
    carried = kc * jnp.exp(G[:, :, -1:, :] - G)  # k_j as the chunk's last position sees it
    state = state * jnp.swapaxes(gamma[:, :, -1:, :], 2, 3) + jnp.einsum(
        "bhck,bhcv->bhkv", carried, u, precision=_HI)
    return o, state


def delta_rule_chunked_xla(q, k, v, g, beta, state, *, chunk: int = CHUNK,
                           first_chunk: Optional[jax.Array] = None):
    """The recurrence a chunk at a time from ``state``: ``(o [B, S, H, dv]
    float32, the last state)``. ``S`` is padded behind to whole chunks with
    identities. ``first_chunk`` (an int32 scalar): chunks in front of it hold
    nothing but pads in every row; they are not visited and their rows of
    ``o`` are zeros."""
    f32 = jnp.float32
    B, S, H, dk = q.shape
    C = min(chunk, S)
    n = -(-S // C)

    def chunks(a):  # [B, S, H, ...] -> [n, B, H, C, ...]
        a = a.astype(f32)
        a = jnp.pad(a, ((0, 0), (0, n * C - S)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, n, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qs, ks, vs, gs, bs = (chunks(a) for a in (q, k, v, g, beta))

    def one(i, carry):
        s, out = carry
        o, s = _chunk(qs[i], ks[i], vs[i], gs[i], bs[i], s)
        return s, jax.lax.dynamic_update_index_in_dim(out, o, i, 0)

    out = jnp.zeros((n, B, H, C, v.shape[-1]), f32)
    first = jnp.int32(0) if first_chunk is None else jnp.clip(jnp.asarray(first_chunk, jnp.int32), 0, n)
    state, out = jax.lax.fori_loop(first, n, one, (state.astype(f32), out))
    o = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 3, 2).reshape(B, n * C, H, -1)
    return o[:, :S], state


def _halves(C: int) -> Tuple[int, ...]:
    """The half-block sizes of the kernel's levels: 1, 2, ..., C / 2."""
    return tuple(1 << i for i in range(C.bit_length() - 1))


@functools.lru_cache(maxsize=None)
def _segment_sums(C: int) -> np.ndarray:
    """The 0/1 matrix ``[2 * levels * C, C]`` whose product with a chunk's
    ``g [C, d]`` stacks, for ``s`` = 2, 4, ..., C: ``P_s[i]``, the sum of
    ``g_t`` over ``t <= i`` of ``i``'s aligned block of ``s`` positions
    (``P_C`` is the running log decay ``G``), then ``R_s[j]``, the sum over
    ``t > j`` of ``j``'s block (``R_C = G_C - G``). ``P_1 = g`` and ``R_1 = 0``
    are not in it. Three copies side by side, ``[.., 3 * C]``: one for each
    bfloat16 piece of ``g``."""
    at = np.arange(C)
    spans = [2 * s for s in _halves(C)]
    same = [at[:, None] // s == at[None, :] // s for s in spans]
    rows = [m & (at[None, :] <= at[:, None]) for m in same] + [m & (at[None, :] > at[:, None]) for m in same]
    return np.tile(np.concatenate(rows).astype(np.float32), (1, 3))


def _top_bf16(x):
    """``x`` (float32) cut to the bits a bfloat16 holds (no rounding: the
    rest, ``x - _top_bf16(x)``, is exact)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _pieces(x):
    """``x`` (float32) as three bfloat16 pieces whose sum it is, exactly."""
    x1 = _top_bf16(x)
    x2 = _top_bf16(x - x1)
    return tuple(piece.astype(jnp.bfloat16) for piece in (x1, x2, x - x1 - x2))


def _mm(a, b, dims=((1,), (0,))):
    """A float32 matmul with float32-accurate operands (contracting ``dims``):
    the six products of the operands' bfloat16 pieces that are not under
    float32's last bit (what ``Precision.HIGHEST`` multiplies), as ONE
    bfloat16 matmul over the pieces side by side on the contracted axis, so
    the MXU sums them in float32 and two pieces of 64 share a pass (my chip
    runs, PR 50: 3.45 ms a layer-row at the served shape against 4.24 with
    ``precision=HIGHEST`` on the same products, the same error)."""
    (ca,), (cb,) = dims
    a1, a2, a3 = _pieces(a)
    b1, b2, b3 = _pieces(b)
    return jax.lax.dot_general(jnp.concatenate([a1, a1, a2, a1, a2, a3], axis=ca),
                               jnp.concatenate([b1, b2, b1, b3, b2, b1], axis=cb), (dims, ((), ())),
                               precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)


def _each(fn, *per_head):
    """``fn`` of every head's operands: a stage of the kernel is written for
    ALL the heads of its group before the next stage, so that one head's
    matmul waits (a chunk is a chain of ten dependent ones) beside the other
    heads' work and not in front of it."""
    return [fn(*operands) for operands in zip(*per_head)]


def _chunk_of_heads(q, k, v, g, beta, st, sums, level_masks, diagonal, eye):
    """A group of heads' chunk from their states, as the kernel computes it:
    every operand a list with an entry a head, ``q, k, g [C, dk]``, ``v [C,
    dv]``, ``beta [C, 1]`` (float32), ``st [dv, dk]`` the state TRANSPOSED
    (its decay is then a row's broadcast) -> ``(o [C, dv], st)`` a head.
    ``sums``: ``_segment_sums(C)`` in bfloat16; ``level_masks`` (one a level,
    ``[2C, C]``), ``diagonal`` (the pairs of one 16-block) and ``eye``: the
    chunk's index planes.

    The pairs. A level is a half-block size ``s``: the pairs ``j < i`` of one
    aligned block of ``2s`` positions with ``j`` in its first half and ``i``
    in its second (every pair is of exactly one level: that of the highest
    bit in which ``i`` and ``j`` differ). With ``m`` the first half's last
    position, ``exp(G_i - G_j) = exp(G_i - G_m) * exp(G_m - G_j) = exp(P_s[i])
    * exp(R_s[j])``: both factors at most 1, and ONE matmul ``[k * exp(P_s);
    q * exp(P_s)] (k * exp(R_s))^T`` a level makes the level's entries of
    ``A`` and ``B`` (the others of its product are finite and masked off)."""
    f32 = jnp.float32
    C = q[0].shape[0]
    n = len(_halves(C))

    # the segment sums of g, exact to float32: its three bfloat16 pieces under
    # one another against the 0/1 matrix three times side by side
    sums_g = _each(lambda g: jnp.dot(sums, jnp.concatenate(_pieces(g), axis=0), precision=jax.lax.Precision.DEFAULT,
                                     preferred_element_type=f32), g)  # [2nC, dk] a head

    def up(level):  # exp(P_s), s = 2 ** level, a head; P_1 = g
        return _each(lambda g, sums_g: jnp.exp(g if level == 0 else sums_g[(level - 1) * C:level * C]), g, sums_g)

    def down(level):  # exp(R_s)
        return _each(lambda sums_g: jnp.exp(sums_g[(n + level - 1) * C:(n + level) * C]), sums_g)

    pairs = [jnp.zeros((2 * C, C), f32)] * len(g)  # A over B
    for level in range(n):
        lhs = _each(lambda q, k, e: jnp.concatenate([k * e, q * e], axis=0), q, k, up(level))
        rhs = k if level == 0 else _each(jnp.multiply, k, down(level))  # R_1 = 0
        products = _each(lambda a, b: _mm(a, b, ((1,), (1,))), lhs, rhs)
        pairs = _each(lambda product, pairs: jnp.where(level_masks[level], product, pairs), products, pairs)
    # B with its diagonal q_i . k_i; A is strictly lower
    qk = _each(lambda q, k, pairs: jnp.where(eye, jnp.sum(q * k, axis=1, keepdims=True), pairs[C:]), q, k, pairs)

    # U = (I + N)^-1 rhs, N = tril(Diag(beta) A, -1), by blocks of 16: the
    # diagonal blocks' inverses D^-1 by squaring (N_d^16 = 0), then with X =
    # D^-1 L (L the blocks under the diagonal: X^4 = 0) (I + X)^-1 = (I - X)(I
    # + X^2), applied to D^-1 rhs
    system = _each(lambda beta, pairs: beta * pairs[:C], beta, pairs)
    power = _each(lambda system: jnp.where(diagonal, system, 0.0), system)
    below = _each(jnp.subtract, system, power)
    unit = eye.astype(f32)
    inv_d = _each(lambda near: unit - near, power)
    for _ in range(3):
        power = _each(_mm, power, power)
        inv_d = _each(lambda inv_d, power: inv_d + _mm(inv_d, power), inv_d, power)
    x = _each(_mm, inv_d, below)
    xx = _each(_mm, x, x)

    gamma = _each(lambda sums_g: jnp.exp(sums_g[(n - 1) * C:n * C]), sums_g)  # exp(G)
    from_state = _each(lambda q, k, gamma, st: _mm(jnp.concatenate([gamma * k, gamma * q], axis=0), st, ((1,), (1,))),
                       q, k, gamma, st)  # [2C, dv]
    u = _each(lambda inv_d, beta, v, from_state: _mm(inv_d, beta * (v - from_state[:C])), inv_d, beta, v, from_state)
    u = _each(lambda u, xx: u + _mm(xx, u), u, xx)
    u = _each(lambda u, x: u - _mm(x, u), u, x)
    o = _each(lambda from_state, qk, u: from_state[C:] + _mm(qk, u), from_state, qk, u)
    carried = _each(jnp.multiply, k, down(n))  # k_j as the chunk's last position sees it
    st = _each(lambda st, gamma, u, carried: st * gamma[C - 1:C] + _mm(u, carried, ((0,), (0,))), st, gamma, u, carried)
    return o, st


def _chunk_kernel(first_ref, sums_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref, st_scr):
    c = pl.program_id(2)
    heads = st_scr.shape[0]

    @pl.when(c == 0)
    def _():  # the walk keeps a state transposed: its decay is then a row's broadcast
        for h in range(heads):
            st_scr[h] = s0_ref[0, h].T

    @pl.when(c < first_ref[0])
    def _():  # nothing but pads: the state passes it, its rows of o are zeros
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c >= first_ref[0])
    def _():
        C = q_ref.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        differ = rows ^ cols
        level_masks = []
        for s in _halves(C):
            m = (rows > cols) & (differ >= s) & (differ < 2 * s)
            level_masks.append(jnp.concatenate([m, m], axis=0))

        def head(ref, h):  # a head's [C, d] of a block [1, C, heads, d]: its rows lie ``heads`` sublanes apart
            return ref.reshape(C * heads, ref.shape[-1])[pl.ds(h, C, stride=heads), :]

        o, st = _chunk_of_heads(
            *([head(ref, h) for h in range(heads)] for ref in (q_ref, k_ref, v_ref, g_ref)),
            [beta_ref[0, 0, :, h:h + 1] for h in range(heads)], [st_scr[h] for h in range(heads)],
            sums_ref[...], level_masks, differ < min(16, C), rows == cols)
        for h in range(heads):
            o_ref[0, :, h, :] = o[h]
            st_scr[h] = st[h]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            s_ref[0, h] = st_scr[h].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_chunked_pallas(q, k, v, g, beta, state, first_chunk=None, *, interpret: bool = False):
    """``delta_rule_chunked_xla`` at chunks of ``CHUNK`` by the kernel: one
    call walks a row's chunks from ``first_chunk`` with a group of heads'
    states in VMEM (read from ``state`` in front of the first, written behind
    the last). ``q, k, v, g`` and ``o`` are read and written as they lie (a
    block is a chunk of ``HEAD_GROUP`` heads of ``[B, S, H, d]``, or of all
    ``H`` where that does not divide them; a head's rows a strided read of it); every product is float32 with float32-accurate
    operands. On the chip ``dk`` and ``dv`` are whole numbers of 128 lanes."""
    f32 = jnp.float32
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = CHUNK
    n = -(-S // C)
    Hg = HEAD_GROUP if H % HEAD_GROUP == 0 else H

    def whole(a):  # identities behind, to whole chunks
        return jnp.pad(a, ((0, 0), (0, n * C - S)) + ((0, 0),) * (a.ndim - 2)) if n * C > S else a

    # q, k, v in float32: a block of eight heads is one float32 tile a position
    q, k, v, g, beta = (whole(a.astype(f32)) for a in (q, k, v, g, beta))
    beta = jnp.moveaxis(beta.reshape(B, n * C, H // Hg, Hg), 2, 1)  # [B, H / Hg, n * C, Hg]
    first = jnp.zeros((1,), jnp.int32) if first_chunk is None else jnp.clip(
        jnp.asarray(first_chunk, jnp.int32).reshape(1), 0, n)
    sums = jnp.asarray(_segment_sums(C), jnp.bfloat16)

    def live(c, first):  # a chunk in front of the first live one re-reads that one's block: no copy
        return jnp.minimum(jnp.maximum(c, first[0]), n - 1)

    def seq(d):
        return pl.BlockSpec((1, C, Hg, d), lambda b, j, c, first: (b, live(c, first), j, 0))

    held = pl.BlockSpec((1, Hg, dk, dv), lambda b, j, c, first: (b, j, 0, 0))
    o, last = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // Hg, n),
            in_specs=[pl.BlockSpec(sums.shape, lambda b, j, c, first: (0, 0)), seq(dk), seq(dk), seq(dv), seq(dk),
                      pl.BlockSpec((1, 1, C, Hg), lambda b, j, c, first: (b, j, live(c, first), 0)), held],
            out_specs=[pl.BlockSpec((1, C, Hg, dv), lambda b, j, c, first: (b, c, j, 0)), held],
            scratch_shapes=[pltpu.VMEM((Hg, dv, dk), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, n * C, H, dv), f32), jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL,
    )(first, sums, q, k, v, g, beta, state.astype(f32))
    return o[:, :S], last


def chunk_form(impl: str) -> str:
    """``"delta_rule_chunked_pallas"`` where a walk over chunks goes through
    the kernel, else ``"delta_rule_chunked_xla"``: ``impl`` is a resolved
    ``attn_impl``."""
    return f"{KERNEL}_pallas" if impl != "xla" else f"{KERNEL}_xla"


def delta_rule_chunked(q, k, v, g, beta, state, *, chunk: int = CHUNK, first_chunk: Optional[jax.Array] = None,
                       impl: str = "xla"):
    """``(o [B, S, H, dv] float32, the last state)`` by the form
    ``chunk_form`` names (the kernel's chunk is ``CHUNK``: another is XLA's)."""
    if chunk == CHUNK and impl != "xla":
        return delta_rule_chunked_pallas(q, k, v, g, beta, state, first_chunk, interpret=impl == "pallas_interpret")
    return delta_rule_chunked_xla(q, k, v, g, beta, state, chunk=chunk, first_chunk=first_chunk)


def delta_rule_replay(k, v, g, beta, state, kept):
    """The state behind the first ``kept`` of the ``n`` positions whose ``k,
    v, g, beta`` a verify step left (``kept`` an int32 scalar, 0 <= kept <=
    n), from the ``state`` in front of the step: one chunk in which the
    positions from ``kept`` on are identities."""
    live = jnp.arange(k.shape[1]) < kept
    g = jnp.where(live[None, :, None, None], g, 0.0)
    beta = jnp.where(live[None, :, None], beta, 0.0)
    _, state = delta_rule_chunked_xla(k, k, v, g, beta, state, chunk=k.shape[1])
    return state

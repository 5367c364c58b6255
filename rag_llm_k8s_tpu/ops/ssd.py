"""The Mamba-2 recurrence (state-space duality): ONE decay a head.

For a head ``h`` of ``P`` channels whose ``B`` and ``C`` (``N`` wide) it
shares with the other heads of its group, a time step ``dt >= 0`` and ``A <
0`` (both a head's scalars), the state ``H [P, N]`` float32 moves a position
by ``H <- exp(dt A) H + dt x (x) B`` and gives ``y = H C + D x``. A position
whose ``dt`` is 0 is an identity of the recurrence (``exp(0) = 1``, nothing
added): that is how a pad passes the state on.

Because the decay is a scalar a head, a chunk of ``Q`` positions is MATMULS
(the dual form), not a scan: with ``c`` the running sum of ``a = dt A``
inside the chunk,

    Y  = ((C B^T) * L) (dt * X) + exp(c) * (C H0^T),   L_ij = exp(c_i - c_j) (i >= j), else 0
    H1 = exp(c_end) H0 + sum_j exp(c_end - c_j) dt_j x_j (x) B_j

``C B^T`` is made once a GROUP and shared by its heads. Every exponent is of a
difference that is <= 0 (``L`` is masked BEFORE the exponential), so nothing
is formed that a later factor has to bring back down.

Three forms, one arithmetic (float32 inputs to every product, the highest
matmul precision: the state is float32 by construction and a product that
rounded it to bf16 would make that a pretence):

- ``ssd_chunked``: a prompt's walk, the state carried chunk to chunk. The
  loop is over CHUNKS, from the first that holds a live position
  (``first_chunk``; outputs in front of it are the ``D`` skip alone, which nothing reads).
- ``ssd_step``: one position (a decode step): elementwise on the state.
- ``ssd_replay``: the state behind the first ``kept`` positions of a step
  that fed ``n`` (``Family.commit`` after a verify step, which leaves the
  state as it was and keeps ``x, B, dt`` and the log decay of what it fed): ``H1`` of one
  chunk with ``dt`` zeroed from ``kept`` on. A verify step's outputs are
  ``ssd_chunked`` over one chunk of the fed positions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _grouped(a: jax.Array, groups: int, axis: int) -> jax.Array:
    """The heads' axis split ``[groups, heads a group]``: head ``h`` reads group ``h // (heads / groups)``."""
    return a.reshape(a.shape[:axis] + (groups, a.shape[axis] // groups) + a.shape[axis + 1:])


def _chunk(x, dt, A, Bm, Cm, h0) -> Tuple[jax.Array, jax.Array]:
    """One chunk from ``h0``: ``x [R, Q, H, P]``, ``dt [R, Q, H]``, ``A [H]``,
    ``Bm, Cm [R, Q, G, N]``, ``h0 [R, H, P, N]``, all float32. Returns ``(y [R,
    Q, H, P]`` without the ``D`` skip, ``h1)``. Every product is one matmul a
    GROUP (its heads side by side), so ``B`` and ``C`` are read once."""
    R, Q, H, P = x.shape
    G = Bm.shape[2]
    c = jnp.cumsum(dt * A, axis=1)  # [R, Q, H], every step <= 0
    at = jnp.arange(Q)
    causal = at[:, None] >= at[None, :]  # [Q(i), Q(j)]
    cb = jnp.einsum("rign,rjgn->rgij", Cm, Bm, precision=_HI)  # once a group
    ch = _grouped(c.transpose(0, 2, 1), G, 1)  # [R, G, K, Q]
    decay = jnp.exp(jnp.where(causal, ch[..., :, None] - ch[..., None, :], -jnp.inf))  # L [R, G, K, Q, Q]
    dx = _grouped(dt[..., None] * x, G, 2)  # [R, Q, G, K, P]
    h0g = _grouped(h0, G, 1)  # [R, G, K, P, N]
    y = jnp.einsum("rgkij,rjgkp->rigkp", cb[:, :, None] * decay, dx, precision=_HI)
    y = y + _grouped(jnp.exp(c), G, 2)[..., None] * jnp.einsum("rign,rgkpn->rigkp", Cm, h0g, precision=_HI)
    tail = _grouped(jnp.exp(c[:, -1:, :] - c), G, 2)  # exp(c_end - c_j) [R, Q, G, K]
    h1 = _grouped(jnp.exp(c[:, -1, :]), G, 1)[..., None, None] * h0g + jnp.einsum(
        "rjgkp,rjgn->rgkpn", tail[..., None] * dx, Bm, precision=_HI)
    return y.reshape(R, Q, H, P), h1.reshape(h0.shape)


def chunks_of(S: int, chunk: int) -> Tuple[int, int]:
    """``(chunk length, chunks)`` of a call of ``S`` positions: the
    configuration's chunk, or the whole call where it is shorter; a last
    chunk that is not whole is padded with identity positions."""
    Q = min(chunk, S)
    return Q, -(-S // Q)


def ssd_chunked(x, dt, A, Bm, Cm, D, h0, *, chunk: int, first_chunk: Optional[jax.Array] = None):
    """``(y [R, S, H, P] float32, last state [R, H, P, N])`` of the recurrence
    over ``S`` positions from ``h0``: ``x [R, S, H, P]``, ``dt [R, S, H]``
    (after the softplus; 0 at a pad), ``A, D [H]``, ``Bm, Cm [R, S, G, N]``.
    ``first_chunk`` (a traced scalar): chunks in front of it hold pads only
    and are not walked."""
    f32 = jnp.float32
    R, S, H, P = x.shape
    Q, n = chunks_of(S, chunk)
    x, dt, Bm, Cm = (a.astype(f32) for a in (x, dt, Bm, Cm))
    A, h0 = A.astype(f32), h0.astype(f32)
    skip = D.astype(f32)[:, None] * x
    if n == 1:
        y, h1 = _chunk(x, dt, A, Bm, Cm, h0)
        return y + skip, h1
    pad = n * Q - S
    if pad:  # identity positions behind the last
        x, dt, Bm, Cm, skip = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                               for a in (x, dt, Bm, Cm, skip))

    def piece(a, i):
        return jax.lax.dynamic_slice_in_dim(a, i * Q, Q, axis=1)

    def walk(i, carry):  # the outputs start as the skip: a chunk adds its part in place
        y, h = carry
        yc, h = _chunk(piece(x, i), piece(dt, i), A, piece(Bm, i), piece(Cm, i), h)
        return jax.lax.dynamic_update_slice_in_dim(y, piece(y, i) + yc, i * Q, axis=1), h

    lo = jnp.int32(0) if first_chunk is None else jnp.clip(jnp.asarray(first_chunk, jnp.int32), 0, n)
    y, h1 = jax.lax.fori_loop(lo, n, walk, (skip, h0))
    return y[:, :S], h1


def ssd_step(x, dt, A, Bm, Cm, D, h0):
    """One position: ``x [R, H, P]``, ``dt [R, H]``, ``Bm, Cm [R, G, N]``, ``h0
    [R, H, P, N]`` -> ``(y [R, H, P] float32, state)``. Elementwise on the
    state, which is read once and written once."""
    f32 = jnp.float32
    G = Bm.shape[1]
    x, dt, h0 = x.astype(f32), dt.astype(f32), h0.astype(f32)
    Bg, Cg = (a.astype(f32)[:, :, None, None, :] for a in (Bm, Cm))  # [R, G, 1, 1, N]: a group's heads read one row
    decay = jnp.exp(dt * A.astype(f32))[..., None, None]
    h1 = _grouped(decay * h0, G, 1) + _grouped((dt[..., None] * x)[..., None], G, 1) * Bg
    y = jnp.sum(h1 * Cg, axis=-1).reshape(x.shape) + D.astype(f32)[:, None] * x
    return y, h1.reshape(h0.shape)


def ssd_replay(x, dt, a, Bm, h0, kept):
    """The state behind the first ``kept`` of ``n`` fed positions, from the
    state in front of them: ``x [R, n, H, P]``, ``dt [R, n, H]`` and the log
    decay ``a = dt A [R, n, H]`` as the step formed them, ``Bm [R, n, G, N]``,
    ``h0 [R, H, P, N]``; ``kept`` a traced scalar, 0 <= kept <= n."""
    f32 = jnp.float32
    n, G = x.shape[1], Bm.shape[2]
    x, Bm, h0 = x.astype(f32), Bm.astype(f32), h0.astype(f32)
    live = (jnp.arange(n) < kept)[None, :, None]
    dt, a = jnp.where(live, dt.astype(f32), 0.0), jnp.where(live, a.astype(f32), 0.0)
    c = jnp.cumsum(a, axis=1)
    weighed = _grouped((jnp.exp(c[:, -1:, :] - c) * dt)[..., None] * x, G, 2)  # exp(c_end - c_j) dt_j x_j
    h1 = _grouped(jnp.exp(c[:, -1, :])[..., None, None] * h0, G, 1) + jnp.einsum(
        "rjgkp,rjgn->rgkpn", weighed, Bm, precision=_HI)
    return h1.reshape(h0.shape)

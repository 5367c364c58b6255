"""The gated delta rule with a decay a CHANNEL: a linear-attention layer's
recurrence over a float32 matrix state a head.

For one head (``d_k`` key channels, ``d_v`` value channels), ``S`` the state
``[d_k, d_v]``, ``q_t``, ``k_t`` the normed query and key, ``v_t`` the value,
``g_t <= 0`` the log decay of each key channel and ``beta_t`` in (0, 1]:

    S'  = Diag(exp(g_t)) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)            what the state does not yet say of v_t
    S_t = S' + k_t u_t^T                     (= (I - beta k k^T) Diag(alpha) S + beta k v^T)
    o_t = S_t^T q_t

Three forms that agree (``tests/test_delta_rule.py``):

- ``delta_rule_steps``: a ``lax.scan`` over positions, every product an
  elementwise float32 one (no matmul unit, so the same numbers on every
  backend): the oracle inside the program, and with ``S == 1`` the
  single-token step (read the state, decay it, correct it by rank one, write
  it: ``2 * 4 * d_k * d_v`` bytes a head).
- ``delta_rule_chunked``: the prefill form. Inside a chunk of ``C`` positions
  with ``G_i = sum_{j <= i} g_j`` the running log decay, ``u`` solves the
  unit lower-triangular system ``(I + tril(Diag(beta) A, -1)) U = Diag(beta)
  (V - (exp(G) * K) S_0)`` with ``A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] -
  G_j[c])``, in float32; ``O = (exp(G) * Q) S_0 + tril(B) U`` with ``B`` as
  ``A`` from ``q_i``; and the state moves by ``S_C = Diag(exp(G_C)) S_0 + (K *
  exp(G_C - G))^T U``: ``[d_k, d_v]`` matmuls between chunks. Decay enters
  only as DIFFERENCES ``exp(G_i - G_j)``, ``j <= i``: every factor is at most
  1 and ``1 / exp(G)`` is never formed (a channel whose ``alpha`` is near 0
  overflows it within a chunk). Chunks in front of ``first_chunk`` (a bucket's
  left pads) are not visited. (A form that makes ``A``, ``B`` and the system's
  inverse for every chunk at once, with the pairs of different 16-position
  sub-blocks as matmuls, read no faster on the chip: PERF.md section 6, PR 49.)
- ``delta_rule_replay``: the chunk form over ONE chunk with the positions
  from ``kept`` on made identities (``g = 0``, ``beta = 0``): what a verify
  step's commit runs from the state in front of the step.

A pad position is an identity of the recurrence (``g = 0``, ``beta = 0``): the
caller masks both, and a row of nothing but pads leaves its state exactly
zero, since ``u = 0 * (...)`` adds ``k 0^T``.

Shapes: ``q, k [B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``g [B, S, H, d_k]``
float32, ``beta [B, S, H]`` float32, the state ``[B, H, d_k, d_v]`` float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 64  # positions a chunk of the prefill form (the usual)
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


def delta_rule_chunked(q, k, v, g, beta, state, *, chunk: int = CHUNK,
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


def delta_rule_replay(k, v, g, beta, state, kept):
    """The state behind the first ``kept`` of the ``n`` positions whose ``k,
    v, g, beta`` a verify step left (``kept`` an int32 scalar, 0 <= kept <=
    n), from the ``state`` in front of the step: one chunk in which the
    positions from ``kept`` on are identities."""
    live = jnp.arange(k.shape[1]) < kept
    g = jnp.where(live[None, :, None, None], g, 0.0)
    beta = jnp.where(live[None, :, None], beta, 0.0)
    _, state = delta_rule_chunked(k, k, v, g, beta, state, chunk=k.shape[1])
    return state

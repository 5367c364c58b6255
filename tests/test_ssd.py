"""``ops/ssd.py`` (the Mamba-2 recurrence: the chunked matmul form, the single
step, the replay) against a float64 loop a position at a time, and
``ops/moe.py held_expert_ffn``'s two kinds of expert."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.ops import moe, ssd

R, S, H, P, G, N = 3, 37, 8, 16, 2, 16
START = np.array([0, 11, 37])  # a row of no pads, one of some, one of nothing else


@pytest.fixture(scope="module")
def drawn():
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (R, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (R, S, H)))
    dt = jnp.where(jnp.arange(S)[None, :, None] >= jnp.asarray(START)[:, None, None], dt, 0.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=-3.0, maxval=1.0))  # memories of 1 to 100 positions
    Bm, Cm = jax.random.normal(k[3], (R, S, G, N)), jax.random.normal(k[4], (R, S, G, N))
    return x, dt, A, Bm, Cm, jnp.linspace(0.5, 1.5, H), jax.random.normal(k[5], (R, H, P, N))


def loop(x, dt, A, Bm, Cm, D, h0, upto=None):
    """float64, a position at a time: head ``h`` reads group ``h // (H / G)``."""
    x, dt, A, Bm, Cm, D, h = (np.asarray(a, np.float64) for a in (x, dt, A, Bm, Cm, D, h0))
    ys = []
    for t in range(x.shape[1] if upto is None else upto):
        Bh, Ch = np.repeat(Bm[:, t], H // G, axis=1), np.repeat(Cm[:, t], H // G, axis=1)
        h = np.exp(dt[:, t] * A)[..., None, None] * h + (dt[:, t][..., None] * x[:, t])[..., None] * Bh[:, :, None, :]
        ys.append((h * Ch[:, :, None, :]).sum(-1) + D[:, None] * x[:, t])
    return (np.stack(ys, 1) if ys else None), h


@pytest.mark.parametrize("chunk,why", [(8, "five chunks, the last not whole"), (5, "a pad ends inside a chunk"),
                                       (64, "one chunk"), (37, "exactly one chunk")])
def test_the_chunked_form_is_the_recurrence(drawn, chunk, why):
    y, h1 = jax.jit(functools.partial(ssd.ssd_chunked, chunk=chunk))(*drawn)
    want_y, want_h = loop(*drawn)
    np.testing.assert_allclose(y, want_y, atol=5e-5)
    np.testing.assert_allclose(h1, want_h, atol=5e-5)
    np.testing.assert_array_equal(h1[2], drawn[-1][2])  # nothing but pads: the state is passed on bit for bit


def test_chunks_of_nothing_but_pads_are_not_walked(drawn):
    x, dt, A, Bm, Cm, D, h0 = (a[1:2] if a.ndim > 1 else a for a in drawn)  # the row whose first 11 are pads
    y, h1 = jax.jit(functools.partial(ssd.ssd_chunked, chunk=5))(x, dt, A, Bm, Cm, D, h0, first_chunk=jnp.int32(2))
    want_y, want_h = loop(x, dt, A, Bm, Cm, D, h0)
    np.testing.assert_allclose(y[:, 10:], want_y[:, 10:], atol=5e-5)
    np.testing.assert_allclose(h1, want_h, atol=5e-5)
    # nothing was computed in front of the first chunk walked: what is there is the skip alone
    np.testing.assert_array_equal(np.asarray(y[:, :10]), np.asarray(D[:, None] * x[:, :10]))


def test_the_single_step_is_the_recurrence(drawn):
    x, dt, A, Bm, Cm, D, h = drawn
    step, ys = jax.jit(ssd.ssd_step), []
    for t in range(S):
        y, h = step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, h)
        ys.append(y)
    want_y, want_h = loop(*drawn)
    np.testing.assert_allclose(np.stack(ys, 1), want_y, atol=5e-5)
    np.testing.assert_allclose(h, want_h, atol=5e-5)


@pytest.mark.parametrize("kept", [0, 1, 5, 36, 37])
def test_the_replay_is_the_state_behind_what_was_kept(drawn, kept):
    x, dt, A, Bm, Cm, D, h0 = drawn
    got = jax.jit(ssd.ssd_replay)(x, dt, dt * A, Bm, h0, jnp.int32(kept))
    np.testing.assert_allclose(got, loop(*drawn, upto=kept)[1], atol=5e-5)
    if kept == 0:
        np.testing.assert_array_equal(got, h0)


# ---- ops/moe.py held_expert_ffn: a SwiGLU expert, or two matrices and a squared relu ----

TOKENS, D, F, HELD, E, TOP = 40, 32, 48, 8, 16, 3


@pytest.fixture(scope="module")
def experts():
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(k[0], (TOKENS, D))
    logits = jax.random.normal(k[1], (TOKENS, E))
    chosen, weights = moe.route(logits, jnp.zeros((E,)), top_k=TOP, n_group=1, topk_group=1, scaling=2.0)
    stacks = tuple(jax.random.normal(k[i], shape) / np.sqrt(shape[-2]) for i, shape in (
        (2, (2, HELD, D, F)), (3, (2, HELD, D, F)), (4, (2, HELD, F, D))))
    return x, chosen, weights, stacks


def dense(x, chosen, weights, stacks, layer, first, act):
    """A loop over the held experts, each over every token."""
    y = np.zeros(x.shape, np.float64)
    for e in range(HELD):
        w = np.where(np.asarray(chosen) == first + e, np.asarray(weights, np.float64), 0.0).sum(-1)
        y += w[:, None] * act(np.asarray(x, np.float64), *(np.asarray(s[layer, e], np.float64) for s in stacks))
    return y


def test_a_gateless_expert_is_relu_squared_between_two_matrices(experts):
    x, chosen, weights, (_, up, down) = experts
    y, counts = moe.held_expert_ffn(x, chosen, weights, None, up, down, jnp.int32(1), HELD, E)
    want = dense(x, chosen, weights, (up, down), 1, HELD, lambda x, u, d: np.square(np.maximum(x @ u, 0.0)) @ d)
    np.testing.assert_allclose(y, want, atol=2e-4)
    held = int(((np.asarray(chosen) >= HELD) & (np.asarray(chosen) < 2 * HELD)).sum())
    assert (int(counts.routed), int(counts.computed), int(counts.combined)) == (held, held, held)


def test_a_gated_expert_computes_what_the_parent_computed(experts):
    """``held_expert_ffn``'s SwiGLU path, bit for bit against the body it had
    before an expert could lack a gate (PR 54's, copied here as the oracle)."""
    x, chosen, weights, (gate, up, down) = experts
    layer, first = jnp.int32(0), 0
    y, counts = moe.held_expert_ffn(x, chosen, weights, gate, up, down, layer, first, E)

    A = TOKENS * TOP
    local = chosen.reshape(A) - first
    mine = (local >= 0) & (local < HELD)
    key = jnp.where(mine, local, HELD)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(HELD, dtype=key.dtype)[None, :], axis=0).astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts, total = ends - sizes, ends[-1]
    C = moe.rows_per_pass(TOKENS, TOP, E, HELD)
    passes = -(-A // C)
    order, flat_w = jnp.pad(order, (0, passes * C - A)), weights.reshape(A)
    acc = jnp.zeros((TOKENS, D), x.dtype)
    for p in range(int(-(-int(total) // C))):
        lo = p * C
        a = jax.lax.dynamic_slice(order, (lo,), (C,))
        valid = lo + jnp.arange(C, dtype=jnp.int32) < total
        token = a // TOP
        rows = jnp.take(x, token, axis=0)
        here = jnp.clip(ends - lo, 0, C) - jnp.clip(starts - lo, 0, C)
        h = jax.nn.silu(moe._grouped_xla(rows, gate, here, layer)[0]) * moe._grouped_xla(rows, up, here, layer)[0]
        out = moe._grouped_xla(h, down, here, layer)[0]
        acc, _ = moe.combine(acc, out, jnp.take(flat_w, a), jnp.where(valid, token, TOKENS),
                             jnp.where(valid, jnp.take(key, a), HELD), HELD)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(acc))
    want = dense(x, chosen, weights, (gate, up, down), 0, first,
                 lambda x, g, u, d: ((x @ g) / (1.0 + np.exp(-(x @ g))) * (x @ u)) @ d)
    np.testing.assert_allclose(y, want, atol=2e-4)
    assert int(counts.routed) == int(total)

"""The gated delta rule's four forms (``ops/delta_rule.py``): the kernel = the
XLA chunk form = the step form = a plain token-at-a-time reference written
here, over chunk sizes, ragged lengths, left pads and a decay draw with alpha
down to 1e-6. The kernel's cases run it in interpret mode at its own geometry
(heads of 128 key and value channels, chunks of 64): what the MXU's passes do
to it is ``tests_tpu/test_on_chip.py``'s to see.

Tolerances. Everything is float32 at the highest matmul precision; the forms
order their sums differently (a triangular solve against a running rank-one
correction), so outputs of magnitude ~0.3 agree to ~1e-6: ``ATOL`` is 2e-5.
The faults the comparison must see are far above it (the last tests): a
clamped ``1 / exp(G)`` moves an output by 1e-2 or more, a scalar decay a head
by 1e-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.ops import delta_rule as dr

ATOL = 2e-5
B, H, DK, DV = 2, 3, 16, 24
GEOMETRY = {"xla": (B, H, DK, DV), "kernel": (1, 2, 128, 128)}  # batch, heads, key and value channels a form is drawn at


def draw(seed, S, lo=1e-6, batch=None, form="xla"):
    """Unit keys, queries of length ``dk^-1/2``, values of unit spread, alpha
    log-uniform in ``[lo, 1]`` a channel, beta across (0, 1), a state of unit
    spread, at ``form``'s geometry."""
    b, heads, dk, dv = GEOMETRY[form]
    batch = batch or b
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (batch, S, heads, dk))
    k = jax.random.normal(ks[1], (batch, S, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, S, heads, dv))
    g = jax.random.uniform(ks[3], (batch, S, heads, dk), minval=np.log(lo), maxval=0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, S, heads)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (batch, heads, dk, dv))


def chunked(form, *args, **kw):
    """The chunk form ``form`` names: XLA's, or the kernel in interpret mode."""
    return dr.delta_rule_chunked(*args, impl="pallas_interpret" if form == "kernel" else "xla", **kw)


def plain(q, k, v, g, beta, state):
    """The recurrence in numpy float64, a token and a head at a time."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    state = np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape)
    for b in range(q.shape[0]):
        for h in range(q.shape[2]):
            s = state[b, h]
            for t in range(q.shape[1]):
                s = np.exp(g[b, t, h])[:, None] * s
                s = s + beta[b, t, h] * np.outer(k[b, t, h], v[b, t, h] - s.T @ k[b, t, h])
                out[b, t, h] = s.T @ q[b, t, h]
            state[b, h] = s
    return out, state


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("S,chunk,form", [
    (37, 16, "xla"), (64, 64, "xla"), (130, 32, "xla"), (5, 64, "xla"), (96, 64, "xla"), (17, 8, "xla"),
    (100, 64, "kernel"), (256, 64, "kernel"), (5, 64, "kernel")])  # the kernel pads a ragged S to its chunks
def test_the_chunk_form_is_the_step_form_is_the_plain_recurrence(S, chunk, form):
    args = draw(S, S, form=form)
    want_o, want_s = plain(*args)
    o, s = dr.delta_rule_steps(*args)
    np.testing.assert_allclose(np.asarray(o), want_o, atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=ATOL)
    o, s = jax.jit(lambda *a: chunked(form, *a, chunk=chunk))(*args)
    np.testing.assert_allclose(np.asarray(o), want_o, atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=ATOL)


@pytest.mark.parametrize("lo,form", [(1e-6, "xla"), (1e-3, "xla"), (0.5, "xla"), (0.999, "xla"),
                                     (1e-6, "kernel"), (1e-3, "kernel"), (0.999, "kernel")])
def test_a_channel_whose_alpha_is_near_zero_overflows_nothing(lo, form):
    """Decay enters as differences ``exp(G_i - G_j)`` only: 64 positions at
    alpha 1e-6 are ``exp(-884)``, and ``1 / exp(G)`` is never formed (the
    kernel splits a pair's decay at a position between the two: both factors
    are at most 1)."""
    args = draw(11, 128, lo=lo, form=form)
    want_o, want_s = plain(*args)
    o, s = chunked(form, *args)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    np.testing.assert_allclose(np.asarray(o), want_o, atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=ATOL)


@pytest.mark.parametrize("pads,form", [(0, "xla"), (1, "xla"), (63, "xla"), (64, "xla"), (100, "xla"), (128, "xla"),
                                       (63, "kernel"), (64, "kernel"), (100, "kernel"), (128, "kernel")])
def test_left_pads_are_identities_and_their_chunks_are_not_visited(pads, form):
    """A pad has ``g = 0`` and ``beta = 0``: the state a row's first real
    token sees is the one it was handed, exactly; ``first_chunk`` skips the
    chunks that hold nothing else (their rows of ``o`` are zeros), and the
    rows of ``o`` behind them are the row's alone."""
    S = 128
    q, k, v, g, beta, s0 = draw(pads + 1, S, lo=0.1, batch=1, form=form)
    live = jnp.arange(S) >= pads
    g, beta = jnp.where(live[None, :, None, None], g, 0.0), jnp.where(live[None, :, None], beta, 0.0)
    o, s = chunked(form, q, k, v, g, beta, s0, first_chunk=jnp.int32(pads // dr.CHUNK))
    assert not np.asarray(o[:, :pads // dr.CHUNK * dr.CHUNK]).any()
    if pads < S:
        alone_o, alone_s = plain(q[:, pads:], k[:, pads:], v[:, pads:], g[:, pads:], beta[:, pads:], s0)
        np.testing.assert_allclose(np.asarray(o[:, pads:]), alone_o, atol=ATOL)
        np.testing.assert_allclose(np.asarray(s), alone_s, atol=ATOL)
    else:  # nothing but pads: the state is the one handed in, bit for bit
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))
    zero = jnp.zeros_like(s0)
    _, s = chunked(form, q[:, :pads or 1], k[:, :pads or 1], v[:, :pads or 1], jnp.zeros_like(g[:, :pads or 1]),
                   jnp.zeros_like(beta[:, :pads or 1]), zero)
    assert not np.asarray(s).any()  # a row of nothing but pads leaves a zero state exactly zero


def test_the_single_token_step_is_one_position_of_the_recurrence():
    q, k, v, g, beta, s0 = draw(3, 9, lo=0.05)
    want_o, want_s = plain(q, k, v, g, beta, s0)
    s = s0
    for t in range(9):
        o, s = dr.delta_rule_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        np.testing.assert_allclose(np.asarray(o), want_o[:, t], atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=ATOL)


@pytest.mark.parametrize("cut,S,form", [(1, 6, "steps"), (4, 6, "steps"), (64, 160, "kernel"), (70, 134, "kernel")])
def test_a_walk_resumes_from_the_state_it_left(cut, S, form):
    """The step form a position at a time, and the kernel's walk from a state
    that is not zero (a prompt past the largest bucket prefills in chunks
    from the state the chunk in front of it left)."""
    q, k, v, g, beta, s0 = draw(4, S, lo=0.2, form="xla" if form == "steps" else form)
    want_o, want_s = plain(q, k, v, g, beta, s0)
    head = tuple(a[:, :cut] for a in (q, k, v, g, beta))
    tail = tuple(a[:, cut:] for a in (q, k, v, g, beta))
    walk = dr.delta_rule_steps if form == "steps" else lambda *a: chunked(form, *a)
    o1, s1 = walk(*head, s0)
    o2, s2 = walk(*tail, s1)
    np.testing.assert_allclose(np.concatenate([np.asarray(o1), np.asarray(o2)], axis=1), want_o, atol=ATOL)
    np.testing.assert_allclose(np.asarray(s2), want_s, atol=ATOL)


@pytest.mark.parametrize("kept", [0, 1, 7, 16])
def test_replay_is_the_state_behind_the_kept_positions(kept):
    """What ``commit`` runs: of 16 fed positions the first ``kept`` replayed
    from the state in front of the step; the rest (rejected drafts) leave no
    trace, whatever they held."""
    q, k, v, g, beta, s0 = draw(5, 16, lo=0.3)
    want = plain(q[:, :kept], k[:, :kept], v[:, :kept], g[:, :kept], beta[:, :kept], s0)[1] if kept else np.asarray(s0)
    got = jax.jit(dr.delta_rule_replay)(k, v, g, beta, s0, jnp.int32(kept))
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)
    junk = jnp.where(jnp.arange(16)[None, :, None, None] >= kept, 7.0, v)
    np.testing.assert_array_equal(np.asarray(dr.delta_rule_replay(k, junk, g, beta, s0, jnp.int32(kept))),
                                  np.asarray(dr.delta_rule_replay(k, v, g, beta, s0, jnp.int32(kept))))


def _clamped(q, k, v, g, beta, state, clamp=30.0):
    """The form the chunk form must NOT take: ``exp(G_i)`` times a clamped
    ``1 / exp(G_j)`` (one chunk)."""
    G = jnp.cumsum(g, axis=1)
    up, down = jnp.exp(G), jnp.exp(jnp.minimum(-G, clamp))
    C = q.shape[1]
    at = jnp.arange(C)
    kk = jnp.einsum("bihc,bjhc->bhij", k * up, k * down)
    qk = jnp.einsum("bihc,bjhc->bhij", q * up, k * down)
    bt = jnp.swapaxes(beta, 1, 2)
    system = jnp.where(at[:, None] > at[None, :], bt[..., None] * kk, 0.0) + jnp.eye(C)
    rhs = bt[..., None] * (jnp.swapaxes(v, 1, 2) - jnp.einsum("bihc,bhcv->bhiv", k * up, state))
    u = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("bihc,bhcv->bhiv", q * up, state) + jnp.einsum(
        "bhij,bhjv->bhiv", jnp.where(at[:, None] >= at[None, :], qk, 0.0), u)
    return jnp.swapaxes(o, 1, 2)


@pytest.mark.parametrize("fault,form", [("clamped_inverse", "xla"), ("scalar_decay", "xla"), ("no_decay", "xla"),
                                        ("beta_one", "xla"), ("scalar_decay", "kernel")])
def test_a_fault_fails_the_tolerance(fault, form):
    q, k, v, g, beta, s0 = draw(6, 64, lo=1e-3, form=form)
    want, _ = plain(q, k, v, g, beta, s0)
    if fault == "clamped_inverse":
        got = _clamped(q, k, v, g, beta, s0)
    else:
        g2 = {"scalar_decay": jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), "no_decay": jnp.zeros_like(g)}.get(
            fault, g)
        got, _ = chunked(form, q, k, v, g2, jnp.ones_like(beta) if fault == "beta_one" else beta, s0)
    assert np.nanmax(np.abs(np.asarray(got) - want)) > 500 * ATOL


def test_a_mild_decay_passes_the_clamped_form_too():
    """The control's fault is the clamp, not the algebra: where no ``1 /
    exp(G)`` reaches the clamp the clamped form is the recurrence."""
    q, k, v, g, beta, s0 = draw(7, 32, lo=0.9)
    np.testing.assert_allclose(np.asarray(_clamped(q, k, v, g, beta, s0)), plain(q, k, v, g, beta, s0)[0], atol=ATOL)

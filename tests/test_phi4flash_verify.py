"""The decoder-hybrid-decoder family's calls over a cache that already holds
a prompt (a prompt chunk, a verify step and what ``commit`` keeps of it), and
the faults the comparison with the plain reference must see
(tests/phi4flash_reference.py ``CONTROLS``), at the toy size of
tests/phi4flash_toy.py on the CPU. ``phi4flash_toy.ATOL`` says what the
tolerance is and why."""

import jax.numpy as jnp
import numpy as np
import pytest

import phi4flash_reference as ref
from phi4flash_toy import ATOL, calls, chunk_call, forward, prompt_of, reference, through_the_cache
from rag_llm_k8s_tpu.models import cross_decoder as cd

N_STATE = 4  # of 12 layers: the layers that keep a state


# ---- (c) a chunk over the cache; the verify step and what it commits ----


@pytest.mark.parametrize("kept,why", [(8, "accepted in full"), (3, "accepted in part"), (1, "none accepted")])
def test_a_verify_step_commits_the_state_it_kept(kept, why):
    """A verify step feeds 8 positions of which only the first ``kept`` are
    the sequence's; ``commit`` leaves the state behind them, and decoding on
    one token at a time equals the reference on the sequence."""
    tokens = prompt_of(60, 7)
    start, n = 27, 8
    junk = tokens[:start + kept] + prompt_of(n - kept, 99)  # rejected proposals behind the kept ones
    (logits, cache), ks = chunk_call(junk, start, n, keep_steps=True)
    np.testing.assert_allclose(np.asarray(logits[0, :kept]), reference(tokens[:start + kept])[start:], atol=ATOL)
    assert cache.ssm_steps.shape == (N_STATE, 1, n, 16, 128) and cache.conv_steps.shape == (N_STATE, 1, 3 + n, 128)
    cache = cd.commit(cache, jnp.int32(kept))
    assert cache.ssm_steps is None and cache.conv_steps is None
    counted = cd.fold_counters(np.asarray(cache.counters))
    assert (counted["verify_positions_fed"], counted["verify_positions_kept"]) == (n, kept)
    S = 32
    for at in range(start + kept, start + kept + 4):  # the frontier stands behind the kept positions
        slot = S + at - start
        step, cache = calls()[0](jnp.asarray([[tokens[at]]], jnp.int32), jnp.asarray([[at]]), cache, ks,
                                 jnp.full((1,), slot + 1, jnp.int32), jnp.int32(slot))
        np.testing.assert_allclose(np.asarray(step[0, 0]), reference(tokens[:at + 1])[-1], atol=ATOL)


def test_an_uncommitted_verify_step_is_the_fault_commit_cures():
    tokens = prompt_of(60, 7)
    junk = tokens[:28] + prompt_of(7, 99)
    (_, cache), ks = chunk_call(junk, 27, 8)  # the chunk form leaves the state behind ALL it fed
    step, _ = calls()[0](jnp.asarray([[tokens[28]]], jnp.int32), jnp.asarray([[28]]), cache, ks,
                         jnp.full((1,), 34, jnp.int32), jnp.int32(33))
    assert np.abs(np.asarray(step[0, 0]) - reference(tokens[:29])[-1]).max() > 100 * ATOL


@pytest.mark.parametrize("impl,S,start,n", [("xla", 32, 20, 11), ("pallas_interpret", 128, 90, 128)])
def test_a_chunk_over_the_cache_runs_every_layer_on_what_it_is_fed(impl, S, start, n):
    tokens = prompt_of(start + n + 1, 5)
    (logits, cache), ks = chunk_call(tokens, start, n, S=S, impl=impl)
    np.testing.assert_allclose(np.asarray(logits[0]), reference(tokens[:start + n])[start:], atol=ATOL)
    counted = cd.fold_counters(np.asarray(cache.counters))
    assert counted["ssm_positions_scanned"] == S + n
    assert counted["cross_positions_fed"] == S  # counted a fresh prompt call at a time, not a chunk


# ---- (d) the faults the comparison must see ----


_SERVED = {}


def served(tokens):
    """The program's logits of ``tokens`` through the cache, once for all controls."""
    if tokens not in _SERVED:
        (_SERVED[tokens],), _ = through_the_cache([list(tokens)], 48, [40])
    return _SERVED[tokens]


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_fails_the_tolerance(control):
    """Each control of the reference moves a logit by a hundred tolerances,
    and the program stands on the sound side: the chip's limits are set
    against the same controls (benchmark/tests/controls_phi4flash.py), and one
    the chip cannot tell from sound is held here, against float32."""
    tokens = prompt_of(52, 1)
    sound = reference(tokens)
    kw = dict(pads=8) if control == "pads_unmasked" else dict(handover=40) if control == "commit_short" else {}
    ids = [0] * kw.get("pads", 0) + tokens
    bad = forward(ids, control=control, **kw)[kw.get("pads", 0):]
    assert np.abs(bad - sound).max() > 100 * ATOL
    got = served(tuple(tokens))
    assert np.abs(got - bad).max() > 100 * ATOL
    np.testing.assert_allclose(got, sound, atol=ATOL)


def test_a_left_pad_is_no_token_to_the_reference_either():
    """``forward(pads=n)`` masks what ``pads_unmasked`` lets through: the
    sound reference of a padded sequence is the sequence's."""
    tokens = prompt_of(30, 3)
    np.testing.assert_allclose(forward([0] * 8 + tokens, pads=8)[8:], reference(tokens), atol=ATOL)

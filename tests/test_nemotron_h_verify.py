"""The state-space-duality family's calls over a cache that already holds a
prompt: a prompt chunk, a verify step, and what ``commit`` keeps of it by
replaying the kept positions as one chunk (the state is 64 kB a row-layer here
and 4.2 MB at the published widths: a state a fed position is not kept), at the
toy size of tests/nemotron_h_toy.py on the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from nemotron_h_toy import ATOL, CFG, M, calls, chunk_call, prompt_of, reference
from rag_llm_k8s_tpu.models import ssd_moe as sm

N = 6  # positions a verify step feeds
START, S = 27, 32


@pytest.fixture(scope="module")
def tokens():
    return prompt_of(60, 7)


@pytest.mark.parametrize("kept", range(N + 1))
def test_commit_at_every_kept_count_equals_stepping(tokens, kept):
    """A verify step feeds 6 positions of which only the first ``kept`` are
    the sequence's; ``commit`` leaves the state behind them, and decoding on a
    token at a time equals the reference on the sequence."""
    junk = tokens[:START + kept] + prompt_of(N - kept, 99)  # rejected proposals behind the kept ones
    (logits, cache), ks = chunk_call(junk, START, N, keep_steps=True)
    if kept:
        np.testing.assert_allclose(np.asarray(logits[0, :kept]), reference(tokens[:START + kept])[START:], atol=ATOL)
    run, x, Bm, dt, a = cache.steps  # the step's inputs, not a state a position
    assert run.shape == (M, 1, 3 + N, CFG.conv_width) and x.shape == (M, 1, N, 8, 16)
    assert Bm.shape == (M, 1, N, 2, 16) and dt.shape == a.shape == (M, 1, N, 8)
    before = np.asarray(cache.state)
    cache = sm.commit(cache, jnp.int32(kept))
    assert cache.steps is None
    if kept == 0:
        np.testing.assert_array_equal(np.asarray(cache.state), before)  # nothing kept: the state as it was
    counted = sm.fold_counters(np.asarray(cache.counters))
    assert (counted["ssd_verify_positions"], counted["ssd_verify_positions_kept"]) == (M * N, M * kept)
    for at in range(START + kept, START + kept + 3):  # the frontier stands behind the kept positions
        slot = S + at - START
        step, cache = calls()[0](jnp.asarray([[tokens[at]]], jnp.int32), jnp.asarray([[at]]), cache, ks,
                                 jnp.full((1,), slot + 1, jnp.int32), jnp.int32(slot))
        np.testing.assert_allclose(np.asarray(step[0, 0]), reference(tokens[:at + 1])[-1], atol=ATOL)


def test_an_uncommitted_verify_step_is_the_fault_commit_cures(tokens):
    junk = tokens[:28] + prompt_of(N - 1, 99)
    (_, cache), ks = chunk_call(junk, START, N)  # the chunk form leaves the state behind ALL it fed
    step, _ = calls()[0](jnp.asarray([[tokens[28]]], jnp.int32), jnp.asarray([[28]]), cache, ks,
                         jnp.full((1,), 34, jnp.int32), jnp.int32(33))
    assert np.abs(np.asarray(step[0, 0]) - reference(tokens[:29])[-1]).max() > 100 * ATOL


@pytest.mark.parametrize("impl,bucket,start,n", [("xla", 32, 20, 11), ("xla", 32, 20, 19),
                                                 ("pallas_interpret", 128, 90, 128)])
def test_a_chunk_over_the_cache_runs_from_the_state_it_holds(impl, bucket, start, n):
    """A prompt chunk (chunked prefill, the scorer): the recurrence goes on
    from the cached state in chunks of 8, the last not whole."""
    sequence = prompt_of(start + n + 1, 5)
    (logits, cache), _ = chunk_call(sequence, start, n, S=bucket, impl=impl)
    np.testing.assert_allclose(np.asarray(logits[0]), reference(sequence[:start + n])[start:], atol=ATOL)
    counted = sm.fold_counters(np.asarray(cache.counters))
    assert counted["ssd_prefill_positions"] == M * (start + n)
    assert counted["moe_chunk_assignments_held"] == counted["moe_chunk_assignments_computed"] > 0

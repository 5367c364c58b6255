"""The decoder-hybrid-decoder family (models/cross_decoder.py over ops/ssm.py
and models/llama.py's attention seam) against its plain reference
(tests/phi4flash_reference.py: float32, all layers at every position, no
cache), at the toy size of tests/phi4flash_toy.py on the CPU: the logits, not
the tokens. ``phi4flash_toy.ATOL`` says what the tolerance is and why."""

import jax.numpy as jnp
import numpy as np
import pytest

from phi4flash_toy import ATOL, prompt_of, reference, through_the_cache
from rag_llm_k8s_tpu.models import cross_decoder as cd

N_STATE, N_PLANE = 4, 4  # of 12 layers: the layers that keep a state, the layers that own a plane


# ---- (a) prefill, then decode through the three kinds of state ----


@pytest.mark.parametrize("impl,S,prompt_len,tied", [
    ("xla", 40, 33, True), ("xla", 40, 33, False), ("xla", 40, 40, True),
    ("pallas_interpret", 128, 101, True),
], ids=lambda v: {True: "tied", False: "untied"}.get(v, str(v)))
def test_prefill_then_decode_matches_reference_at_every_position(impl, S, prompt_len, tied):
    tokens = prompt_of(prompt_len + 12, prompt_len)
    (got,), cache = through_the_cache([tokens], S, [prompt_len], impl=impl, tied=tied)
    np.testing.assert_allclose(got, reference(tokens, tied), atol=ATOL)
    counted = cd.fold_counters(np.asarray(cache.counters))
    assert counted["prefill_tokens_computed"] == counted["prefill_tokens_bucketed"] == S
    assert counted["cross_positions_computed"] == counted["cross_positions_fed"] == S  # every position's logits left
    assert counted["ssm_positions_scanned"] == S and counted["ssm_state_updates"] == 12 * N_STATE
    assert (counted["decode_slots_streamed"] > 0) == (impl != "xla")
    # three kinds of state, and 4 of 12 layers own none: planes for the window layers and the full one
    # (a key pair is one head of twice the width), a state without positions for the four Mamba layers
    T = cache.k.shape[3]
    assert cache.k.shape == (N_PLANE, 1, 1, T, 32) and cache.ssm.shape == (N_STATE, 1, 16, 128)
    assert cache.conv.shape == (N_STATE, 1, 3, 128) and cache.ssm.dtype == jnp.float32


def test_rows_of_one_bucket_with_different_left_padding():
    rows = [prompt_of(44, 2), prompt_of(29, 3), prompt_of(9, 4)]
    lengths = [40, 25, 2]  # the last row is shorter than the convolution and than the window
    got, cache = through_the_cache(rows, 40, lengths)
    for row, g in zip(rows, got):
        np.testing.assert_allclose(g, reference(row)[:len(g)], atol=ATOL)
    _, alone = through_the_cache([rows[1][:25 + 4]], 25, [25])
    np.testing.assert_allclose(np.asarray(cache.ssm[:, 1]), np.asarray(alone.ssm[:, 0]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(cache.conv[:, 1]), np.asarray(alone.conv[:, 0]), atol=ATOL)


# ---- (b) a fresh prompt's prefill stops half way ----


@pytest.mark.parametrize("impl,S,lens", [("xla", 40, (40, 25, 2)), ("pallas_interpret", 128, (101,))])
def test_the_fresh_prefill_is_the_all_positions_form_at_its_position(impl, S, lens):
    """The engine's prompt call runs the cross-decoder at the last position
    only: the same logits there (bit-near: the same numbers, a single-query
    walk in place of a row of the causal form), the same cache, the same
    decode steps behind it; and the counters say 1 of S."""
    rows = [prompt_of(n + 3, 50 + n) for n in lens]
    fresh, cache = through_the_cache(rows, S, lens, impl=impl, fresh=True)
    whole, full = through_the_cache(rows, S, lens, impl=impl)
    for f, w, n in zip(fresh, whole, lens):
        np.testing.assert_allclose(f, w[n - 1:], atol=2e-5)
    for name in ("k", "v", "conv", "ssm"):
        np.testing.assert_array_equal(np.asarray(getattr(cache, name)), np.asarray(getattr(full, name)))
    counted, plain = (cd.fold_counters(np.asarray(c.counters)) for c in (cache, full))
    B = len(lens)
    assert (counted["cross_positions_computed"], counted["cross_positions_fed"]) == (B, B * S)
    assert (plain["cross_positions_computed"], plain["cross_positions_fed"]) == (B * S, B * S)
    assert counted["prefill_tokens_computed"] == counted["prefill_tokens_bucketed"] == B * S

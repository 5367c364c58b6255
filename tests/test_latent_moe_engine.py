"""Every one-shot program of the engine for the latent-attention sparse-expert
family, against the plain reference's greedy stream (section (b) of
``tests/test_latent_moe.py``, whose helpers and tolerances these cases use; a
file of its own so that ``--dist loadfile`` can run the two halves side by
side)."""

import jax.numpy as jnp
import numpy as np
import pytest

import latent_moe_reference as ref
from test_latent_moe import ATOL, CFG, engine_for, greedy_reference, params, prompt_of  # noqa: F401


def test_batched_rows_of_unequal_length(params):
    prompts = [prompt_of(n, 10 + n) for n in (20, 31, 7)]
    got = engine_for(params).generate(prompts)
    assert got == [greedy_reference(params, CFG, p, 8) for p in prompts]


def test_verify_16_drafts_is_the_vanilla_stream(params):
    base = prompt_of(6, 3)
    prompt = (base * 5)[:28]  # repeats: prompt lookup has something to draft
    e = engine_for(params, speculative="prompt_lookup", spec_tokens=16)
    assert e.generate([prompt]) == [greedy_reference(params, CFG, prompt, 8)]
    counted = e.stats.family_counters
    assert e.stats.spec_verify_steps > 0 and counted["moe_chunk_assignments_held"] > 0
    assert counted["moe_chunk_assignments_held"] == counted["moe_chunk_assignments_computed"]


def test_chunked_prefill_past_the_largest_bucket(params):
    prompt = prompt_of(100, 4)  # > 64: two chunks of 64 through the cache
    assert engine_for(params).generate([prompt]) == [greedy_reference(params, CFG, prompt, 8)]


def test_score_exact_matches_reference_logits(params):
    prompt, emitted = prompt_of(20, 5), prompt_of(6, 6)
    got = engine_for(params).score_exact(prompt, emitted)
    logits = ref.forward(params, CFG, prompt + emitted)[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(6), emitted], atol=ATOL)


def test_fused_single_fetch_path(params):
    e = engine_for(params)
    a_ids, b_ids = np.asarray(prompt_of(5, 7), np.int32), np.asarray(prompt_of(4, 8), np.int32)
    store = np.zeros((8, 12), np.int32)
    lens = np.asarray([12, 9, 12, 5, 12, 12, 12, 12], np.int32)
    for i in range(8):
        store[i, :lens[i]] = prompt_of(int(lens[i]), 20 + i)
    packed = jnp.asarray([[0.1, 0.2, 0.3, 3.0, 1.0, 6.0]], jnp.float32)  # dists | ids
    got = e.generate_rag(a_ids, b_ids, packed, jnp.asarray(store), jnp.asarray(lens), n_chunks=2)
    prompt = list(a_ids) + list(store[3, :5]) + list(store[1, :9]) + list(b_ids)
    assert got == greedy_reference(params, CFG, [int(t) for t in prompt], 8)
    counted = e.stats.family_counters
    assert counted["moe_prefill_assignments_held"] == counted["moe_prefill_assignments_computed"] > 0


@pytest.mark.parametrize("impl", ["pallas_interpret"])
def test_pallas_path_is_the_xla_path(params, impl):
    prompts = [prompt_of(n, 30 + n) for n in (20, 9)]
    assert engine_for(params, attn_impl=impl).generate(prompts) == engine_for(params).generate(prompts)

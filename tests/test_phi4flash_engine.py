"""The decoder-hybrid-decoder family through every one-shot program of the
engine (the ``Family`` row of models/families.py: no engine option, no side
path), at the toy size of tests/phi4flash_toy.py, and what the row refuses."""

import jax
import numpy as np
import pytest

from phi4flash_toy import ATOL, CFG, FP32, PARAMS, greedy_reference, prompt_of, reference
from rag_llm_k8s_tpu.core.config import (
    CrossDecoderConfig, EngineConfig, LlamaConfig, MeshConfig, PrefixCacheConfig, SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import cross_decoder as cd, families

NEW = 6
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)


def engine_for(**kw):
    ec = EngineConfig(**{**dict(prompt_buckets=(32, 64), max_batch_size=4, max_seq_len=128,
                                speculative="off", attn_impl="xla", max_chunked_prompt=256,
                                prefix_cache=PrefixCacheConfig(enabled=False)), **kw})
    return InferenceEngine(CFG, PARAMS[True], sampling=GREEDY, engine_config=ec, dtypes=FP32)


def test_batched_rows_of_unequal_length():
    prompts = [prompt_of(n, 10 + n) for n in (61, 40, 35)]
    engine = engine_for()
    assert engine.generate(prompts) == [greedy_reference(p, NEW) for p in prompts]
    counted = engine.stats.family_counters
    # the three rows ride the batch ladder's rung of four; the prompt call ran the cross-decoder at one position a row
    assert counted["ssm_state_updates"] == (NEW - 1) * 4 * CFG.num_state_layers
    assert (counted["cross_positions_computed"], counted["cross_positions_fed"]) == (4, 4 * 64)


def test_a_prompt_past_the_largest_bucket_prefills_in_chunks():
    prompt = prompt_of(150, 21)  # three chunks of 64, left-padded by 42: every chunk runs every layer
    assert engine_for().generate([prompt]) == [greedy_reference(prompt, NEW)]


def repeating(n, period, seed):
    return [prompt_of(period, seed)[i % period] for i in range(n)]


@pytest.mark.parametrize("prompt,why", [
    (repeating(50, 7, 31), "a prompt that repeats: proposals accepted in full and in part"),
    (prompt_of(50, 32), "no repeat: nothing accepted"),
])
def test_the_verify_loop_is_the_vanilla_loop(prompt, why):
    """Prompt-lookup speculation commits the state of what it kept: the
    stream is the vanilla greedy stream, which is the reference's."""
    sampling = SamplingConfig(do_sample=False, max_new_tokens=16)
    engine = InferenceEngine(CFG, PARAMS[True], sampling=sampling, dtypes=FP32, engine_config=EngineConfig(
        prompt_buckets=(32, 64), max_batch_size=4, max_seq_len=128, attn_impl="xla",
        speculative="prompt_lookup", spec_tokens=5, spec_ngram=2))
    assert engine.generate([prompt]) == [greedy_reference(prompt, 16)]
    counted = engine.stats.family_counters
    assert counted["verify_positions_fed"] == 6 * engine.stats.spec_verify_steps
    assert counted["verify_positions_kept"] == engine.stats.spec_emitted_tokens
    assert (counted["cross_positions_computed"], counted["cross_positions_fed"]) == (1, 64)  # the prompt call's


def test_score_exact_is_the_reference():
    prompt = prompt_of(45, 41)
    emitted = greedy_reference(prompt, NEW)
    got = engine_for().score_exact(prompt, emitted)
    logits = reference(prompt + emitted)[len(prompt) - 1:-1]
    np.testing.assert_array_equal(got["argmax"], np.argmax(logits, axis=-1))
    np.testing.assert_allclose(got["max_logit"], logits.max(axis=-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(NEW), emitted], atol=ATOL)


@pytest.mark.parametrize("kw,engine,names", [
    (dict(batching="continuous"), "one-shot", "continuous"),
    (dict(), "continuous", "paged KV pool"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "one-shot", "prefix cache"),
    (dict(kv_quant="int8"), "one-shot", "kv_quant='int8'"),
    (dict(weight_quant="int8"), "one-shot", "weight_quant='int8'"),
])
def test_refusals_name_the_mechanism(kw, engine, names):
    ec = EngineConfig(**{**dict(prefix_cache=PrefixCacheConfig(enabled=False)), **kw})
    with pytest.raises(NotImplementedError, match="decoder-hybrid-decoder family") as e:
        families.refuse_unsupported(CFG, ec, None, engine=engine)
    assert names in str(e.value)


def test_tensor_parallel_is_refused_by_name_and_the_row_is_the_familys():
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    ec = EngineConfig(prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(NotImplementedError, match="tp=2"):
        families.refuse_unsupported(CFG, ec, mesh)
    family = families.of(CFG)
    assert family.commit is cd.commit and family.verify_span is None
    assert "name map" in family.checkpoint_loader_refusal
    assert family.counter_names == cd.COUNTER_NAMES and family.counters_width == 15
    assert families.of(LlamaConfig.tiny()).commit is None  # a frontier does the job there


@pytest.mark.parametrize("kw,match", [
    (dict(mb_per_layer=4), "mb_per_layer"), (dict(num_hidden_layers=30), "fours"),
    (dict(num_hidden_layers=4), "fours"), (dict(num_key_value_heads=1, num_attention_heads=4), "pairs heads up"),
])
def test_the_configuration_refuses_what_the_rule_cannot_derive(kw, match):
    with pytest.raises(ValueError, match=match):
        CrossDecoderConfig.tiny(**kw)

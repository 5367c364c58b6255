"""The state-space-duality latent-expert family through every one-shot program
of the engine (the ``Family`` row of models/families.py: no engine option, no
side path), at the toy size of tests/nemotron_h_toy.py, and what the row
refuses. The engines are built once a module."""

import jax
import numpy as np
import pytest

from nemotron_h_toy import ATOL, CFG, FP32, M, PARAMS, greedy_reference, prompt_of, reference
from rag_llm_k8s_tpu.core.config import EngineConfig, LlamaConfig, MeshConfig, PrefixCacheConfig, SamplingConfig
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import families, ssd_moe as sm

NEW = 6
BASE = dict(prompt_buckets=(32, 64), max_batch_size=4, max_seq_len=128, attn_impl="xla", max_chunked_prompt=256,
            prefix_cache=PrefixCacheConfig(enabled=False))


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(CFG, PARAMS, sampling=SamplingConfig(do_sample=False, max_new_tokens=NEW),
                           engine_config=EngineConfig(**BASE, speculative="off"), dtypes=FP32)


@pytest.fixture(scope="module")
def speculating():
    return InferenceEngine(CFG, PARAMS, sampling=SamplingConfig(do_sample=False, max_new_tokens=16), dtypes=FP32,
                           engine_config=EngineConfig(**BASE, speculative="prompt_lookup", spec_tokens=5,
                                                      spec_ngram=2))


def test_batched_rows_of_unequal_length(engine):
    prompts = [prompt_of(n, 20 + n) for n in (61, 40, 35)]
    before = dict(engine.stats.family_counters)
    assert engine.generate(prompts) == [greedy_reference(p, NEW) for p in prompts]
    counted = {k: v - before.get(k, 0) for k, v in engine.stats.family_counters.items()}
    # the three rows ride the batch ladder's rung of four, whose fourth row is one filler token behind pads
    assert counted["ssd_decode_positions"] == (NEW - 1) * 4 * M
    assert counted["ssd_prefill_positions"] == M * (61 + 40 + 35 + 1)
    assert counted["moe_decode_layer_steps"] == (NEW - 1) * CFG.num_moe_layers


def test_a_prompt_past_the_largest_bucket_prefills_in_chunks(engine):
    prompt = prompt_of(150, 21)  # three chunks of 64, left-padded by 42: the state goes from chunk to chunk
    assert engine.generate([prompt]) == [greedy_reference(prompt, NEW)]


def test_score_exact_is_the_reference(engine):
    prompt = prompt_of(45, 41)
    emitted = greedy_reference(prompt, NEW)
    got = engine.score_exact(prompt, emitted)
    logits = reference(prompt + emitted)[len(prompt) - 1:-1]
    np.testing.assert_array_equal(got["argmax"], np.argmax(logits, axis=-1))
    np.testing.assert_allclose(got["max_logit"], logits.max(axis=-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(NEW), emitted], atol=ATOL)


def repeating(n, period, seed):
    return [prompt_of(period, seed)[i % period] for i in range(n)]


@pytest.mark.parametrize("prompt,why", [
    (repeating(50, 7, 31), "a prompt that repeats: proposals accepted in full and in part"),
    (prompt_of(50, 32), "no repeat: nothing accepted"),
])
def test_the_verify_loop_is_the_vanilla_loop(speculating, prompt, why):
    """Prompt-lookup speculation replays what it kept: the stream is the
    vanilla greedy stream, which is the reference's."""
    before = dict(speculating.stats.family_counters)
    steps, emitted = speculating.stats.spec_verify_steps, speculating.stats.spec_emitted_tokens
    assert speculating.generate([prompt]) == [greedy_reference(prompt, 16)]
    counted = {k: v - before.get(k, 0) for k, v in speculating.stats.family_counters.items()}
    assert counted["ssd_verify_positions"] == M * 6 * (speculating.stats.spec_verify_steps - steps)
    assert counted["ssd_verify_positions_kept"] == M * (speculating.stats.spec_emitted_tokens - emitted)


@pytest.mark.parametrize("kw,engine_kind,names", [
    (dict(batching="continuous"), "one-shot", "continuous"),
    (dict(), "continuous", "paged KV pool"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "one-shot", "prefix cache"),
    (dict(kv_quant="int8"), "one-shot", "kv_quant='int8'"),
    (dict(weight_quant="int8"), "one-shot", "weight_quant='int8'"),
])
def test_refusals_name_the_mechanism(kw, engine_kind, names):
    ec = EngineConfig(**{**dict(prefix_cache=PrefixCacheConfig(enabled=False)), **kw})
    with pytest.raises(NotImplementedError, match="state-space-duality latent-expert family") as e:
        families.refuse_unsupported(CFG, ec, None, engine=engine_kind)
    assert names in str(e.value)


def test_tensor_parallel_is_refused_by_name_and_the_row_is_the_familys():
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    ec = EngineConfig(prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(NotImplementedError, match="tp=2"):
        families.refuse_unsupported(CFG, ec, mesh)
    family = families.of(CFG)
    assert family.commit is sm.commit and family.verify_span is None
    assert "name map" in family.checkpoint_loader_refusal
    assert family.counter_names == sm.COUNTER_NAMES and family.counters_width == 36
    assert families.of(LlamaConfig.tiny()).commit is None  # a frontier does the job there

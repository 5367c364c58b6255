"""Prompt-lookup speculative decoding (EngineConfig.speculative): the greedy
batch-1 fast path must be token-IDENTICAL to the vanilla loop on every input
— acceptance only ever keeps tokens equal to the model's own greedy argmax —
while the all-accept regime provably emits k+1 tokens per verify forward."""

import dataclasses

import jax
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=12)
ENG = EngineConfig(prompt_buckets=(32, 64), max_batch_size=2, max_seq_len=128)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    vanilla = InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ENG, dtypes=FP32)
    spec = InferenceEngine(
        cfg, params, sampling=GREEDY,
        engine_config=dataclasses.replace(ENG, speculative="prompt_lookup"),
        dtypes=FP32,
    )
    return cfg, params, vanilla, spec


PROMPTS = [
    [3, 17, 42, 7, 99],  # no obvious repeats
    [5, 9, 2, 5, 9, 2, 5, 9, 2],  # trailing n-gram repeats in-prompt
    [11] * 20,  # degenerate repeat
    [3, 17, 42, 7, 99, 3, 17, 42],  # repeat ending mid-span
    [8],  # shorter than the n-gram itself
    list(range(3, 30)),  # long distinct prompt
]


class TestExactness:
    def test_matches_vanilla_greedy(self, setup):
        _, _, vanilla, spec = setup
        for p in PROMPTS:
            want = vanilla.generate([p])[0]
            got = spec.generate([p])[0]
            assert got == want, p

    def test_budget_edges(self, setup):
        _, _, vanilla, spec = setup
        p = [5, 9, 2, 5, 9, 2, 5, 9, 2]
        for mn in (1, 2, 7, 8, 9, 20):  # around k+1 = 8 emission chunks
            assert spec.generate([p], max_new_tokens=mn)[0] == \
                vanilla.generate([p], max_new_tokens=mn)[0], mn

    def test_zero_slack_cache_shape_stays_exact(self, setup):
        """S + max_new an exact 128-multiple (the shapes of the round-4
        capture): without k slack slots, the last verify forwards' KV writes
        would clamp-shift onto valid accepted KV and diverge near the
        budget. Repeat-heavy prompt drives acceptance right to the edge."""
        _, _, vanilla, spec = setup
        p = [5, 9, 2] * 6  # repeats: long accepted spans reach the budget
        for mn in (96, 95):  # 32 + 96 = 128 exactly
            want = vanilla.generate([p], max_new_tokens=mn)[0]
            got = spec.generate([p], max_new_tokens=mn)[0]
            assert got == want, mn

    def test_eos_mid_span(self, setup):
        """EOS inside an accepted span must truncate exactly where vanilla
        does. The EOS id is taken from the vanilla stream so it fires."""
        cfg, params, vanilla, _ = setup
        p = [5, 9, 2, 5, 9, 2, 5, 9, 2]
        stream = vanilla.generate([p])[0]
        assert len(stream) >= 4
        cfg_eos = dataclasses.replace(cfg, eos_token_ids=(stream[3],))
        v2 = InferenceEngine(cfg_eos, params, sampling=GREEDY, engine_config=ENG, dtypes=FP32)
        s2 = InferenceEngine(
            cfg_eos, params, sampling=GREEDY,
            engine_config=dataclasses.replace(ENG, speculative="prompt_lookup"),
            dtypes=FP32,
        )
        want = v2.generate([p])[0]
        got = s2.generate([p])[0]
        assert got == want
        assert len(want) == 3  # truncated at the injected EOS

    def test_fallbacks_to_vanilla(self, setup):
        cfg, params, vanilla, spec = setup
        # batch > 1: vanilla path (still correct)
        two = spec.generate([[3, 17, 42], [5, 9, 2]])
        assert two == vanilla.generate([[3, 17, 42], [5, 9, 2]])
        assert (2, 32, GREEDY.max_new_tokens, None) in spec._compiled
        # sampling at batch 1 now TAKES the spec path (rejection-sampling
        # verification preserves the distribution — TestSampledDistribution)
        sam = InferenceEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=True, max_new_tokens=6, seed=3),
            engine_config=dataclasses.replace(ENG, speculative="prompt_lookup"),
            dtypes=FP32,
        )
        out = sam.generate([[3, 17, 42]], seed=7)[0]
        assert any(k[3] == "spec" for k in sam._compiled)
        assert len(out) <= 6 and all(isinstance(t, int) for t in out)
        assert sam.stats.spec_verify_steps >= 1


class TestAcceptance:
    def test_all_accept_regime_emits_k_plus_1_per_step(self, setup):
        """Zero params make the model a constant emitter (uniform logits →
        argmax 0 forever); a prompt seeded with 0-runs makes every proposal
        correct, so max_new tokens must arrive in ceil((max_new-1)/(k+1))
        verify steps — the machinery's best case, measured not assumed."""
        cfg, _, _, _ = setup
        params0 = jax.tree.map(
            lambda x: np.zeros_like(x), init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
        )
        ec = dataclasses.replace(ENG, speculative="prompt_lookup")
        spec = InferenceEngine(cfg, params0, sampling=GREEDY, engine_config=ec, dtypes=FP32)
        p = [1] + [0] * 8
        out = spec.generate([p], max_new_tokens=12)[0]
        assert out == [0] * 12
        k1 = ec.spec_tokens + 1
        want_steps = -(-(12 - 1) // k1)
        assert spec.stats.spec_verify_steps == want_steps

    def test_verify_steps_never_exceed_tokens(self, setup):
        _, _, _, spec = setup
        before = spec.stats.spec_verify_steps
        out = spec.generate([[3, 17, 42, 7, 99]], max_new_tokens=9)[0]
        steps = spec.stats.spec_verify_steps - before
        assert 1 <= steps <= len(out)


class TestSpecWithQuantization:
    """Speculation composes with int8 weights and the int8 KV cache: the
    verify forward is the q8 chunked-prefill path, acceptance compares the
    QUANTIZED model's own greedy choices — exactness is vs the quantized
    vanilla loop (the same numerics)."""

    def test_exact_vs_vanilla_int8_w_and_kv(self):
        cfg = LlamaConfig.tiny()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
        ec = dataclasses.replace(ENG, weight_quant="int8", kv_quant="int8")
        vanilla = InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)
        spec = InferenceEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(ec, speculative="prompt_lookup"),
            dtypes=FP32,
        )
        for p in ([3, 17, 42, 7, 99], [5, 9, 2] * 5, [11] * 16):
            want = vanilla.generate([p])[0]
            got = spec.generate([p])[0]
            assert got == want, p
        assert spec.stats.spec_verify_steps > 0


    @pytest.mark.parametrize("spec_tokens,kernel", [
        (15, "chunk_attention_grouped_q8"),  # G*S = 2*16: one MXU pass
        (71, "chunk_prefill_attention_q8"),  # G*S = 2*72 > 128: per head
    ])
    def test_exact_vs_vanilla_through_the_fused_verify_kernels(self, spec_tokens, kernel):
        """The verify program built on the Pallas path (interpret mode) over
        the int8 cache: whichever chunk kernel the draft length selects, the
        greedy stream is token-identical to the vanilla loop's."""
        from rag_llm_k8s_tpu.obs import tracing

        cfg = LlamaConfig.tiny()
        params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
        ec = dataclasses.replace(
            ENG, kv_quant="int8", attn_impl="pallas_interpret",
            prompt_buckets=(32,), max_seq_len=256,
        )
        vanilla = InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)
        spec = InferenceEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                ec, speculative="prompt_lookup", spec_tokens=spec_tokens
            ),
            dtypes=FP32,
        )
        before = tracing.kernel_builds().get(("chunk", kernel), 0)
        for p in ([3, 17, 42, 7, 99], [5, 9, 2] * 5):
            assert spec.generate([p])[0] == vanilla.generate([p])[0], p
        assert spec.stats.spec_verify_steps > 0
        assert tracing.kernel_builds().get(("chunk", kernel), 0) > before


class TestSampledDistribution:
    """Rejection-sampling verification must preserve the SAMPLED output
    distribution exactly: accept proposal x w.p. p(x) under the filtered
    target, else draw from the residual (p with x masked, renormalized) —
    so each emitted token is marginally one vanilla sampling step given its
    prefix. Verified empirically: the marginal of the token at position 1
    (the first token a VERIFY forward emits; position 0 is sampled
    identically in both paths) over many seeded runs must match vanilla
    within TV-distance noise. Tiny vocab keeps the support small enough for
    a sharp bound at a few thousand samples."""

    N = 3000
    TV_BOUND = 0.08  # empirical-vs-empirical noise at N=3000, support ~30

    @pytest.fixture(scope="class")
    def engines(self):
        cfg = LlamaConfig.tiny(vocab_size=32)
        params = init_llama_params(jax.random.PRNGKey(1), cfg, FP32)
        sampling = SamplingConfig(do_sample=True, temperature=0.7, top_p=0.9,
                                  max_new_tokens=3)
        vanilla = InferenceEngine(
            cfg, params, sampling=sampling, engine_config=ENG, dtypes=FP32
        )
        spec = InferenceEngine(
            cfg, params, sampling=sampling,
            engine_config=dataclasses.replace(ENG, speculative="prompt_lookup"),
            dtypes=FP32,
        )
        return cfg, vanilla, spec

    def _marginal(self, engine, cfg, prompt):
        counts = np.zeros(cfg.vocab_size, np.int64)
        for seed in range(self.N):
            out = engine.generate([prompt], seed=seed)[0]
            # row excludes EOS; len==1 with budget 3 means EOS at position 1
            sym = out[1] if len(out) > 1 else cfg.eos_token_ids[0]
            counts[sym] += 1
        return counts / counts.sum()

    def test_position1_marginal_matches_vanilla(self, engines):
        cfg, vanilla, spec = engines
        # repeats in the prompt so proposals actually fire (and get
        # accepted/rejected — the code path under test)
        prompt = [5, 9, 7, 5, 9, 7, 5, 9]
        pv = self._marginal(vanilla, cfg, prompt)
        ps = self._marginal(spec, cfg, prompt)
        tv = 0.5 * float(np.abs(pv - ps).sum())
        assert spec.stats.spec_verify_steps >= self.N  # spec path really ran
        assert tv < self.TV_BOUND, f"TV distance {tv:.4f}"

    def test_pinned_seed_is_reproducible(self, engines):
        cfg, _, spec = engines
        a = spec.generate([[5, 9, 7, 5, 9, 7]], seed=11)
        b = spec.generate([[5, 9, 7, 5, 9, 7]], seed=11)
        assert a == b

    def test_greedy_temperature_zero_equivalence(self, engines):
        """temperature <= 0 with do_sample=True compiles the GREEDY
        acceptance rule (matches sample_token's own greedy degeneration)."""
        cfg, _, _ = engines
        params = init_llama_params(jax.random.PRNGKey(1), cfg, FP32)
        g0 = SamplingConfig(do_sample=True, temperature=0.0, max_new_tokens=8)
        van = InferenceEngine(
            cfg, params,
            sampling=dataclasses.replace(g0, do_sample=False),
            engine_config=ENG, dtypes=FP32,
        )
        spc = InferenceEngine(
            cfg, params, sampling=g0,
            engine_config=dataclasses.replace(ENG, speculative="prompt_lookup"),
            dtypes=FP32,
        )
        p = [5, 9, 2, 5, 9, 2, 5, 9]
        assert spc.generate([p])[0] == van.generate([p])[0]


class TestAutoMode:
    """speculative="auto" (the default) must self-disable on measured low
    acceptance — a flat-logits model under sampling accepts ~nothing, so
    paying a verify forward per token would be pure overhead — and keep
    speculating where acceptance is high (greedy all-accept regime)."""

    def test_auto_disables_on_low_acceptance(self):
        cfg = LlamaConfig.tiny(vocab_size=64)
        params0 = jax.tree.map(
            lambda x: np.zeros_like(x),
            init_llama_params(jax.random.PRNGKey(0), cfg, FP32),
        )
        eng = InferenceEngine(
            cfg, params0,
            sampling=SamplingConfig(do_sample=True, max_new_tokens=8),
            engine_config=dataclasses.replace(ENG, speculative="auto"),
            dtypes=FP32,
        )
        p = [3, 17, 42, 3, 17, 42]
        for s in range(6):
            eng.generate([p], seed=s)
        assert eng._spec_ema is not None and eng._spec_ema < 1.1
        steps_before = eng.stats.spec_verify_steps
        for s in range(6, 10):
            eng.generate([p], seed=s)
        # vanilla path now serves: no further verify steps, and the vanilla
        # batch-1 executable exists
        assert eng.stats.spec_verify_steps == steps_before
        assert (1, 32, 8, None) in eng._compiled

    def test_auto_keeps_speculating_when_accepting(self):
        cfg = LlamaConfig.tiny()
        params0 = jax.tree.map(
            lambda x: np.zeros_like(x),
            init_llama_params(jax.random.PRNGKey(0), cfg, FP32),
        )
        eng = InferenceEngine(
            cfg, params0,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=12),
            engine_config=dataclasses.replace(ENG, speculative="auto"),
            dtypes=FP32,
        )
        p = [1] + [0] * 8  # constant emitter: every proposal accepted
        for _ in range(5):
            eng.generate([p])
        assert eng._spec_ema is not None and eng._spec_ema > 4.0
        before = eng.stats.spec_verify_steps
        eng.generate([p])
        assert eng.stats.spec_verify_steps > before

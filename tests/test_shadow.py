"""Shadow-traffic quality auditor (ISSUE 15): online divergence tracking
for every approximation in the serving path.

The contracts under test (obs/shadow.py, engine.score_exact,
docs/OBSERVABILITY.md "Shadow quality auditor"):

- **Exact replay**: ``score_exact`` is a teacher-forced forward whose
  argmax chain reproduces the greedy decode stream bit-for-bit — so
  byte-identity traffic (exact-chain prefix reuse, paged speculation)
  audits at divergence rate 0.0, non-vacuously.
- **Tolerance**: FORCED warm-tier (int8) serving audits within the
  pinned 0.15 logit tolerance — the divergence evidence (minimal
  explaining logit perturbation) can never exceed the per-logit drift
  the warm contract already bounds — and the audit's attribution names
  ``warm_tier``.
- **Same report, two sources**: ``GET /debug/quality`` (live state) and
  ``scripts/flightview.py --quality`` (offline ``shadow_audit`` journal
  events) render through ONE function and agree figure for figure.
- **Bursts**: the second diverged audit inside the burst window spools a
  ``quality_divergence`` incident bundle.
- **Discipline**: sampling/backlog/headroom/eligibility skips are
  counted honestly; the auditor never queues unboundedly and never
  fails the response it rides on.
"""

import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EngineConfig,
    FlightConfig,
    KVTieringConfig,
    LlamaConfig,
    PrefixCacheConfig,
    SamplingConfig,
    ShadowConfig,
)
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import flight
from rag_llm_k8s_tpu.obs import shadow as obs_shadow
from rag_llm_k8s_tpu.obs import slo as obs_slo
from rag_llm_k8s_tpu.server.app import RagService, create_app

from scripts import flightview  # noqa: E402

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=10)


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(vocab_size=128)
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    return cfg, params


def _oneshot(cfg, params, **ec_kw):
    ec = EngineConfig(
        prompt_buckets=(64,), max_batch_size=2, max_seq_len=256,
        speculative="off", **ec_kw,
    )
    return InferenceEngine(
        cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32
    )


class _FixedRng:
    """Deterministic sampler: yields the given values in order."""

    def __init__(self, values):
        self._values = list(values)

    def random(self):
        return self._values.pop(0) if self._values else 1.0


def _auditor(score_fn, sample_rate=1.0, **kw):
    return obs_shadow.ShadowAuditor(
        ShadowConfig(sample_rate=sample_rate), score_fn=score_fn, **kw
    )


# ---------------------------------------------------------------------------
# state / report primitives (pure, jax-free)
# ---------------------------------------------------------------------------
class TestStateAndReport:
    def test_record_and_render(self):
        st = obs_shadow.new_state()
        obs_shadow.record(st, {
            "outcome": "clean", "n": 8, "err": 0.0,
            "approx": ["prefix_reuse"],
        })
        obs_shadow.record(st, {
            "outcome": "diverged", "n": 4, "pos": 3, "err": 0.12,
            "approx": ["warm_tier", "prefix_reuse"],
        })
        obs_shadow.record(st, {"outcome": "skipped", "reason": "sampled"})
        rep = obs_shadow.render_report(st)
        assert rep["audits"] == {
            "clean": 1, "diverged": 1, "skipped": 1, "failed": 0,
        }
        assert rep["divergence_rate"] == 0.5
        assert rep["skips"] == {"sampled": 1}
        assert rep["attribution"]["prefix_reuse"] == {
            "clean": 1, "diverged": 1,
        }
        assert rep["attribution"]["warm_tier"] == {"clean": 0, "diverged": 1}
        assert rep["tokens_compared"] == 12
        assert rep["logit_err"]["max"] == 0.12
        # 0.12 lands in the le_0.15 bucket — the tolerance bound
        assert rep["logit_err"]["hist"]["le_0.15"] == 1
        assert rep["first_divergence_token"]["hist"]["le_4"] == 1

    def test_no_approx_counts_as_none(self):
        st = obs_shadow.new_state()
        obs_shadow.record(st, {"outcome": "clean", "n": 2, "err": 0.0})
        assert obs_shadow.render_report(st)["attribution"]["none"] == {
            "clean": 1, "diverged": 0,
        }

    def test_state_from_events_matches_live_record(self):
        evs = [
            {"seq": 2, "type": "shadow_audit", "outcome": "diverged",
             "n": 3, "pos": 2, "err": 0.3, "approx": ["splice"]},
            {"seq": 1, "type": "shadow_audit", "outcome": "clean",
             "n": 5, "err": 0.0, "approx": []},
            {"seq": 3, "type": "goodput_window", "kind": "decode"},
        ]
        st = obs_shadow.state_from_events(evs)
        live = obs_shadow.new_state()
        obs_shadow.record(live, evs[1])
        obs_shadow.record(live, evs[0])
        assert obs_shadow.render_report(st) == obs_shadow.render_report(live)

    def test_quantiles_from_hist(self):
        st = obs_shadow.new_state()
        for err in (0.01, 0.01, 0.01, 2.0):
            obs_shadow.record(
                st, {"outcome": "diverged", "n": 1, "pos": 0, "err": err}
            )
        rep = obs_shadow.render_report(st)
        assert rep["logit_err"]["p50"] == 0.01
        # quantiles report BUCKET BOUNDS (2.0 lands in the le_2.5 bucket)
        assert rep["logit_err"]["p99"] == 2.5
        # overflow quantile falls back to the tracked max
        obs_shadow.record(
            st, {"outcome": "diverged", "n": 1, "pos": 0, "err": 7.5}
        )
        rep = obs_shadow.render_report(st)
        assert rep["logit_err"]["max"] == 7.5


# ---------------------------------------------------------------------------
# auditor discipline (fake score_fn — no device work)
# ---------------------------------------------------------------------------
class TestAuditorDiscipline:
    @staticmethod
    def _score_clean(prompt, emitted):
        return {
            "argmax": list(emitted),
            "max_logit": [1.0] * len(emitted),
            "chosen_logit": [1.0] * len(emitted),
        }

    def test_sampler_selects_by_rate(self):
        aud = _auditor(
            self._score_clean, sample_rate=0.5,
            rng=_FixedRng([0.4, 0.6, 0.4]),
        )
        try:
            assert aud.observe([1, 2], prompt_ids=[3]) is True
            assert aud.observe([1, 2], prompt_ids=[3]) is False  # 0.6 >= 0.5
            assert aud.observe([1, 2], prompt_ids=[3]) is True
            assert aud.drain()
            st = aud.stats()
            assert st["seen"] == 3 and st["selected"] == 2
            assert st["audits_clean"] == 2
        finally:
            aud.shutdown()

    def test_ineligible_counts_sampled_skip_only_when_selected(self):
        aud = _auditor(
            self._score_clean, sample_rate=0.5, rng=_FixedRng([0.9, 0.1]),
        )
        try:
            # unsampled: NOT a skip
            aud.observe([1], prompt_ids=[2], eligible=False)
            # selected + ineligible: counted
            aud.observe([1], prompt_ids=[2], eligible=False)
            assert aud.drain()
            st = aud.stats()
            assert st["skip_sampled"] == 1.0
            assert st["audits_skipped"] == 1.0
        finally:
            aud.shutdown()

    def test_empty_and_missing_prompt_skip(self):
        aud = _auditor(self._score_clean)
        try:
            aud.observe([], prompt_ids=[1], force=True)
            aud.observe([1], prompt_fn=lambda: None, force=True)
            aud.observe([1], prompt_fn=lambda: 1 / 0, force=True)
            assert aud.drain()
            st = aud.stats()
            assert st["skip_empty"] == 1.0
            assert st["skip_no_prompt"] == 2.0
        finally:
            aud.shutdown()

    def test_backlog_bound_skips_instead_of_queueing(self):
        import threading

        gate = threading.Event()

        def slow(prompt, emitted):
            gate.wait(5.0)
            return self._score_clean(prompt, emitted)

        aud = obs_shadow.ShadowAuditor(
            ShadowConfig(sample_rate=1.0, backlog=1), score_fn=slow,
        )
        try:
            aud.observe([1], prompt_ids=[2], force=True)  # worker takes it
            time.sleep(0.1)  # let the worker pop it (inflight, queue empty)
            aud.observe([1], prompt_ids=[2], force=True)  # queued
            aud.observe([1], prompt_ids=[2], force=True)  # over backlog
            st = aud.stats()
            assert st["skip_backlog"] >= 1.0
            gate.set()
            assert aud.drain()
        finally:
            gate.set()
            aud.shutdown()

    def test_headroom_never_clears_skips(self):
        aud = obs_shadow.ShadowAuditor(
            ShadowConfig(sample_rate=1.0), score_fn=self._score_clean,
            headroom_fn=lambda: False,
        )
        aud._HEADROOM_TRIES = 2  # keep the poll budget test-sized
        try:
            aud.observe([1], prompt_ids=[2], force=True)
            assert aud.drain()
            assert aud.stats()["skip_headroom"] == 1.0
        finally:
            aud.shutdown()

    def test_oversize_valueerror_is_a_skip_not_a_failure(self):
        def oversize(prompt, emitted):
            raise ValueError("too long")

        aud = _auditor(oversize)
        try:
            aud.observe([1], prompt_ids=[2], force=True)
            assert aud.drain()
            st = aud.stats()
            assert st["skip_oversize"] == 1.0 and st["audits_failed"] == 0.0
        finally:
            aud.shutdown()

    def test_crash_is_contained_as_failed(self):
        def boom(prompt, emitted):
            raise RuntimeError("device fell over")

        aud = _auditor(boom)
        try:
            aud.observe([1], prompt_ids=[2], force=True)
            assert aud.drain()
            assert aud.stats()["audits_failed"] == 1.0
        finally:
            aud.shutdown()

    def test_burst_hook_fires_on_second_divergence_in_window(self):
        def diverge(prompt, emitted):
            return {
                "argmax": [t + 1 for t in emitted],
                "max_logit": [1.0] * len(emitted),
                "chosen_logit": [0.9] * len(emitted),
            }

        clock = {"t": 0.0}
        bursts = []
        aud = obs_shadow.ShadowAuditor(
            ShadowConfig(sample_rate=1.0, burst_window_s=10.0),
            score_fn=diverge,
            on_burst=lambda: bursts.append(1),
            clock=lambda: clock["t"],
        )
        try:
            aud.observe([1], prompt_ids=[2], force=True)
            assert aud.drain()
            assert not bursts  # one divergence is routine
            clock["t"] = 20.0  # the first stamp ages out of the window
            aud.observe([1], prompt_ids=[2], force=True)
            assert aud.drain()
            assert not bursts
            clock["t"] = 25.0  # second divergence INSIDE the window
            aud.observe([1], prompt_ids=[2], force=True)
            assert aud.drain()
            assert bursts == [1]
        finally:
            aud.shutdown()

    def test_on_result_receives_the_journal_payload(self):
        got = []
        aud = _auditor(
            self._score_clean, on_result=lambda rid, ev: got.append((rid, ev))
        )
        try:
            aud.observe([5, 6], prompt_ids=[1], approx=("spec_verify",),
                        request_id=42, force=True)
            assert aud.drain()
            rid, ev = got[0]
            assert rid == 42
            assert ev["outcome"] == "clean" and ev["n"] == 2
            assert ev["approx"] == ["spec_verify"]
            # the live state folded EXACTLY this payload (round-trip anchor)
            st = obs_shadow.state_from_events(
                [dict(ev, type="shadow_audit", seq=0)]
            )
            assert st["audits"]["clean"] == 1
        finally:
            aud.shutdown()


# ---------------------------------------------------------------------------
# the exact-path scorer (engine.score_exact)
# ---------------------------------------------------------------------------
class TestScoreExact:
    def test_argmax_chain_matches_greedy_stream(self, tiny):
        cfg, params = tiny
        eng = _oneshot(cfg, params)
        prompt = [cfg.bos_token_id, 5, 9, 12, 7, 7, 9]
        out = eng.generate([prompt])[0]
        assert out
        score = eng.score_exact(prompt, out)
        assert [int(t) for t in score["argmax"]] == out
        gaps = score["max_logit"] - score["chosen_logit"]
        assert float(np.max(gaps)) == 0.0  # delivered IS the exact argmax

    def test_perturbed_stream_locates_the_divergence(self, tiny):
        cfg, params = tiny
        eng = _oneshot(cfg, params)
        prompt = [cfg.bos_token_id, 5, 9, 12, 7, 7, 9]
        out = eng.generate([prompt])[0]
        bad = list(out)
        bad[3] = (bad[3] + 1) % cfg.vocab_size
        s = eng.score_exact(prompt, bad)
        assert int(s["argmax"][3]) != bad[3]
        assert [int(t) for t in s["argmax"][:3]] == bad[:3]
        gap = float(s["max_logit"][3] - s["chosen_logit"][3])
        assert gap > 0.0

    def test_oversize_raises_value_error(self, tiny):
        cfg, params = tiny
        eng = _oneshot(cfg, params)
        cap = eng.engine_config.max_chunked_prompt
        with pytest.raises(ValueError):
            eng.score_exact([1] * (cap + 1), [2])
        with pytest.raises(ValueError):
            eng.score_exact([1, 2, 3], [])

    def test_long_sequence_chunks_through_the_scorer(self, tiny):
        """A sequence longer than the largest prompt bucket still scores
        (the scorer's own chunked path) and stays consistent with the
        engine's chunked-prefill greedy stream."""
        cfg, params = tiny
        eng = _oneshot(cfg, params)
        prompt = [cfg.bos_token_id] + [3 + (i % 40) for i in range(90)]
        out = eng.generate([prompt])[0]
        assert out
        score = eng.score_exact(prompt, out)
        assert [int(t) for t in score["argmax"]] == out


    def test_warmed_scorer_covers_every_length_a_bucket_reaches(self, tiny):
        """warm_score_exact builds the scorer for each padded length a
        prompt of the given buckets plus its budget can have — after it, an
        audit at any such length finds its executable (RagService.warmup
        calls it, so the first audits never compile after ready)."""
        cfg, params = tiny
        ec = EngineConfig(
            prompt_buckets=(16, 48), max_batch_size=2, max_seq_len=64,
            speculative="off",
        )
        eng = InferenceEngine(
            cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32
        )

        def scorers():
            return {k[1] for k in eng._compiled if k[3] == ("shadow", 48)}

        eng.warm_score_exact((48,))  # prompts of 17..48 tokens, 1..10 emitted
        assert scorers() == {48, 96}  # multiples of the chunk: 18..58 tokens
        for n_prompt, n_out in ((17, 1), (40, 8), (41, 8), (48, 10)):
            eng.score_exact([cfg.bos_token_id] + [5] * (n_prompt - 1), [7] * n_out)
        assert scorers() == {48, 96}  # nothing new was built
        eng.warm_score_exact((16, 48))  # the small bucket adds nothing below one chunk
        assert scorers() == {48, 96}


# ---------------------------------------------------------------------------
# approximation fingerprints
# ---------------------------------------------------------------------------
PC = PrefixCacheConfig(
    enabled=True, hbm_budget_mb=64, max_prefix_tokens=128,
    segment_buckets=(16, 32, 64), suffix_buckets=(16, 32),
)


def _segments(cfg, rng, tag):
    head = [cfg.bos_token_id] + list(map(int, rng.integers(3, 120, 7)))
    chunk = list(map(int, rng.integers(3, 120, 11)))
    return [(f"head:{tag}", head), (f"chunk:{tag}", chunk)]


class TestFingerprints:
    def test_fresh_build_is_unfingerprinted_then_reuse_marks(self, tiny):
        cfg, params = tiny
        eng = _oneshot(cfg, params, prefix_cache=PC)
        rng = np.random.default_rng(3)
        segments = _segments(cfg, rng, "fp")
        cp0 = eng.prefix_cache.prefix_for(segments)
        assert cp0.approx == ()  # everything built fresh: no approximation
        # memo re-serve: the whole chain came from cache
        cp1 = eng.prefix_cache.prefix_for(segments)
        assert "prefix_reuse" in cp1.approx
        # non-memo hit path too: drop the assembled buffer, keep entries
        eng.prefix_cache._assembled.clear()
        eng.prefix_cache.assembled_bytes = 0
        cp2 = eng.prefix_cache.prefix_for(segments)
        assert "prefix_reuse" in cp2.approx
        assert cp2.computed_tokens == 0

    def test_forced_warm_marks_warm_tier(self, tiny):
        cfg, params = tiny
        tiering = KVTieringConfig(
            enabled=True, warm_below=1e9, cold_below=0.01,
            half_life_s=3600.0, retier_interval_s=3600.0,
        )
        eng = _oneshot(cfg, params, prefix_cache=PC, kv_tiering=tiering)
        rng = np.random.default_rng(5)
        segments = _segments(cfg, rng, "warmfp")
        cache = eng.prefix_cache
        cache.prefix_for(segments)
        assert cache.force_demote("warm") == 2
        cache._assembled.clear()
        cache.assembled_bytes = 0
        cp = cache.prefix_for(segments)
        assert "warm_tier" in cp.approx and "prefix_reuse" in cp.approx
        # a memo re-serve of the warm-built buffer keeps the fingerprint
        cp2 = cache.prefix_for(segments)
        assert "warm_tier" in cp2.approx

    @pytest.mark.parametrize("ledger_on", [True, False])
    def test_continuous_spec_stamps_info_approx(self, tiny, ledger_on):
        """The spec_verify fingerprint comes from ENGINE state, so
        turning the goodput ledger off (an unrelated observability knob)
        must not erase speculation attribution from shadow audits."""
        from rag_llm_k8s_tpu.core.config import GoodputConfig

        cfg, params = tiny
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=EngineConfig(
                prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64,
                kv_paged=True, kv_block_size=16,
                spec_paged=True, spec_paged_tokens=4,
                goodput=GoodputConfig(enabled=ledger_on),
            ),
            dtypes=FP32,
        )
        sched = ContinuousScheduler(eng)
        try:
            info = {}
            out = sched.submit(
                [5, 7, 5, 7, 5, 7, 5, 7, 5, 7], max_new_tokens=10,
                timeout=120, info=info,
            )
            assert out
            assert "spec_verify" in info.get("approx", ())
            assert not eng._spec_rids  # popped at delivery, never leaked
        finally:
            sched.shutdown()


# ---------------------------------------------------------------------------
# config + SLO wiring
# ---------------------------------------------------------------------------
class TestConfig:
    def test_env_round_trip(self):
        cfg = AppConfig.from_env({
            "TPU_RAG_SHADOW": "0",
            "TPU_RAG_SHADOW_SAMPLE_RATE": "0.5",
            "TPU_RAG_SHADOW_BACKLOG": "3",
            "TPU_RAG_SHADOW_BURST_WINDOW_S": "60",
            "TPU_RAG_SLO_QUALITY_OBJECTIVE": "0.9",
            "TPU_RAG_SLO_QUALITY_LOGIT_ERR": "0.3",
        })
        assert cfg.shadow == ShadowConfig(
            enabled=False, sample_rate=0.5, backlog=3, burst_window_s=60.0,
        )
        assert cfg.slo.quality_objective == 0.9
        assert cfg.slo.quality_logit_err == 0.3

    def test_defaults_on_at_five_percent(self):
        sh = AppConfig().shadow
        assert sh.enabled is True
        assert sh.sample_rate <= 0.05

    @pytest.mark.parametrize("env", [
        {"TPU_RAG_SHADOW": "2"},
        {"TPU_RAG_SHADOW_SAMPLE_RATE": "1.5"},
        {"TPU_RAG_SHADOW_BACKLOG": "0"},
        {"TPU_RAG_SHADOW_BURST_WINDOW_S": "0"},
    ])
    def test_invalid_values_raise(self, env):
        with pytest.raises(ValueError):
            ShadowConfig.from_env(env)

    def test_slo_quality_hostile_env_falls_back(self):
        cfg = AppConfig.from_env({
            "TPU_RAG_SLO_QUALITY_OBJECTIVE": "1.5",
            "TPU_RAG_SLO_QUALITY_LOGIT_ERR": "bogus",
        })
        assert cfg.slo.quality_objective == 0.99
        assert cfg.slo.quality_logit_err == 0.15

    def test_default_specs_include_the_quality_slo(self):
        specs = {s.name: s for s in obs_slo.default_specs()}
        q = specs["quality_p99_logit_err"]
        assert q.metric == "rag_quality_logit_err"
        assert q.kind == "latency"
        assert q.objective == 0.99 and q.threshold_s == 0.15


# ---------------------------------------------------------------------------
# smoke (make shadow-smoke)
# ---------------------------------------------------------------------------
class ByteTokenizer:
    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode(
            "utf-8", "replace"
        )


def _drain_shadow(svc_or_aud):
    aud = getattr(svc_or_aud, "shadow", svc_or_aud)
    assert aud.drain(timeout=60.0), "shadow audits did not finish"
    return aud


class TestShadowSmoke:
    """`make shadow-smoke`: forced-sample shadow audits on the tiny
    config — byte-identity traffic audits clean, forced-warm audits
    within the pinned tolerance with the right attribution, and a
    divergence burst spools a bundle flightview round-trips."""

    def test_spec_on_greedy_audits_clean_with_attribution(self, tiny):
        """Greedy paged-speculation traffic through the continuous
        scheduler audits at divergence rate 0.0 — the spec byte-identity
        contract observed on 'live' traffic — attributed to spec_verify
        (non-vacuously: the request really drafted)."""
        cfg, params = tiny
        oneshot = _oneshot(cfg, params)
        aud = _auditor(oneshot.score_exact)
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=EngineConfig(
                prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64,
                kv_paged=True, kv_block_size=16,
                spec_paged=True, spec_paged_tokens=4,
            ),
            dtypes=FP32,
        )
        sched = ContinuousScheduler(eng)
        try:
            prompts = [
                [5, 7, 5, 7, 5, 7, 5, 7, 5, 7],
                [11, 11, 11, 11, 11, 11, 11, 11],
            ]
            for p in prompts:
                info = {}
                out = sched.submit(p, max_new_tokens=10, timeout=120,
                                   info=info)
                assert out
                aud.observe(
                    out, approx=tuple(info.get("approx", ())),
                    request_id=info.get("request_id"),
                    prompt_ids=p, force=True,
                )
            _drain_shadow(aud)
            st = aud.stats()
            assert st["audits_clean"] == 2.0
            assert st["audits_diverged"] == 0.0
            assert st["divergence_rate"] == 0.0
            assert st.get("attr_spec_verify_clean", 0.0) >= 1.0, (
                "no audit carried the spec_verify fingerprint — the "
                "clean rate above is vacuous"
            )
            assert eng.stats.spec_accepted_tokens > 0
        finally:
            sched.shutdown()
            aud.shutdown()

    def test_exact_chain_reuse_audits_clean(self, tiny):
        """Exact-chain prefix-reuse traffic (memo re-serve included)
        audits at divergence rate 0.0 with prefix_reuse attributed."""
        cfg, params = tiny
        eng = _oneshot(cfg, params, prefix_cache=PC)
        aud = _auditor(eng.score_exact)
        rng = np.random.default_rng(9)
        segments = _segments(cfg, rng, "smoke")
        suffix = list(map(int, rng.integers(3, 120, 6)))
        prompt = [t for _, seg in segments for t in seg] + suffix
        try:
            for _ in range(2):  # build, then memo re-serve
                cp = eng.prefix_cache.prefix_for(segments)
                out = eng.generate_prefixed(suffix, cp)
                assert out
                aud.observe(out, approx=cp.approx, prompt_ids=prompt,
                            force=True)
            _drain_shadow(aud)
            st = aud.stats()
            assert st["audits_clean"] == 2.0 and st["audits_diverged"] == 0.0
            assert st.get("attr_prefix_reuse_clean", 0.0) >= 1.0
        finally:
            aud.shutdown()

    def test_forced_warm_audits_within_pinned_tolerance(self, tiny):
        """FORCED warm-tier serving: every audit measures within the
        pinned 0.15 logit tolerance (clean or diverged — the minimal
        explaining perturbation can never exceed the warm drift bound)
        and the audit carries the warm_tier attribution."""
        cfg, params = tiny
        tiering = KVTieringConfig(
            enabled=True, warm_below=1e9, cold_below=0.01,
            half_life_s=3600.0, retier_interval_s=3600.0,
        )
        eng = _oneshot(cfg, params, prefix_cache=PC, kv_tiering=tiering)
        aud = _auditor(eng.score_exact)
        cache = eng.prefix_cache
        rng = np.random.default_rng(13)
        try:
            audited = 0
            for tag in ("w0", "w1", "w2"):
                segments = _segments(cfg, rng, tag)
                suffix = list(map(int, rng.integers(3, 120, 6)))
                prompt = [t for _, seg in segments for t in seg] + suffix
                cache.prefix_for(segments)
                assert cache.force_demote("warm") == 2
                cache._assembled.clear()
                cache.assembled_bytes = 0
                cp = cache.prefix_for(segments)
                assert "warm_tier" in cp.approx
                out = eng.generate_prefixed(suffix, cp)
                if not out:
                    continue
                aud.observe(out, approx=cp.approx, prompt_ids=prompt,
                            force=True)
                audited += 1
            assert audited > 0
            _drain_shadow(aud)
            st = aud.stats()
            judged = st["audits_clean"] + st["audits_diverged"]
            assert judged == audited and st["audits_failed"] == 0
            # attribution names warm_tier on every judged audit
            warm = (st.get("attr_warm_tier_clean", 0.0)
                    + st.get("attr_warm_tier_diverged", 0.0))
            assert warm == judged
            # whatever diverged did so WITHIN the pinned tolerance: the
            # minimal explaining perturbation is bounded by the warm
            # tier's 0.15 per-logit drift contract
            rep = obs_shadow.render_report(aud.state())
            assert rep["logit_err"]["max"] <= 0.15 + 1e-6
        finally:
            aud.shutdown()

    def test_divergence_burst_bundle_and_flightview_round_trip(
        self, tiny, tmp_path, monkeypatch
    ):
        """A forced divergence burst spools a quality_divergence incident
        bundle, and flightview --quality rebuilds EXACTLY the report
        GET /debug/quality serves, from the bundle file alone."""
        monkeypatch.delenv("TPU_RAG_FAULTS", raising=False)
        cfg, params = tiny
        app_cfg = AppConfig(
            model=cfg,
            flight=FlightConfig(
                spool_dir=str(tmp_path / "spool"), cooldown_s=0.0,
                debug_endpoints=True,
            ),
            shadow=ShadowConfig(sample_rate=1.0, burst_window_s=300.0),
            system_message="ctx",
        )
        engine = _oneshot(cfg, params)
        svc = RagService(
            app_cfg, engine, ByteTokenizer(), None, ByteTokenizer(), None,
        )
        svc.ready = True
        try:
            flight.recorder().clear()
            prompt = [cfg.bos_token_id, 5, 9, 12, 7, 7, 9]
            good = engine.generate([prompt])[0]
            bad = list(good)
            bad[1] = (bad[1] + 1) % cfg.vocab_size
            for _ in range(2):  # the SECOND diverged audit is the burst
                svc.shadow.observe(bad, approx=("warm_tier",),
                                   prompt_ids=prompt, force=True)
                _drain_shadow(svc)
            client = create_app(svc).test_client()
            # the burst spooled a quality_divergence bundle
            incidents = client.get("/debug/incidents").get_json()["incidents"]
            triggers = [i["trigger"] for i in incidents]
            assert "quality_divergence" in triggers
            bid = next(
                i["id"] for i in incidents
                if i["trigger"] == "quality_divergence"
            )
            bundle = client.get(f"/debug/incidents?id={bid}").get_json()
            # the journal in the bundle carries the shadow_audit facts
            types = [e["type"] for e in bundle["journal"]]
            assert types.count("shadow_audit") == 2
            assert types.count("quality_divergence") == 2
            # live report == offline report, through one renderer
            live = client.get("/debug/quality").get_json()
            assert live["enabled"] is True
            assert live["report"]["audits"]["diverged"] == 2
            assert live["report"]["attribution"]["warm_tier"]["diverged"] == 2
            bpath = tmp_path / "bundle.json"
            bpath.write_text(json.dumps(bundle))
            offline = flightview.build_quality_report(
                flightview.load_events(bundle)
            )
            assert offline == live["report"]
            # the CLI renders both forms standalone
            assert flightview.main([str(bpath), "--quality", "--json"]) == 0
            assert flightview.main([str(bpath), "--quality"]) == 0
            # and the divergences moved the metric families
            snap = svc.metrics.snapshot()
            assert snap.get("rag_quality_divergence_rate") == 1.0
        finally:
            svc.shutdown()

    def test_debug_quality_contract_and_served_audit(
        self, tiny, tmp_path, monkeypatch
    ):
        """403 unless armed; armed, a real /query rides the full serving
        path, is audited clean, and the report says so."""
        monkeypatch.delenv("TPU_RAG_FAULTS", raising=False)
        monkeypatch.delenv("TPU_RAG_DEBUG", raising=False)
        cfg, params = tiny
        from rag_llm_k8s_tpu.core.config import EncoderConfig
        from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
        from rag_llm_k8s_tpu.index.store import VectorStore
        from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params

        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        app_cfg = AppConfig(
            model=cfg, encoder=enc_cfg,
            flight=FlightConfig(spool_dir=str(tmp_path / "spool")),
            shadow=ShadowConfig(sample_rate=1.0),
            system_message="Use the context.",
        )
        engine = _oneshot(cfg, params)
        encoder = EncoderRunner(
            enc_cfg, init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32, length_buckets=(32, 64), max_batch=4,
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        svc = RagService(
            app_cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store,
        )
        svc.ready = True
        try:
            texts = ["alpha beta gamma", "delta epsilon zeta"]
            vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
            store.add(list(vecs), [
                {"filename": "f", "chunk_id": i, "text": t}
                for i, t in enumerate(texts)
            ])
            client = create_app(svc).test_client()
            assert client.get("/debug/quality").status_code == 403
            r = client.post("/query", json={"prompt": "alpha"})
            assert r.status_code == 200
            _drain_shadow(svc)
            monkeypatch.setenv("TPU_RAG_DEBUG", "1")
            app_cfg2 = dataclasses.replace(
                app_cfg,
                flight=dataclasses.replace(
                    app_cfg.flight, debug_endpoints=True
                ),
            )
            svc.config = app_cfg2
            client = create_app(svc).test_client()
            rep = client.get("/debug/quality").get_json()
            assert rep["enabled"] is True
            assert rep["sampling"]["seen"] >= 1
            assert rep["report"]["audits"]["diverged"] == 0
            assert rep["report"]["audits"]["failed"] == 0
            judged = (rep["report"]["audits"]["clean"]
                      + rep["report"]["audits"]["skipped"])
            assert judged >= 1
        finally:
            svc.shutdown()

    def test_warmup_leaves_the_first_audited_query_nothing_to_build(
        self, tiny, tmp_path
    ):
        """RagService.warmup() with the auditor on also warms the exact
        scorer: a query served after ready, and its audit, add no
        executable to the engine (the chip found the first audit at each
        padded length compiling on the audit thread, PR 21)."""
        cfg, params = tiny
        from rag_llm_k8s_tpu.core.config import EncoderConfig
        from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
        from rag_llm_k8s_tpu.index.store import VectorStore
        from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params

        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        app_cfg = AppConfig(
            model=cfg, encoder=enc_cfg,
            flight=FlightConfig(spool_dir=str(tmp_path / "spool")),
            shadow=ShadowConfig(sample_rate=1.0),
            system_message="Use the context.",
        )
        engine = InferenceEngine(  # a bucket the byte-level prompt fits
            cfg, params, sampling=GREEDY, dtypes=FP32,
            engine_config=EngineConfig(
                prompt_buckets=(128,), max_batch_size=2, max_seq_len=256,
                speculative="off",
            ),
        )
        encoder = EncoderRunner(
            enc_cfg, init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32, length_buckets=(32, 64), max_batch=4,
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        texts = ["alpha beta gamma", "delta epsilon zeta"]
        vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
        store.add(list(vecs), [
            {"filename": "f", "chunk_id": i, "text": t}
            for i, t in enumerate(texts)
        ])
        svc = RagService(
            app_cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store,
        )
        try:
            svc.warmup()
            assert svc.ready
            warmed = set(engine._compiled)
            assert any(k[3] == ("shadow", 128) for k in warmed)
            r = create_app(svc).test_client().post("/query", json={"prompt": "alpha"})
            assert r.status_code == 200
            _drain_shadow(svc)
            audits = svc.shadow.state()["audits"]
            assert audits["clean"] == 1 and not audits["failed"]
            assert set(engine._compiled) == warmed
        finally:
            svc.shutdown()

"""ISSUE 3 decision-layer tests: W3C traceparent propagation round-trips,
burn-rate math against hand-computed fixtures, the fast-burn/slow-burn
window split, the /slo endpoint and the device telemetry gauges."""

import json
import logging
import re

import jax
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EncoderConfig,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import logging as obs_logging
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.obs import slo as obs_slo
from rag_llm_k8s_tpu.server.app import RagService, create_app

FP32 = DTypePolicy.fp32()


class ByteTokenizer:
    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode("utf-8", "replace")


# ---------------------------------------------------------------------------
# traceparent parse/emit
# ---------------------------------------------------------------------------

VALID_TP = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"


class TestTraceparent:
    def test_valid_round_trip(self):
        ctx = obs_logging.parse_traceparent(VALID_TP)
        assert ctx is not None
        assert ctx.trace_id == "4bf92f3577b34da6a3ce929d0e0e4736"
        assert ctx.span_id == "00f067aa0ba902b7"
        assert ctx.sampled is True
        assert (
            obs_logging.format_traceparent(ctx.trace_id, ctx.span_id, ctx.sampled)
            == VALID_TP
        )

    def test_unsampled_flag(self):
        ctx = obs_logging.parse_traceparent(VALID_TP[:-2] + "00")
        assert ctx is not None and ctx.sampled is False

    @pytest.mark.parametrize(
        "header",
        [
            None,
            "",
            "garbage",
            "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7",  # 3 fields
            "ff-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",  # ver ff
            "00-" + "0" * 32 + "-00f067aa0ba902b7-01",  # all-zero trace
            "00-4bf92f3577b34da6a3ce929d0e0e4736-" + "0" * 16 + "-01",  # zero span
            "00-4BF92F3577B34DA6A3CE929D0E0E4736-00f067aa0ba902b7-01",  # uppercase
            "00-4bf92f3577b34da6-00f067aa0ba902b7-01",  # short trace id
            "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01-xx",  # v00 extra
            "zz-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",  # bad version
        ],
    )
    def test_malformed_returns_none(self, header):
        assert obs_logging.parse_traceparent(header) is None

    def test_future_version_accepted_with_extra_fields(self):
        ctx = obs_logging.parse_traceparent(VALID_TP.replace("00-", "01-", 1) + "-extra")
        assert ctx is not None and ctx.trace_id.startswith("4bf9")

    def test_new_traceparent_parses(self):
        ctx = obs_logging.parse_traceparent(obs_logging.new_traceparent())
        assert ctx is not None and ctx.sampled


# ---------------------------------------------------------------------------
# burn-rate math on hand-computed fixtures (fake clock — hours in microseconds)
# ---------------------------------------------------------------------------


class TestSloConfig:
    """PR 10 satellite: the TPU_RAG_SLO_* knobs route through
    core/config.py with a SAFE parse — a malformed or out-of-range env
    value must retune to the default, never raise at scrape/eval time
    (an out-of-range objective previously survived the float() guard and
    blew up in SloSpec.__post_init__)."""

    def test_defaults(self):
        from rag_llm_k8s_tpu.core.config import SloConfig

        cfg = SloConfig.from_env({})
        assert cfg.availability_objective == 0.999
        assert cfg.request_p95_s == 2.0
        assert cfg.ttft_p95_s == 1.0

    def test_valid_overrides_apply(self):
        from rag_llm_k8s_tpu.core.config import SloConfig

        cfg = SloConfig.from_env({
            "TPU_RAG_SLO_REQUEST_P95_S": "3.5",
            "TPU_RAG_SLO_TTFT_P95_OBJECTIVE": "0.9",
        })
        assert cfg.request_p95_s == 3.5
        assert cfg.ttft_p95_objective == 0.9

    def test_malformed_values_fall_back(self):
        from rag_llm_k8s_tpu.core.config import SloConfig

        cfg = SloConfig.from_env({
            "TPU_RAG_SLO_REQUEST_P95_S": "two seconds",
            "TPU_RAG_SLO_AVAILABILITY_OBJECTIVE": "",
        })
        assert cfg.request_p95_s == 2.0
        assert cfg.availability_objective == 0.999

    def test_out_of_range_values_fall_back(self):
        # 1.5 parses as float but violates SloSpec's (0,1) objective
        # contract; 0/-1 thresholds violate "latency SLO needs threshold"
        from rag_llm_k8s_tpu.core.config import SloConfig

        cfg = SloConfig.from_env({
            "TPU_RAG_SLO_REQUEST_P95_OBJECTIVE": "1.5",
            "TPU_RAG_SLO_TTFT_P95_S": "0",
            "TPU_RAG_SLO_REQUEST_P95_S": "-1",
        })
        assert cfg.request_p95_objective == 0.95
        assert cfg.ttft_p95_s == 1.0
        assert cfg.request_p95_s == 2.0

    def test_default_specs_construct_from_hostile_env(self, monkeypatch):
        # end-to-end: a hostile environment still yields valid SloSpecs
        monkeypatch.setenv("TPU_RAG_SLO_REQUEST_P95_S", "bogus")
        monkeypatch.setenv("TPU_RAG_SLO_AVAILABILITY_OBJECTIVE", "7")
        specs = obs_slo.default_specs()
        by_name = {s.name: s for s in specs}
        assert by_name["request_p95"].threshold_s == 2.0
        assert by_name["availability"].objective == 0.999

    def test_app_config_threads_slo(self):
        cfg = AppConfig.from_env({"TPU_RAG_SLO_TTFT_P95_S": "0.75"})
        assert cfg.slo.ttft_p95_s == 0.75
        specs = obs_slo.default_specs(cfg.slo)
        assert {s.name: s for s in specs}["ttft_p95"].threshold_s == 0.75


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _availability_engine(objective=0.999):
    reg = obs_metrics.MetricsRegistry()
    fam = reg.labeled_counter("rag_http_requests_total", "test")
    clock = FakeClock()
    spec = obs_slo.SloSpec(
        "availability", "availability", "rag_http_requests_total",
        objective=objective,
    )
    eng = obs_slo.SloEngine(
        reg, specs=[spec], clock=clock, min_eval_interval_s=0.0,
        register_gauges=False,
    )
    return reg, fam, clock, eng


class TestBurnRateMath:
    def test_no_traffic_is_calm_and_compliant(self):
        _, _, _, eng = _availability_engine()
        (s,) = eng.evaluate(force=True)["slos"]
        assert s["burn_rate"] == {"5m": 0.0, "30m": 0.0, "1h": 0.0, "6h": 0.0}
        assert s["compliant"] and s["error_budget_remaining"] == 1.0
        assert not s["fast_burn"] and not s["slow_burn"]

    def test_hand_computed_windows(self):
        """6h of clean traffic, then 50 bad of 100 in the last minute.

        Sample ring (sample at t=i*1800 holds the i-th epoch's 1000 good;
        the burst lands at now = 11*1800 + 1801). budget = 0.001.
        Hand-computed window diffs (baseline = newest sample <= now - W):
          5m:  base t=19800 -> bad 50 / 100    -> burn 500.0
          30m: base t=19800 -> bad 50 / 100    -> burn 500.0
          1h:  base t=18000 -> bad 50 / 1100   -> burn ~45.45
          6h:  base t=0     -> bad 50 / 11100  -> burn ~4.50
        """
        _, fam, clock, eng = _availability_engine(objective=0.999)
        good = fam.labels(route="/generate", code="200")
        bad = fam.labels(route="/generate", code="500")
        for _ in range(12):  # every 30 min over 6h: 1000 good requests
            good.inc(1000)
            eng.sample()
            clock.advance(1800)
        good.inc(50)
        bad.inc(50)
        clock.advance(1)  # the burst lands "now", inside every window
        (s,) = eng.evaluate(force=True)["slos"]
        br = s["burn_rate"]
        assert br["5m"] == pytest.approx(500.0, rel=1e-3)
        assert br["30m"] == pytest.approx(500.0, rel=1e-3)
        assert br["1h"] == pytest.approx(50 / 1100 / 0.001, rel=1e-2)
        assert br["6h"] == pytest.approx(50 / 11100 / 0.001, rel=1e-2)
        # the acceptance shape: the FAST pair (5m and 1h both >= 14.4)
        # fires while the SLOW pair stays calm (6h ~4.5 < 6)
        assert s["fast_burn"] is True
        assert s["slow_burn"] is False
        # the 6h burst overspent the whole window budget (burn 4.5 > 1):
        # remaining floors at 0 and compliance over the long window is gone
        assert s["error_budget_remaining"] == 0.0
        assert s["compliant"] is False  # 6h bad-rate 0.45% > 0.1% objective

    def test_burn_clears_after_calm_period(self):
        _, fam, clock, eng = _availability_engine()
        good = fam.labels(route="/generate", code="200")
        bad = fam.labels(route="/generate", code="500")
        good.inc(50)
        bad.inc(50)
        eng.sample()
        clock.advance(1)
        (s,) = eng.evaluate(force=True)["slos"]
        assert s["fast_burn"]
        # 7h of clean traffic pushes the burst out of every window
        for _ in range(14):
            clock.advance(1800)
            good.inc(1000)
            eng.sample()
        clock.advance(1)
        (s,) = eng.evaluate(force=True)["slos"]
        assert not s["fast_burn"] and not s["slow_burn"]
        assert s["compliant"]

    def test_latency_sli_counts_threshold_buckets(self):
        """Latency good-event counting reads the SAME histogram /metrics
        exposes: observations <= threshold are good, others spend budget."""
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram(
            "rag_request_duration_seconds", buckets=(0.5, 2.0, 8.0)
        )
        clock = FakeClock()
        spec = obs_slo.SloSpec(
            "request_p95", "latency", "rag_request_duration_seconds",
            objective=0.95, threshold_s=2.0,
        )
        eng = obs_slo.SloEngine(
            reg, specs=[spec], clock=clock, min_eval_interval_s=0.0,
            register_gauges=False,
        )
        for _ in range(90):
            h.observe(0.3)  # good
        for _ in range(10):
            h.observe(5.0)  # bad: over the 2 s threshold
        clock.advance(1)
        (s,) = eng.evaluate(force=True)["slos"]
        # bad_frac = 10/100 = 0.1; budget = 0.05 -> burn 2.0 on every window
        assert s["burn_rate"]["5m"] == pytest.approx(2.0, rel=1e-6)
        assert s["threshold_bucket_s"] == 2.0
        assert not s["compliant"]

    def test_threshold_above_ladder_is_not_vacuous(self):
        """A threshold over the histogram's top bound clamps to the top
        bound — the +Inf overflow bucket must never count as 'good', or
        the SLO goes vacuously compliant at any latency."""
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("rag_request_duration_seconds", buckets=(0.5, 2.0))
        clock = FakeClock()
        spec = obs_slo.SloSpec(
            "request_p95", "latency", "rag_request_duration_seconds",
            objective=0.95, threshold_s=100.0,  # above the 2.0 top bound
        )
        eng = obs_slo.SloEngine(
            reg, specs=[spec], clock=clock, min_eval_interval_s=0.0,
            register_gauges=False,
        )
        for _ in range(10):
            h.observe(50.0)  # lands in +Inf: slow no matter the threshold
        clock.advance(1)
        (s,) = eng.evaluate(force=True)["slos"]
        assert s["burn_rate"]["5m"] == pytest.approx(20.0)  # all bad
        assert not s["compliant"]
        assert s["threshold_bucket_s"] == 2.0  # the bound actually judged

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            obs_slo.SloSpec("x", "latency", "m", objective=0.95)  # no threshold
        with pytest.raises(ValueError):
            obs_slo.SloSpec("x", "availability", "m", objective=1.5)
        with pytest.raises(ValueError):
            obs_slo.SloSpec("x", "nope", "m", objective=0.9)

    def test_burn_gauges_exported(self):
        reg = obs_metrics.MetricsRegistry()
        fam = reg.labeled_counter("rag_http_requests_total", "test")
        clock = FakeClock()
        spec = obs_slo.SloSpec(
            "availability", "availability", "rag_http_requests_total",
            objective=0.9,
        )
        obs_slo.SloEngine(reg, specs=[spec], clock=clock, min_eval_interval_s=0.0)
        fam.labels(route="/q", code="500").inc(10)
        clock.advance(1)
        text = reg.render_prometheus()
        m = re.search(
            r'rag_slo_burn_rate\{slo="availability",window="5m"\} ([0-9.]+)', text
        )
        assert m, text[:2000]
        assert float(m.group(1)) == pytest.approx(10.0, rel=1e-6)  # all-bad / 0.1
        assert 'rag_slo_error_budget_remaining{slo="availability"} 0.0' in text
        assert 'rag_slo_fast_burn_active{slo="availability"}' in text


# ---------------------------------------------------------------------------
# HTTP: trace propagation + /slo + log correlation (one tiny shared service)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    llama_cfg = LlamaConfig.tiny(vocab_size=300)
    enc_cfg = EncoderConfig.tiny(vocab_size=300)
    cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)
    engine = InferenceEngine(
        llama_cfg,
        init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
        sampling=SamplingConfig(do_sample=False, max_new_tokens=6),
        engine_config=EngineConfig(prompt_buckets=(128, 512), max_batch_size=2,
                                   max_seq_len=640),
        dtypes=FP32,
    )
    encoder = EncoderRunner(
        enc_cfg,
        init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
        dtypes=FP32, length_buckets=(32,), max_batch=4,
    )
    store = VectorStore(dim=enc_cfg.hidden_size)
    svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store)
    svc.ready = True
    vec = encoder.encode([ByteTokenizer().encode("tiny doc text")])[0]
    store.add([vec], [{"filename": "f", "chunk_id": 0, "text": "kernels tile queries"}])
    client = create_app(svc).test_client()
    r = client.post("/query", json={"prompt": "warm"})
    assert r.status_code == 200, r.get_json()
    return svc, client


class _JsonCapture(logging.Handler):
    """Captures records rendered through the production JsonLogFormatter."""

    def __init__(self):
        super().__init__()
        self.setFormatter(obs_logging.JsonLogFormatter())
        self.lines = []

    def emit(self, record):
        self.lines.append(self.format(record))


class TestTracePropagationHttp:
    def test_inbound_traceparent_round_trip(self, served):
        """The acceptance contract: one trace_id in x-trace-id, in the
        inline tree, and on every structured log line the request emitted."""
        _, client = served
        capture = _JsonCapture()
        root = logging.getLogger("rag_llm_k8s_tpu")
        old_level = root.level
        root.addHandler(capture)
        root.setLevel(logging.INFO)
        try:
            r = client.post(
                "/generate",
                json={"prompt": "what do kernels do?", "trace": True},
                headers={"traceparent": VALID_TP},
            )
        finally:
            root.removeHandler(capture)
            root.setLevel(old_level)
        assert r.status_code == 200, r.get_data()
        want = "4bf92f3577b34da6a3ce929d0e0e4736"
        assert r.headers["x-trace-id"] == want
        # the response traceparent names OUR span under the caller's trace
        ctx = obs_logging.parse_traceparent(r.headers["traceparent"])
        assert ctx is not None and ctx.trace_id == want
        assert ctx.span_id != "00f067aa0ba902b7"
        body = r.get_json()
        assert body["trace"]["trace_id"] == want
        assert body["trace"]["parent_span_id"] == "00f067aa0ba902b7"
        # every structured line emitted inside the request carries the id
        assert capture.lines, "no structured log lines captured"
        for line in capture.lines:
            rec = json.loads(line)
            assert rec["trace_id"] == want, rec
            assert rec["span_id"] == ctx.span_id
        served_lines = [
            json.loads(l) for l in capture.lines
            if json.loads(l)["logger"] == "rag_llm_k8s_tpu.access"
        ]
        assert served_lines and served_lines[-1]["status"] == 200
        assert served_lines[-1]["duration_ms"] > 0

    def test_malformed_traceparent_never_500s(self, served):
        _, client = served
        for bad in ("garbage", "00-zzz-yyy-01", "00-" + "0" * 32 + "-" + "1" * 16):
            r = client.post(
                "/generate", json={"prompt": "hi"}, headers={"traceparent": bad}
            )
            assert r.status_code == 200, (bad, r.get_data())
            tid = r.headers["x-trace-id"]
            assert re.fullmatch(r"[0-9a-f]{32}", tid), tid  # fresh trace

    def test_absent_header_generates_fresh_trace(self, served):
        _, client = served
        r1 = client.post("/query", json={"prompt": "a"})
        r2 = client.post("/query", json={"prompt": "b"})
        t1, t2 = r1.headers["x-trace-id"], r2.headers["x-trace-id"]
        assert re.fullmatch(r"[0-9a-f]{32}", t1)
        assert t1 != t2

    def test_query_alias_contract_identical(self, served):
        """BASELINE.json calls the endpoint /query; the README maps it to
        /generate. Same handler -> identical response contract, including
        the trace headers."""
        _, client = served
        rq = client.post("/query", json={"prompt": "alias?"})
        rg = client.post("/generate", json={"prompt": "alias?"})
        assert rq.status_code == rg.status_code == 200
        bq, bg = rq.get_json(), rg.get_json()
        assert set(bq) == set(bg)
        assert {"generated_text", "context", "timings"} <= set(bq)
        for r in (rq, rg):
            assert "x-trace-id" in r.headers and "traceparent" in r.headers

    def test_http_request_counter_by_route_and_code(self, served):
        svc, client = served
        client.post("/query", json={"prompt": "count me"})
        text = client.get("/metrics").get_data(as_text=True)
        m = re.search(
            r'tpu_rag_rag_http_requests_total\{code="200",route="/query"\} '
            r"([0-9.]+)",
            text,
        )
        # rag_-prefixed names render verbatim (no tpu_rag_ prefix)
        m = m or re.search(
            r'rag_http_requests_total\{code="200",route="/query"\} ([0-9.]+)', text
        )
        assert m, text[:1500]
        assert float(m.group(1)) >= 1


class TestSloEndpoint:
    def test_slo_report_reads_served_histograms(self, served):
        svc, client = served
        client.post("/query", json={"prompt": "traffic"})
        r = client.get("/slo?force=1")
        assert r.status_code == 200
        body = r.get_json()
        names = {s["name"] for s in body["slos"]}
        assert {"availability", "request_p95", "ttft_p95"} <= names
        req = next(s for s in body["slos"] if s["name"] == "request_p95")
        # the same histogram /metrics exposes fed the window: events counted
        assert req["window_events"]["6h"] >= 1
        assert req["threshold_s"] == 2.0
        assert set(req["burn_rate"]) == {"5m", "30m", "1h", "6h"}
        assert all(v >= 0 for v in req["burn_rate"].values())
        assert 0.0 <= req["error_budget_remaining"] <= 1.0
        avail = next(s for s in body["slos"] if s["name"] == "availability")
        assert avail["compliant"] is True  # every test request returned 200
        assert avail["burn_rate"]["6h"] == 0.0
        assert isinstance(body["page"], bool) and isinstance(body["ticket"], bool)

    def test_slo_gauges_share_the_scrape(self, served):
        _, client = served
        text = client.get("/metrics").get_data(as_text=True)
        assert "rag_slo_burn_rate{" in text
        assert "rag_slo_error_budget_remaining{" in text
        assert "rag_device_hbm_bytes_in_use{" in text  # per-device telemetry

    def test_synthetic_latency_flips_fast_burn_on_served_registry(self, served):
        """Acceptance: inject slow observations into the SAME histogram the
        server scrapes; the fast window burns while the slow one stays
        calm. A fresh SloEngine with a fake clock reads the service's own
        registry — proving /slo math and /metrics data share one source."""
        svc, _ = served
        clock = FakeClock()
        spec = obs_slo.SloSpec(
            "request_p95", "latency", "rag_request_duration_seconds",
            objective=0.95, threshold_s=2.0,
        )
        eng = obs_slo.SloEngine(
            svc.metrics, specs=[spec], clock=clock, min_eval_interval_s=0.0,
            register_gauges=False,
        )
        h = svc.metrics.histogram("rag_request_duration_seconds")
        # 6h of history: plenty of fast traffic (the served fixture's real
        # requests plus a synthetic steady stream)
        for _ in range(12):
            for _ in range(200):
                h.observe(0.05)
            eng.sample()
            clock.advance(1800)
        # the injection: 30 slow requests land in the last 5 minutes
        for _ in range(30):
            h.observe(30.0)
        for _ in range(5):
            h.observe(0.05)
        clock.advance(1)
        (s,) = eng.evaluate(force=True)["slos"]
        # 5m: 30/35 bad -> burn ~17 >= 14.4; 1h: 30/435 -> ~1.4 (calm)
        assert s["burn_rate"]["5m"] >= 14.4
        assert s["burn_rate"]["1h"] < 14.4
        assert s["burn_rate"]["6h"] < 6.0
        assert s["fast_burn"] is False  # both-windows rule: 1h is calm
        assert s["slow_burn"] is False
        # keep burning for an hour -> the 1h window joins and the PAGE fires
        for _ in range(2):
            for _ in range(300):
                h.observe(30.0)
            eng.sample()
            clock.advance(1800)
        for _ in range(50):
            h.observe(30.0)
        clock.advance(1)
        (s,) = eng.evaluate(force=True)["slos"]
        assert s["burn_rate"]["5m"] >= 14.4 and s["burn_rate"]["1h"] >= 14.4
        assert s["fast_burn"] is True
        assert s["burn_rate"]["6h"] < 6.0  # slow window still calm
        assert s["slow_burn"] is False


# ---------------------------------------------------------------------------
# per-device telemetry units
# ---------------------------------------------------------------------------


class TestDeviceTelemetry:
    def test_cpu_devices_report_zero_gracefully(self):
        from rag_llm_k8s_tpu.obs import devices as obs_devices

        reg = obs_metrics.MetricsRegistry()
        n = obs_devices.register_device_gauges(reg, lambda: {0: 4096})
        assert n >= 1  # the CPU test platform still enumerates devices
        text = reg.render_prometheus()
        assert re.search(r'rag_device_hbm_bytes_in_use\{device="0"\} 0\.0', text)
        assert re.search(r'rag_device_hbm_bytes_limit\{device="0"\} 0\.0', text)
        # the prefix-cache attribution flows through per device
        assert re.search(
            r'rag_prefix_cache_device_bytes\{device="0"\} 4096\.0', text
        )

    def test_prefix_cache_bytes_by_device_empty(self):
        from rag_llm_k8s_tpu.core.config import PrefixCacheConfig
        from rag_llm_k8s_tpu.engine.prefix_cache import PrefixCache

        cache = PrefixCache(PrefixCacheConfig(enabled=True), engine=None)
        assert cache.bytes_by_device() == {}

"""Observability subsystem tests (ISSUE 2): metrics registry primitives,
strict Prometheus exposition checking, span-tree tracing through a real
``/generate``, and JSON-snapshot ↔ exposition equivalence."""

import collections
import re
import time

import jax
import jax.numpy as jnp
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EncoderConfig,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.batching import BatchScheduler
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.server.app import RagService, create_app

FP32 = DTypePolicy.fp32()


class ByteTokenizer:
    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode("utf-8", "replace")


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


class TestPrimitives:
    def test_counter_monotonic(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("rag_test_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_callback_counter_rejects_inc(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("rag_cb_total", fn=lambda: 7)
        assert c.value == 7.0
        with pytest.raises(RuntimeError):
            c.inc()

    def test_gauge_and_broken_probe(self):
        reg = obs_metrics.MetricsRegistry()
        g = reg.gauge("rag_level")
        g.set(4)
        g.dec()
        assert g.value == 3.0
        boom = reg.gauge("rag_boom", fn=lambda: 1 / 0)
        assert boom.value == 0.0  # a broken probe must not 500 /metrics

    def test_kind_conflict_rejected(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("rag_x_total")
        with pytest.raises(ValueError):
            reg.gauge("rag_x_total")

    def test_log_buckets_strictly_increasing(self):
        for b in (obs_metrics.LATENCY_BUCKETS, obs_metrics.REQUEST_BUCKETS,
                  obs_metrics.TOKEN_LATENCY_BUCKETS,
                  obs_metrics.log_buckets(0.001, 10, 1.07)):
            assert all(b2 > b1 for b1, b2 in zip(b, b[1:]))

    def test_histogram_buckets_and_quantile(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("rag_h_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        counts, hsum, count = h.snapshot()
        assert counts == (1, 2, 1, 0) and count == 4
        assert hsum == pytest.approx(6.05)
        # p50 lands in the (0.1, 1.0] bucket, p99 in (1.0, 10.0]
        assert 0.1 <= h.quantile(0.5) <= 1.0
        assert 1.0 <= h.quantile(0.99) <= 10.0
        assert reg.histogram("rag_empty_seconds").quantile(0.5) is None

    def test_histogram_snapshot_diff_quantile(self):
        """Windowing by subtraction (obs/slo.py): quantile over a snapshot diff."""
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("rag_win_seconds", buckets=(1.0, 2.0, 4.0))
        h.observe(0.5)
        before = h.snapshot()
        h.observe(3.0)
        h.observe(3.0)
        after = h.snapshot()
        diff = (
            tuple(a - b for a, b in zip(after[0], before[0])),
            after[1] - before[1],
            after[2] - before[2],
        )
        q = h.quantile(0.5, diff)
        assert 2.0 <= q <= 4.0  # the early 0.5 observation is excluded

    def test_labels_are_distinct_series(self):
        reg = obs_metrics.MetricsRegistry()
        fam = reg.labeled_histogram("rag_lab_seconds", buckets=(1.0,))
        fam.labels(stage="a").observe(0.5)
        fam.labels(stage="b").observe(0.5)
        fam.labels(stage="a").observe(0.5)
        assert fam.labels(stage="a").count == 2
        assert fam.labels(stage="b").count == 1

    def test_label_value_escaping_keeps_one_line(self):
        """Newline/quote/backslash in a label value must become two-char
        escapes — a raw newline would split the sample line and make a
        scraper reject the whole exposition."""
        reg = obs_metrics.MetricsRegistry()
        reg.labeled_counter("rag_esc_total").labels(k='a"b\\c\nd').inc()
        text = reg.render_prometheus()
        (line,) = [l for l in text.splitlines() if l.startswith("rag_esc_total{")]
        assert line == 'rag_esc_total{k="a\\"b\\\\c\\nd"} 1.0'


class TestTracingUnit:
    def test_span_nesting_and_finish(self):
        tr = tracing.start_trace("t1")
        with tracing.span("outer"):
            with tracing.span("inner"):
                time.sleep(0.002)
        buf = tracing.TraceBuffer(capacity=2)
        tree = tracing.finish_trace(tr, buf)
        assert tracing.current_trace() is None
        assert tree["trace_id"] == "t1"
        (outer,) = tree["spans"]
        assert outer["name"] == "outer"
        (inner,) = outer["spans"]
        assert inner["name"] == "inner"
        assert inner["duration_ms"] <= outer["duration_ms"]
        assert len(buf) == 1

    def test_ring_buffer_capacity(self):
        buf = tracing.TraceBuffer(capacity=3)
        for i in range(5):
            buf.add({"trace_id": str(i)})
        ids = [t["trace_id"] for t in buf.list()]
        assert ids == ["2", "3", "4"]
        assert [t["trace_id"] for t in buf.list(limit=1)] == ["4"]
        # non-positive limits mean "no trim", never "drop the oldest"
        assert len(buf.list(limit=0)) == 3
        assert len(buf.list(limit=-1)) == 3

    def test_span_without_trace_is_noop(self):
        with tracing.span("orphan") as sp:
            assert sp is None


# ---------------------------------------------------------------------------
# builds: one helper, one census (obs/tracing.build_span and its listener)
# ---------------------------------------------------------------------------


def _census_delta(before):
    """What the process-wide census gained since ``before``: (seconds, events)."""
    seconds, events = tracing.compile_census()
    return ({k: v - before[0].get(k, 0.0) for k, v in seconds.items() if v != before[0].get(k, 0.0)},
            {k: n - before[1].get(k, 0) for k, n in events.items() if n != before[1].get(k, 0)})


def _toy_program(scale):
    """``make`` of a build: a fresh function every call, so no build finds the
    last one's lowering in JAX's in-memory caches."""
    aval = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    return lambda: (jax.jit(lambda x: jnp.sin(x) @ x * scale), (aval,))


class _BackendCompiles:
    """A listener of the test's own: every ``backend_compile_duration`` event
    the process sees (what ``benchmark/lib/serve.py CompileCounter`` counts)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        self.n += event == "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def backend_compiles():
    return _BackendCompiles()


class TestBuildSpan:
    def test_one_span_whose_stages_sum_to_it_and_each_series_ticks_once(self):
        before = tracing.compile_census()
        tr = tracing.start_trace()
        fn = tracing.build_span("generate", (2, 32, 6, None), _toy_program(2.0),
                                rows=2, bucket=32, max_new=6)
        tracing.finish_trace(tr)
        seconds, events = _census_delta(before)
        assert fn(jnp.ones((16, 16))).shape == (16, 16)  # an executable came back
        (sp,) = [s for s in tr.spans if s.name.startswith("build")]
        assert sp.name == "build/generate"
        stages = [sp.attrs[f"{k}_s"] for k in tracing.BUILD_STAGES]
        assert all(v >= 0 for v in stages) and sp.attrs["compile_s"] > 0
        assert sum(stages) == pytest.approx(sp.duration_ms() / 1e3, abs=1e-3)
        assert (sp.attrs["rows"], sp.attrs["bucket"], sp.attrs["max_new"]) == (2.0, 32.0, 6.0)
        assert sp.attrs["cache_hit"] in (1.0, 0.0, -1.0)
        assert set(seconds) == {("generate", k) for k in tracing.BUILD_STAGES}
        for k in tracing.BUILD_STAGES:
            assert seconds[("generate", k)] == pytest.approx(sp.attrs[f"{k}_s"], abs=1e-9)
        assert sum(events.values()) == 1 and {p for p, _ in events} == {"generate"}

    def test_persistent_cache_reads_miss_then_hit(self, tmp_path):
        from jax._src import compilation_cache

        was = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
        try:
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            compilation_cache.reset_cache()
            outcomes = []
            for _ in range(2):  # the same program twice, a fresh function each
                before = tracing.compile_census()
                tr = tracing.start_trace()
                tracing.build_span("score_exact", (1, 16, 0), _toy_program(3.0))
                tracing.finish_trace(tr)
                _, events = _census_delta(before)
                ((_, cache),) = events
                outcomes.append((cache, tr.spans[0].attrs["cache_hit"]))
            assert outcomes == [("miss", 0.0), ("hit", 1.0)]
        finally:
            for k, v in was.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()

    def test_unknown_program_raises(self):
        for name in ("generate_fast", "undeclared", ""):
            with pytest.raises(ValueError, match="vocabulary"):
                tracing.build_span(name, (1,), _toy_program(1.0))

    def test_lazy_jit_first_call_lands_under_undeclared(self, backend_compiles):
        before, n0 = tracing.compile_census(), backend_compiles.n
        jax.jit(lambda x: jnp.cos(x) * 5.0 + 7.0)(jnp.ones((3, 5))).block_until_ready()
        seconds, events = _census_delta(before)
        assert {p for p, _ in events} == {"undeclared"}
        assert sum(events.values()) == backend_compiles.n - n0 >= 1
        assert seconds[("undeclared", "compile")] > 0 and seconds[("undeclared", "lower")] > 0


@pytest.fixture(scope="module")
def warmed(backend_compiles):
    """A tiny fused-RAG service taken to ready by ``warmup()`` itself, with
    what the census and the test's own listener gained across it."""
    llama_cfg = LlamaConfig.tiny(vocab_size=300)
    enc_cfg = EncoderConfig.tiny(vocab_size=300)
    cfg = AppConfig(model=llama_cfg, encoder=enc_cfg, system_message="SYS")
    engine = InferenceEngine(
        llama_cfg,
        init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
        sampling=SamplingConfig(do_sample=False, max_new_tokens=4),
        engine_config=EngineConfig(prompt_buckets=(128, 256), max_batch_size=4,
                                   rag_fused=True),
        dtypes=FP32,
    )
    encoder = EncoderRunner(
        enc_cfg, init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
        dtypes=FP32, length_buckets=(32,), max_batch=4,
    )
    store = VectorStore(dim=enc_cfg.hidden_size)
    svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store,
                     scheduler=BatchScheduler(engine, max_wait_ms=25.0))
    texts = ["alpha beta gamma", "delta epsilon", "zeta eta theta"]
    vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
    store.add(list(vecs), [{"filename": "f", "chunk_id": i, "text": t}
                           for i, t in enumerate(texts)])
    before, n0 = tracing.compile_census(), backend_compiles.n
    svc.warmup()
    gained = _census_delta(before)
    yield svc, gained, backend_compiles.n - n0
    svc.shutdown()


def _spans(tree):
    """Every span of a served tree, depth first."""
    for sp in tree.get("spans", []):
        yield sp
        yield from _spans(sp)


class TestBootTree:
    def test_census_counts_every_executable_the_backend_built(self, warmed):
        _, (_, events), backend_built = warmed
        assert sum(events.values()) == backend_built > 0

    def test_every_engine_key_has_one_build_span_under_a_stage(self, warmed, monkeypatch):
        svc, (_, events), _ = warmed
        assert svc.ready
        monkeypatch.setenv("TPU_RAG_FAULTS", "1")
        boot = create_app(svc).test_client().get("/debug/traces").get_json()["boot"]
        assert boot["attrs"]["kind"] == "boot"
        assert boot["attrs"]["process_started_at"] < boot["started_at"] < boot["attrs"]["ready_at"]
        stages = [s["name"] for s in boot["spans"]]
        assert stages == ["warm_generate", "warm_ladder", "warm_score", "warm_retrieve", "warm_rag"]
        builds = [s for s in _spans(boot) if s["name"].startswith("build/")]
        engine_programs = {"generate", "generate_spec", "generate_rag", "score_exact"}
        by_key = collections.Counter(
            (int(s["attrs"]["rows"]), int(s["attrs"]["bucket"]), int(s["attrs"]["max_new"]))
            for s in builds if s["name"].split("/")[1] in engine_programs)
        assert by_key == collections.Counter(k[:3] for k in svc.engine._compiled)
        # and the declared programs' census is the tree's build spans
        declared = collections.Counter()
        for (program, _), n in events.items():
            if program != "undeclared":
                declared[program] += n
        assert declared == collections.Counter(s["name"].split("/")[1] for s in builds)
        assert declared["retrieve"] == 2 and declared["encode"] >= 1

    def test_ready_seconds_and_labeled_families_in_the_scrape(self, warmed):
        svc, _, _ = warmed
        text = create_app(svc).test_client().get("/metrics").get_data(as_text=True)
        samples = _parse_samples(text)
        assert samples[("rag_ready_seconds", "")] > 0
        assert samples[("rag_ready_seconds", "")] == pytest.approx(
            svc.boot_trace["attrs"]["ready_at"] - svc.boot_trace["attrs"]["process_started_at"])
        labels = {lab for (n, lab) in samples if n == "rag_compile_seconds_total"}
        assert '{program="generate",stage="trace"}' in labels
        assert all("program=" in lab and "stage=" in lab for lab in labels)
        events = {lab for (n, lab) in samples if n == "rag_compile_events_total"}
        assert all("program=" in lab and "cache=" in lab for lab in events)


# ---------------------------------------------------------------------------
# HTTP-level: exposition, traces, healthz (one tiny service for the module)
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
    # one answered query so every request-path metric has data
    r = client.post("/query", json={"prompt": "what?"})
    assert r.status_code == 200, r.get_json()
    return svc, client


# strict exposition grammar (text format 0.0.4, the subset we emit)
_HELP_RE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* \S.*$")
_TYPE_RE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*")*\})?'
    r" (-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|NaN)$"
)


def _parse_samples(text):
    """{(name, labelstr): float} for every sample line, strict-checked."""
    samples = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP"):
            assert _HELP_RE.match(line), line
            continue
        if line.startswith("# TYPE"):
            assert _TYPE_RE.match(line), line
            continue
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
        head, val = line.rsplit(" ", 1)
        name, brace, labels = head.partition("{")
        samples[(name, brace + labels)] = (
            float(val) if val != "+Inf" else float("inf")
        )
    return samples


class TestExposition:
    def test_strict_line_format_and_required_families(self, served):
        _, client = served
        r = client.get("/metrics")
        assert r.status_code == 200
        assert r.content_type.startswith("text/plain")
        text = r.get_data(as_text=True)
        samples = _parse_samples(text)
        names = {n for n, _ in samples}
        # the acceptance-criteria families
        assert "rag_request_duration_seconds_bucket" in names
        assert "rag_request_duration_seconds_count" in names
        assert "rag_decode_inter_token_seconds_bucket" in names
        assert "rag_batch_occupancy" in names
        assert "rag_compile_seconds_total" in names
        # engine + legacy families still scrape from the SAME endpoint
        assert "tpu_rag_engine_generate_calls" in names
        assert "tpu_rag_index_vectors" in names
        assert "rag_coalesce_wait_seconds_bucket" in names
        assert "rag_time_to_first_token_seconds_count" in names
        assert "rag_stage_duration_seconds_bucket" in names
        # the query actually landed in the request histogram and compile
        # time was attributed
        assert samples[("rag_request_duration_seconds_count", "")] >= 1
        assert sum(v for (n, _), v in samples.items()
                   if n == "rag_compile_seconds_total") > 0
        # every serving stage observed — including assemble/detokenize,
        # which have no timings key and observe at their span sites
        for stage in ("retrieve", "assemble", "generate", "detokenize"):
            key = ("rag_stage_duration_seconds_count", f'{{stage="{stage}"}}')
            assert samples[key] >= 1, stage
        # stage counts track request counts one-for-one (a fallback path
        # must never double-count a stage for one request)
        n_req = samples[("rag_request_duration_seconds_count", "")]
        for stage in ("assemble", "detokenize"):
            key = ("rag_stage_duration_seconds_count", f'{{stage="{stage}"}}')
            assert samples[key] == n_req, stage

    def test_histogram_bucket_monotonicity(self, served):
        _, client = served
        text = client.get("/metrics").get_data(as_text=True)
        samples = _parse_samples(text)
        # group bucket series by (family, non-le labels)
        series = {}
        for (name, labels), val in samples.items():
            if not name.endswith("_bucket"):
                continue
            base = name[: -len("_bucket")]
            inner = labels.strip("{}")
            parts = [p for p in inner.split(",") if p and not p.startswith("le=")]
            le = next(p for p in inner.split(",") if p.startswith("le="))
            le_val = le[4:-1]
            le_f = float("inf") if le_val == "+Inf" else float(le_val)
            series.setdefault((base, tuple(parts)), []).append((le_f, val))
        assert series, "no histogram series found"
        for (base, labels), pts in series.items():
            pts.sort()
            values = [v for _, v in pts]
            assert values == sorted(values), f"{base}{labels} not cumulative"
            assert pts[-1][0] == float("inf")
            # +Inf bucket equals the series count
            count_key = (f"{base}_count", "{" + ",".join(labels) + "}" if labels else "")
            assert pts[-1][1] == samples[count_key], base

    def test_json_snapshot_equivalent_to_exposition(self, served):
        svc, client = served
        body = client.get("/metrics", headers={"Accept": "application/json"}).get_json()
        text = client.get("/metrics").get_data(as_text=True)
        samples = _parse_samples(text)
        # every scalar in the JSON view equals the exposition's value for
        # the same (canonicalized) name, label children summed
        by_name = {}
        for (name, _), val in samples.items():
            if not name.endswith("_bucket"):
                by_name[name] = by_name.get(name, 0.0) + val
        skipped = 0
        for key, val in body.items():
            canon = key if key.startswith("rag_") else f"tpu_rag_{key}"
            if canon not in by_name:
                skipped += 1
                continue
            # callback metrics can tick between the two scrapes (uptime-ish
            # values); everything is monotonic or level, so equality holds
            # for all but actively-changing gauges — require near-equality
            assert by_name[canon] == pytest.approx(val, rel=1e-6, abs=1e-6), key
        assert skipped == 0, "JSON snapshot carries names the exposition lacks"
        # and the legacy JSON keys the seed's consumers read are intact
        assert body["index_vectors"] >= 1
        assert body["engine_generate_calls"] >= 1
        assert "query_seconds_sum" in body

    def test_legacy_prometheus_names_preserved(self, served):
        _, client = served
        text = client.get("/metrics").get_data(as_text=True)
        samples = _parse_samples(text)
        assert samples[("tpu_rag_index_vectors", "")] >= 1
        assert samples[("tpu_rag_engine_generate_calls", "")] >= 1


    def test_attend_kernel_builds_follow_the_tally(self, served):
        """``rag_attend_kernel_builds_total{mode, kernel}``: the fixture's
        programs were traced with the XLA attention (CPU); a kernel traced
        later (here: counted by hand, as ``LlamaModel._attend`` does where it
        chooses) shows in the next scrape, in both renderings."""
        _, client = served
        fam = "rag_attend_kernel_builds_total"
        samples = _parse_samples(client.get("/metrics").get_data(as_text=True))
        assert samples[(fam, '{kernel="xla",mode="prefill"}')] >= 1
        key = (fam, '{kernel="chunk_attention_grouped_q8",mode="chunk"}')
        before = samples.get(key, 0)
        tracing.count_kernel_build("chunk", "chunk_attention_grouped_q8")
        samples = _parse_samples(client.get("/metrics").get_data(as_text=True))
        assert samples[key] == before + 1
        body = client.get("/metrics", headers={"Accept": "application/json"}).get_json()
        assert body[fam] == sum(v for (n, _), v in samples.items() if n == fam)


class TestTracedGenerate:
    def test_span_tree_matches_timings(self, served):
        _, client = served
        r = client.post("/generate", json={"prompt": "what do kernels do?",
                                           "trace": True})
        assert r.status_code == 200, r.get_json()
        body = r.get_json()
        # trace is additive: the timings contract is untouched (chip_ms /
        # goodput_frac are the goodput ledger's per-request attribution —
        # ISSUE 14; cost_usd joins them only when a chip-hour price is set;
        # the last six link the request to the dispatch it rode — ISSUE 51)
        assert set(body["timings"]) == {
            "tokenize_ms", "embed_retrieve_ms", "generate_ms", "total_ms",
            "chip_ms", "goodput_frac",
            "dispatch_seq", "dispatch_rows", "queue_wait_ms",
            "launch_ms", "device_ms", "deliver_ms",
        }
        tree = body["trace"]
        names = [s["name"] for s in tree["spans"]]
        assert names == ["retrieve", "assemble", "generate", "detokenize"]
        # ordering: spans start in pipeline order and do not regress
        starts = [s["start_ms"] for s in tree["spans"]]
        assert starts == sorted(starts)
        # nesting: the retrieve stage carries its synthesized interior
        retrieve = tree["spans"][0]
        inner = [s["name"] for s in retrieve.get("spans", [])]
        assert inner == ["tokenize", "embed_knn"]
        for child in retrieve["spans"]:
            assert child["start_ms"] >= retrieve["start_ms"] - 5.0
            assert (child["start_ms"] + child["duration_ms"]
                    <= retrieve["start_ms"] + retrieve["duration_ms"] + 5.0)
        # the acceptance contract: stage durations sum to ~total_ms
        stage_sum = sum(s["duration_ms"] for s in tree["spans"])
        assert stage_sum == pytest.approx(body["timings"]["total_ms"], rel=0.05)

    def test_untraced_response_has_no_trace_key(self, served):
        _, client = served
        body = client.post("/query", json={"prompt": "again"}).get_json()
        assert "trace" not in body

    def test_debug_traces_ring(self, served, monkeypatch):
        # /debug/traces follows the uniform 403-unless-armed contract
        # since the flight-recorder round (tests/test_flight.py pins the
        # contract across every /debug route; arming here exercises the
        # served payload)
        svc, client = served
        monkeypatch.delenv("TPU_RAG_FAULTS", raising=False)
        assert client.get("/debug/traces").status_code == 403
        monkeypatch.setenv("TPU_RAG_FAULTS", "1")
        n_before = len(svc.traces)
        client.post("/query", json={"prompt": "ring me"})
        r = client.get("/debug/traces")
        assert r.status_code == 200
        traces = r.get_json()["traces"]
        assert len(traces) == n_before + 1
        last = traces[-1]
        assert last["attrs"]["prompt"].startswith("ring me")
        assert {s["name"] for s in last["spans"]} >= {"retrieve", "generate"}
        limited = client.get("/debug/traces?limit=1").get_json()["traces"]
        assert len(limited) == 1


class TestHealthz:
    def test_fleet_segmentation_fields(self, served):
        _, client = served
        body = client.get("/healthz").get_json()
        assert body["status"] == "ok"
        assert body["uptime_s"] >= 0
        assert body["version"]
        assert body["engine_mode"] == "one-shot"
        assert body["device_platform"] == "cpu"
        assert body["device_count"] >= 1


class TestProfileRoute:
    def test_seconds_validation(self, served):
        _, client = served
        r = client.post("/profile", json={"seconds": -1})
        assert r.status_code == 400
        r = client.post("/profile", json={"seconds": 1e9})
        assert r.status_code == 400


class TestCoalesceWaitHistogram:
    def test_coalescer_observes_item_wait(self):
        from rag_llm_k8s_tpu.engine.batching import Coalescer

        reg = obs_metrics.MetricsRegistry()
        hist = reg.histogram("rag_coalesce_wait_seconds")
        co = Coalescer(lambda xs: [x * 2 for x in xs], max_batch=4, max_wait_ms=1.0)
        co.wait_histogram = hist
        try:
            assert co.submit(21) == 42
            assert hist.count >= 1
            assert hist.sum >= 0.0
        finally:
            co.shutdown()


class TestOneShotEngineInstrumentation:
    def test_generate_feeds_histograms(self, served):
        svc, _ = served
        reg = svc.metrics
        stages = reg.labeled_histogram("rag_generate_dispatch_stage_seconds")
        device = [child for labels, child in stages.items() if dict(labels)["stage"] == "device"]
        # the fixture's query went through generate: its call's duration is
        # the dispatch's ``device`` stage, by the path that launched it
        assert sum(child.count for child in device) >= 1
        itl = reg.labeled_histogram("rag_decode_inter_token_seconds")
        assert itl.labels(mode="oneshot_est").count >= 1
        svc._sync_kernel_builds()  # what a scrape does: the census' children
        events = reg.labeled_counter("rag_compile_events_total")
        assert sum(child.value for _, child in events.items()) >= 1

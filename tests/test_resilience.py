"""Resilience layer (ISSUE 4): admission control + load shedding, end-to-end
deadlines with mid-decode slot eviction, EngineStateLost recovery behind a
circuit breaker, and the fault-injection harness that makes all of it
provable on CPU. ``make chaos`` runs this file with ``TPU_RAG_FAULTS``
armed; it also runs inside the ordinary tier-1 gate (arming there is
programmatic, so no env is needed)."""

import threading
import time

import jax
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EncoderConfig,
    EngineConfig,
    KVTieringConfig,
    LlamaConfig,
    LookaheadConfig,
    PrefixCacheConfig,
    ResilienceConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.resilience.admission import AdmissionController, AdmissionRejected
from rag_llm_k8s_tpu.resilience.breaker import CircuitBreaker
from rag_llm_k8s_tpu.resilience.deadline import Deadline, DeadlineExceeded
from rag_llm_k8s_tpu.server.app import RagService, create_app

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)
ENG_CFG = EngineConfig(prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny()
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    oracle = InferenceEngine(
        cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
    )
    return cfg, params, oracle


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# fault harness
# ---------------------------------------------------------------------------
class TestFaults:
    def test_count_based_arming_fires_exactly_n_times(self):
        faults.arm("embed", times=2)
        for _ in range(2):
            with pytest.raises(faults.InjectedFault) as ei:
                faults.maybe_fail("embed")
            assert ei.value.site == "embed"
        faults.maybe_fail("embed")  # disarmed: no-op
        assert faults.armed() == {}

    def test_unknown_site_is_loud(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            # the bad name is the point here  # ragcheck: disable=FAULT-SITE-REGISTRY
            faults.arm("definitely_not_a_site")
        with pytest.raises(ValueError, match="expected >= 1"):
            faults.arm("embed", times=0)

    def test_arm_from_env(self):
        armed = faults.arm_from_env({"TPU_RAG_FAULTS": "decode_step:2, embed"})
        assert armed == {"decode_step": 2, "embed": 1}
        faults.clear()
        # enable-only forms arm nothing
        assert faults.arm_from_env({"TPU_RAG_FAULTS": "1"}) == {}
        assert faults.arm_from_env({}) == {}
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.arm_from_env({"TPU_RAG_FAULTS": "tpyo:1"})

    def test_endpoint_enabled_tracks_env_presence(self):
        assert faults.endpoint_enabled({"TPU_RAG_FAULTS": ""})
        assert not faults.endpoint_enabled({})


# ---------------------------------------------------------------------------
# deadline
# ---------------------------------------------------------------------------
class TestDeadline:
    def test_expiry_and_check(self):
        clk = FakeClock()
        dl = Deadline(100.0, clock=clk)
        assert not dl.expired()
        assert dl.remaining() == pytest.approx(0.1)
        dl.check("retrieve")  # fine
        clk.advance(0.2)
        assert dl.expired()
        with pytest.raises(DeadlineExceeded) as ei:
            dl.check("assemble")
        assert ei.value.stage == "assemble"
        assert dl.wait_timeout() > 0  # floored, never a negative wait

    def test_invalid_budget(self):
        with pytest.raises(ValueError):
            Deadline(0)


# ---------------------------------------------------------------------------
# breaker
# ---------------------------------------------------------------------------
class TestBreaker:
    def test_opens_at_threshold_and_self_heals(self):
        clk = FakeClock()
        b = CircuitBreaker(threshold=3, window_s=100.0, clock=clk)
        b.record_reset()  # t=0
        clk.advance(10.0)
        b.record_reset()  # t=10
        assert not b.open
        assert b.retry_after_s() == 0.0
        clk.advance(10.0)
        b.record_reset()  # t=20: third inside the window -> open
        assert b.open
        assert b.recent_resets() == 3
        # Retry-After counts down to the FIRST reset aging out (t=100)
        assert b.retry_after_s() == pytest.approx(80.0)
        clk.advance(60.0)
        assert b.retry_after_s() == pytest.approx(20.0)
        clk.advance(21.0)  # t=101: the t=0 reset left the window
        assert not b.open
        assert b.recent_resets() == 2

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker(window_s=0)


# ---------------------------------------------------------------------------
# admission gate
# ---------------------------------------------------------------------------
class TestAdmission:
    def test_queue_cap_rejection_under_concurrent_submits(self):
        gate = AdmissionController(max_concurrency=2, max_queue=3)
        reg = obs_metrics.MetricsRegistry()
        gate.reject_counter = reg.labeled_counter("rag_admission_rejected_total")
        hold = threading.Event()
        outcomes = []
        lock = threading.Lock()

        def run():
            try:
                with gate.admit():
                    hold.wait(timeout=30)
                with lock:
                    outcomes.append("served")
            except AdmissionRejected as e:
                with lock:
                    outcomes.append(e.reason)

        threads = [threading.Thread(target=run) for _ in range(10)]
        for t in threads:
            t.start()
        # settle: 2 active + 3 waiting; the other 5 shed immediately
        for _ in range(200):
            with lock:
                shed = len([o for o in outcomes if o == "queue_full"])
            if gate.active == 2 and gate.waiting == 3 and shed == 5:
                break
            time.sleep(0.01)
        assert gate.active == 2 and gate.queue_depth() == 3
        hold.set()
        for t in threads:
            t.join(timeout=30)
        with lock:
            assert sorted(outcomes) == ["queue_full"] * 5 + ["served"] * 5
        child = gate.reject_counter.labels(reason="queue_full",
                                           tenant="__other__")
        assert child.value == 5
        assert gate.active == 0 and gate.waiting == 0

    def test_fair_share_displaces_the_hog_tenants_newest_waiter(self):
        """One tenant holding every slot AND every queue position cannot
        lock a second tenant out: the under-share arrival displaces the
        hog's newest waiter (shed reason="fair_share"), keeping shed
        attribution on the tenant that caused the pressure."""
        gate = AdmissionController(max_concurrency=2, max_queue=2)
        reg = obs_metrics.MetricsRegistry()
        gate.reject_counter = reg.labeled_counter(
            "rag_admission_rejected_total"
        )
        hold = threading.Event()
        outcomes = []
        lock = threading.Lock()

        def run(tenant):
            try:
                with gate.admit(tenant=tenant):
                    hold.wait(timeout=30)
                with lock:
                    outcomes.append((tenant, "served"))
            except AdmissionRejected as e:
                with lock:
                    outcomes.append((tenant, e.reason))

        hogs = [threading.Thread(target=run, args=("hog",)) for _ in range(4)]
        for t in hogs:
            t.start()
        for _ in range(300):  # settle: 2 hog active + 2 hog queued
            if gate.active == 2 and gate.waiting == 2:
                break
            time.sleep(0.01)
        assert gate.active == 2 and gate.waiting == 2
        small = threading.Thread(target=run, args=("small",))
        small.start()
        for _ in range(300):  # the displaced hog waiter sheds
            with lock:
                shed = [o for o in outcomes if o == ("hog", "fair_share")]
            if shed:
                break
            time.sleep(0.01)
        with lock:
            assert ("hog", "fair_share") in outcomes
        hold.set()
        for t in hogs + [small]:
            t.join(timeout=30)
        with lock:
            assert ("small", "served") in outcomes
            assert outcomes.count(("hog", "fair_share")) == 1
            assert outcomes.count(("hog", "served")) == 3
        child = gate.reject_counter.labels(reason="fair_share", tenant="hog")
        assert child.value == 1
        assert gate.active == 0 and gate.waiting == 0

    def test_over_share_arrival_cannot_displace(self):
        """The displacing tenant must itself be within fair share: a
        FIFTH request from the hog (share = 4/1 = 4, its own count 5)
        sheds plain queue_full — fair-share never helps a hog cut its
        own line."""
        gate = AdmissionController(max_concurrency=2, max_queue=2)
        hold = threading.Event()
        errs = []
        lock = threading.Lock()

        def run():
            try:
                with gate.admit(tenant="hog"):
                    hold.wait(timeout=30)
            except AdmissionRejected as e:
                with lock:
                    errs.append(e.reason)

        hogs = [threading.Thread(target=run) for _ in range(4)]
        for t in hogs:
            t.start()
        for _ in range(300):
            if gate.active == 2 and gate.waiting == 2:
                break
            time.sleep(0.01)
        with pytest.raises(AdmissionRejected) as ei:
            with gate.admit(tenant="hog"):
                pass
        assert ei.value.reason == "queue_full"
        hold.set()
        for t in hogs:
            t.join(timeout=30)
        assert errs == []

    def test_rejection_contract(self):
        gate = AdmissionController(max_concurrency=1, max_queue=0,
                                   retry_after_s=2.5)
        with gate.admit():
            with pytest.raises(AdmissionRejected) as ei:
                with gate.admit():
                    pass
        assert ei.value.status == 429
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after_s == 2.5
        # slot released: admissible again
        with gate.admit():
            pass

    def test_breaker_open_sheds_everything_with_503(self):
        clk = FakeClock()
        b = CircuitBreaker(threshold=1, window_s=50.0, clock=clk)
        gate = AdmissionController(max_concurrency=8, max_queue=8, breaker=b)
        b.record_reset()
        with pytest.raises(AdmissionRejected) as ei:
            with gate.admit():
                pass
        assert ei.value.status == 503
        assert ei.value.reason == "breaker_open"
        assert ei.value.retry_after_s >= 1.0
        clk.advance(51.0)  # breaker heals -> gate admits again
        with gate.admit():
            pass

    def test_deadline_expiry_while_queued(self):
        gate = AdmissionController(max_concurrency=1, max_queue=4)
        clk = FakeClock()
        dl = Deadline(50.0, clock=clk)
        clk.advance(1.0)  # expired before it ever waits
        with gate.admit():
            with pytest.raises(DeadlineExceeded) as ei:
                with gate.admit(deadline=dl):
                    pass
        assert ei.value.stage == "queue"


# ---------------------------------------------------------------------------
# continuous engine: deadline eviction + reset recovery via fault injection
# ---------------------------------------------------------------------------
class TestDeadlineEviction:
    def test_expired_mid_decode_frees_slot_within_a_step(self, tiny):
        cfg, params, _ = tiny
        eng = ContinuousEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=2000),
            engine_config=EngineConfig(
                prompt_buckets=(16,), max_batch_size=4, max_seq_len=2048
            ),
            dtypes=FP32,
        )
        sched = ContinuousScheduler(eng)
        try:
            with pytest.raises(DeadlineExceeded) as ei:
                sched.submit([3, 17, 42], deadline=Deadline(300.0))
            assert ei.value.stage in ("decode", "generate")
            # the zombie's slot must free within one scheduler iteration —
            # poll briefly to absorb the step in flight
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if len(eng.free_slots()) == eng.B:
                    break
                time.sleep(0.02)
            assert len(eng.free_slots()) == eng.B, "evicted row still active"
            # and the scheduler still serves
            out = sched.submit([5, 5, 8], max_new_tokens=4, timeout=120)
            assert isinstance(out, list) and out
        finally:
            sched.shutdown()

    def test_expired_in_queue_is_never_admitted(self, tiny):
        cfg, params, _ = tiny
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng)
        try:
            clk = FakeClock()
            dl = Deadline(10.0, clock=clk)
            clk.advance(1.0)  # already expired on arrival
            before = eng.stats.generate_calls
            with pytest.raises(DeadlineExceeded) as ei:
                sched.submit([3, 17, 42], deadline=dl, timeout=30)
            assert ei.value.stage == "queue"
            assert eng.stats.generate_calls == before  # no prefill happened
        finally:
            sched.shutdown()


class TestResetRecovery:
    def test_insert_fault_recovers_via_resubmit(self, tiny):
        """An injected EngineStateLost at admission completes the request
        via resubmission — the caller never sees the fault."""
        cfg, params, oracle = tiny
        want = oracle.generate([[3, 17, 42, 7, 99]])[0]
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        reg = obs_metrics.MetricsRegistry()
        sched.bind_metrics(reg)
        try:
            faults.arm("insert", times=1)
            out = sched.submit([3, 17, 42, 7, 99], timeout=120)
            assert out == want
            assert faults.armed() == {}, "the fault never fired"
            assert reg.counter("rag_engine_resets_total").value == 1
            fam = reg.labeled_counter("rag_inflight_retries_total")
            assert fam.labels(outcome="resubmitted").value == 1
            assert fam.labels(outcome="succeeded").value == 1
            assert fam.labels(outcome="gave_up").value == 0
        finally:
            sched.shutdown()

    def test_decode_fault_recovers_and_preserves_greedy_stream(self, tiny):
        cfg, params, oracle = tiny
        want = oracle.generate([[3, 17, 42, 7, 99]])[0]
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        try:
            faults.arm("decode_step", times=1)
            out = sched.submit([3, 17, 42, 7, 99], timeout=120)
            assert out == want
        finally:
            sched.shutdown()

    def test_recovery_with_prompt_at_largest_bucket_stays_exact(self, tiny):
        """A prompt already filling the largest bucket cannot resume as
        prompt+emitted (admit_many would left-truncate the context) — the
        recovery restarts from scratch instead, which is still exact."""
        cfg, params, oracle = tiny
        prompt = [5] * 32  # fills the largest bucket: no room for emitted tokens
        want = oracle.generate([prompt])[0]
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        try:
            faults.arm("decode_step", times=1)
            out = sched.submit(prompt, timeout=120)
            assert out == want
        finally:
            sched.shutdown()

    def test_paged_reset_returns_all_blocks_to_the_pool(self, tiny):
        """ISSUE 5 chaos contract: an injected EngineStateLost on the PAGED
        engine recovers via resubmit (greedy stream intact) and hands every
        pool block back — a leak here compounds a reset at a time into
        permanent pool backpressure while /healthz stays green."""
        import dataclasses

        cfg, params, oracle = tiny
        want = oracle.generate([[3, 17, 42, 7, 99]])[0]
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                ENG_CFG, kv_paged=True, kv_block_size=16
            ),
            dtypes=FP32,
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        try:
            for site in ("insert", "decode_step"):
                faults.arm(site, times=1)
                out = sched.submit([3, 17, 42, 7, 99], timeout=120)
                assert out == want, site
                assert faults.armed() == {}, f"{site} fault never fired"
                assert eng.kv_pool.blocks_in_use() == 0, (
                    site, eng.kv_pool.stats(),
                )
        finally:
            sched.shutdown()

    def test_paged_tp2_reset_returns_all_blocks_to_the_pool(self, tiny):
        """ISSUE 6 chaos contract: the same zero-leak guarantee on the
        HEAD-SHARDED arena — an injected EngineStateLost at tp=2 recovers
        via resubmit with the greedy stream intact, and the (replicated,
        host-side) allocator hands every block back. The tp split must not
        open a leak path reset recovery misses."""
        import dataclasses

        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        cfg, params, oracle = tiny
        want = oracle.generate([[3, 17, 42, 7, 99]])[0]
        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        eng = ContinuousEngine(
            cfg, shard_llama_params(params, ctx), sampling=GREEDY,
            engine_config=dataclasses.replace(
                ENG_CFG, kv_paged=True, kv_block_size=16
            ),
            dtypes=FP32, mesh=ctx,
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        try:
            for site in ("insert", "decode_step"):
                faults.arm(site, times=1)
                out = sched.submit([3, 17, 42, 7, 99], timeout=120)
                assert out == want, site
                assert faults.armed() == {}, f"{site} fault never fired"
                assert eng.kv_pool.blocks_in_use() == 0, (
                    site, eng.kv_pool.stats(),
                )
        finally:
            sched.shutdown()

    def test_second_fault_gives_up_with_the_error(self, tiny):
        """retries=1 means exactly one recovery: a device that faults on
        the retry too fails the request (no infinite resubmit loop)."""
        cfg, params, _ = tiny
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        reg = obs_metrics.MetricsRegistry()
        sched.bind_metrics(reg)
        try:
            faults.arm("insert", times=2)
            with pytest.raises(Exception) as ei:
                sched.submit([3, 17, 42], timeout=120)
            assert "insert failed" in str(ei.value)
            fam = reg.labeled_counter("rag_inflight_retries_total")
            assert fam.labels(outcome="gave_up").value == 1
            # and the engine still serves afterwards
            out = sched.submit([5, 5, 8], timeout=120)
            assert isinstance(out, list) and out
        finally:
            sched.shutdown()

    def test_reset_storm_opens_breaker(self, tiny):
        cfg, params, _ = tiny
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        breaker = CircuitBreaker(threshold=2, window_s=600.0)
        sched.breaker = breaker
        try:
            for _ in range(2):
                faults.arm("decode_step", times=1)
                sched.submit([3, 17, 42], timeout=120)  # recovered each time
            assert breaker.open
        finally:
            sched.shutdown()


class TestSchedulerLifecycle:
    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_submit_after_worker_death_fails_fast(self, tiny):
        """Satellite: a dead worker must not let submit() enqueue into a
        queue nobody drains (the caller would block forever)."""
        cfg, params, _ = tiny
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng)
        try:
            # kill the worker with an error its loop does not guard
            eng.free_slots = None  # TypeError on next call
            try:
                sched.submit([3, 17, 42], timeout=30)
            except BaseException:  # noqa: BLE001 — delivery form is not the point
                pass
            sched._worker.join(timeout=30)
            assert not sched._worker.is_alive()
            # post-mortem submits fail fast instead of blocking forever
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="shut down"):
                sched.submit([5, 5], timeout=None)
            assert time.monotonic() - t0 < 5.0
        finally:
            sched.shutdown()


# ---------------------------------------------------------------------------
# HTTP integration: 429 shape, Retry-After, 504, breaker readiness, degraded
# ---------------------------------------------------------------------------
class ByteTokenizer:
    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode("utf-8", "replace")


def make_service(resilience=None, prompt_buckets=(128, 256), max_seq_len=4096 + 256,
                 lookahead=None):
    llama_cfg = LlamaConfig.tiny(vocab_size=300)
    enc_cfg = EncoderConfig.tiny(vocab_size=300)
    cfg = AppConfig(
        model=llama_cfg, encoder=enc_cfg,
        resilience=resilience or ResilienceConfig(),
        lookahead=lookahead or LookaheadConfig(),
    )
    engine = InferenceEngine(
        llama_cfg,
        init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
        sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
        engine_config=EngineConfig(
            prompt_buckets=prompt_buckets, max_batch_size=2,
            max_seq_len=max_seq_len,
        ),
        dtypes=FP32,
    )
    encoder = EncoderRunner(
        enc_cfg,
        init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
        dtypes=FP32, length_buckets=(32, 64), max_batch=4,
    )
    store = VectorStore(dim=enc_cfg.hidden_size)
    svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store)
    svc.ready = True
    texts = ["alpha beta gamma", "delta epsilon zeta"]
    vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
    store.add(list(vecs), [
        {"filename": "f", "chunk_id": i, "text": t} for i, t in enumerate(texts)
    ])
    return svc


@pytest.fixture(scope="module")
def http_service():
    return make_service()


class TestHttpShedding:
    def test_429_body_shape_and_retry_after_header(self, http_service):
        svc = http_service
        client = create_app(svc).test_client()
        gate = svc.admission
        old = (gate.max_concurrency, gate.max_queue)
        gate.max_concurrency, gate.max_queue = 1, 0
        try:
            with gate.admit():  # the one slot is taken; queue cap is 0
                r = client.post("/generate", json={"prompt": "alpha"})
            assert r.status_code == 429
            body = r.get_json()
            assert body["reason"] == "queue_full"
            assert body["error"] == "server overloaded"
            assert body["retry_after_s"] == pytest.approx(1.0)
            assert int(r.headers["Retry-After"]) >= 1
            # the shed is counted
            snap = svc.metrics.snapshot()
            assert snap["rag_admission_rejected_total"] >= 1
        finally:
            gate.max_concurrency, gate.max_queue = old

    def test_shed_requests_count_toward_availability_family(self, http_service):
        svc = http_service
        fam = svc.metrics.get_family("rag_http_requests_total")
        before = sum(
            c.value for labels, c in fam.items() if dict(labels).get("code") == "429"
        )
        client = create_app(svc).test_client()
        gate = svc.admission
        old = (gate.max_concurrency, gate.max_queue)
        gate.max_concurrency, gate.max_queue = 1, 0
        try:
            with gate.admit():
                client.post("/generate", json={"prompt": "alpha"})
        finally:
            gate.max_concurrency, gate.max_queue = old
        after = sum(
            c.value for labels, c in fam.items() if dict(labels).get("code") == "429"
        )
        assert after == before + 1

    def test_breaker_open_flips_healthz_readiness_and_sheds_503(self, http_service):
        svc = http_service
        client = create_app(svc).test_client()
        assert client.get("/healthz").status_code == 200
        for _ in range(svc.breaker.threshold):
            svc.breaker.record_reset()
        try:
            r = client.get("/healthz")
            assert r.status_code == 503
            body = r.get_json()
            assert body["breaker_open"] is True
            assert body["status"] == "draining"
            # liveness is NOT affected: draining, not restarting
            assert client.get("/healthz?live=1").status_code == 200
            # and /generate sheds with 503 + Retry-After
            r = client.post("/generate", json={"prompt": "alpha"})
            assert r.status_code == 503
            assert r.get_json()["reason"] == "breaker_open"
            assert "Retry-After" in r.headers
        finally:
            svc.breaker._events.clear()
        assert client.get("/healthz").status_code == 200

    def test_deadline_404_shapes(self, http_service):
        client = create_app(http_service).test_client()
        # malformed deadline -> 400, not silently defaulted
        r = client.post("/generate", json={"prompt": "a", "deadline_ms": "soon"})
        assert r.status_code == 400
        r = client.post("/generate", json={"prompt": "a", "deadline_ms": -5})
        assert r.status_code == 400
        # non-finite values must be 400, not an OverflowError-500 ("inf")
        # or a silent never-expiring request ("nan")
        for bad in ("inf", "nan", "-inf"):
            r = client.post("/generate", json={"prompt": "a", "deadline_ms": bad})
            assert r.status_code == 400, (bad, r.get_json())
        # a microscopic budget -> 504 naming the stage it died at
        r = client.post("/generate", json={"prompt": "alpha", "deadline_ms": 0.001})
        assert r.status_code == 504
        body = r.get_json()
        assert body["stage"] in ("queue", "retrieve", "assemble", "generate")
        snap = http_service.metrics.snapshot()
        assert snap["rag_deadline_exceeded_total"] >= 1

    def test_header_deadline_is_honored(self, http_service):
        client = create_app(http_service).test_client()
        r = client.post(
            "/generate", json={"prompt": "alpha"},
            headers={"x-request-deadline-ms": "0.001"},
        )
        assert r.status_code == 504

    def test_normal_request_unaffected_and_undegraded(self, http_service):
        client = create_app(http_service).test_client()
        r = client.post("/generate", json={"prompt": "alpha"})
        assert r.status_code == 200
        body = r.get_json()
        assert "generated_text" in body
        assert "degraded" not in body

    def test_debug_faults_endpoint_gated_on_env(self, http_service, monkeypatch):
        client = create_app(http_service).test_client()
        monkeypatch.delenv("TPU_RAG_FAULTS", raising=False)
        assert client.get("/debug/faults").status_code == 403
        monkeypatch.setenv("TPU_RAG_FAULTS", "1")
        r = client.get("/debug/faults")
        assert r.status_code == 200
        assert r.get_json()["armed"] == {}
        r = client.post("/debug/faults", json={"site": "embed", "times": 3})
        assert r.status_code == 200
        assert r.get_json()["armed"] == {"embed": 3}
        assert client.post(
            "/debug/faults", json={"site": "nope"}
        ).status_code == 400
        r = client.post("/debug/faults", json={"clear": True})
        assert r.get_json()["armed"] == {}

    def test_store_fault_surfaces_as_500_not_hang(self, http_service):
        client = create_app(http_service).test_client()
        faults.arm("store_lookup", times=1)
        r = client.post("/generate", json={"prompt": "alpha"})
        assert r.status_code == 500
        assert "injected fault" in r.get_json()["error"]
        # disarmed: next request serves
        assert client.post(
            "/generate", json={"prompt": "alpha"}
        ).status_code == 200


class TestDegradedMarking:
    def test_prefix_cache_failure_marks_response_degraded(self):
        # bucket must fit the byte-tokenized system head + tail with >= 16
        # tokens of context room, or the prefixed path never engages
        svc = make_service(prompt_buckets=(128, 1024), max_seq_len=1024 + 128)

        class BrokenCache:
            def prefix_for(self, segments):
                raise RuntimeError("cache exploded")

        svc.engine.prefix_cache = BrokenCache()
        try:
            client = create_app(svc).test_client()
            r = client.post("/generate", json={"prompt": "alpha"})
            assert r.status_code == 200, r.get_json()
            body = r.get_json()
            assert body.get("degraded") is True
            assert body["degraded_reasons"] == ["prefix_cache"]
            snap = svc.metrics.snapshot()
            assert snap["rag_degraded_responses_total"] == 1
        finally:
            svc.engine.prefix_cache = None


# ---------------------------------------------------------------------------
# lookahead chaos (ISSUE 7): the lookahead_retrieve fault site + stale-
# prefetch cancellation, under the same armed-harness lane as the rest of
# this file (tests/test_lookahead.py carries the full pipeline matrix)
# ---------------------------------------------------------------------------
class TestLookaheadChaos:
    def test_lookahead_fault_falls_back_and_serves(self):
        """Armed ``lookahead_retrieve``: the speculation's worker faults,
        the serving tail's join surfaces it, the request falls back to the
        INLINE retrieve path and serves the identical greedy answer — a
        failed speculation must never fail (or change) a request."""
        svc = make_service(lookahead=LookaheadConfig(enabled=True))
        try:
            client = create_app(svc).test_client()
            clean = client.post("/query", json={"prompt": "alpha"}).get_json()
            faults.arm("lookahead_retrieve", times=1)
            faulted = client.post("/query", json={"prompt": "alpha"}).get_json()
            assert faults.armed() == {}, "lookahead_retrieve never fired"
            assert faulted["generated_text"] == clean["generated_text"]
            assert svc.lookahead._m_wasted["failed"].value >= 1
            # harness healthy afterwards: the next lookahead join serves
            after = client.post("/query", json={"prompt": "alpha"}).get_json()
            assert after["generated_text"] == clean["generated_text"]
        finally:
            svc.shutdown()

    def test_superseded_prestage_returns_every_block(self, tiny):
        """Stale-prefetch cancellation, both substrates: a speculation that
        loses before admission releases every prefix-cache byte AND every
        registered pool block it warmed — zero leaks, idempotent."""
        cfg, params, _ = tiny
        pc = PrefixCacheConfig(
            enabled=True, max_prefix_tokens=48, segment_buckets=(16,),
            suffix_buckets=(16,), hbm_budget_mb=64,
        )
        ie = InferenceEngine(
            cfg, params, sampling=GREEDY,
            engine_config=EngineConfig(
                prompt_buckets=(64,), max_batch_size=2, max_seq_len=128,
                prefix_cache=pc,
            ),
            dtypes=FP32,
        )
        import dataclasses

        cont = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                ie.engine_config, kv_paged=True, kv_block_size=16
            ),
            dtypes=FP32,
        )
        cache = ie.prefix_cache
        bytes0 = cache.counters()["prefix_cache_bytes"]
        blocks0 = cont.kv_pool.blocks_in_use()
        segments = [
            ("head:chaos", [cfg.bos_token_id] + [7] * 15),
            ("chunk:chaos", [9] * 16),
        ]
        cp, record = cache.stage(segments)
        assert cp is not None and cp.chain_key is not None
        assert cont.prestage_prefix(cp) == "registered"
        assert cont.kv_pool.blocks_in_use() > blocks0
        # the speculation loses: release must return BOTH substrates to
        # their pre-staging footprint, and double-release must be a no-op
        # (only_unused is honest here — no admission mapped the chain)
        assert cache.release_staged(record) > 0
        assert cont.release_prestaged(cp.chain_key, only_unused=True) is True
        assert cache.counters()["prefix_cache_bytes"] == bytes0
        assert cont.kv_pool.blocks_in_use() == blocks0
        assert cache.release_staged(record) == 0
        assert cont.release_prestaged(cp.chain_key) is False


class TestKvSwapInChaos:
    def test_failed_swap_in_recomputes_and_leaks_nothing(self, tiny):
        """Armed ``kv_swap_in`` (ISSUE 8 chaos contract): a cold chunk
        whose host→HBM swap fails is rebuilt FROM TOKENS — the request
        serves the identical greedy stream — its host buffer releases with
        the failed entry, and the paged prestage path frees every block it
        took before declining. Zero leaks on both substrates."""
        import dataclasses

        cfg, params, _ = tiny
        pc = PrefixCacheConfig(
            enabled=True, max_prefix_tokens=48, segment_buckets=(16,),
            suffix_buckets=(16,), hbm_budget_mb=64,
        )
        tiering = KVTieringConfig(enabled=True, retier_interval_s=3600.0)
        ie = InferenceEngine(
            cfg, params, sampling=GREEDY,
            engine_config=EngineConfig(
                prompt_buckets=(64,), max_batch_size=2, max_seq_len=128,
                prefix_cache=pc, kv_tiering=tiering,
            ),
            dtypes=FP32,
        )
        cache = ie.prefix_cache
        segments = [
            ("head:swap", [cfg.bos_token_id] + [7] * 15),
            ("chunk:swap", [9] * 16),
        ]
        suffix = [5, 6, 7]
        cp = cache.prefix_for(segments)
        want = ie.generate_prefixed(suffix, cp)
        assert cache.force_demote("cold") == 2
        cache._assembled.clear()
        cache.assembled_bytes = 0
        faults.arm("kv_swap_in", times=2)  # BOTH segments' swaps fail
        cp2 = cache.prefix_for(segments)
        assert faults.armed() == {}, "kv_swap_in never fired"
        assert cp2 is not None and cp2.computed_tokens == cp.length
        assert len(cache.spill) == 0  # host buffers released
        assert cache.tier_stats()["swap_in_fallbacks"] == 2
        assert ie.generate_prefixed(suffix, cp2) == want

        # paged pool substrate: the prestage swap-in fault frees the
        # blocks it allocated and declines — no reset, no leak
        cont = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                ie.engine_config, kv_paged=True, kv_block_size=16
            ),
            dtypes=FP32,
        )
        free0 = cont.kv_pool.available()
        faults.arm("kv_swap_in", times=1)
        assert cont.prestage_prefix(cp2) is False
        assert faults.armed() == {}, "paged kv_swap_in never fired"
        assert cont.kv_pool.available() == free0
        # fault cleared: the identical prestage succeeds and releases clean
        assert cont.prestage_prefix(cp2) == "registered"
        assert cont.release_prestaged(cp2.chain_key) is True
        assert cont.kv_pool.available() == free0


class TestChunkSpliceChaos:
    def test_mid_splice_fault_recomputes_and_leaks_nothing(self, tiny):
        """Armed ``chunk_splice`` (ISSUE 12 chaos contract): a shifted
        chunk splice that dies mid-flight falls back to RECOMPUTE — the
        cache rebuilds the chunk from tokens with no entry lost and exact
        byte accounting, and the paged per-chunk assembly declines its
        plan BEFORE allocating, so the admission scatters the buffer
        instead. Zero leaked entries/blocks on both substrates."""
        import dataclasses

        cfg, params, _ = tiny
        pc = PrefixCacheConfig(
            enabled=True, max_prefix_tokens=64, segment_buckets=(16,),
            suffix_buckets=(16,), hbm_budget_mb=64, reuse="chunk",
            boundary_tokens=4, chunk_hot_min=0.0,
        )
        ie = InferenceEngine(
            cfg, params, sampling=GREEDY,
            engine_config=EngineConfig(
                prompt_buckets=(64, 128), max_batch_size=2, max_seq_len=256,
                prefix_cache=pc,
            ),
            dtypes=FP32,
        )
        cache = ie.prefix_cache
        head = [int(cfg.bos_token_id)] + [7] * 15
        a, b = [9] * 16, [11] * 16
        suffix = [5, 6, 7]
        cache.prefix_for([("head:cs", head), ("A:cs", a), ("B:cs", b)])
        entries0 = len(cache._entries)
        faults.arm("chunk_splice", times=2)  # both shifted chunks
        cp = cache.prefix_for([("head:cs", head), ("B:cs", b), ("A:cs", a)])
        assert faults.armed() == {}, "chunk_splice never fired"
        counts = cache.chunk_reuse_counters()
        assert counts["splice_faults"] == 2 and counts["rerotated"] == 0
        assert len(cache._entries) == entries0
        assert cache.entry_bytes == sum(
            e.nbytes for e in cache._entries.values()
        )

        # paged substrate: the plan declines before any allocation — the
        # admission scatters the fresh buffer and every block is accounted
        cont = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=dataclasses.replace(
                ie.engine_config, kv_paged=True, kv_block_size=16
            ),
            dtypes=FP32,
        )
        _, fin = cont.admit_prefixed(1, suffix, cp, max_new=4)
        while cont.has_active():
            for _r, toks in cont.step():
                fin = toks
        assert cont._chunk_regs  # exact spans registered for next time
        cache._assembled.clear()
        cache.assembled_bytes = 0
        cache._assembled_spans.clear()
        cp2 = cache.prefix_for(
            [("head:cs", head), ("B:cs", b), ("A:cs", a)]
        )
        faults.arm("chunk_splice", times=1)
        assert cont._chunk_splice_plan(cp2) is None  # declined, pre-alloc
        assert faults.armed() == {}, "paged chunk_splice never fired"
        _, fin2 = cont.admit_prefixed(2, suffix, cp2, max_new=4)
        while cont.has_active():
            for _r, toks in cont.step():
                fin2 = toks
        for k in list(cont._chunk_regs):
            cont._drop_chunk_reg(k)
        for k in list(cont._prefix_blocks):
            cont._drop_registration(k)
        assert cont.kv_pool.blocks_in_use() == 0


class TestSpecChaos:
    """ISSUE 13 chaos contracts (rides `make chaos`, tp=1 and tp=2): a
    decode-step fault landing MID-verify-window and a pool-exhaustion
    preemption of a SPECULATING row must both recover to byte-identical
    streams with zero leaked blocks — a verify window holds more in
    flight per fetch (K+1 writes, junk lanes, per-row acceptance), so
    every recovery path is re-proven with speculation live."""

    SPEC_CFG = None  # set lazily: EngineConfig is imported at module top

    @classmethod
    def _spec_cfg(cls, **over):
        import dataclasses

        base = dataclasses.replace(
            ENG_CFG, kv_paged=True, kv_block_size=16, spec_paged=True,
            spec_paged_tokens=4,
        )
        return dataclasses.replace(base, **over) if over else base

    def _run_with_mid_stream_fault(self, cfg, params, mesh=None):
        """Submit a long repeat-heavy request, arm decode_step only after
        >= 2 verify windows have run (the fault provably lands MID-verify,
        tokens already emitted by verify steps on both sides of the
        reset), and return (stream, engine, request_info)."""
        from rag_llm_k8s_tpu.obs import flight

        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY, engine_config=self._spec_cfg(),
            dtypes=FP32, mesh=mesh,
        )
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        info = {}
        out = [None]
        err = [None]

        def submit():
            try:
                out[0] = sched.submit(
                    [11] * 12, max_new_tokens=40, timeout=300, info=info
                )
            except BaseException as e:  # noqa: BLE001
                err[0] = e

        try:
            th = threading.Thread(target=submit)
            th.start()
            deadline = time.monotonic() + 120
            while (
                eng.stats.spec_verify_steps < 2
                and time.monotonic() < deadline
            ):
                time.sleep(0.005)
            assert eng.stats.spec_verify_steps >= 2, (
                "no verify window ever ran — the fault would not land "
                "mid-verify; fixture is vacuous"
            )
            faults.arm("decode_step", times=1)
            th.join(timeout=300)
            assert err[0] is None, err[0]
            assert faults.armed() == {}, "decode_step fault never fired"
            assert eng.kv_pool.blocks_in_use() == 0, eng.kv_pool.stats()
            # the delivered stream's flight anchor: complete.stream_fnv
            # over exactly the bytes the caller received
            completes = [
                e for e in flight.recorder().snapshot(etype="complete")
                if e.get("rid") == info.get("request_id")
            ]
            if completes:
                assert completes[-1]["stream_fnv"] == flight.stream_hash(
                    out[0]
                )
            return out[0]
        finally:
            sched.shutdown()

    def test_decode_fault_mid_verify_window_byte_identical(self, tiny):
        cfg, params, oracle = tiny
        want = oracle.generate([[11] * 12], max_new_tokens=40)[0]
        got = self._run_with_mid_stream_fault(cfg, params)
        assert got == want

    def test_pool_exhaustion_preempts_speculating_row(self, tiny):
        """A pool sized for half the batch's decode growth: speculating
        rows preempt mid-verify-stream, resubmit (prompt + emitted), and
        every stream still matches the fault-free oracle — zero leaks."""
        prompts = [[3, 17, 42, 3, 17, 42, 3, 17], [5, 5, 8], [11] * 12,
                   [2, 9, 2, 9, 2, 9, 2]]
        cfg, params, oracle = tiny
        want = [oracle.generate([p], max_new_tokens=40)[0] for p in prompts]
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=self._spec_cfg(kv_pool_blocks=8), dtypes=FP32,
        )
        sched = ContinuousScheduler(eng)
        try:
            outs = [None] * len(prompts)
            errs = [None] * len(prompts)

            def run(i):
                try:
                    outs[i] = sched.submit(
                        prompts[i], max_new_tokens=40, timeout=300
                    )
                except BaseException as e:  # noqa: BLE001
                    errs[i] = e

            threads = [
                threading.Thread(target=run, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert errs == [None] * len(prompts), errs
            assert outs == want
            assert eng.stats.spec_verify_steps > 0, "nothing speculated"
            assert eng.kv_pool.blocks_in_use() == 0
        finally:
            sched.shutdown()

    @pytest.fixture(scope="class")
    def tp2(self, tiny):
        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        cfg, params, oracle = tiny
        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        return cfg, shard_llama_params(params, ctx), oracle, ctx

    def test_tp2_decode_fault_mid_verify_window(self, tp2):
        """The same mid-verify fault recovery over the head-sharded
        arena: the tp split must not open a leak or divergence path."""
        cfg, params, oracle, ctx = tp2
        want = oracle.generate([[11] * 12], max_new_tokens=40)[0]
        got = self._run_with_mid_stream_fault(cfg, params, mesh=ctx)
        assert got == want

    def test_tp2_pool_exhaustion_preempts_speculating_row(self, tp2):
        cfg, params, oracle, ctx = tp2
        prompts = [[3, 17, 42, 3, 17, 42, 3, 17], [11] * 12,
                   [2, 9, 2, 9, 2, 9, 2]]
        want = [oracle.generate([p], max_new_tokens=40)[0] for p in prompts]
        # pool = MB (the construction minimum): three rows' decode growth
        # (~4 blocks each at 40 new tokens) cannot coexist — preemption
        # must fire while rows speculate
        eng = ContinuousEngine(
            cfg, params, sampling=GREEDY,
            engine_config=self._spec_cfg(kv_pool_blocks=8), dtypes=FP32,
            mesh=ctx,
        )
        sched = ContinuousScheduler(eng)
        try:
            outs = [None] * len(prompts)
            errs = [None] * len(prompts)

            def run(i):
                try:
                    outs[i] = sched.submit(
                        prompts[i], max_new_tokens=40, timeout=300
                    )
                except BaseException as e:  # noqa: BLE001
                    errs[i] = e

            threads = [
                threading.Thread(target=run, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert errs == [None] * len(prompts), errs
            assert outs == want
            assert eng.stats.spec_verify_steps > 0, "nothing speculated"
            assert eng.kv_pool.blocks_in_use() == 0
        finally:
            sched.shutdown()

"""Crash-safe lifecycle (ISSUE 19): graceful drain, durable flight WAL,
and warm restart that resumes in-flight requests.

Four layers, bottom-up:

- **durability primitives** — ``durable_write``'s tmp-fsync-rename
  discipline and the segment-rotated ``FlightWAL`` (rotation, pruning,
  epoch bumps, torn-tail-tolerant ``scan_wal``, the recorder tee);
- **the drain machine** — ``AdmissionController.drain`` shedding queued
  and new work with 503 ``reason="draining"``, and the
  ``LifecycleCoordinator`` state machine proven with injected
  clock/sleep/active_fn (clean drain, deadline overrun with a
  ``drain_timeout`` incident, idempotence);
- **restore plumbing** — ``sim/replay.extract_inflight`` /
  ``build_restore_report``, the prefix cache's warmth manifest, and the
  service-level ``restore_from_wal`` resuming a hand-built dead epoch
  byte-identically to an uninterrupted oracle;
- **the chaos pin** — a real SIGKILL mid-decode in a subprocess with two
  requests in flight, a second process restoring against the same WAL
  dir, and every delivered stream equal to the uninterrupted run
  (``make restart-smoke``).

The drain HTTP contract (503 + Retry-After while in-flight completes
with zero 500s) runs through the real WSGI app (``make drain-smoke``).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EncoderConfig,
    EngineConfig,
    FlightConfig,
    KVTieringConfig,
    LlamaConfig,
    PrefixCacheConfig,
    ResilienceConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.engine.prefix_cache import PrefixCache
from rag_llm_k8s_tpu.engine.tiering import HostSpillStore
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import flight
from rag_llm_k8s_tpu.resilience.admission import AdmissionController, AdmissionRejected
from rag_llm_k8s_tpu.resilience.lifecycle import (
    DRAINED,
    DRAINING,
    SERVING,
    LifecycleCoordinator,
)
from rag_llm_k8s_tpu.server.app import RagService, create_app
from rag_llm_k8s_tpu.sim import replay

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)
ENG_CFG = EngineConfig(prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64)


@pytest.fixture(autouse=True)
def _detach_wal():
    """The recorder is process-global; never leak a test's WAL tee into
    the next test (or another file's tests)."""
    yield
    flight.configure(wal=None)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# durable_write
# ---------------------------------------------------------------------------
class TestDurableWrite:
    def test_round_trip_and_no_tmp_residue(self, tmp_path):
        path = str(tmp_path / "state.json")
        flight.durable_write(path, {"a": 1, "nested": [1, 2, 3]})
        with open(path) as f:
            assert json.load(f) == {"a": 1, "nested": [1, 2, 3]}
        # the tmp staging file must not survive the rename
        assert os.listdir(tmp_path) == ["state.json"]

    def test_overwrite_replaces_atomically(self, tmp_path):
        path = str(tmp_path / "state.json")
        flight.durable_write(path, {"gen": 1})
        flight.durable_write(path, {"gen": 2})
        with open(path) as f:
            assert json.load(f) == {"gen": 2}


# ---------------------------------------------------------------------------
# FlightWAL: rotation, pruning, epochs, torn tails, recorder tee
# ---------------------------------------------------------------------------
def _ev(seq, etype, rid=None, **attrs):
    d = {"seq": seq, "t": seq / 10.0, "type": etype}
    if rid is not None:
        d["rid"] = rid
    d.update(attrs)
    return d


class TestFlightWAL:
    def test_segment_rotation_and_scan_order(self, tmp_path):
        wal = flight.FlightWAL(str(tmp_path), segment_events=4)
        for i in range(10):
            wal.append(_ev(i, "arrival", rid=i, prompt_len=2, max_new=4))
        wal.close()
        names = sorted(os.listdir(tmp_path))
        assert names == [
            "wal_00000001_000001.jsonl",
            "wal_00000001_000002.jsonl",
            "wal_00000001_000003.jsonl",
        ]
        epochs = flight.scan_wal(str(tmp_path))
        assert list(epochs) == [1]
        assert [e["seq"] for e in epochs[1]] == list(range(10))
        assert wal.appends == 10 and wal.dropped == 0

    def test_prune_drops_oldest_past_max_segments(self, tmp_path):
        wal = flight.FlightWAL(str(tmp_path), segment_events=2,
                               max_segments=2)
        for i in range(9):
            wal.append(_ev(i, "arrival", rid=i))
        wal.close()
        names = sorted(os.listdir(tmp_path))
        assert len(names) == 2
        # only the NEWEST segments survive
        assert names[-1] == "wal_00000001_000005.jsonl"
        events = flight.scan_wal(str(tmp_path))[1]
        assert [e["seq"] for e in events] == [6, 7, 8]

    def test_epoch_bumps_per_incarnation_and_stays_frozen(self, tmp_path):
        w1 = flight.FlightWAL(str(tmp_path))
        w1.append(_ev(1, "arrival", rid=1))
        w1.close()
        w2 = flight.FlightWAL(str(tmp_path))
        assert w2.epoch == 2
        w2.append(_ev(1, "arrival", rid=9))
        w2.close()
        epochs = flight.scan_wal(str(tmp_path))
        assert sorted(epochs) == [1, 2]
        # the dead epoch's contents are exactly as the "crash" left them
        assert epochs[1][0]["rid"] == 1 and epochs[2][0]["rid"] == 9

    def test_scan_skips_torn_tail(self, tmp_path):
        wal = flight.FlightWAL(str(tmp_path))
        wal.append(_ev(1, "arrival", rid=1))
        wal.append(_ev(2, "token_emit", rid=1, toks=[7, 8]))
        wal.close()
        # a SIGKILL mid-append leaves a partial final line
        name = sorted(os.listdir(tmp_path))[-1]
        with open(tmp_path / name, "a") as f:
            f.write('{"seq": 3, "type": "tok')
        events = flight.scan_wal(str(tmp_path))[1]
        assert [e["seq"] for e in events] == [1, 2]

    def test_append_never_raises_counts_drops(self, tmp_path):
        wal = flight.FlightWAL(str(tmp_path / "gone"))
        os.rmdir(tmp_path / "gone")
        wal.append(_ev(1, "arrival"))  # dir vanished: logged + counted
        assert wal.dropped == 1

    def test_recorder_tees_into_wal(self, tmp_path):
        wal = flight.FlightWAL(str(tmp_path))
        flight.configure(enabled=True, wal=wal)
        assert flight.wal_enabled()
        flight.emit("arrival", 7, prompt_len=3, max_new=4)
        flight.emit("token_emit", 7, toks=[11, 12])
        events = flight.scan_wal(str(tmp_path))[wal.epoch]
        assert [e["type"] for e in events] == ["arrival", "token_emit"]
        assert all(e["rid"] == 7 for e in events)
        assert events[1]["toks"] == [11, 12]
        # seq/t survive the tee (scan re-sorts by seq across segments)
        assert events[0]["seq"] < events[1]["seq"]
        flight.configure(wal=None)
        assert not flight.wal_enabled()

    def test_validation(self, tmp_path):
        with pytest.raises(ValueError, match="segment_events"):
            flight.FlightWAL(str(tmp_path), segment_events=0)
        with pytest.raises(ValueError, match="max_segments"):
            flight.FlightWAL(str(tmp_path), max_segments=1)


# ---------------------------------------------------------------------------
# admission draining
# ---------------------------------------------------------------------------
class TestAdmissionDraining:
    def test_new_requests_shed_503_with_drain_retry_after(self):
        gate = AdmissionController(max_concurrency=2, max_queue=2)
        gate.drain(retry_after_s=4.5)
        assert gate.draining
        with pytest.raises(AdmissionRejected) as ei:
            with gate.admit():
                pass
        assert ei.value.reason == "draining"
        assert ei.value.status == 503
        assert ei.value.retry_after_s == pytest.approx(4.5)

    def test_queued_waiter_is_woken_and_shed(self):
        gate = AdmissionController(max_concurrency=1, max_queue=4)
        entered = threading.Event()
        outcome = {}

        def queued():
            entered.set()
            try:
                with gate.admit():
                    outcome["admitted"] = True
            except AdmissionRejected as e:
                outcome["reason"] = e.reason

        with gate.admit():  # the one slot is taken
            t = threading.Thread(target=queued)
            t.start()
            entered.wait(5)
            deadline = time.monotonic() + 5
            while gate.waiting == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert gate.waiting == 1
            gate.drain()  # default retry_after: the gate's own
            t.join(5)
        assert outcome == {"reason": "draining"}
        # the in-flight slot released normally — draining sheds QUEUED
        # work, never the work already past the gate
        assert gate.active == 0


# ---------------------------------------------------------------------------
# LifecycleCoordinator state machine (injected clock/sleep/active)
# ---------------------------------------------------------------------------
class TestLifecycleCoordinator:
    def test_clean_drain_runs_persist_then_exit(self):
        active = [3]
        calls = []
        lc = LifecycleCoordinator(
            deadline_s=10.0, active_fn=lambda: active[0],
            persist_fn=lambda: calls.append("persist"),
            exit_fn=lambda: calls.append("exit"),
            incident_hook=lambda t: calls.append(("incident", t)),
            clock=FakeClock(), sleep=lambda _dt: active.__setitem__(
                0, max(0, active[0] - 1)),
        )
        assert lc.state == SERVING and not lc.draining
        assert lc.begin_drain("sigterm")
        assert lc.wait_drained(5)
        assert lc.state == DRAINED and lc.reason == "sigterm"
        assert not lc.timed_out and lc.stragglers == 0
        assert calls == ["persist", "exit"]  # no incident on a clean pass

    def test_deadline_overrun_sheds_and_spools_drain_timeout(self):
        clk = FakeClock()
        calls = []
        lc = LifecycleCoordinator(
            deadline_s=1.0, active_fn=lambda: 2,  # wedged forever
            persist_fn=lambda: calls.append("persist"),
            incident_hook=lambda t: calls.append(("incident", t)),
            clock=clk, sleep=lambda _dt: clk.advance(0.5),
        )
        assert lc.begin_drain("http")
        assert lc.wait_drained(5)
        assert lc.timed_out and lc.stragglers == 2
        # incident BEFORE persist: the bundle captures the overrun journal
        assert calls == [("incident", "drain_timeout"), "persist"]

    def test_begin_drain_is_idempotent_first_reason_wins(self):
        lc = LifecycleCoordinator(
            deadline_s=5.0, active_fn=lambda: 0, clock=FakeClock(),
            sleep=lambda _dt: None,
        )
        assert lc.begin_drain("sigterm")
        assert not lc.begin_drain("http")  # preStop racing SIGTERM
        assert lc.reason == "sigterm"
        assert lc.wait_drained(5)

    def test_drain_flips_admission_gate(self):
        gate = AdmissionController(max_concurrency=2, max_queue=2)
        lc = LifecycleCoordinator(
            admission=gate, deadline_s=5.0, retry_after_s=2.5,
            clock=FakeClock(), sleep=lambda _dt: None,
        )
        assert lc.begin_drain()
        assert gate.draining
        with pytest.raises(AdmissionRejected) as ei:
            with gate.admit():
                pass
        assert ei.value.retry_after_s == pytest.approx(2.5)
        assert lc.wait_drained(5)

    def test_broken_active_fn_cannot_stall_exit(self):
        def boom():
            raise RuntimeError("probe died")

        lc = LifecycleCoordinator(
            deadline_s=5.0, active_fn=boom, clock=FakeClock(),
            sleep=lambda _dt: None,
        )
        assert lc.begin_drain()
        assert lc.wait_drained(5)  # treated as 0 in flight

    def test_events_journaled(self):
        flight.configure(enabled=True)
        lc = LifecycleCoordinator(
            deadline_s=5.0, active_fn=lambda: 0, clock=FakeClock(),
            sleep=lambda _dt: None,
        )
        lc.begin_drain("sigterm")
        lc.wait_drained(5)
        evs = flight.recorder().snapshot(etype="drain")
        phases = [e["phase"] for e in evs[-2:]]
        assert phases == ["begin", "complete"]


# ---------------------------------------------------------------------------
# extract_inflight / build_restore_report (sim/replay.py)
# ---------------------------------------------------------------------------
class TestExtractInflight:
    def _epoch1(self):
        return [
            _ev(1, "arrival", rid=1, prompt_len=3, max_new=6,
                ids=[5, 6, 7], seed=11, tenant="acme"),
            _ev(2, "token_emit", rid=1, toks=[20, 21]),
            _ev(3, "token_emit", rid=1, toks=[22]),
            _ev(4, "arrival", rid=2, prompt_len=4, max_new=6),  # no ids
            _ev(5, "arrival", rid=3, prompt_len=2, max_new=6, ids=[8, 9]),
            _ev(6, "complete", rid=3, n_tokens=6, stream_fnv=123),
            _ev(7, "arrival", rid=4, prompt_len=2, max_new=6, ids=[8, 9]),
            _ev(8, "resubmit", rid=4, outcome="gave_up", n_emitted=0),
            _ev(9, "drain", phase="begin", reason="sigterm", in_flight=2),
        ]

    def test_inflight_records_concat_token_emits(self):
        got = replay.extract_inflight(self._epoch1())
        assert got["arrivals"] == 4
        assert got["terminal"] == {"complete": 1, "gave_up": 1}
        recs = {r["rid"]: r for r in got["inflight"]}
        assert sorted(recs) == [1, 2]
        r1 = recs[1]
        assert r1["prompt"] == [5, 6, 7]
        assert r1["emitted"] == [20, 21, 22]
        assert not r1["synthetic_prompt"]
        assert r1["seed"] == 11 and r1["tenant"] == "acme"
        # lengths-only arrival: deterministic filler, marked synthetic
        r2 = recs[2]
        assert r2["synthetic_prompt"] and len(r2["prompt"]) == 4

    def test_restore_report_cross_epoch(self):
        epoch2 = [
            _ev(1, "restore", phase="rehydrate", key="doc:1", tokens=64),
            _ev(2, "restore", phase="resume", orig_rid=1, orig_epoch=1,
                n_emitted=3),
            _ev(3, "restore", phase="skip", orig_rid=2,
                reason="synthetic_prompt"),
            _ev(4, "arrival", rid=5, prompt_len=3, max_new=6, ids=[5, 6, 7]),
            _ev(5, "complete", rid=5, n_tokens=6, stream_fnv=9),
        ]
        rep = replay.build_restore_report({1: self._epoch1(), 2: epoch2})
        assert [e["epoch"] for e in rep["epochs"]] == [1, 2]
        e1, e2 = rep["epochs"]
        assert e1["arrivals"] == 4 and e1["completes"] == 1
        assert [r["rid"] for r in e1["inflight_at_end"]] == [1, 2]
        assert e1["drain"][0]["phase"] == "begin"
        assert e2["restored"] == [
            {"rid": None, "orig_rid": 1, "orig_epoch": 1, "n_emitted": 3}
        ]
        assert e2["rehydrated"] == [{"key": "doc:1", "tokens": 64}]
        assert e2["skipped"] == [
            {"orig_rid": 2, "reason": "synthetic_prompt"}
        ]


# ---------------------------------------------------------------------------
# warmth manifest (prefix cache + host spill store)
# ---------------------------------------------------------------------------
class _StubEngine:
    def __init__(self, block_bytes=8):
        self.block_bytes = block_bytes

    def prefix_buffer_zero(self):
        return (np.zeros(1, np.int8),)

    def build_segment_kv(self, ids, ctx, off):
        return (np.zeros(self.block_bytes, np.int8),)

    def splice_prefix(self, buf, block, off):
        return buf


def _pc_cfg(**kw):
    base = dict(
        enabled=True, max_prefix_tokens=4096, segment_buckets=(64, 2048),
        suffix_buckets=(128,), hbm_budget_mb=4, assembled_cache_entries=2,
    )
    base.update(kw)
    return PrefixCacheConfig(**base)


class TestWarmthManifest:
    def test_hotness_ranked_ids_round_trip(self):
        cache = PrefixCache(_pc_cfg(), _StubEngine(),
                            tiering=KVTieringConfig(enabled=True))
        hot = [("hot", list(range(16)))]
        cold = [("cold", list(range(8)))]
        for _ in range(4):
            cache.prefix_for(hot)
        cache.prefix_for(cold)
        man = cache.warmth_manifest(top_n=8)
        assert [r["key"] for r in man] == ["hot", "cold"]
        assert man[0]["ids"] == list(range(16))
        assert man[0]["tokens"] == 16
        assert man[0]["score"] > man[1]["score"]
        # top_n truncation (scores decay in real time, so compare keys)
        assert [r["key"] for r in cache.warmth_manifest(top_n=1)] == ["hot"]

    def test_spilled_flag_marks_host_spill_residents(self):
        cache = PrefixCache(
            _pc_cfg(), _StubEngine(),
            tiering=KVTieringConfig(enabled=True, host_spill_mb=1),
        )
        cache.prefix_for([("a", list(range(8)))])
        cache.prefix_for([("b", list(range(8)))])
        # park "a"'s planes in the host store the way a cold demotion does
        # (entry keys are (chunk_key, slot) tuples)
        cache.spill.put(("a", 0), (np.zeros(16, np.int8),), {"tier": "cold"})
        man = {r["key"]: r for r in cache.warmth_manifest()}
        assert man["a"]["spilled"] and not man["b"]["spilled"]

    def test_host_spill_manifest_inventory(self):
        store = HostSpillStore(budget_mb=1)
        store.put("k1", (np.zeros(4, np.int8),), {"layer": 0})
        store.put("k2", (np.zeros(8, np.int8),))
        man = store.manifest()
        assert [r["key"] for r in man] == ["k1", "k2"]  # oldest first
        assert man[0]["nbytes"] == 4 and man[0]["meta"] == {"layer": 0}
        assert man[1]["nbytes"] == 8


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------
class TestLifecycleConfig:
    def test_wal_knobs_round_trip(self):
        fl = FlightConfig.from_env({
            "TPU_RAG_FLIGHT_WAL": "1",
            "TPU_RAG_FLIGHT_WAL_DIR": "/pvc/wal",
            "TPU_RAG_FLIGHT_WAL_SEGMENT_EVENTS": "128",
            "TPU_RAG_FLIGHT_WAL_SEGMENTS": "16",
            "TPU_RAG_FLIGHT_WAL_RESTORE": "0",
            "TPU_RAG_FLIGHT_WAL_RESTORE_CHUNKS": "3",
        })
        assert fl.wal and fl.wal_dir == "/pvc/wal"
        assert fl.wal_segment_events == 128 and fl.wal_segments == 16
        assert not fl.wal_restore and fl.wal_restore_chunks == 3

    def test_wal_defaults_off(self):
        fl = FlightConfig.from_env({})
        assert not fl.wal and fl.wal_restore

    def test_wal_knob_validation(self):
        with pytest.raises(ValueError, match="SEGMENT_EVENTS"):
            FlightConfig.from_env({"TPU_RAG_FLIGHT_WAL_SEGMENT_EVENTS": "0"})
        with pytest.raises(ValueError, match="WAL_SEGMENTS"):
            FlightConfig.from_env({"TPU_RAG_FLIGHT_WAL_SEGMENTS": "1"})
        with pytest.raises(ValueError, match="RESTORE_CHUNKS"):
            FlightConfig.from_env(
                {"TPU_RAG_FLIGHT_WAL_RESTORE_CHUNKS": "-1"})

    def test_drain_knobs_round_trip(self):
        cfg = AppConfig.from_env({
            "TPU_RAG_DRAIN_DEADLINE_S": "12.5",
            "TPU_RAG_DRAIN_RETRY_AFTER_S": "0.5",
        })
        assert cfg.resilience.drain_deadline_s == pytest.approx(12.5)
        assert cfg.resilience.drain_retry_after_s == pytest.approx(0.5)
        with pytest.raises(ValueError, match="DRAIN_DEADLINE_S"):
            AppConfig.from_env({"TPU_RAG_DRAIN_DEADLINE_S": "0"})


# ---------------------------------------------------------------------------
# HTTP drain contract (make drain-smoke)
# ---------------------------------------------------------------------------
class ByteTokenizer:
    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode(
            "utf-8", "replace"
        )


def make_lifecycle_service(tmp_path, resilience=None, flight_cfg=None,
                           continuous=False):
    """make_service (tests/test_resilience.py) with the lifecycle knobs
    exposed: drain deadlines, a test-local incident spool, optionally a
    WAL-backed flight recorder and a continuous scheduler (the restore
    path's substrate)."""
    llama_cfg = LlamaConfig.tiny(vocab_size=300)
    enc_cfg = EncoderConfig.tiny(vocab_size=300)
    cfg = AppConfig(
        model=llama_cfg, encoder=enc_cfg,
        resilience=resilience or ResilienceConfig(),
        flight=flight_cfg or FlightConfig(
            spool_dir=str(tmp_path / "spool"), cooldown_s=0.0,
        ),
    )
    params = init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32)
    engine = InferenceEngine(
        llama_cfg, params, sampling=GREEDY,
        engine_config=EngineConfig(
            prompt_buckets=(128, 256), max_batch_size=2,
            max_seq_len=4096 + 256,
        ),
        dtypes=FP32,
    )
    sched = None
    if continuous:
        ceng = ContinuousEngine(
            llama_cfg, params, sampling=GREEDY, engine_config=ENG_CFG,
            dtypes=FP32,
        )
        sched = ContinuousScheduler(ceng, retry_backoff_s=0.0)
    encoder = EncoderRunner(
        enc_cfg, init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
        dtypes=FP32, length_buckets=(32, 64), max_batch=4,
    )
    store = VectorStore(dim=enc_cfg.hidden_size)
    svc = RagService(
        cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store,
        scheduler=sched,
    )
    svc.ready = True
    texts = ["alpha beta gamma", "delta epsilon zeta"]
    vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
    store.add(list(vecs), [
        {"filename": "f", "chunk_id": i, "text": t}
        for i, t in enumerate(texts)
    ])
    return svc


class TestHttpDrain:
    def test_drain_sheds_new_work_while_inflight_completes(self, tmp_path):
        svc = make_lifecycle_service(
            tmp_path,
            resilience=ResilienceConfig(drain_deadline_s=30.0,
                                        drain_retry_after_s=3.0),
        )
        try:
            client = create_app(svc).test_client()
            # make the in-flight window deterministic: the request holds
            # its admission slot until the test says otherwise
            release = threading.Event()
            orig_answer = svc.answer

            def slow_answer(*a, **k):
                body = orig_answer(*a, **k)
                release.wait(30)
                return body

            svc.answer = slow_answer
            results = []
            t = threading.Thread(target=lambda: results.append(
                client.post("/generate", json={"prompt": "alpha"})
            ))
            t.start()
            deadline = time.monotonic() + 10
            while svc.admission.active == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert svc.admission.active == 1

            r = client.post("/drain")
            assert r.status_code == 202
            body = r.get_json()
            assert body["state"] == DRAINING and body["started"]
            assert body["active"] == 1
            # second POST: idempotent report, not a second drain
            r2 = client.post("/drain")
            assert r2.status_code == 200 and not r2.get_json()["started"]

            # readiness flips (endpoints stop routing); liveness holds
            # (the kubelet must NOT restart a pod mid-drain)
            h = client.get("/healthz")
            assert h.status_code == 503
            hb = h.get_json()
            assert hb["status"] == "draining" and hb["draining"]
            assert client.get("/healthz?live=1").status_code == 200

            # new work sheds 503 reason="draining" + the drain Retry-After
            shed = client.post("/generate", json={"prompt": "alpha"})
            assert shed.status_code == 503
            sb = shed.get_json()
            assert sb["reason"] == "draining"
            assert sb["error"] == "server draining"
            assert sb["retry_after_s"] == pytest.approx(3.0)
            assert int(shed.headers["Retry-After"]) >= 3

            # the in-flight request finishes under the deadline: 200, not
            # a 5xx — the whole point of draining over killing
            release.set()
            t.join(30)
            assert results and results[0].status_code == 200
            assert svc.lifecycle.wait_drained(10)
            assert svc.lifecycle.state == DRAINED
            assert not svc.lifecycle.timed_out
        finally:
            release.set()
            svc.shutdown()

    def test_drain_deadline_overrun_spools_incident(self, tmp_path):
        spool = tmp_path / "spool"
        svc = make_lifecycle_service(
            tmp_path,
            resilience=ResilienceConfig(drain_deadline_s=0.3),
        )
        try:
            flight.configure(enabled=True)
            flight.emit("arrival", 1, prompt_len=1, max_new=1)
            with svc.admission.admit():  # wedged in-flight work
                assert svc.lifecycle.begin_drain("http")
                assert svc.lifecycle.wait_drained(10)
            assert svc.lifecycle.timed_out
            assert svc.lifecycle.stragglers == 1
            bundles = [
                n for n in os.listdir(spool) if n.endswith(".json")
            ]
            assert bundles, "drain_timeout must spool an incident bundle"
            with open(spool / sorted(bundles)[-1]) as f:
                bundle = json.load(f)
            assert bundle["trigger"] == "drain_timeout"
        finally:
            svc.shutdown()


# ---------------------------------------------------------------------------
# service-level warm restart (in-process, deterministic)
# ---------------------------------------------------------------------------
class TestServiceRestore:
    def _service_with_wal(self, tmp_path):
        wal_dir = str(tmp_path / "wal")
        return make_lifecycle_service(
            tmp_path,
            flight_cfg=FlightConfig(
                spool_dir=str(tmp_path / "spool"), cooldown_s=0.0,
                wal=True, wal_dir=wal_dir, arrival_ids=True,
            ),
            continuous=True,
        ), wal_dir

    def test_restore_resumes_byte_identical_to_oracle(self, tmp_path):
        # epoch 1: a dead incarnation that had rid 1 in flight with the
        # first tokens already emitted. The emitted prefix must be what
        # the engine REALLY emits (the WAL only ever holds true history),
        # so compute the oracle first on an identical engine.
        prompt = [5, 6, 7, 8]
        oracle_eng = ContinuousEngine(
            LlamaConfig.tiny(vocab_size=300),
            init_llama_params(
                jax.random.PRNGKey(0), LlamaConfig.tiny(vocab_size=300),
                FP32),
            sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32,
        )
        oracle_sched = ContinuousScheduler(oracle_eng, retry_backoff_s=0.0)
        try:
            oracle = oracle_sched.submit(prompt, max_new_tokens=8,
                                         timeout=60)
        finally:
            oracle_sched.shutdown()
        assert len(oracle) == 8

        wal_dir = str(tmp_path / "wal")
        w1 = flight.FlightWAL(wal_dir)
        w1.append(_ev(1, "arrival", rid=1, prompt_len=len(prompt),
                      max_new=8, ids=prompt))
        w1.append(_ev(2, "token_emit", rid=1, toks=oracle[:3]))
        w1.append(_ev(3, "arrival", rid=2, prompt_len=3, max_new=8))
        w1.close()

        svc, _ = self._service_with_wal(tmp_path)
        try:
            assert svc.flight_wal is not None and svc.flight_wal.epoch == 2
            summary = svc.restore_from_wal(wait=True)
            assert summary["resumed"] == 1
            # lengths-only arrival: skipped, journaled as such
            assert summary["skipped"] == 1
            assert summary["results"][1] == oracle
            # the resumed request completed INTO the new epoch's WAL —
            # a second crash would reconstruct the full stream from it
            epochs = flight.scan_wal(wal_dir)
            e2 = epochs[2]
            assert any(e["type"] == "complete" for e in e2)
            skips = [e for e in e2 if e["type"] == "restore"
                     and e.get("phase") == "skip"]
            assert skips and skips[0]["reason"] == "synthetic_prompt"
        finally:
            svc.shutdown()

    def test_restore_disabled_by_knob(self, tmp_path):
        w1 = flight.FlightWAL(str(tmp_path / "wal"))
        w1.append(_ev(1, "arrival", rid=1, prompt_len=2, max_new=4,
                      ids=[5, 6]))
        w1.close()
        svc = make_lifecycle_service(
            tmp_path,
            flight_cfg=FlightConfig(
                spool_dir=str(tmp_path / "spool"), wal=True,
                wal_dir=str(tmp_path / "wal"), wal_restore=False,
            ),
            continuous=True,
        )
        try:
            summary = svc.restore_from_wal(wait=True)
            assert summary == {"resumed": 0, "skipped": 0,
                               "rehydrated": 0, "results": {}}
        finally:
            svc.shutdown()

    def test_persist_writes_warmth_manifest_durably(self, tmp_path):
        svc, wal_dir = self._service_with_wal(tmp_path)
        try:
            staged = [("doc:0", [4, 5, 6, 7])]

            class FakeCache:
                def warmth_manifest(self, top_n=8):
                    return [{"key": k, "ids": ids, "tokens": len(ids),
                             "score": 1.0, "spilled": False}
                            for k, ids in staged[:top_n]]

                def prefix_for(self, segments):
                    calls.append(segments)
                    return object()

            calls = []
            svc.engine.prefix_cache = FakeCache()
            svc._persist_for_restart()
            path = os.path.join(wal_dir, "warmth_manifest.json")
            with open(path) as f:
                doc = json.load(f)
            assert doc["entries"][0]["key"] == "doc:0"
            # ...and the next incarnation pre-stages exactly those ids
            flight.configure(enabled=True)
            n = svc._rehydrate_warmth(svc.config.flight)
            assert n == 1
            assert calls == [[("doc:0", [4, 5, 6, 7])]]
            rehy = [e for e in flight.recorder().snapshot(etype="restore")
                    if e.get("phase") == "rehydrate"]
            assert rehy and rehy[-1]["tokens"] == 4
        finally:
            svc.shutdown()


# ---------------------------------------------------------------------------
# the chaos pin: SIGKILL mid-decode, restore, byte-identical streams
# (make restart-smoke)
# ---------------------------------------------------------------------------
_CHAOS_COMMON = """
import sys, time, threading
import jax
from rag_llm_k8s_tpu.core.config import (
    DTypePolicy, EngineConfig, GoodputConfig, LlamaConfig, SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import (
    ContinuousEngine, ContinuousScheduler,
)
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import flight

FP32 = DTypePolicy.fp32()
CFG = LlamaConfig.tiny()
# the CPU has no DEVICE_PEAKS row: pin nominal roofline peaks (conftest.py
# does it for the suite; this script runs in a process of its own)
ENG_CFG = EngineConfig(prompt_buckets=(16, 32), max_batch_size=4,
                       max_seq_len=64,
                       goodput=GoodputConfig(peak_tflops=275.0, hbm_gbs=1200.0))
SAMP = SamplingConfig(do_sample=False, max_new_tokens=40)
PROMPTS = ([5, 6, 7, 8], [9, 10, 11, 12])

def build_engine():
    params = init_llama_params(jax.random.PRNGKey(0), CFG, FP32)
    return ContinuousEngine(CFG, params, sampling=SAMP,
                            engine_config=ENG_CFG, dtypes=FP32)
"""

_CHAOS_VICTIM = _CHAOS_COMMON + """
wal_dir = sys.argv[1]
eng = build_engine()
# throttle decode so the parent's SIGKILL reliably lands mid-stream
orig_step = eng.step
def slow_step(*a, **k):
    time.sleep(0.05)
    return orig_step(*a, **k)
eng.step = slow_step
flight.configure(enabled=True, arrival_ids=True,
                 wal=flight.FlightWAL(wal_dir))
sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
for p in PROMPTS:
    threading.Thread(
        target=lambda p=p: sched.submit(p, max_new_tokens=40, timeout=600),
        daemon=True,
    ).start()
print("VICTIM-UP", flush=True)
time.sleep(600)  # the parent SIGKILLs us mid-decode
"""

_CHAOS_RESTORER = _CHAOS_COMMON + """
import json
from rag_llm_k8s_tpu.sim import replay

wal_dir, out_path = sys.argv[1], sys.argv[2]
eng = build_engine()
wal = flight.FlightWAL(wal_dir)
flight.configure(enabled=True, arrival_ids=True, wal=wal)
sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
epochs = flight.scan_wal(wal_dir)
dead = [e for e in sorted(epochs) if e < wal.epoch]
records = replay.extract_inflight(epochs[dead[-1]])["inflight"]
out = {}
for rec in records:
    flight.emit("restore", phase="resume", orig_rid=rec["rid"],
                orig_epoch=dead[-1], n_emitted=len(rec["emitted"]))
    toks = sched.submit(rec["prompt"], max_new_tokens=rec["max_new"],
                        resume_emitted=rec["emitted"], timeout=600)
    out[str(rec["rid"])] = {
        "prompt": rec["prompt"], "tokens": toks,
        "n_emitted": len(rec["emitted"]),
    }
with open(out_path, "w") as f:
    json.dump(out, f)
sched.shutdown()
print("RESTORED", flush=True)
"""


class TestCrashRestartChaos:
    def test_sigkill_mid_decode_then_byte_identical_resume(
            self, tmp_path, tiny_oracle_streams):
        """The acceptance pin: SIGKILL a process with two requests
        mid-decode, restore a fresh process against the same WAL dir,
        and require every delivered stream byte-identical to an
        uninterrupted run — prefill work and already-decoded tokens are
        not re-earned, they are replayed from the WAL."""
        wal_dir = str(tmp_path / "wal")
        victim_py = tmp_path / "victim.py"
        victim_py.write_text(_CHAOS_VICTIM)
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=repo_root)
        victim = subprocess.Popen(
            [sys.executable, str(victim_py), wal_dir],
            cwd=os.path.dirname(os.path.dirname(__file__)), env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        try:
            # wait until BOTH requests have proven token_emit progress in
            # the WAL and neither has completed — the mid-decode moment
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if victim.poll() is not None:
                    pytest.fail(
                        "victim exited early:\n" + victim.stdout.read()
                    )
                evs = flight.scan_wal(wal_dir).get(1, [])
                emitted = {e.get("rid") for e in evs
                           if e["type"] == "token_emit"}
                done = {e.get("rid") for e in evs
                        if e["type"] == "complete"}
                if len(emitted) >= 2 and not done:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("WAL never showed 2 requests mid-decode")
            os.kill(victim.pid, signal.SIGKILL)
            victim.wait(30)
        finally:
            if victim.poll() is None:
                victim.kill()

        evs = flight.scan_wal(wal_dir)[1]
        dead = replay.extract_inflight(evs)
        assert len(dead["inflight"]) == 2
        assert all(r["emitted"] for r in dead["inflight"])
        assert all(not r["synthetic_prompt"] for r in dead["inflight"])

        restorer_py = tmp_path / "restorer.py"
        restorer_py.write_text(_CHAOS_RESTORER)
        out_path = str(tmp_path / "restored.json")
        r = subprocess.run(
            [sys.executable, str(restorer_py), wal_dir, out_path],
            cwd=os.path.dirname(os.path.dirname(__file__)), env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        with open(out_path) as f:
            restored = json.load(f)
        assert len(restored) == 2
        oracle = tiny_oracle_streams
        for rec in restored.values():
            assert rec["n_emitted"] >= 1  # genuinely resumed, not redone
            want = oracle[tuple(rec["prompt"])]
            assert rec["tokens"] == want, (
                "resumed stream diverged from the uninterrupted oracle"
            )
        # the restart journaled its side: epoch 2 resumes + completions
        e2 = flight.scan_wal(wal_dir)[2]
        resumes = [e for e in e2 if e["type"] == "restore"
                   and e.get("phase") == "resume"]
        assert {e["orig_rid"] for e in resumes} == \
            {r["rid"] for r in dead["inflight"]}
        assert sum(1 for e in e2 if e["type"] == "complete") == 2


@pytest.fixture(scope="module")
def tiny_oracle_streams():
    """Uninterrupted greedy streams for the chaos prompts, computed on an
    engine identical to the subprocess scripts' (same config, same
    PRNGKey(0) init — cross-process deterministic)."""
    cfg = LlamaConfig.tiny()
    eng = ContinuousEngine(
        cfg, init_llama_params(jax.random.PRNGKey(0), cfg, FP32),
        sampling=SamplingConfig(do_sample=False, max_new_tokens=40),
        engine_config=ENG_CFG, dtypes=FP32,
    )
    sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
    out = {}
    try:
        for p in ([5, 6, 7, 8], [9, 10, 11, 12]):
            out[tuple(p)] = sched.submit(p, max_new_tokens=40, timeout=120)
    finally:
        sched.shutdown()
    return out

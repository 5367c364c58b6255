"""Journal-replay harness tests (ISSUE 17, docs/REPLAY.md).

Four layers, cheapest first:

- the pure decision core (``sim/policy.py``) — arithmetic pins;
- the trace generator (``sim/tracegen.py``) — seeded determinism;
- journal plumbing — export/load round-trip over EVERY ``flight.EVENTS``
  entry, the forward-compat unknown-event skip, decision-stream diffing;
- the fidelity contract itself: record a live run (real engine, CPU)
  under the lockstep driver, ``extract_trace`` it, re-drive it, and the
  decision streams are IDENTICAL — including under a chaos-reset
  recording — plus the pure-host simulator's own fixed point, speedup,
  and calibrated-model fidelity band.

``make replay-smoke`` runs the ``TestReplaySmoke`` class alone.
"""

import json
import logging

import jax
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    FlightConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import flight, goodput, shadow
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.sim import policy, replay, simulator, tracegen

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)
ENG = EngineConfig(
    prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64,
    kv_paged=True, kv_block_size=16,
)
CFG = LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return init_llama_params(jax.random.PRNGKey(0), CFG, FP32)


def make_engine(params, engine_config=ENG):
    return ContinuousEngine(
        CFG, params, sampling=GREEDY, engine_config=engine_config,
        dtypes=FP32,
    )


#: seven requests over four slots: one admission wave, staggered tail
#: arrivals, and an idle clock-jump (rid 107 at t_step 5)
TRACE = {"arrivals": [
    {"rid": 101 + i, "t_step": [0, 0, 0, 0, 2, 3, 5][i],
     "ids": [3 + i, 17, 42, 7 + i], "prompt_len": 4, "max_new": 8,
     "seed": None}
    for i in range(7)
]}


def record(params, trace, engine_config=ENG, fault=None):
    """Drive ``trace`` against a fresh real engine under the lockstep
    driver, journaling to the flight recorder; returns (journal,
    results)."""
    eng = make_engine(params, engine_config)
    flight.configure(enabled=True, capacity=8192)
    flight.recorder().clear()
    if fault is not None:
        faults.arm(fault, times=1)
    drv = replay.LockstepDriver(eng, emit=flight.emit)
    results = drv.drive(trace)
    return flight.recorder().snapshot(), results


# ---------------------------------------------------------------------------
# the decision core
# ---------------------------------------------------------------------------


class TestPolicy:
    def test_block_arithmetic(self):
        assert policy.blocks_for(0, 16) == 0
        assert policy.blocks_for(1, 16) == 1
        assert policy.blocks_for(16, 16) == 1
        assert policy.blocks_for(17, 16) == 2
        assert policy.admission_blocks(0, 16) == 1  # BOS floor
        assert policy.window_blocks(30, 4, 16, max_blocks_per_row=4) == 3
        assert policy.window_blocks(62, 4, 16, max_blocks_per_row=4) == 4

    def test_admission_verdict(self):
        assert policy.admission_verdict(10, 8, False, 64) == ("never", 0)
        assert policy.admission_verdict(4, 8, True, 64) == ("ok", 0)
        # +1 headroom, capped at the row table size
        assert policy.admission_verdict(4, 8, False, 64) == ("check", 5)
        assert policy.admission_verdict(4, 8, False, 4) == ("check", 4)

    def test_bucket_and_budget(self):
        assert policy.bucket_len(5, (16, 32)) == 16
        assert policy.bucket_len(17, (16, 32)) == 32
        assert policy.bucket_len(99, (16, 32)) == 32  # clamp to largest
        assert policy.clamp_max_new(100, 16, 64) == 48
        assert policy.clamp_max_new(0, 16, 64) == 1

    def test_admission_chunks_pow2_and_order(self):
        chunks = policy.admission_chunks(
            [(0, 16), (1, 32), (2, 16), (3, 16)], max_batch=4
        )
        # bucket insertion order (16 first), pow2 sizes, arrival order
        assert chunks == [(16, [0, 2]), (16, [3]), (32, [1])]
        # max_batch caps the pow2
        chunks = policy.admission_chunks(
            [(i, 16) for i in range(8)], max_batch=2
        )
        assert [len(m) for _, m in chunks] == [2, 2, 2, 2]

    def test_grow_shortfall_orders_oldest_first(self):
        rows = [(7, 1, 30, 1), (3, 0, 30, 1), (9, 2, 10, 1)]
        short = policy.grow_shortfall(rows, 4, None, 16, 8)
        # row 2 needs nothing (10+4 = 14 < 16 → 1 block, already held);
        # the others need blocks_for(34) = 3, holding 1 → missing 2 —
        # ordered oldest admission first (seq 3 before seq 7)
        assert short == [(3, 0, 2, 1), (7, 1, 2, 1)]

    def test_preempt_victim_is_newest(self):
        assert policy.preempt_victim([(3, 0), (9, 2), (7, 1)]) == (9, 2)

    def test_reclaim_registration_cold_then_oldest(self):
        tiers = {"a": "hot", "b": "warm", "c": "warm"}
        gens = {"a": 1, "b": 5, "c": 2}
        assert policy.reclaim_registration(["a", "b", "c"], tiers, gens) == "c"
        assert policy.reclaim_registration([], {}, {}) is None

    def test_plan_mixed_window_budget_split(self):
        adm = [(1, 100, 0), (2, 100, 90), (3, 50, 0)]
        sched = policy.plan_mixed_window(
            adm, window_budget=40, n_decode=8, chunk_tokens=16
        )
        # 32 tokens of budget: 16 to rid1, 10 (final) to rid2, 6 to rid3
        assert sched == [
            (1, 0, 16, False), (2, 90, 10, True), (3, 0, 6, False),
        ]
        assert policy.plan_mixed_window(adm, 8, 8, 16) == []

    def test_resume_fits(self):
        assert policy.resume_fits(10, 5, 32)
        assert not policy.resume_fits(10, 0, 32)   # nothing emitted
        assert not policy.resume_fits(30, 5, 32)   # would truncate


# ---------------------------------------------------------------------------
# the trace generator
# ---------------------------------------------------------------------------


class TestTraceGen:
    def test_seeded_determinism(self):
        a = tracegen.generate(150, seed=11, emit_ids=True)
        b = tracegen.generate(150, seed=11, emit_ids=True)
        assert a == b
        assert a != tracegen.generate(150, seed=12, emit_ids=True)

    def test_shape_and_clocks(self):
        t = tracegen.generate(100, seed=5, step_period_s=0.02)
        arr = t["arrivals"]
        assert len(arr) == 100
        ts = [a["t"] for a in arr]
        assert ts == sorted(ts)
        assert all(a["t_step"] == int(a["t"] / 0.02) for a in arr)
        assert all(
            tracegen.generate(1, seed=0)["arrivals"][0].keys()
            >= {"rid", "t", "t_step", "prompt_len", "max_new",
                "session", "tenant"}
        for _ in (0,))

    def test_hot_chunk_skew(self):
        t = tracegen.generate(300, seed=9, emit_ids=True, hot_chunks=32,
                              chunk_len=16, zipf_a=1.2)
        # rank-0 chunk tokens (ids 1000..1015) must dominate rank-20's
        hot = sum(
            1 for a in t["arrivals"] for x in a["ids"] if 1000 <= x < 1016
        )
        cold = sum(
            1 for a in t["arrivals"]
            for x in a["ids"] if 1320 <= x < 1336
        )
        assert hot > 4 * max(cold, 1)

    def test_sessions_accumulate_history(self):
        t = tracegen.generate(300, seed=13)
        by_session = {}
        for a in t["arrivals"]:
            by_session.setdefault(a["session"], []).append(a["prompt_len"])
        multi = [v for v in by_session.values() if len(v) >= 3]
        assert multi, "no multi-turn sessions generated"
        # follow-up turns trend longer (history folds forward); compare
        # aggregate first-turn vs later-turn means to ride out noise
        first = [v[0] for v in multi]
        later = [x for v in multi for x in v[2:]]
        assert sum(later) / len(later) > sum(first) / len(first)

    def test_describe(self):
        d = tracegen.describe(tracegen.generate(50, seed=2))
        assert d["requests"] == 50
        assert set(d["tenants"]) <= {"free", "pro"}
        assert d["sessions"] >= 1 and d["prompt_len"]["p50"] >= 16


# ---------------------------------------------------------------------------
# journal plumbing: export/load, forward compat, diffing
# ---------------------------------------------------------------------------


class TestJournalRoundTrip:
    def test_every_event_type_survives_export_parse_replay(self, tmp_path):
        """Each ``flight.EVENTS`` entry: emit → export_journal →
        load_journal → parse_journal keeps it, and both offline state
        reconstructions (goodput, shadow) accept the full journal."""
        flight.configure(enabled=True, capacity=2048)
        flight.recorder().clear()
        for i, etype in enumerate(flight.EVENTS):
            flight.emit(etype, i, n=1)
        path = str(tmp_path / "all_events.json")
        flight.export_journal(path, meta={"trigger": "test"})
        events = flight.load_journal(path)
        parsed = replay.parse_journal(events)
        assert parsed["skipped"] == {}
        assert [e["type"] for e in parsed["events"]] == list(flight.EVENTS)
        # the replay parser's order is the recorder's seq order
        assert [e["rid"] for e in parsed["events"]] == list(
            range(len(flight.EVENTS))
        )
        # offline reconstructions consume the same journal unchanged
        goodput.render_report(goodput.state_from_events(events))
        shadow.render_report(shadow.state_from_events(events))

    def test_unknown_event_type_skipped_with_warning(self, caplog):
        """Forward-compat pin: a journal recorded by a NEWER build (an
        event type this build has never heard of) replays on the known
        subset — warned, never raised."""
        flight.configure(enabled=True, capacity=64)
        flight.recorder().clear()
        flight.emit("admit", 1, slot=0, prompt_len=4, bucket=16, tok0=5)
        events = flight.recorder().snapshot()
        events.append({"seq": 10 ** 9, "t": 0.0,
                       "type": "warp_drive_engaged", "rid": 1})
        events.append("not even a dict")
        with caplog.at_level(logging.WARNING,
                             logger="rag_llm_k8s_tpu.sim.replay"):
            parsed = replay.parse_journal(events)
        assert parsed["skipped"] == {
            "warp_drive_engaged": 1, "<malformed>": 1,
        }
        assert [e["type"] for e in parsed["events"]] == ["admit"]
        assert any("warp_drive_engaged" in r.message for r in caplog.records)
        # the trace extractor and differ ride the same tolerant parser
        replay.extract_trace(events)
        assert replay.diff_journals(events, events)["identical"]

    def test_load_journal_warns_on_newer_schema(self, tmp_path, caplog):
        path = str(tmp_path / "future.json")
        with open(path, "w") as f:
            json.dump({"schema_version": flight.SCHEMA_VERSION + 1,
                       "journal": [{"seq": 1, "type": "admit", "rid": 1}]},
                      f)
        with caplog.at_level(logging.WARNING):
            events = flight.load_journal(path)
        assert len(events) == 1
        assert any("schema_version" in r.message for r in caplog.records)

    def test_load_journal_shapes(self, tmp_path):
        bare = str(tmp_path / "bare.json")
        with open(bare, "w") as f:
            json.dump([{"seq": 1, "type": "admit"}], f)
        assert flight.load_journal(bare) == [{"seq": 1, "type": "admit"}]
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"nope": 1}, f)
        with pytest.raises(ValueError):
            flight.load_journal(bad)


class TestDecisionDiff:
    def _j(self, *types, extra=None):
        out = []
        for i, t in enumerate(types):
            e = {"seq": i, "t": 0.1 * i, "type": t, "rid": 1,
                 "duration_ms": 5.0 * i}
            if extra and i in extra:
                e.update(extra[i])
            out.append(e)
        return out

    def test_timing_attrs_stripped(self):
        a = self._j("admit", "eos")
        b = [dict(e, t=e["t"] + 99, duration_ms=0.001, seq=e["seq"] + 7)
             for e in a]
        d = replay.diff_journals(a, b)
        assert d["identical"] and d["requests_identical"]

    def test_first_divergence_located(self):
        a = self._j("admit", "eos", "complete")
        b = self._j("admit", "eos", "complete", extra={1: {"n_tokens": 9}})
        d = replay.diff_journals(a, b)
        assert not d["identical"]
        assert d["first_divergence"]["index"] == 1
        assert d["first_divergence"]["b"]["n_tokens"] == 9
        assert d["requests_diverged"] == [1]

    def test_length_mismatch_diverges_at_tail(self):
        a = self._j("admit", "eos")
        b = self._j("admit")
        d = replay.diff_journals(a, b)
        assert d["first_divergence"]["index"] == 1
        assert d["first_divergence"]["b"] is None
        assert d["event_counts"]["eos"]["delta"] == -1

    def test_measurements_are_not_decisions(self):
        a = self._j("admit") + [
            {"seq": 5, "type": "goodput_window", "kind": "decode",
             "dur_ms": 3.0}
        ]
        b = self._j("admit") + [
            {"seq": 5, "type": "goodput_window", "kind": "decode",
             "dur_ms": 9999.0}
        ]
        assert replay.diff_journals(a, b)["identical"]


# ---------------------------------------------------------------------------
# the fidelity contract (real engine on CPU)
# ---------------------------------------------------------------------------


class TestReplaySmoke:
    """``make replay-smoke``: record → extract_trace → re-drive is a
    fixed point of the decision stream."""

    def test_plain_paged_fixed_point(self, params):
        j1, r1 = record(params, TRACE)
        t1 = replay.extract_trace(j1)
        # the lockstep clock round-trips: staggered arrivals stay put
        assert [(a["rid"], a["t_step"]) for a in t1["arrivals"]] == [
            (101, 0), (102, 0), (103, 0), (104, 0),
            (105, 2), (106, 3), (107, 5),
        ]
        assert all(a["ids"] for a in t1["arrivals"])  # arrival_ids on
        j2, r2 = record(params, t1)
        diff = replay.diff_journals(j1, j2)
        assert diff["identical"], diff["first_divergence"]
        assert r1 == r2 and len(r1) == 7  # token streams too, not just shapes

    def test_chaos_reset_fixed_point(self, params):
        """The acceptance pin: a recording that crossed a mid-decode
        fault (reset + resubmit) still replays decision-identical —
        failed steps count on the lockstep clock."""
        j1, r1 = record(params, TRACE, fault="decode_step")
        assert any(e["type"] == "reset" for e in j1)
        assert any(e["type"] == "resubmit" for e in j1)
        j2, r2 = record(params, replay.extract_trace(j1),
                        fault="decode_step")
        diff = replay.diff_journals(j1, j2)
        assert diff["identical"], diff["first_divergence"]
        assert r1 == r2

    def test_interleave_fixed_point(self, params):
        """Chunked-prefill mode: the mixed-window planner's decisions
        (window_budget / prefill_chunk_sched) replay exactly too."""
        import dataclasses
        eng_i = dataclasses.replace(ENG, interleave_prefill=True)
        j1, r1 = record(params, TRACE, engine_config=eng_i)
        assert any(e["type"] == "window_budget" for e in j1)
        j2, r2 = record(params, replay.extract_trace(j1),
                        engine_config=eng_i)
        diff = replay.diff_journals(j1, j2)
        assert diff["identical"], diff["first_divergence"]
        assert r1 == r2

    def test_simulated_goodput_lands_in_band(self, params):
        """Simulate the recorded trace through a step model CALIBRATED
        on the recording: the simulator's busy chip-time must land
        within ±25% of the recording's (the fidelity band of docs/REPLAY.md,
        measured here on the CPU engine's own journal)."""
        j1, _ = record(params, TRACE)
        trace = replay.extract_trace(j1)
        model = simulator.CalibratedStepModel.from_journal(j1)
        res = simulator.simulate(
            trace, step_model=model,
            buckets=ENG.prompt_buckets, max_batch_size=ENG.max_batch_size,
            max_seq_len=ENG.max_seq_len, block_size=ENG.kv_block_size,
        )
        rec_busy = sum(
            e.get("dur_ms", 0.0) for e in j1
            if e.get("type") == "goodput_window"
        ) / 1e3
        sim_busy = res["report"]["busy_s"]
        assert rec_busy > 0
        assert abs(sim_busy - rec_busy) / rec_busy <= 0.25, (
            f"simulated busy {sim_busy:.4f}s vs recorded "
            f"{rec_busy:.4f}s — outside the ±25% fidelity band"
        )


# ---------------------------------------------------------------------------
# the pure-host simulator
# ---------------------------------------------------------------------------


class TestSimulator:
    BUCKETS = (64, 128, 256, 512)

    def _run(self, trace, **kw):
        args = dict(max_batch_size=8, max_seq_len=1024,
                    buckets=self.BUCKETS, chip_hour_usd=3.2)
        args.update(kw)
        return simulator.simulate(trace, **args)

    def test_deterministic_and_fixed_point(self):
        trace = tracegen.generate(60, seed=21)
        r1, r2 = self._run(trace), self._run(trace)
        assert replay.diff_journals(r1["journal"], r2["journal"])["identical"]
        assert r1["results"] == r2["results"]
        # the simulator's own journal re-extracts and re-simulates to
        # the same decision stream (the harness composes with itself)
        t2 = replay.extract_trace(r1["journal"])
        r3 = self._run(t2)
        assert replay.diff_journals(
            r1["journal"], r3["journal"]
        )["identical"]

    def test_renderers_consume_synthetic_journal(self, tmp_path):
        from scripts import flightview
        res = self._run(tracegen.generate(30, seed=4))
        path = str(tmp_path / "sim.json")
        flight.export_journal(path, events=res["journal"],
                              meta={"source": "simulator"})
        assert flightview.main([path]) == 0
        assert flightview.main([path, "--goodput"]) == 0
        rep = res["report"]
        assert rep["busy_frac"] > 0
        assert rep["cost"]["per_query_chip_ms"]["n"] == 30
        assert rep["cost"]["chip_hour_usd"] == 3.2

    def test_faster_than_real_time(self):
        """The acceptance floor: ≥100× virtual-over-wall speedup
        (roofline-modeled TPU windows against host dict math clear 100×
        with a wide margin)."""
        res = self._run(tracegen.generate(300, seed=31))
        assert not res["errors"]
        assert res["speedup_x"] >= 100, res["speedup_x"]

    def test_preemption_under_tight_pool(self):
        """An undersized pool produces preempt → resubmit →
        re-admission chains, driven by the SAME policy ordering the
        live engine uses — and every request still completes."""
        trace = tracegen.generate(24, seed=8, prompt_len_range=(64, 480),
                                  max_new_range=(32, 64))
        res = self._run(trace, pool_blocks=60, decode_sync_steps=4)
        types = [e["type"] for e in res["journal"]]
        assert "preempt" in types and "resubmit" in types
        assert not res["errors"]
        assert len(res["results"]) == 24

    def test_oracle_output_lengths(self):
        trace = {"arrivals": [
            {"rid": 1, "t_step": 0, "prompt_len": 40, "max_new": 32,
             "n_out": 5},
            {"rid": 2, "t_step": 0, "prompt_len": 40, "max_new": 32},
        ]}
        res = self._run(trace)
        assert len(res["results"][1]) == 5   # recorded length wins
        assert len(res["results"][2]) == 32  # budget otherwise

    def test_never_admissible_prompt_errors(self):
        trace = {"arrivals": [
            {"rid": 7, "t_step": 0, "prompt_len": 600, "max_new": 4},
        ]}
        res = self._run(trace, pool_blocks=8, max_seq_len=1024)
        assert "7" in str(list(res["errors"].keys()))
        assert res["results"] == {}

    def test_calibrated_model_fit(self):
        events = [
            {"type": "goodput_window", "kind": "decode",
             "dur_ms": 2.0 + 0.5 * n, "tokens": n}
            for n in (2, 4, 8, 16)
        ] + [
            {"type": "goodput_window", "kind": "prefill",
             "dur_ms": 30.0, "tokens": 64},
            {"type": "goodput_window", "kind": "decode",
             "dur_ms": 1.5, "tokens": 0, "preempt_rework": 1.5},
        ]
        m = simulator.CalibratedStepModel.from_journal(events)
        a, b = m.coeffs["decode"]
        assert abs(a - 2.0) < 1e-6 and abs(b - 0.5) < 1e-6
        assert m.decode(1, 10, 0) == pytest.approx(7.0 / 1e3)
        assert m.prefill(64, 1, 64) == pytest.approx(30.0 / 1e3)
        assert m.stall() == pytest.approx(1.5 / 1e3)
        # unseen kind falls back, empty model falls back to default
        assert m._pred_ms("mixed", 10) > 0
        assert simulator.CalibratedStepModel({})._pred_ms("decode", 5) == \
            simulator.CalibratedStepModel.DEFAULT_MS


# ---------------------------------------------------------------------------
# flightview --replay-diff
# ---------------------------------------------------------------------------


class TestFlightviewReplayDiff:
    def test_identical_and_divergent_exit_codes(self, tmp_path, capsys):
        from scripts import flightview
        res = simulator.simulate(
            tracegen.generate(10, seed=1), max_batch_size=4,
            buckets=(64, 128), max_seq_len=512,
        )
        a = str(tmp_path / "a.json")
        b = str(tmp_path / "b.json")
        c = str(tmp_path / "c.json")
        flight.export_journal(a, events=res["journal"])
        flight.export_journal(b, events=res["journal"])
        mutated = [dict(e) for e in res["journal"]]
        for e in mutated:
            if e["type"] == "admit":
                e["slot"] = 99
                break
        flight.export_journal(c, events=mutated)
        assert flightview.main([a, "--replay-diff", b]) == 0
        out = capsys.readouterr().out
        assert "identical=True" in out
        assert flightview.main([a, "--replay-diff", c, "--json"]) == 1
        diff = json.loads(capsys.readouterr().out)
        assert diff["first_divergence"]["b"]["slot"] == 99

    def test_arrival_ids_config_knob(self):
        assert FlightConfig().arrival_ids is True
        fc = FlightConfig.from_env({"TPU_RAG_FLIGHT_ARRIVAL_IDS": "0"})
        assert fc.arrival_ids is False
        flight.configure(enabled=True, capacity=64, arrival_ids=False)
        try:
            assert flight.arrival_ids() is False
        finally:
            flight.configure(enabled=True, capacity=64, arrival_ids=True)

"""Tests of ``lib/host_stages.py`` and the five per-layer metrics of PR 51 that
read a dispatch's own clock. Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The ``ctx``s are made by hand: ``/metrics`` expositions as ``{series: value}``
at a window's edges, and records whose ``timings`` name their dispatch.
``recorded_host_stages.json`` is a cut of a ``--trace 1`` run of
``mistral-7b-int8.closed8`` on a TPU v5 lite (PR 51): 60 ms either side of one
round's ``gather``, the device's operations merged into busy intervals (gaps
under 2 us closed), the host planes' spans by name, times from the cut's start,
in the plain form ``lib/trace.load_xplane`` gives.
"""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import host_stages, stats  # noqa: E402

STAGES = "rag_generate_dispatch_stage_seconds"
ROWS = "rag_coalesce_dispatch_rows_total"


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stage_series(path, stage, seconds, count):
    labels = f'{{path="{path}",stage="{stage}"}}'
    return {f"{STAGES}_sum{labels}": seconds, f"{STAGES}_count{labels}": count}


def exposition(dispatches):
    """``{(path, stage): (seconds, count)}`` -> the parsed exposition."""
    out = {}
    for (path, stage), (seconds, count) in dispatches.items():
        out.update(stage_series(path, stage, seconds, count))
    return out


def request(seq, rows, queue=5.0, launch=4.0, device=4700.0, deliver=0.3, status=200):
    return {"status": status, "timings": {
        "dispatch_seq": float(seq), "dispatch_rows": float(rows), "queue_wait_ms": queue,
        "launch_ms": launch, "device_ms": device, "deliver_ms": deliver, "generate_ms": 4750.0}}


def ctx_of(before, after, requests=(), trace=None, **more):
    return dict({"before": before, "after": after, "requests": list(requests), "stats": stats,
                 "trace": trace, "traffic": {"clients": 8}}, **more)


# ---- a split round -------------------------------------------------------------


@pytest.fixture
def split_round():
    """Ten rounds of eight callers; in the last, one caller's retrieval was
    coalesced alone and took the fused path, and the scheduler waited out its
    window for a caller who never came. The counters stood at a lead-in's
    values when the window opened."""
    before = exposition({
        ("batched", "gather"): (0.008, 1), ("batched", "launch"): (0.004, 1),
        ("batched", "device"): (4.7, 1), ("batched", "deliver"): (0.0004, 1),
    })
    before[f'{ROWS}{{rows="8",stage="retrieve"}}'] = 8.0
    after = exposition({
        ("batched", "gather"): (0.008 + 9 * 0.006 + 0.118, 11),
        ("batched", "launch"): (0.004 + 10 * 0.004, 11),
        ("batched", "device"): (4.7 + 9 * 4.7 + 4.2, 11),
        ("batched", "deliver"): (0.0004 + 10 * 0.0004, 11),
        ("fused", "gather"): (0.0, 1), ("fused", "launch"): (0.002, 1),
        ("fused", "device"): (1.1, 1), ("fused", "deliver"): (0.0002, 1),
    })
    after[f'{ROWS}{{rows="8",stage="retrieve"}}'] = 8.0 + 72.0
    after[f'{ROWS}{{rows="7",stage="retrieve"}}'] = 7.0
    after[f'{ROWS}{{rows="1",stage="retrieve"}}'] = 1.0
    after[f'{ROWS}{{rows="3",stage="embed"}}'] = 3.0  # another stage's: not read
    return ctx_of(before, after)


def test_a_split_round_shows_in_the_retrieve_stage_and_in_the_window(split_round):
    # 80 retrievals in 9 + 1 + 1 batches; every round one batch reads 8.0
    assert reader("retrieve_rows_per_dispatch").read(split_round) == pytest.approx(80 / 11)
    # nine windows of 6 ms and one the scheduler waited out
    assert reader("dispatch_gather_ms").read(split_round) == pytest.approx((9 * 6.0 + 118.0) / 10)
    # launch + deliver over the eleven dispatches of every path
    host = (10 * 4.0 + 2.0) + (10 * 0.4 + 0.2)
    assert reader("dispatch_host_ms").read(split_round) == pytest.approx(host / 11)


def test_stage_delta_reads_one_path_or_all(split_round):
    assert host_stages.stage_delta(split_round, "device", path="fused") == pytest.approx((1.1, 1))
    assert host_stages.stage_delta(split_round, "device") == pytest.approx((9 * 4.7 + 4.2 + 1.1, 11))
    assert host_stages.stage_delta(split_round, "device", path="direct") is None


# ---- a slow round --------------------------------------------------------------


def test_a_slow_round_whose_excess_is_all_launch(capsys):
    requests = [request(seq, 8, queue=4.0 + 0.1 * rider, device=4700.0 + seq)
                for seq in range(101, 111) for rider in range(8)]
    for r in requests:
        if r["timings"]["dispatch_seq"] == 107.0:
            r["timings"]["launch_ms"] = 4.0 + 1300.0
    # a fused dispatch of one row is not of the modal rows; a failure names none
    requests += [request(200, 1, queue=0.0, device=900.0), request(201, 8, status=500)]
    got = reader("slowest_dispatch_excess_ms").read(ctx_of({}, {}, requests))
    # the median dispatch is between 105 and 106: 0.5 ms of device time apart
    assert got == pytest.approx(1300.0 + 1.5, abs=0.01)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "slowest_dispatch" and line["dispatch_seq"] == 107
    assert (line["rows"], line["dispatches"], line["of_modal_rows"]) == (8, 11, 10)
    assert line["stage_excess_ms"] == {"queue_wait_ms": 0.0, "launch_ms": 1300.0,
                                       "device_ms": 1.5, "deliver_ms": 0.0}


def test_a_dispatch_takes_its_riders_largest_wait():
    slow = reader("slowest_dispatch_excess_ms")
    found = slow.dispatches([request(5, 2, queue=3.0, deliver=0.2), request(5, 2, queue=9.0, deliver=0.6)])
    assert found == {5: {"rows": 2, "queue_wait_ms": 9.0, "launch_ms": 4.0, "device_ms": 4700.0,
                         "deliver_ms": 0.6}}


# ---- nothing to read -----------------------------------------------------------


def test_a_window_with_no_batched_dispatch():
    """One caller on the fused path: the scheduler gathered nothing."""
    after = exposition({("fused", "gather"): (0.0, 30), ("fused", "launch"): (0.06, 30),
                        ("fused", "device"): (30.0, 30), ("fused", "deliver"): (0.006, 30)})
    after[f'{ROWS}{{rows="1",stage="retrieve"}}'] = 30.0
    ctx = ctx_of({}, after, [request(seq, 1, queue=0.0) for seq in range(30)])
    assert reader("dispatch_gather_ms").read(ctx) is None
    assert reader("dispatch_host_ms").read(ctx) == pytest.approx(2.2)
    assert reader("retrieve_rows_per_dispatch").read(ctx) == 1.0
    assert reader("slowest_dispatch_excess_ms").read(ctx) == 0.0  # thirty alike


def test_a_program_from_before_the_record_reads_nothing():
    """The parent's side of this PR's check: no family, no key in ``timings``;
    the readers return None and do not raise."""
    old = {"status": 200, "timings": {"generate_ms": 4750.0, "total_ms": 4800.0}}
    ctx = ctx_of({"tpu_rag_engine_generate_calls": 1.0}, {"tpu_rag_engine_generate_calls": 11.0}, [old] * 8)
    for name in ("dispatch_gather_ms", "retrieve_rows_per_dispatch", "dispatch_host_ms",
                 "slowest_dispatch_excess_ms", "host_held_idle_share"):
        assert reader(name).read(ctx) is None, name


def test_host_held_idle_share_is_zero_where_there_is_no_gap():
    share = reader("host_held_idle_share")
    # a capture with no device plane, and one whose operations leave no gap
    for planes in ({}, {"/device:TPU:0": {"XLA Ops": [["busy", 0.0, 1e6], ["busy", 1e6, 1e6]]}}):
        ctx = ctx_of({}, {}, trace={"window_s": 8.0},
                     host_stages=host_stages.reduce_host_stages(planes))
        assert share.read(ctx) == 0.0
    assert share.read(ctx_of({}, {})) is None  # an untraced run has no capture


# ---- the rule of the filing ----------------------------------------------------

MS = 1e6


def planes_of(busy, spans):
    return {"/device:TPU:0": {"XLA Ops": [["busy", s * MS, (e - s) * MS] for s, e in busy]},
            "/host:CPU": {"thread": [[n, s * MS, (e - s) * MS] for n, s, e in spans]}}


def test_a_gap_is_cut_at_span_edges_and_each_piece_goes_to_the_innermost_span():
    # device busy 0-100, 110-200, 204-300 ms, and a launch gap of 20 us inside
    busy = [(0, 100), (110, 200), (204, 250), (250.02, 300)]
    spans = [("generate", 95, 290)] * 8  # eight callers cover a piece once, not eight times
    spans += [("gather", 101, 106), ("dispatch", 106, 203.9), ("launch", 106, 111),
              ("fetch", 111, 200.2), ("deliver", 200.2, 203.9)]
    got = host_stages.reduce_host_stages(planes_of(busy, spans))
    assert got["idle_s"] == pytest.approx({"gather": 0.005, "launch": 0.004, "deliver": 0.0037,
                                           "generate": 0.0011, "fetch": 0.0002})
    assert list(got["idle_s"])[:2] == ["gather", "launch"]  # largest first
    assert got["short_gaps_s"] == pytest.approx(20e-6)
    assert got["span_s"] == pytest.approx(0.300)
    assert got["host_held_s"] == pytest.approx(0.0138)
    assert got["host_held_idle_share"] == pytest.approx(100 * 0.0138 / 0.300)
    assert got["host_spans"]["generate"] == 8 and got["gaps"] == 2


def test_fetch_is_the_devices_own_and_no_span_is_the_hosts():
    busy = [(0, 100), (103, 200), (230, 300)]
    spans = [("fetch", 90, 205)]
    got = host_stages.reduce_host_stages(planes_of(busy, spans))
    assert got["idle_s"] == pytest.approx({"fetch": 0.008, host_stages.NO_SPAN: 0.025})
    assert got["host_held_s"] == pytest.approx(0.025)
    # what leads and trails the slice's operations is not a gap
    assert host_stages.gaps_between([[5.0, 6.0], [8.0, 9.0]]) == [(6.0, 8.0)]


def test_attribute_takes_the_names_it_is_handed():
    """Over ``lib/trace.py``'s four names a gap inside one span is filed as
    that module files it: a ``benchmark`` PR can fold its copy into this one."""
    from benchmark.lib import trace

    spans = [["generate", 0.0, 50 * MS], ["launch", 10 * MS, 5 * MS]]
    gap = [(9 * MS, 16 * MS)]
    assert host_stages.attribute(gap, spans, trace.HOST_SPANS) == {"generate": 7 * MS}
    assert host_stages.attribute(gap, spans, host_stages.SPANS) == {"generate": 2 * MS, "launch": 5 * MS}
    assert trace.attribute_gaps([[0.0, 9 * MS], [16 * MS, 60 * MS]], spans, 0.0, 60 * MS) == {"generate": 0.007}


# ---- the recorded cut ----------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "tests", "recorded_host_stages.json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_recorded_round_boundary_is_filed_by_stage(recorded):
    """One round's boundary of eight callers: the decode loop ends, two
    ``deliver`` spans and eight detokenizations follow, the answers go out and
    the next questions come in under no span, the round's retrievals run as
    TWO batches (six callers, then two: each ``retrieve_batch`` holds a device
    program), then ``assemble``, the scheduler's ``gather`` of 12.6 ms and the
    worker's ``launch``."""
    got = host_stages.reduce_host_stages(recorded)
    assert got["gaps"] == 5 and got["host_spans"]["retrieve_batch"] == 2
    assert got["host_spans"] == {"deliver": 2, "fetch": 1, "launch": 1, "retrieve_batch": 2, "gather": 1,
                                 "dispatch": 1, "detokenize": 8, "generate": 8, "assemble": 8, "retrieve": 8}
    assert got["idle_s"] == pytest.approx({
        host_stages.NO_SPAN: 0.025077704, "retrieve_batch": 0.012715424, "retrieve": 0.004736569,
        "assemble": 0.00449295, "gather": 0.002555517, "dispatch": 0.002102661, "detokenize": 0.00196471,
        "launch": 0.001819053, "deliver": 0.0008647, "generate": 0.00012554}, rel=1e-6)
    assert list(got["idle_s"])[0] == host_stages.NO_SPAN
    # the program ran all through its ``fetch``: none of the idle time is the device's own
    assert "fetch" not in got["idle_s"] and got["host_held_s"] == pytest.approx(0.056454828)
    assert got["host_held_idle_share"] == pytest.approx(100 * 0.056454828 / got["span_s"])
    # every instant of a rider's ``generate`` is under ``gather``, ``dispatch`` or a child of it
    assert got["idle_s"]["generate"] < 0.01 * got["host_held_s"]


def test_the_whole_gap_rule_gives_the_same_boundary_to_the_outermost_spans(recorded):
    """What ``lib/trace.attribute_gaps`` makes of the same cut: whole gaps to
    ``retrieve``, ``generate`` and ``assemble``, as the ledger's ``idle_gaps`` read."""
    from benchmark.lib import trace

    ops = recorded["/device:TPU:0"]["XLA Ops"]
    busy = trace.union([s, s + d] for _, s, d in ops)
    spans = [ev for ev in recorded["/host:CPU"]["spans"] if ev[0] in trace.HOST_SPANS]
    old = trace.attribute_gaps(busy, spans, busy[0][0], busy[-1][1])
    assert set(old) <= {"retrieve", "generate", "assemble", trace.NO_SPAN, trace.SHORT_GAPS}
    assert old["generate"] > 0.01  # the gather and the launch, unnamed

"""The reader of the windowed prefill's live-pair share (``layer_metrics/window_flash_live_pair_share.py``)
on stub windows, and its entry. Not tier 1 (see ``test_benchmark.py``)."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import stats  # noqa: E402

NAME = "window_flash_live_pair_share"
CELLS = ["laguna-s-ep16.closed8"]
LIVE = "tpu_rag_engine_prefill_window_pairs_live"
MULTIPLIED = "tpu_rag_engine_prefill_window_pairs_multiplied"


def read(before, after):
    spec = importlib.util.spec_from_file_location("reader_" + NAME, os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read({"stats": stats, "before": before, "after": after, "trace": None})


@pytest.mark.parametrize("case,before,after,want", [
    # 12 sliding layers x 8 rows, 31 live query blocks of 128 a row: one slice of 640 keys each
    ("a_window_of_one_step_prefills", {LIVE: 7.0e6, MULTIPLIED: 9.0e6},
     {LIVE: 7.0e6 + 96 * 1.94e6, MULTIPLIED: 9.0e6 + 96 * 31 * 128 * 640}, 100.0 * 1.94e6 / (31 * 128 * 640)),
    ("a_walk_over_two_key_blocks_a_query_block", {}, {LIVE: 96 * 1.94e6, MULTIPLIED: 96 * 31 * 128 * 1024.0},
     100.0 * 1.94e6 / (31 * 128 * 1024)),
    ("a_program_without_the_counters", {"tpu_rag_engine_decode_slots_streamed_window": 0.0},
     {"tpu_rag_engine_decode_slots_streamed_window": 4096.0}, None),  # a parent before PR 47
    ("another_family", {}, {"tpu_rag_engine_prefill_tokens_computed": 4096.0}, None),
    ("no_prefill_through_the_kernel", {LIVE: 5.0, MULTIPLIED: 8.0}, {LIVE: 5.0, MULTIPLIED: 8.0}, None),
])
def test_the_reader_divides_the_live_pairs_by_the_pairs_multiplied(case, before, after, want):
    got = read(before, after)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_name_resolves_to_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        benchmark = json.load(f)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "kernels",
        "moves": "latency_p50_ms", "workloads": CELLS}
    cells = {w["name"] for w in benchmark["workloads"]}
    assert set(CELLS) <= cells and "latency_p50_ms" in {m["name"] for m in benchmark["end_to_end"]}


def test_the_program_exports_both_counters_under_the_reader_s_names():
    from rag_llm_k8s_tpu.models import windowed_moe as wm

    for series in (LIVE, MULTIPLIED):
        assert series.removeprefix("tpu_rag_engine_") in wm.COUNTER_NAMES

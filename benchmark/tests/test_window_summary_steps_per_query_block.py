"""The reader of the block-window prefill kernel's softmax steps a live query block
(``layer_metrics/window_summary_steps_per_query_block.py``) on stub windows, and its entry.
Not tier 1 (see ``test_benchmark.py``)."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import stats  # noqa: E402

NAME = "window_summary_steps_per_query_block"
CELLS = ["evabyte-pp4.closed8"]
STEPS = "tpu_rag_engine_prefill_window_softmax_steps"
BLOCKS = "tpu_rag_engine_prefill_window_query_blocks"


def read(before, after):
    spec = importlib.util.spec_from_file_location("reader_" + NAME, os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read({"stats": stats, "before": before, "after": after, "trace": None})


@pytest.mark.parametrize("case,before,after,want", [
    # 8 layers x 8 rows of 35 live query blocks each: a window in one step, the summaries behind it in pieces
    ("a_window_in_one_step", {STEPS: 900.0, BLOCKS: 500.0},
     {STEPS: 900.0 + 64 * 81, BLOCKS: 500.0 + 64 * 35}, 81 / 35),
    ("the_walk_over_key_blocks_and_summary_blocks", {}, {STEPS: 64 * 165.0, BLOCKS: 64 * 35.0}, 165 / 35),
    ("a_program_without_the_counters", {"tpu_rag_engine_windows_closed": 0.0},
     {"tpu_rag_engine_windows_closed": 64.0}, None),  # a parent before PR 54
    ("another_family", {}, {"tpu_rag_engine_prefill_tokens_computed": 4096.0}, None),
    ("no_prefill_through_the_kernel", {STEPS: 5.0, BLOCKS: 3.0}, {STEPS: 5.0, BLOCKS: 3.0}, None),
])
def test_the_reader_divides_the_steps_by_the_live_query_blocks(case, before, after, want):
    got = read(before, after)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_name_resolves_to_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        benchmark = json.load(f)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "steps/block", "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "latency_p50_ms", "workloads": CELLS}
    cells = {w["name"] for w in benchmark["workloads"]}
    assert set(CELLS) <= cells and "latency_p50_ms" in {m["name"] for m in benchmark["end_to_end"]}


def test_the_program_exports_both_counters_under_the_reader_s_names():
    from rag_llm_k8s_tpu.models import block_window as bwm

    for series in (STEPS, BLOCKS):
        assert series.removeprefix("tpu_rag_engine_") in bwm.COUNTER_NAMES

"""The reader of the grouped matmul's tile fill (``layer_metrics/grouped_matmul_tile_fill_share.py``)
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

NAME = "grouped_matmul_tile_fill_share"
SPARSE_CELLS = ["dots-vlm1-ep16.closed8", "longcat-flash-ep32.closed8", "laguna-s-ep16.closed8",
                "lfm2-24b-a2b-pp4.solo"]
COMPUTED = "tpu_rag_engine_moe_prefill_assignments_computed"
TILE_ROWS = "tpu_rag_engine_moe_prefill_tile_rows"


def read(before, after):
    spec = importlib.util.spec_from_file_location("reader_" + NAME, os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read({"stats": stats, "before": before, "after": after, "trace": None})


@pytest.mark.parametrize("case,before,after,want", [
    # 8 layer calls of 16384 rows in 191 visits of 128 rows, on top of what the lead-in counted
    ("a_window_of_prefills", {COMPUTED: 1000.0, TILE_ROWS: 3000.0},
     {COMPUTED: 1000.0 + 8 * 16384, TILE_ROWS: 3000.0 + 8 * 191 * 128}, 100.0 * 16384 / (191 * 128)),
    ("the_xla_path_reports_the_rows_it_was_given", {}, {COMPUTED: 512.0, TILE_ROWS: 512.0}, 100.0),
    ("a_program_without_the_counter", {COMPUTED: 0.0}, {COMPUTED: 131072.0}, None),  # a parent before PR 46
    ("a_dense_family", {}, {"tpu_rag_engine_prefill_tokens_computed": 4096.0}, None),
    ("no_prefill_in_the_window", {COMPUTED: 5.0, TILE_ROWS: 128.0}, {COMPUTED: 5.0, TILE_ROWS: 128.0}, None),
])
def test_the_reader_divides_the_rows_stored_by_the_rows_multiplied(case, before, after, want):
    got = read(before, after)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_name_resolves_to_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        benchmark = json.load(f)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "kernels",
        "moves": "latency_p50_ms", "workloads": SPARSE_CELLS}
    cells = {w["name"] for w in benchmark["workloads"]}
    assert set(SPARSE_CELLS) <= cells and "latency_p50_ms" in {m["name"] for m in benchmark["end_to_end"]}


def test_the_program_exports_both_counters_under_the_reader_s_names():
    from rag_llm_k8s_tpu.models import latent_moe as lm

    for series in (COMPUTED, TILE_ROWS):
        assert series.removeprefix("tpu_rag_engine_") in lm.COUNTER_STATS

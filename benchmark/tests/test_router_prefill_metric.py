"""The reader of the router's prefill time (``layer_metrics/router_prefill_ms_per_row.py``)
on synthetic splits, and its entry. Not tier 1 (see ``test_benchmark.py``)."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

NAME = "router_prefill_ms_per_row"
SPARSE_CELLS = ["dots-vlm1-ep16.closed8", "longcat-flash-ep32.closed8", "laguna-s-ep16.closed8"]


def read(by, rows):
    spec = importlib.util.spec_from_file_location("reader_" + NAME, os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx = {"trace": None} if by is None else {
        "trace": {}, "fine_scopes": by, "phases": {"prefill_rows": rows, "steps": {}}}
    return mod.read(ctx)


@pytest.mark.parametrize("case,by,rows,want", [
    ("a_slice_with_the_scope", {"prefill": {"experts": 0.24, "router": 0.12}, "decode": {"router": 7.0}}, 24.0, 5.0),
    ("a_program_without_the_scope", {"prefill": {"dense": 1.0}}, 24.0, None),  # the Mistral cells; a parent before PR 27
    ("no_prefill_row_in_the_slice", {"prefill": {"router": 0.12}}, 0.0, None),
    ("no_trace", None, 24.0, None),
])
def test_the_reader_divides_the_router_s_prefill_seconds_by_the_rows(case, by, rows, want):
    got = read(by, rows)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_name_resolves_to_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        benchmark = json.load(f)
    assert benchmark["per_layer"][-1] == {
        "name": NAME, "unit": "ms", "better": "lower", "source": "device_trace", "layer": "model step",
        "moves": "latency_p50_ms", "workloads": SPARSE_CELLS}
    cells = {w["name"] for w in benchmark["workloads"]}
    assert set(SPARSE_CELLS) <= cells and "latency_p50_ms" in {m["name"] for m in benchmark["end_to_end"]}

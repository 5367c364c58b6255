"""The four readers of set-up (``layer_metrics/setup_*.py``) on synthetic
scrapes, and their entries. Not tier 1 (see ``test_benchmark.py``)."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import stats  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCHMARK = json.load(f)

NAMES = ("setup_trace_lower_s", "setup_compile_s", "setup_ingest_s", "setup_cache_miss_builds")

# what a program from before the census serves: two unlabeled sums, no ingest family
PARENT = """\
# TYPE rag_compile_events_total counter
rag_compile_events_total 22
# TYPE rag_compile_seconds_total counter
rag_compile_seconds_total 185.4
tpu_rag_ingest_seconds_sum 47.1
"""

LABELED = """\
# TYPE rag_compile_events_total counter
rag_compile_events_total{cache="hit",program="generate"} 9
rag_compile_events_total{cache="hit",program="generate_rag"} 4
rag_compile_events_total{cache="miss",program="generate_rag"} 2
rag_compile_events_total{cache="miss",program="undeclared"} 3
rag_compile_events_total{cache="off",program="undeclared"} 120
# TYPE rag_compile_seconds_total counter
rag_compile_seconds_total{program="generate",stage="trace"} 60.5
rag_compile_seconds_total{program="generate",stage="lower"} 30.25
rag_compile_seconds_total{program="generate",stage="compile"} 4.5
rag_compile_seconds_total{program="generate",stage="other"} 0.125
rag_compile_seconds_total{program="generate_rag",stage="trace"} 20.0
rag_compile_seconds_total{program="generate_rag",stage="lower"} 10.0
rag_compile_seconds_total{program="generate_rag",stage="compile"} 5.5
rag_compile_seconds_total{program="undeclared",stage="lower"} 1.25
rag_compile_seconds_total{program="undeclared",stage="compile"} 2.0
# TYPE rag_ingest_stage_seconds histogram
rag_ingest_stage_seconds_bucket{stage="embed",le="+Inf"} 1
rag_ingest_stage_seconds_sum{stage="extract"} 0.5
rag_ingest_stage_seconds_sum{stage="chunk"} 0.25
rag_ingest_stage_seconds_sum{stage="embed"} 12.0
rag_ingest_stage_seconds_sum{stage="index"} 3.0
rag_ingest_stage_seconds_sum{stage="warm"} 31.0
rag_ingest_stage_seconds_count{stage="warm"} 1
"""


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name, exposition):
    return reader(name).read({"before": stats.parse_exposition(exposition), "stats": stats})


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_unlabeled_exposition_reads_as_nothing(name):
    assert read(name, PARENT) is None
    assert read(name, "") is None


@pytest.mark.parametrize("name,want", [
    ("setup_trace_lower_s", 60.5 + 30.25 + 20.0 + 10.0 + 1.25),
    ("setup_compile_s", 4.5 + 5.5 + 2.0),
    ("setup_ingest_s", 0.5 + 0.25 + 12.0 + 3.0),  # ``warm`` is builds: left out
    ("setup_cache_miss_builds", 5.0),
])
def test_a_labeled_exposition_reads_as_the_sums(name, want):
    assert read(name, LABELED) == pytest.approx(want)


def test_a_warm_cache_reads_zero_misses_not_nothing():
    warm = "\n".join(ln for ln in LABELED.splitlines() if 'cache="miss"' not in ln)
    assert read("setup_cache_miss_builds", warm) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_the_name_resolves_to_a_file_and_an_entry(name):
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["source"] == "program_counter" and entry["layer"] == "set-up: builds and ingest"
    assert "workloads" not in entry  # every cell reports setup_s, so every cell reads these
    assert {m["name"] for m in BENCHMARK["end_to_end"]} >= {entry["moves"]}

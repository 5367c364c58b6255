"""The engagement reader of the executable store (``layer_metrics/
setup_stored_build_share.py``) on recorded scrapes, and its entry. Not tier 1
(see ``test_benchmark.py``)."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import stats  # noqa: E402

NAME = "setup_stored_build_share"

# ``rag_compile_events_total`` as ``/metrics`` served it at a window's opening:
# the toy service of ``tests_tpu``'s store case, its first boot and its second
COLD = """\
# TYPE rag_compile_events_total counter
rag_compile_events_total{cache="miss",program="generate"} 2
rag_compile_events_total{cache="off",program="generate"} 3
rag_compile_events_total{cache="off",program="generate_spec"} 2
rag_compile_events_total{cache="miss",program="score_exact"} 1
rag_compile_events_total{cache="off",program="score_exact"} 2
rag_compile_events_total{cache="off",program="generate_rag"} 2
rag_compile_events_total{cache="off",program="retrieve"} 2
rag_compile_events_total{cache="off",program="encode"} 2
rag_compile_events_total{cache="miss",program="undeclared"} 1
rag_compile_events_total{cache="off",program="undeclared"} 41
"""
WARM = """\
# TYPE rag_compile_events_total counter
rag_compile_events_total{cache="stored",program="generate"} 5
rag_compile_events_total{cache="stored",program="generate_spec"} 2
rag_compile_events_total{cache="stored",program="score_exact"} 3
rag_compile_events_total{cache="stored",program="generate_rag"} 2
rag_compile_events_total{cache="stored",program="retrieve"} 2
rag_compile_events_total{cache="stored",program="encode"} 2
rag_compile_events_total{cache="hit",program="undeclared"} 1
rag_compile_events_total{cache="off",program="undeclared"} 41
"""
# one entry was unreadable and built again; a program from before the census
PARTLY = WARM.replace('cache="stored",program="encode"} 2',
                      'cache="stored",program="encode"} 1\n'
                      'rag_compile_events_total{cache="hit",program="encode"} 1')
UNLABELED = "# TYPE rag_compile_events_total counter\nrag_compile_events_total 22\n"
ONLY_UNDECLARED = 'rag_compile_events_total{cache="off",program="undeclared"} 41\n'


def reader():
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(exposition):
    return reader().read({"before": stats.parse_exposition(exposition), "stats": stats})


@pytest.mark.parametrize("exposition,want", [
    (COLD, 0.0), (WARM, 100.0), (PARTLY, 100.0 * 15 / 16),
    (UNLABELED, None), (ONLY_UNDECLARED, None), ("", None)])
def test_the_share_of_declared_builds_the_store_held(exposition, want):
    got = read(exposition)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_reader_names_every_declared_program():
    from rag_llm_k8s_tpu.obs import tracing

    assert set(reader().DECLARED) == set(tracing.BUILD_PROGRAMS) - {"undeclared"}


def test_the_name_resolves_to_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        benchmark = json.load(f)
    entry = benchmark["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "set-up: builds and ingest", "moves": "setup_s"}
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))

"""Every module of the package is somebody's: an entry point imports it.

A module that no program of the tree can reach costs its reader what any
other module costs and serves nobody: PR 20's front-tier router stood in the
package for 36 PRs, constructed by no entry point and imported by its own
tests only, with five options, two executables and a migration protocol
behind it (removed at PR 57). One case a module: it is imported, at any depth
and inside a function or not, from one of the entry points below. Tests are
not entry points. Stdlib only (``ast``); nothing of the package is imported.

Two more cases keep what PR 57 took out from coming back by halves: no file
the program or its operators read still holds one of its names, and a journal
recorded while those event kinds existed still loads, renders and replays.
"""

import ast
import functools
import importlib.util
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PACKAGE = "rag_llm_k8s_tpu"

#: who starts a walk, each with who runs it
ENTRY_POINTS = {
    "rag_llm_k8s_tpu/server/main.py": "the pod's command (deploy/llm/Dockerfile, `python -m rag_llm_k8s_tpu.server.main`)",
    "chip_smoke.py": "the builder, on the chip: does the system still start",
    "__graft_entry__.py": "the driver: the entry it compiles, and its train leg",
    "benchmark/run.py": "the driver: BENCHMARK.json's command",
    "benchmark/lib/serve.py": "benchmark/run.py, which finds it by path; what every family's cell shares",
    "scripts/validate_8b.py": "`make validate-8b`",
    "scripts/flightview.py": "an operator, offline, on an incident bundle or a WAL directory",
    "scripts/check_metrics_docs.py": "`make lint`",
    "scripts/ragcheck/__main__.py": "`make analyze`",
    # libraries an operator's own script imports: the document is the caller
    "rag_llm_k8s_tpu/sim/simulator.py": "an operator's capacity script (docs/REPLAY.md)",
    "rag_llm_k8s_tpu/sim/tracegen.py": "an operator's capacity script (docs/REPLAY.md)",
}
#: the line that makes docs/REPLAY.md a caller; without it the two above are nobody's
REPLAY_IMPORT = "from rag_llm_k8s_tpu.sim import replay, simulator, tracegen"


def module_name(path: Path) -> str:
    parts = list(path.relative_to(ROOT).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


MODULES = {module_name(p): p for p in sorted((ROOT / PACKAGE).rglob("*.py"))}


def imported_names(path: Path, name: str) -> set:
    """Every dotted name an ``import`` of the file could bind to a module:
    ``import a.b``, ``from a import b`` (``a`` and ``a.b``), relative forms
    resolved against ``name``; anywhere in the file, a function's body too."""
    package = name.split(".") if path.name == "__init__.py" else name.split(".")[:-1]
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - (node.level - 1)] if node.level else []
            source = ".".join(base + ([node.module] if node.module else []))
            out.add(source)
            out.update(f"{source}.{a.name}" for a in node.names)
    return out


def main_guarded(path: Path) -> bool:
    return bool(re.search(r'^if __name__ == "__main__":', path.read_text(), re.M))


@functools.cache
def reached() -> frozenset:
    starts = [ROOT / e for e in ENTRY_POINTS]
    starts += [p for p in MODULES.values() if main_guarded(p)]
    seen = {module_name(p) for p in starts if module_name(p) in MODULES}
    stack = [(p, module_name(p)) for p in starts]
    while stack:
        path, name = stack.pop()
        for dotted in imported_names(path, name):
            parts = dotted.split(".")
            # importing a.b.c runs a/__init__.py and a/b/__init__.py first
            for i in range(1, len(parts) + 1):
                prefix = ".".join(parts[:i])
                if prefix in MODULES and prefix not in seen:
                    seen.add(prefix)
                    stack.append((MODULES[prefix], prefix))
    return frozenset(seen)


def test_the_entry_points_are_there():
    assert [e for e in ENTRY_POINTS if not (ROOT / e).is_file()] == []
    programs = {str(p.relative_to(ROOT)) for p in (ROOT / "scripts").rglob("*.py") if main_guarded(p)}
    assert programs <= set(ENTRY_POINTS), "a script nobody is said to run"
    assert REPLAY_IMPORT in (ROOT / "docs" / "REPLAY.md").read_text()


@pytest.mark.parametrize("module", sorted(MODULES))
def test_an_entry_point_reaches_the_module(module):
    assert module in reached(), (
        f"{MODULES[module].relative_to(ROOT)} is imported by no entry point "
        f"({', '.join(ENTRY_POINTS)}): call it from one, or remove it with its tests"
    )


#: PR 20's control plane, by the names only it used
RETIRED = (
    "pool_role", "POOL_ROLE", "TPU_RAG_ROUTER_", "migrate_packet", "submit_migrated",
    "route_decision", "migrate_begin", "migrate_done",
)


def test_the_retired_control_plane_is_named_nowhere():
    """The package, the scripts, the manifests and the documents an operator
    reads; the records of what was done (CHANGES, ROADMAP, PERF, ISSUE) may
    name what is gone."""
    files = [ROOT / "Makefile", ROOT / "README.md"]
    for directory, pattern in ((PACKAGE, "*.py"), ("scripts", "*"), ("deploy", "*"), ("docs", "*")):
        files += [p for p in (ROOT / directory).rglob(pattern) if p.is_file() and p.suffix != ".pyc"]
    found = []
    for p in sorted(files):
        text = p.read_text(errors="replace")
        found += [f"{p.relative_to(ROOT)}: {name}" for name in RETIRED if name in text]
    assert found == []


def _load(relative: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / relative)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def test_a_journal_with_the_retired_kinds_still_loads_and_replays(tmp_path):
    """A journal recorded before PR 57 may hold ``route_decision``,
    ``migrate_begin`` and ``migrate_done``. A reader skips a kind it does not
    know: the bundle loads, flightview renders it, and the trace and the
    decision stream it yields are those of the same journal without them."""
    flight = _load("rag_llm_k8s_tpu/obs/flight.py", "_reach_flight")
    replay = _load("rag_llm_k8s_tpu/sim/replay.py", "_reach_replay")
    retired = ("route_decision", "migrate_begin", "migrate_done")
    assert not set(retired) & set(flight.EVENTS)
    kept = [
        {"seq": 1, "t": 0.0, "type": "arrival", "rid": 7, "prompt_len": 5, "max_new": 4, "seed": 3},
        {"seq": 3, "t": 0.01, "type": "admit", "rid": 7, "slot": 0, "prompt_len": 5, "bucket": 16, "group": 1},
        {"seq": 6, "t": 0.03, "type": "sync_window", "steps": 3, "active": 1},
        {"seq": 7, "t": 0.04, "type": "complete", "rid": 7, "n_tokens": 4, "stream_fnv": 99},
    ]
    old = sorted(kept + [
        {"seq": 2, "t": 0.005, "type": "route_decision", "rid": 7, "mode": "disagg", "prefill": "p0", "decode": "d0"},
        {"seq": 4, "t": 0.02, "type": "migrate_begin", "rid": 7, "blocks": 1, "kv_len": 5, "duration_ms": 0.4},
        {"seq": 5, "t": 0.025, "type": "migrate_done", "rid": 7, "slot": 0, "blocks": 1, "kv_len": 5},
    ], key=lambda e: e["seq"])
    path = tmp_path / "recorded_before_pr57.json"
    flight.export_journal(str(path), events=old)
    loaded = flight.load_journal(str(path))
    assert [e["type"] for e in loaded] == [e["type"] for e in old]
    assert replay.extract_trace(loaded) == replay.extract_trace(kept)
    assert replay.decision_stream(loaded) == replay.decision_stream(kept)
    for args in ([], ["--request", "7"], ["--goodput"], ["--replay-diff", str(path)]):
        out = subprocess.run(
            [sys.executable, str(ROOT / "scripts" / "flightview.py"), str(path), "--json", *args],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        json.loads(out.stdout)

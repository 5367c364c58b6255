"""Tests of ``lib/phases.py`` and the per-layer metrics that read it. Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

``recorded_phases.json`` is a cut of a ``--trace 1`` run of
``mistral-7b-int8.closed8`` on a TPU v5 lite (PR 24, the run of the final
tree), in the plain form ``lib/phases.py`` reduces: the two coalesced retrieve
programs of a round of eight callers, then the start of the batch-8 generate
program (its prefill and its first decode steps), with the scope paths of the
instructions that ran (``prefill/rows8/...`` among them) and the
host spans around them.
"""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import phases, stats  # noqa: E402

LAYERS = 32  # mistral-7b-v0.3


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "tests", "recorded_phases.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(recorded):
    return phases.reduce_phases(recorded, LAYERS)


# ---- scope paths ---------------------------------------------------------------


@pytest.mark.parametrize("op_name, want", [
    ("jit(gen_rag)/verify/while/body/LlamaModel/layers/attn/attn/dot_general", ("verify", "attn")),
    ("jit(gen)/decode/while/body/sample/argmax", ("decode", "sample")),
    ("jit(gen)/prefill/LlamaModel/layers/norm_rope/input_norm/mul", ("prefill", "norm_rope")),
    ("jit(fused)/retrieve/BgeM3Encoder/retrieve/embed/layers/attn/dot_general", ("retrieve", "embed")),
    ("jit(fused)/retrieve/jit(knn_topk_pallas)/retrieve/knn/knn_topk_pallas/pallas_call",
     ("retrieve", "knn")),
    ("decode", ("decode", "")),  # what the compiler wrote inside a decode loop
    ("jit(gen)/LlamaModel/layers/attn/dot_general", (phases.UNSCOPED, "")),  # no phase: unscoped
    ("", (phases.UNSCOPED, "")),
])
def test_scope_of(op_name, want):
    assert phases.scope_of(op_name) == want


@pytest.mark.parametrize("op_name, want", [
    ("jit(gen)/decode/while/body/LlamaModel/lm_head/bsd,dv->bsv/dot_general", ("decode", True, None)),
    ("jit(gen_rag)/verify/while/body/sample/argmax", ("verify", True, None)),
    # the layers of a prefill: once a layer, under the rows the program was built for
    ("jit(gen)/prefill/rows8/LlamaModel/while/body/closed_call/layers/mlp/dot_general",
     ("prefill", False, 8)),
    ("jit(fused)/retrieve/BgeM3Encoder/retrieve/embed/while/body/layers/attn/dot_general",
     ("retrieve", False, None)),
    # a step's layers are two loops deep, the condition is not the body, a branch may not run
    ("jit(gen)/decode/while/body/LlamaModel/while/body/closed_call/layers/mlp/dot_general", None),
    ("jit(gen)/decode/while/cond/reduce_and", None),
    ("jit(gen)/decode/while/body/cond/branch_1_fun/mul", None),
    ("jit(gen)/prefill/rows8/LlamaModel/lm_head/dot_general", None),  # no loop beneath
    ("decode", None),  # what the compiler wrote: no path to tell its depth by
    ("", None),
])
def test_one_loop_beneath(op_name, want):
    assert phases.one_loop_beneath(op_name) == want


def test_the_reader_and_the_program_share_one_vocabulary():
    """The reader keeps a copy (it has to read the parent's traces too, whose
    ``obs/tracing.py`` has none); ``sample`` is a phase there and, under
    another phase, a sub-scope here."""
    from rag_llm_k8s_tpu.obs import tracing

    assert set(phases.PHASES) == set(tracing.PHASES)
    assert set(phases.SUB_SCOPES) - {"sample"} == set(tracing.SUB_SCOPES)


# ---- the profiler's own file, end to end on the CPU -----------------------------


@pytest.fixture(scope="module")
def cpu_capture(tmp_path_factory):
    """A real ``.xplane.pb`` of a toy program with scopes and host spans."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("prefill/rows64"):
            with jax.named_scope("mlp"):
                h = jnp.tanh(x @ w)
            # the layers' loop: four of them
            h = jax.lax.fori_loop(0, 4, lambda i, h: jnp.tanh(h @ w + i), h)
        y = jnp.sin(h) @ w  # traced outside every scope

        def body(c):
            i, h = c
            with jax.named_scope("attn"):
                h = jnp.tanh(h @ w.T)
            return i + 1, h

        with jax.named_scope("decode"):
            _, h = jax.lax.while_loop(lambda c: c[0] < 3, body, (0, h + y))
        return h

    x, w = jnp.ones((64, 128)), jnp.ones((128, 128))
    compiled = jax.jit(f).lower(x, w).compile()
    compiled(x, w).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("capture"))
    jax.profiler.start_trace(log_dir)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("dispatch"):
            with jax.profiler.TraceAnnotation("launch"):
                out = compiled(x, w)
            with jax.profiler.TraceAnnotation("fetch"):
                out.block_until_ready()
    jax.profiler.stop_trace()
    from benchmark.lib import trace

    return compiled, trace.find_xplane(log_dir)


def test_the_capture_holds_each_executables_hlo(cpu_capture):
    compiled, path = cpu_capture
    modules = phases.hlo_modules(path)
    name = next(k for k in modules if k.startswith("jit_f("))
    scopes = phases.module_scopes(modules[name])
    # the same instructions, the same paths, as the executable itself gives
    own = compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto()
    assert scopes == phases.module_scopes(memoryview(own))
    found = {phases.scope_of(p) for p in scopes.values()}
    assert ("prefill", "mlp") in found and ("decode", "attn") in found


def test_what_the_program_left_outside_a_scope_stays_unscoped(cpu_capture):
    compiled, _ = cpu_capture
    proto = compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto()
    scopes = phases.module_scopes(memoryview(proto))
    text = compiled.as_text()
    sin = [k for k, v in scopes.items() if "sin" in v or "sine" in k]
    assert sin and all(phases.scope_of(scopes[k])[0] == phases.UNSCOPED for k in sin), (sin, text)


def test_load_walks_a_cpu_capture(cpu_capture):
    _, path = cpu_capture
    data = phases.load(path)
    assert data["ops"] and data["scopes"] and all(len(op) == 4 for op in data["ops"])
    assert {sp[0] for sp in data["host"]} == {"dispatch", "launch", "fetch"}
    r = phases.reduce_phases(data, layer_loop_trips=4)
    assert r["seconds"].get("decode", 0) > 0 and r["seconds"].get("prefill", 0) > 0
    assert 0 < r["unscoped_share"] < 1  # the sine and its matmul
    # no kernel to count by on the CPU, and none needed: two runs of a program
    # of three passes of the step loop, and of four layers of 64 rows
    assert r["steps"] == {"decode": 6.0} and r["kernel_calls"] == {}
    assert r["prefill_rows"] == 128.0


# ---- the recorded chip trace ----------------------------------------------------


def test_self_time_is_filed_by_phase(recorded, reduced):
    assert set(reduced["seconds"]) >= {"retrieve", "prefill", "decode"}
    assert "verify" not in reduced["seconds"]  # batch 8 decodes vanilla
    # leaves' self times against the union of their intervals
    assert sum(reduced["seconds"].values()) == pytest.approx(reduced["leaf_self_s"])
    assert reduced["leaf_self_s"] == pytest.approx(reduced["busy_s"], rel=0.02)
    assert reduced["unscoped_share"] < 0.02
    # prefill of eight 4096-token rows is most of this cut, its MLP most of that
    assert max(reduced["seconds"], key=reduced["seconds"].get) == "prefill"
    assert max(reduced["seconds_by_scope"], key=reduced["seconds_by_scope"].get) == "prefill/mlp"


def test_steps_and_rows_come_from_the_programs_own_statements(reduced):
    # three whole passes of the decode loop, and all 32 layers of one prefill
    # of eight rows; the kernels agree without being asked
    assert reduced["steps"] == {"decode": 3.0}
    assert reduced["prefill_rows"] == 8.0
    calls = reduced["kernel_calls"]
    assert calls["decode/attn/decode_attention_q8 bf16[8,8,4,128]"] // LAYERS == 3
    assert calls["prefill/attn/flash_attention bf16[256,4096,128]"] == LAYERS
    assert calls["retrieve/knn/knn_topk_pallas f32[8,5]"] == 2  # the coalescer made two calls


def test_the_counts_do_not_hang_on_a_kernels_name_or_layout(recorded, reduced):
    """A PR that swaps a kernel for an XLA operation, renames it or changes
    its result's layout moves no count."""
    def renamed(text):
        return text.replace("decode_attention_q8", "xla_decode").replace("flash_attention", "xla_flash")

    ops = [[renamed(label).replace("tpu_custom_call", "fusion")
            .replace("[256,4096,128]", "[8,4096,32,128]"), start, dur]
           for label, start, dur in recorded["ops"]]
    assert ops != recorded["ops"]
    scopes = {m: {renamed(k): v for k, v in sc.items()} for m, sc in recorded["scopes"].items()}
    swapped = phases.reduce_phases(dict(recorded, ops=ops, scopes=scopes), LAYERS)
    assert swapped["steps"] == reduced["steps"]
    assert swapped["prefill_rows"] == reduced["prefill_rows"]
    assert swapped["seconds"] == pytest.approx(reduced["seconds"])


def test_a_pass_the_slice_cut_counts_by_the_layers_that_ran(recorded, reduced):
    """Half a prefill is half its rows, whichever half, and needs no host span."""
    gen = next(sc for name, sc in recorded["scopes"].items() if name.startswith("jit_gen("))
    prefill = [op for op in recorded["ops"]
               if phases.scope_of(gen.get(op[0].split(" ")[0], ""))[0] == "prefill"]
    middle = sorted(op[1] for op in prefill)[len(prefill) // 2]
    for half in ([op for op in recorded["ops"] if op[1] >= middle],
                 [op for op in recorded["ops"] if op[1] < middle]):
        cut = phases.reduce_phases(dict(recorded, ops=half, host=[]), LAYERS)
        assert 3.0 < cut["prefill_rows"] < 5.0
        per_row = cut["seconds"]["prefill"] / cut["prefill_rows"]
        assert per_row == pytest.approx(reduced["seconds"]["prefill"] / 8.0, rel=0.03)


def test_every_large_operation_is_named_by_phase(reduced):
    assert len(reduced["top_ops"]) == 10
    for name, seconds in reduced["top_ops"]:
        assert name.split("/")[0] in phases.PHASES, name
        assert seconds > 0 and not name.split("/")[-1].split(" ")[0][-1].isdigit(), name


def test_gaps_go_to_the_innermost_span(recorded, reduced):
    assert sum(reduced["idle_gaps"].values()) > 0
    assert set(reduced["idle_gaps"]) <= set(phases.HOST_SPANS) | {phases.NO_SPAN, phases.SHORT_GAPS}
    # a gap inside dispatch > launch goes to launch; outside every span, to none
    spans = [["dispatch", 0.0, 1000e3], ["launch", 100e3, 300e3], ["generate", 0.0, 2000e3]]
    busy = [[0.0, 100e3], [400e3, 2000e3], [2100e3, 2200e3]]
    gaps = phases.attribute_gaps(busy, spans, 0.0, 2200e3)
    assert gaps == {"launch": pytest.approx(300e-6), phases.NO_SPAN: pytest.approx(100e-6)}


def ctx_for(reduced_or_none, **more):
    return {"trace": {} if reduced_or_none is not None else None, "stats": stats,
            "traffic": {"clients": 8}, "before": {}, "after": {}, **more}


def test_the_phase_metrics_read_the_recorded_numbers(reduced, monkeypatch):
    monkeypatch.setattr(phases, "of", lambda ctx: reduced if ctx["trace"] is not None else None)
    ctx = ctx_for(reduced)
    prefill = reader("prefill_device_ms_per_row").read(ctx)
    assert prefill == pytest.approx(reduced["seconds"]["prefill"] / 8 * 1e3)
    assert 350 < prefill < 430  # PERF.md: 388 ms a row on one chip
    step = reader("decode_step_device_ms").read(ctx)
    assert step == pytest.approx(reduced["seconds"]["decode"] / reduced["steps"]["decode"] * 1e3)
    assert 12 < step < 20
    assert reader("verify_step_device_ms").read(ctx) is None  # no verify phase in this slice
    retrieve = reader("retrieve_device_ms_per_answer").read(ctx)
    assert retrieve == pytest.approx(reduced["seconds"]["retrieve"] / reduced["retrievals"] * 1e3)
    assert reader("unscoped_device_time_share").read(ctx) == pytest.approx(
        reduced["unscoped_share"] * 100.0)
    # a run that was not traced reports none of them
    off = ctx_for(None)
    for name in ("prefill_device_ms_per_row", "decode_step_device_ms", "verify_step_device_ms",
                 "retrieve_device_ms_per_answer", "unscoped_device_time_share"):
        assert reader(name).read(off) is None


def test_the_layers_loops_trips_are_handed_in_by_the_cells_family(recorded, reduced, monkeypatch):
    """``of`` reads no depth from the configuration: the family states how
    many trips of the layers' loop a prefill pass makes, and with Mistral's
    (its depth) the recorded slice reads what it read."""
    monkeypatch.setattr(phases.trace, "find_xplane", lambda where: "recorded")
    monkeypatch.setattr(phases, "load", lambda path: recorded)
    got = phases.of({"trace": {}, "layer_loop_trips": LAYERS})  # no "config" in it
    got.pop("seconds_to_reduce")
    assert got == reduced
    assert got["prefill_rows"] == pytest.approx(8.0) and got["steps"] == reduced["steps"]
    # a family with one layer of its depth outside the loop: a pass is fewer trips
    fewer = phases.of({"trace": {}, "layer_loop_trips": LAYERS - 1})
    assert fewer["prefill_rows"] == pytest.approx(8.0 * LAYERS / (LAYERS - 1))
    assert fewer["seconds"] == reduced["seconds"] and fewer["steps"] == reduced["steps"]


def test_a_program_without_scopes_reads_as_unscoped(recorded):
    """The parent's programs: every operation is there, none names a phase."""
    bare = dict(recorded, scopes={})
    r = phases.reduce_phases(bare, LAYERS)
    assert r["unscoped_share"] == pytest.approx(1.0)
    assert set(r["seconds"]) == {phases.UNSCOPED}
    assert r["steps"] == {} and r["prefill_rows"] == 0.0
    ctx = {"trace": {}, "phases": r}
    for name in ("prefill_device_ms_per_row", "decode_step_device_ms", "verify_step_device_ms",
                 "retrieve_device_ms_per_answer"):
        assert reader(name).read(ctx) is None
    assert reader("unscoped_device_time_share").read(ctx) == pytest.approx(100.0)


ROWS = "rag_generate_dispatch_rows_total"


def dispatched(**by_path_rows):
    return {f'{ROWS}{{path="{k.split("_")[0]}",rows="{k.split("_")[1]}"}}': float(v)
            for k, v in by_path_rows.items()}


def test_full_batch_answer_share():
    read = reader("full_batch_answer_share").read
    ctx = ctx_for(None, traffic={"clients": 4})
    # every round one batch of four
    ctx["before"], ctx["after"] = dispatched(batched_4=16, fused_1=7), dispatched(batched_4=100, fused_1=7)
    assert read(ctx) == 100.0
    # three of twenty-odd rounds split one-and-three (PERF.md section 2): 78 answers
    ctx["after"] = dispatched(batched_4=16 + 66, fused_1=7 + 3, batched_3=9)
    assert read(ctx) == pytest.approx(66 / 78 * 100.0)
    # a program without the counter (the parent) reports nothing
    ctx["before"], ctx["after"] = {}, {"tpu_rag_engine_generate_calls": 10.0}
    assert read(ctx) is None


# ---- end to end, tiny, on the CPU ---------------------------------------------


def test_the_rehearsal_walks_the_phase_readers(tmp_path):
    """``--allow-cpu-rehearsal --trace 1`` reduces its own capture with
    ``lib/phases.py`` and reads every new metric of the cell: steps and rows
    come from the program's own statements, which the CPU makes too."""
    import subprocess

    cell = "mistral-7b-int8.closed8"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "6", "--trace", "1", "--allow-cpu-rehearsal"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        # a cache of its own: what the CPU loads from a persistent cache carries no scopes
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines() if x.startswith("{")]
    line = next(x for x in lines if x.get("event") == "phases")
    assert line["seconds"] and line["seconds_to_reduce"] >= 0
    assert {"retrieve", "prefill", "decode"} <= set(line["seconds"])
    assert line["steps"]["decode"] > 0 and line["prefill_rows"] > 0
    assert {"prefill_device_ms_per_row", "decode_step_device_ms", "retrieve_device_ms_per_answer",
            "unscoped_device_time_share", "full_batch_answer_share"} <= set(lines[-1]["metrics"])

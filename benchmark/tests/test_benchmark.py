"""The benchmark's own tests. Not tier 1: run by hand in the CPU rehearsal,

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import serve, stats, trace, traffic  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCHMARK = json.load(f)


def mix(name):
    return traffic.load_traffic(os.path.join(BENCH, "traffic", name + ".json"))


# ---- traffic -----------------------------------------------------------------


def free_arrivals():
    """An open-loop mix (no cell is one yet: PERF.md section 7, row 1): the
    closed mix's content with arrivals at a rate and three bursts of six."""
    m = dict(mix("solo"), loop="open", rate_rps=0.45,
             bursts={"episodes": 3, "arrivals": 6, "rate_multiplier": 4.0})
    for key in ("clients", "plan_requests"):
        m.pop(key)
    return m


def test_an_open_mix_loads_from_a_file(tmp_path):
    p = tmp_path / "open.json"
    p.write_text(json.dumps(free_arrivals()))
    m = traffic.load_traffic(str(p))
    assert m["loop"] == "open" and m["rate_rps"] == 0.45
    p.write_text(json.dumps(dict(free_arrivals(), arrival_seed=1)))  # no replay keys
    with pytest.raises(ValueError, match="unknown keys"):
        traffic.load_traffic(str(p))


def test_schedule_repeats_byte_for_byte():
    m = free_arrivals()
    a = json.dumps(traffic.open_schedule(2147484001, m, 45.0))
    assert a == json.dumps(traffic.open_schedule(2147484001, m, 45.0))
    assert a != json.dumps(traffic.open_schedule(2147484002, m, 45.0))


@pytest.mark.parametrize("seed", [0, 1, 7, 12345, 2**31 + 99])
def test_seed_moves_where_never_how_many(seed):
    m = free_arrivals()
    due = traffic.open_schedule(seed, m, 45.0)
    b = m["bursts"]
    assert len(due) == round(m["rate_rps"] * 45.0) + b["episodes"] * b["arrivals"]
    assert due == sorted(due) and 0.0 < due[0] and due[-1] < 45.0
    # the base gaps are one fixed set, whatever the seed
    ref = traffic.open_schedule(0, dict(m, bursts=dict(b, episodes=0)), 45.0)
    got = traffic.open_schedule(seed, dict(m, bursts=dict(b, episodes=0)), 45.0)
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip([0.0] + xs, xs))  # noqa: E731
    assert gaps(ref) == gaps(got)


@pytest.mark.parametrize("n", [12, 32, 59])
def test_every_seed_asks_the_same_questions_in_another_order(n):
    m = mix("solo")
    a, b = traffic.question_plan(5, m, n), traffic.question_plan(2**31 + 6, m, n)
    assert a == traffic.question_plan(5, m, n)
    assert len(a) == n and sorted(a) == sorted(b) and a != b
    assert len(set(a)) < n  # Zipf: documents are asked about more than once
    assert traffic.question_plan(5, m, n, stream=1) != a  # callers differ
    counts = traffic.zipf_counts(n, m["question_pool"], m["zipf_a"])
    assert sum(counts) == n and counts == sorted(counts, reverse=True)


def test_corpus_is_the_content_seeds_alone():
    a = traffic.corpus_pdf(2**31 + 11, 3, 60)
    assert a == traffic.corpus_pdf(2**31 + 11, 3, 60) != traffic.corpus_pdf(2**31 + 12, 3, 60)
    assert a.startswith(b"%PDF-1.4") and a.count(b"/Type /Page ") == 3
    assert b"Section 3 of the seeded corpus" in a and b"item2x48." in a


def test_unknown_traffic_key_is_an_error(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(dict(mix("solo"), surprise=1)))
    with pytest.raises(ValueError, match="unknown keys"):
        traffic.load_traffic(str(p))


# ---- arithmetic ----------------------------------------------------------------


def test_percentile_on_a_hand_made_list():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_runs_from_due_and_a_failure_is_never_answered():
    reqs = [
        {"due": 1.0, "start": 1.5, "end": 3.0, "status": 200, "tokens": 150},
        {"due": 2.0, "start": 2.0, "end": 2.5, "status": 429, "tokens": 0},
        {"due": 2.0, "start": 2.0, "end": 4.0, "status": 200, "tokens": 149},
    ]
    lat = stats.latencies_ms(reqs, 150)
    assert lat[0] == pytest.approx(2000.0)  # from due, not from start
    assert lat[1] == float("inf") and lat[2] == float("inf")
    assert stats.n_failed(reqs, 150) == 2


def test_exposition_deltas():
    before = stats.parse_exposition('# HELP x\nrag_c_sum{stage="generate"} 1.5\ntpu_rag_n 4\n')
    after = stats.parse_exposition('rag_c_sum{stage="generate"} 4.0\ntpu_rag_n 10\nnew_total 3\n')
    assert stats.delta(before, after, 'rag_c_sum{stage="generate"}') == 2.5
    assert stats.delta(before, after, "tpu_rag_n") == 6
    assert stats.delta(before, after, "new_total") == 3
    assert stats.delta(before, after, "absent") is None


def test_audit_verdict():
    score = {"argmax": [5, 6, 9], "max_logit": [1.0, 2.0, 3.0], "chosen_logit": [1.0, 2.0, 2.6]}
    assert stats.judge_audit(score, [5, 6, 9]) == 0.0
    assert stats.judge_audit(score, [5, 6, 7]) == pytest.approx(0.2)


def test_unknown_device_kind_raises():
    assert stats.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        stats.load_peaks("cpu")


# ---- the trace reduction ---------------------------------------------------------


def hand_made_trace():
    ms = 1e6
    ops = [
        ["while.1 s32[]", 0 * ms, 10 * ms],  # a decode loop holding three children
        ["fusion.1 bf16[1,16,4096]", 0 * ms, 4 * ms],
        ["decode_attention_q8.2 bf16[32,1,128] tpu_custom_call", 4 * ms, 3 * ms],
        ["all-reduce.3 bf16[1,16,4096]", 7 * ms, 2 * ms],
        ["fusion.9 bf16[1,16,4096]", 20 * ms, 5 * ms],  # a 10 ms gap before it
    ]
    host = [["generate", 0 * ms, 12 * ms], ["detokenize", 12 * ms, 6 * ms]]
    return {"/device:TPU:0": {"XLA Ops": ops, "Steps": [["1", 0, 25 * ms]]},
            "/host:CPU": {"wsgi": host}}


def test_busy_union_self_times_and_shares():
    r = trace.reduce_trace(hand_made_trace(), chips=1)
    assert r["busy_s"] == pytest.approx(0.015)  # 0-10 and 20-25 ms
    assert r["window_s"] == pytest.approx(0.025)
    ops = dict(r["device_ops"])
    assert not [k for k in ops if k.startswith("while")]  # a container: only what no child covers
    assert ops["fusion.1 bf16[1,16,4096]"] == pytest.approx(0.004)
    assert r["kernels"] == {"decode_attention_q8 bf16[32,1,128]": [1, pytest.approx(0.003)]}
    assert r["mosaic_share"] == pytest.approx(3 / 14)
    assert r["all_reduce_share"] == pytest.approx(2 / 14)
    assert dict(r["device_op_groups"])["fusion"] == pytest.approx(0.009)


def test_idle_gaps_by_host_span():
    gaps = dict(trace.reduce_trace(hand_made_trace(), chips=1)["idle_gaps"])
    # 10-20 ms idle: generate covers 2 ms of it, detokenize 6 ms
    assert gaps == {"detokenize": pytest.approx(0.010)}
    planes = hand_made_trace()
    planes["/host:CPU"] = {}
    gaps = dict(trace.reduce_trace(planes, chips=1)["idle_gaps"])
    assert gaps == {trace.NO_SPAN: pytest.approx(0.010)}


def test_op_label_from_an_hlo_line():
    line = ('%flash_attention.11 = bf16[32,4096,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[1]{0} '
            '%x, bf16[8,4096,128]{2,1,0} %k), custom_call_target="tpu_custom_call", operand_layout')
    assert trace.op_label(line) == "flash_attention.11 bf16[32,4096,128] tpu_custom_call"
    assert trace.op_label("%fusion.3 = (f32[1,5]{1,0}, s32[1,5]{1,0}) fusion(f32[8] %a)") == "fusion.3 f32[1,5]"
    assert trace.op_group("chunk_prefill_attention_q8.11 bf16[32,16,128] tpu_custom_call") \
        == "chunk_prefill_attention_q8"
    assert trace.op_label("jit_gen_rag(123)") == "jit_gen_rag(123)"


def test_recorded_trace_reduces():
    path = os.path.join(BENCH, "tests", "recorded_trace.json")
    with open(path, encoding="utf-8") as f:
        planes = json.load(f)
    r = trace.reduce_trace(planes, chips=1)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.0 < r["mosaic_share"] < 1.0
    # one prefill of a 4096 bucket: the decoder's flash kernel once a layer
    assert r["kernels"]["flash_attention bf16[32,4096,128]"][0] == 32
    assert "chunk_prefill_attention_q8 bf16[32,16,128]" in r["kernels"]
    assert r["device_ops"] and all(sec >= 0 for _, sec in r["device_ops"])
    assert sum(sec for _, sec in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s_per_chip"][0], rel=1e-6)


# ---- the data the harness is driven by -----------------------------------------


@pytest.mark.parametrize("cell", BENCHMARK["workloads"], ids=lambda c: c["name"])
def test_every_cell_resolves_to_files_that_parse(cell):
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    assert cfg["serving"]["tp"] == cell["chips"]
    assert os.path.exists(os.path.join(BENCH, "references", cfg["model_type"] + ".py"))
    model = family.model_config(cfg)
    assert family.layer_loop_trips(cfg) == model.num_layers == cfg["num_hidden_layers"]
    assert model.num_heads % cell["chips"] == 0 and model.num_kv_heads % cell["chips"] == 0
    m = mix(cell["traffic"])
    assert m["loop"] in ("closed", "open")
    e2e = [x for x in BENCHMARK["end_to_end"] if cell["name"] in x.get("workloads", [cell["name"]])]
    assert "setup_s" in {x["name"] for x in e2e} and len(e2e) >= 2
    for kind, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for x in BENCHMARK[kind]:
            assert os.path.exists(os.path.join(BENCH, folder, x["name"] + ".py")), x["name"]
    for x in BENCHMARK["per_layer"]:
        if cell["name"] in x.get("workloads", [cell["name"]]):
            assert x["moves"] in {y["name"] for y in e2e}, (x["name"], cell["name"])


def test_unknown_config_key_is_an_error(tmp_path):
    src = os.path.join(BENCH, "configs", "mistral-7b-v0.3-int8-tp1.json")
    with open(src, encoding="utf-8") as f:
        cfg = json.load(f)
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(dict(cfg, hidden_dim=1)))
    with pytest.raises(ValueError, match="unknown keys"):
        serve.load_config(str(p))
    p.write_text(json.dumps(dict(cfg, sliding_window=4096)))
    with pytest.raises(ValueError, match="sliding_window"):
        serve.load_config(str(p))


# ---- end to end, tiny, on the CPU ---------------------------------------------


@pytest.mark.parametrize("trace_flag", ["0", "1"])
def test_rehearsal_prints_the_contracts_last_line(trace_flag, tmp_path):
    cell = "mistral-7b-int8.closed8"
    # a compile cache of its own: a program the CPU loads from a persistent
    # cache carries no scopes, and the phase readers then find nothing to read
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "6", "--trace", trace_flag, "--allow-cpu-rehearsal"],
        cwd=REPO, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last) == keys | ({"breakdown"} if trace_flag == "1" else set())
    assert last["correct"] is False  # a rehearsal never says true
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    kind = "per_layer" if trace_flag == "1" else "end_to_end"
    want = {m["name"] for m in BENCHMARK[kind] if cell in m.get("workloads", [cell])}
    # a reader with nothing to read (no request met the coalescer at this
    # toy size) leaves its metric out; none may report what the cell lacks
    assert want - {"coalesce_wait_ms"} <= set(last["metrics"]) <= want
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace_flag == "1":
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}


def test_open_loop_sends_when_due_and_times_from_due():
    """No cell is an open loop yet (PERF.md section 7, row 1), so the sender
    is driven here against a stand-in server."""
    import importlib.util
    import time

    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    class Served:
        def generate(self, question, due):
            start = time.monotonic()
            time.sleep(0.05)  # longer than the gaps: requests overlap
            return {"due": due, "start": start, "end": time.monotonic(), "status": 200,
                    "question": question}

    m = free_arrivals()
    due = [d * 0.01 for d in traffic.open_schedule(3, m, 20.0)]  # 20 s of plan in 0.2 s
    plan = traffic.question_plan(3, m, len(due))
    t_open = time.monotonic()
    recs = []
    run.run_open(Served(), plan, due, t_open, recs)
    assert sorted(r["question"] for r in recs) == sorted(plan)
    assert sorted(round(r["due"] - t_open, 6) for r in recs) == [round(d, 6) for d in due]
    assert all(r["late_ms"] >= 0.0 for r in recs)
    # nobody waited for anybody: 41 answers of 50 ms one after another would take 2 s
    assert max(r["end"] for r in recs) - t_open < due[-1] + 1.0


def test_closed_loop_asks_its_plans_once_and_the_window_cuts_it():
    import importlib.util
    import time

    spec = importlib.util.spec_from_file_location("bench_run2", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    class Served:
        def generate(self, question, due):
            time.sleep(0.01)
            return {"due": due, "end": time.monotonic(), "status": 200, "question": question}

    plans = [traffic.question_plan(9, mix("closed4"), 5, stream=i) for i in range(4)]
    recs = []
    run.run_closed(Served(), plans, time.monotonic(), 30.0, recs)  # through long before 30 s
    assert sorted(r["question"] for r in recs) == sorted(q for p in plans for q in p)
    recs = []
    run.run_closed(Served(), plans, time.monotonic(), 0.025, recs)  # the window cuts the plans
    assert 4 <= len(recs) < 20


def test_reference_is_the_block_written_out():
    """The plain reference against numpy on a toy decoder: one layer, bf16 and
    int8 kernels alike, and the verdict arithmetic on its result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = serve.load_reference("mistral")
    rs = np.random.RandomState(0)
    D, F, H, KV, hd, V, S = 16, 24, 4, 2, 4, 32, 7
    cfg = dict(num_attention_heads=H, num_key_value_heads=KV, head_dim=hd, rms_norm_eps=1e-5,
               rope_theta=1e4, num_hidden_layers=1)
    w = {n: rs.randn(*shape).astype(np.float32) * 0.3 for n, shape in dict(
        wq=(D, H * hd), wk=(D, KV * hd), wv=(D, KV * hd), wo=(H * hd, D),
        w_gate=(D, F), w_up=(D, F), w_down=(F, D), emb=(V, D), head=(D, V)).items()}

    def quant(x):  # an int8 kernel is kernel_q * qscale
        scale = np.abs(x).max(axis=0) / 127.0
        return {"kernel_q": jnp.asarray(np.round(x / scale)[None].astype(np.int8)),
                "qscale": jnp.asarray(scale[None].astype(np.float32))}

    for as_int8 in (False, True):
        group = (lambda x: quant(x)) if as_int8 else (lambda x: {"kernel": jnp.asarray(x[None])})
        eff = {n: (np.asarray(group(x)["kernel_q"][0], np.float32) * np.asarray(group(x)["qscale"][0])
                   if as_int8 else x) for n, x in w.items()}
        ones = {"scale": jnp.ones((1, D), jnp.float32)}
        params = {"embedding": jnp.asarray(w["emb"]), "lm_head": jnp.asarray(w["head"]),
                  "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
                  "layers": {"attn": {n: group(w[n]) for n in ("wq", "wk", "wv", "wo")},
                             "mlp": {n: group(w[n]) for n in ("w_gate", "w_up", "w_down")},
                             "input_norm": ones, "post_attn_norm": ones}}
        toks = list(rs.randint(0, V, S))
        got, again = reference.score(params, cfg, [(toks[:4], toks[4:])] * 2, jax.devices()[0])
        assert all((got[k] == again[k]).all() for k in got)

        def rms(x):
            return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)

        def rope(x):  # [S, heads, hd], by halves
            inv = 1.0 / 1e4 ** (np.arange(0, hd, 2) / hd)
            ph = np.arange(S)[:, None] * inv[None]
            c, s_ = np.cos(ph)[:, None], np.sin(ph)[:, None]
            a, b = x[..., : hd // 2], x[..., hd // 2:]
            return np.concatenate([a * c - b * s_, b * c + a * s_], -1)

        h = w["emb"][toks]
        x = rms(h)
        q, k = rope((x @ eff["wq"]).reshape(S, H, hd)), rope((x @ eff["wk"]).reshape(S, KV, hd))
        v = (x @ eff["wv"]).reshape(S, KV, hd)
        out = np.zeros((S, H, hd))
        for head in range(H):
            sc = q[:, head] @ k[:, head // (H // KV)].T / np.sqrt(hd)
            sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            out[:, head] = (pr / pr.sum(-1, keepdims=True)) @ v[:, head // (H // KV)]
        h = h + out.reshape(S, H * hd) @ eff["wo"]
        x = rms(h)
        g = x @ eff["w_gate"]
        h = h + (g / (1 + np.exp(-g)) * (x @ eff["w_up"])) @ eff["w_down"]
        logits = (rms(h) @ w["head"])[3:6]
        assert list(got["argmax"]) == list(logits.argmax(-1))
        np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
        np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(3), toks[4:]], atol=2e-4)
        assert stats.half_gap_max(got) == pytest.approx(
            (logits.max(-1) - logits[np.arange(3), toks[4:]]).max() / 2, abs=2e-4)
        assert stats.logit_err_max({"chosen_logit": got["chosen_logit"] + 0.03}, got) == pytest.approx(0.03)


def test_no_chip_no_run():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "mistral-7b-int8.solo",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not p.stdout.strip().splitlines()[-1:] or "correct" not in p.stdout.strip().splitlines()[-1]

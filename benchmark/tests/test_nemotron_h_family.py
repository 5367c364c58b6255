"""Tests of the ``nemotron_h`` family's benchmark files (``families/nemotron_h.py``,
``references/nemotron_h.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_nemotron_h_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_nemotron_h_family.py`` prints the weight digests
that ``recorded_weights_nemotron_h.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_phi4flash_family.py`` makes
its own). The controls' walk over the cell's own requests is
``controls_nemotron_h.py`` (chip).
"""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import serve  # noqa: E402

NAME = "nemotron-3-super-120b-a12b-bf16-ep4-share"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_nemotron_h.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "nemotron-3-super-ep4.solo"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "ep_size", "vocab_size", "num_nextn_predict_layers"]
NEW_READERS = ("ssd_prefill_ms_per_row", "ssd_decode_ms_per_step", "ssd_chunk_prefill_roofline",
               "latent_expert_grouped_matmul_roofline", "moe_latent_projection_prefill_ms_per_row")


def toy(recite_gain=5.0):
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy, MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh

    cfg, family = serve.load_config(CONFIG)
    cfg.update(family.REHEARSAL_MODEL)
    model = family.model_config(cfg)
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=jax.devices()[:1])
    return cfg, family, model, family.make_params(model, DTypePolicy(), SEED, "bf16", mesh, recite_gain)


def digests() -> dict:
    import numpy as np

    out = {}
    for gain in (0.0, 5.0):
        params = toy(gain)[3]
        out[f"tp1.bf16.recite{gain:g}"] = {
            name: hashlib.sha256((str(a.dtype) + str(a.shape)).encode() + np.asarray(a).tobytes()).hexdigest()[:16]
            for name, a in sorted(params.items())}
    return out


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_family_draws_the_weights_it_drew():
    """Leaf by leaf: the cell's numbers are properties of one weight draw."""
    with open(RECORDED, encoding="utf-8") as f:
        want = json.load(f)["nemotron_h"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_the_head_is_the_reciting_head_and_the_gains_are_the_familys():
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, family, model, params = toy()
    assert family.layer_loop_trips(cfg) == 7 == model.num_layers
    key = jax.random.fold_in(serve.prng_key(SEED, 0), len(params))
    (want,) = serve.draw_head(key, params["embedding"], model.eos_token_ids, 5.0, params["lm_head"].dtype)
    # (inside the one jitted call the head's float32 sum is fused another way: a bf16 ulp on a few entries of 65536)
    np.testing.assert_allclose(np.asarray(params["lm_head"], np.float32), np.asarray(want, np.float32), atol=2e-3)
    f32 = lambda n: np.asarray(params[n], np.float32)  # noqa: E731
    floats = ("mamba_A_log", "mamba_D", "mamba_dt_bias", "moe_router_bias")
    assert all(params[n].dtype == jnp.float32 for n in floats) and params["mamba_in_proj"].dtype == jnp.bfloat16
    A = np.exp(f32("mamba_A_log"))
    assert 1.0 <= A.min() and A.max() <= 16.0 and A.max() / A.min() > 2  # log U(1, 16) a head (24 draws here)
    dt = np.log1p(np.exp(f32("mamba_dt_bias")))  # softplus of the bias: the log-uniform draw over the published range
    assert cfg["time_step_min"] * 0.99 <= dt.min() and dt.max() <= cfg["time_step_max"] * 1.01
    assert (f32("mamba_D") == 1).all() and (f32("norms") == 1).all() and (f32("mamba_norm") == 1).all()
    D, Di, GN = model.hidden_size, model.d_inner, model.n_groups * model.ssm_state_size
    w = f32("mamba_in_proj") * np.sqrt(D)  # z | x | B | C | dt: B's and C's columns at BC_GAIN
    assert abs(w[..., :2 * Di].std() - family.IN_GAIN) < 0.1 and abs(w[..., -model.mamba_num_heads:].std() - 1) < 0.2
    assert abs(w[..., 2 * Di:2 * Di + 2 * GN].std() - family.BC_GAIN) < 0.3
    assert abs(f32("mamba_conv_b").std() - family.CONV_BIAS_STD) < 0.03
    assert abs(f32("moe_router_bias").std() - family.ROUTER_BIAS_STD) < 0.05
    assert abs(f32("experts_w_up").std() * np.sqrt(model.moe_latent_size) - family.EXPERT_GAIN) < 0.05
    assert abs(f32("moe_shared_down").std() * np.sqrt(96) - family.SHARED_DOWN_GAIN) < 0.05
    assert abs(f32("attn_wq").std() * np.sqrt(D) - family.Q_GAIN) < 0.1


def test_the_configuration_is_the_published_one_cut_to_a_stage_and_a_share():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert (model.hidden_size, model.d_inner, model.conv_width, model.in_proj_width) == (4096, 8192, 10240, 18560)
    assert (model.mamba_num_heads, model.mamba_head_dim, model.n_groups, model.ssm_state_size) == (128, 64, 8, 128)
    assert (model.num_heads, model.num_kv_heads, model.head_dim, model.chunk_size) == (32, 2, 128, 128)
    assert (model.n_routed_experts, model.num_experts_per_tok, model.experts_held) == (512, 22, 128)
    assert (model.moe_latent_size, model.moe_intermediate_size, model.moe_shared_expert_intermediate_size) == (
        1024, 2688, 5376)
    assert model.hybrid_override_pattern == "MEMEMEM*EME" and model.vocab_size == 32768 == cfg["serving"][
        "tokenizer_vocab"]
    assert (model.num_mamba_layers, model.num_moe_layers, model.num_attention_layers) == (5, 5, 1)  # 5 : 5 : 1
    assert cfg["reduced"] == REDUCED and cfg["num_nextn_predict_layers"] == 0 and cfg["ep_size"] == 4
    for said in ("8 pipeline stages", "EP4", "data parallel", "quarter of the vocabulary", "stage 0"):
        assert said in cfg["deployment"], said
    assert sum("a later PR that learns otherwise changes one line" in a for a in cfg["assumed"]) >= 2
    assert any("jax.eval_shape" in a for a in cfg["assumed"]) and any("tokenizer" in a for a in cfg["assumed"])
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models import ssd_moe as sm

    shapes = jax.eval_shape(lambda: sm.init_ssd_moe_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    n = sum(s.size for s in jax.tree.leaves(shapes))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert (n, nbytes) == (4648163712, 9296336384)
    cache = jax.eval_shape(lambda: sm.make_ssd_cache(model, 1, 4352))
    assert cache.state.shape == (5, 1, 128, 64, 128) and cache.conv.shape == (5, 1, 3, 10240)
    assert cache.k.shape == (1, 1, 2, 4352, 128)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cfg["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v) == sorted(
        k for k in REDUCED if k != "ep_size")
    assert row["config"]["hybrid_override_pattern"].startswith(cfg["hybrid_override_pattern"])


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    for key, value in (("mlp_hidden_act", "silu"), ("use_conv_bias", False), ("num_nextn_predict_layers", 1),
                       ("mamba_proj_bias", True), ("intermediate_size", 4096)):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps({**cfg, key: value}), encoding="utf-8")
        with pytest.raises(ValueError, match=key):
            serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    with pytest.raises(ValueError, match="expand"):
        family.model_config({**loaded, "expand": 4})
    with pytest.raises(ValueError, match="dense MLP"):
        family.model_config({**loaded, "hybrid_override_pattern": "MEMEMEM*EM-"})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="ssd_moe"):
        serve.load_family("nemotron_h")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert "11 of 88" in cell["why"] and "quarter" in cell["why"] and family.layer_loop_trips(cfg) == 11
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["question_pool"], mix["zipf_a"], mix["corpus_pages"],
            mix["words_per_page"], mix["lead_in_requests"], mix["max_new_tokens"]) == (
        "closed", 1, 64, 1.1, 400, 500, 3, 150)
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert e2e == {"setup_s", "latency_p50_ms"}
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    assert [x["name"] for x in bench["per_layer"][-len(NEW_READERS):]] == list(NEW_READERS)  # appended, for this cell alone
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-len(NEW_READERS):])
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_new_scopes_and_find_nothing_without_them():
    from benchmark.lib import path_scopes, stats

    trip = "jit(gen)/{}/SSDMoEModel/while/body/closed_call/cond/branch_{}_fun/{}"
    assert path_scopes.fine_scope(trip.format("prefill/rows1", 0, "attn/SSDMoEModel._mamba/ssd/dot")) == (
        "prefill", "attn/ssd")
    assert path_scopes.fine_scope(trip.format("prefill/rows1", 2, "mlp/latent/dot")) == ("prefill", "mlp/latent")
    assert path_scopes.fine_scope(trip.format("prefill/rows1", 0, "attn/dot")) == ("prefill", "attn/other")
    assert path_scopes.fine_scope("jit(gen)/prefill/rows1/M/while/body/attn/latent/dot") == ("prefill", "attn/other")
    assert path_scopes.fine_scope("jit(embed)/attn/ssd") is None  # no phase in front of it
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": trip.format("prefill/rows1", 0, "attn/ssd/dot"),
                                "b": trip.format("prefill/rows1", 2, "mlp/latent/dot"),
                                "c": trip.format("decode/while/body", 0, "attn/ssd/mul"),
                                "d": trip.format("verify/while/body", 0, "attn/ssd/mul"),
                                "e": "jit(gen)/verify/while/body/attn/ssd/replay"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 40.0, 5.0],
                    ["d f32[8]", 50.0, 20.0], ["e f32[8]", 70.0, 8.0]]}
    by = path_scopes.seconds_by_fine_scope(data)
    assert by == {"prefill": {"attn/ssd": 1e-8, "mlp/latent": 3e-8}, "decode": {"attn/ssd": 5e-9},
                  "verify": {"attn/ssd": pytest.approx(2.8e-8)}}
    ctx = {"trace": {}, "phases": {"steps": {"decode": 5}, "prefill_rows": 4.0}, "path_scopes": by}
    assert _reader("ssd_prefill_ms_per_row").read(ctx) == pytest.approx(1e-8 / 4 * 1e3)
    assert _reader("moe_latent_projection_prefill_ms_per_row").read(ctx) == pytest.approx(3e-8 / 4 * 1e3)
    assert _reader("ssd_decode_ms_per_step").read(ctx) == pytest.approx(5e-9 / 5 * 1e3)
    speculating = {**ctx, "phases": {"steps": {"decode": 1, "verify": 7}, "prefill_rows": 4.0}}
    assert _reader("ssd_decode_ms_per_step").read(speculating) == pytest.approx(2.8e-8 / 7 * 1e3)
    # a program that opens no such scope (the parent's, another family's), or no trace at all
    other = {**ctx, "path_scopes": {"decode": {"attn/other": 2e-8, "mlp/router": 1e-9}}}
    for name in NEW_READERS:
        assert _reader(name).read({"trace": None, "config": {}, "peaks": None}) is None, name
        if "grouped" not in name:
            assert _reader(name).read({**other, "config": {}, "peaks": None}) is None, name

    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    chunk = _reader("ssd_chunk_prefill_roofline")
    # a live position of a layer: C B^T once a group and the masked product a head over the causal half of a
    # chunk of 128, two products with the [64, 128] state a head
    assert chunk.flops(1.0, 128, 64, 8, 128, 128) == 2 * (8 * 64.5 * 128 + 128 * (64.5 * 64 + 2 * 64 * 128))
    assert chunk.bytes_moved(1.0, 0.0, 128, 64, 8, 128) == (2 * 8192 + 2 * 1024) * 2 + 128 * 4
    assert chunk.bytes_moved(0.0, 1.0, 128, 64, 8, 128) == 2 * 128 * 64 * 128 * 4  # the state in and out
    live = 3500.0 * 5  # a prompt's live positions over the five Mamba-2 layers
    least = max(chunk.flops(4 * live, 128, 64, 8, 128, 128) / 197e12,
                chunk.bytes_moved(4 * live, 4 * 5, 128, 64, 8, 128) / 819e9)
    ctx = {"trace": {}, "phases": {"steps": {"decode": 5}, "prefill_rows": 4.0}, "config": cfg, "peaks": peaks,
           "path_scopes": {"prefill": {"attn/ssd": least * 10}}, "stats": stats, "requests": [{"status": 200}] * 2,
           "before": {chunk.ADVANCED: 7.0}, "after": {chunk.ADVANCED: 7.0 + 2 * live}}
    assert chunk.read(ctx) == pytest.approx(10.0)
    assert chunk.read({**ctx, "config": {"model_type": "jamba"}}) is None

    grouped = _reader("latent_expert_grouped_matmul_roofline")
    stat = lambda name: grouped.STAT.format(name)  # noqa: E731
    before = {stat(n): 0.0 for n in ("decode_layer_steps", "prefill_layer_calls", "decode_assignments_computed",
                                     "decode_experts_hit", "prefill_assignments_computed")}
    after = {stat("decode_layer_steps"): 100.0, stat("prefill_layer_calls"): 10.0,
             stat("decode_assignments_computed"): 550.0, stat("decode_experts_hit"): 540.0,
             stat("prefill_assignments_computed"): 10 * 22528.0}
    up = max(grouped.flops(22528, 1024, 2688) / 197e12, grouped.bytes_moved(22528, 128, 1024, 2688) / 819e9)
    step = grouped.bytes_moved(5.5, 5.4, 2688, 1024) / 819e9  # a decode step's down projection: its bytes
    tr = {"kernels": {"grouped_matmul bf16[45056,2688]": (10, 10 * up * 2), "grouped_matmul bf16[128,1024]": (100, 100 * step * 2),
                      "grouped_matmul bf16[45056,4096]": (10, 1.0)}}  # another width: another family's
    ctx = {"trace": tr, "config": cfg, "peaks": peaks, "stats": stats, "before": before, "after": after}
    assert grouped.read(ctx) == pytest.approx(50.0)
    assert grouped.read({**ctx, "config": {"num_experts": 256, "moe_intermediate_size": 1024}}) is None
    assert _reader("small_expert_grouped_matmul_roofline").read(ctx) is None  # and the older reader finds nothing here


def test_rehearsal_walks_to_its_last_line(tmp_path):
    # a compile cache of its own: a program the CPU loads from a persistent
    # cache carries no scopes, and the phase readers then find nothing to read
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--allow-cpu-rehearsal", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "12", "--trace", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["moe_dropped_assignment_share"]["value"] == 0.0
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer splits read them too
        for name in ("ssd_decode_ms_per_step", "moe_ffn_decode_ms_per_step", "full_attn_decode_ms_per_step"):
            assert 0 < metrics[name]["value"] < metrics["decode_step_device_ms"]["value"], name
    if "prefill_device_ms_per_row" in metrics:
        for name in ("ssd_prefill_ms_per_row", "moe_latent_projection_prefill_ms_per_row",
                     "held_experts_prefill_ms_per_row", "router_prefill_ms_per_row"):
            assert 0 < metrics[name]["value"] < metrics["prefill_device_ms_per_row"]["value"], name
    # the XLA forms of the rehearsal run no kernel and the CPU has no peaks: nothing to price
    assert "ssd_chunk_prefill_roofline" not in metrics and "latent_expert_grouped_matmul_roofline" not in metrics
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/nemotron_h.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"nemotron_h": digests()}, sort_keys=True))

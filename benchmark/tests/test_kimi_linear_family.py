"""Tests of the ``kimi_linear`` family's benchmark files (``families/kimi_linear.py``,
``references/kimi_linear.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_kimi_linear_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_kimi_linear_family.py`` prints the weight digests
that ``recorded_weights_kimi_linear.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_lfm2_moe_family.py`` makes its
own). The controls' walk over the cell's own requests is ``controls_kimi_linear.py`` (chip).
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
sys.path.insert(0, os.path.join(REPO, "tests"))

from benchmark.lib import serve  # noqa: E402

NAME = "kimi-linear-48b-a3b-bf16-ep16-share"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_kimi_linear.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "kimi-linear-ep16.solo"
NEW_READERS = ("kda_prefill_ms_per_row", "kda_decode_ms_per_step", "kda_chunk_prefill_roofline",
               "kda_prefill_live_position_share")
# the accepted metrics whose readers take this configuration's published names as they are
LISTED = ("spec_tokens_per_verify", "prefill_device_ms_per_row", "decode_step_device_ms",
          "retrieve_device_ms_per_answer", "moe_ffn_decode_ms_per_step", "moe_dropped_assignment_share",
          "held_experts_prefill_ms_per_row", "router_prefill_ms_per_row", "grouped_matmul_tile_fill_share",
          "decode_streamed_slot_share", "mla_flash_attention_roofline", "mla_decode_attention_roofline",
          "small_expert_grouped_matmul_roofline")


def toy(dtypes=None, recite_gain=5.0):
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy, MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh

    cfg, family = serve.load_config(CONFIG)
    cfg.update(family.REHEARSAL_MODEL)
    model = family.model_config(cfg)
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=jax.devices()[:1])
    params = family.make_params(model, dtypes or DTypePolicy(), SEED, "bf16", mesh, recite_gain)
    return cfg, family, model, params


def digests() -> dict:
    import numpy as np
    from flax import traverse_util

    out = {}
    for gain in (0.0, 5.0):
        params = toy(recite_gain=gain)[3]
        out[f"tp1.bf16.recite{gain:g}"] = {
            "/".join(path): hashlib.sha256(
                (str(a.dtype) + str(a.shape)).encode() + np.asarray(a).tobytes()).hexdigest()[:16]
            for path, a in sorted(traverse_util.flatten_dict(params).items())}
    return out


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_family_draws_the_weights_it_drew():
    """Leaf by leaf: the cell's numbers are properties of one weight draw."""
    with open(RECORDED, encoding="utf-8") as f:
        want = json.load(f)["kimi_linear"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_the_head_is_the_reciting_head_and_the_gains_are_the_familys():
    import jax
    import numpy as np
    from flax import traverse_util

    cfg, family, model, params = toy()
    assert family.layer_loop_trips(cfg) == 9 == model.num_moe_layers  # a trip a sparse layer
    flat = traverse_util.flatten_dict(params)
    key = jax.random.fold_in(serve.prng_key(SEED, 0), len(flat))
    (want,) = serve.draw_head(key, params["embedding"], model.eos_token_ids, 5.0, params["lm_head"].dtype)
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32), np.asarray(want, np.float32))
    f32 = lambda *path: np.asarray(flat[path], np.float32)  # noqa: E731
    D, W = model.hidden_size, model.kda_width
    taps = f32("kda_layers", "conv_w")  # [7, 4, 3 W]: four different numbers a channel, not a flat mean
    assert taps.shape == (7, 4, 3 * W) and abs(taps.std() * 2 - family.CONV_GAIN) < 0.1
    assert abs(f32("kda_layers", "wqkv", "kernel").std() * np.sqrt(D) - family.QKV_GAIN) < 0.05
    assert abs(f32("kda_layers", "wo", "kernel").std() * np.sqrt(W) - family.KDA_OUT_GAIN) < 0.05
    assert abs(f32("kda_layers", "f_b", "kernel").std() * np.sqrt(model.kda_gate_rank) - family.LOW_RANK_GAIN) < 0.05
    a, step = np.exp(f32("kda_layers", "A_log")), np.log1p(np.exp(f32("kda_layers", "dt_bias")))
    assert a.shape == (7, 4) and a.min() >= 1 and a.max() <= 16  # A uniform in (1, 16), float32
    assert step.shape == (7, 4, 16) and step.min() >= 0.00099 and step.max() <= 0.101  # the time step log-uniform
    assert flat["kda_layers", "A_log"].dtype == flat["kda_layers", "dt_bias"].dtype == np.float32
    assert (f32("kda_layers", "o_norm") == 1).all() and (f32("mla_layers", "kv_norm", "scale") == 1).all()
    assert abs(f32("mla_layers", "wq", "kernel").std() * np.sqrt(D) - family.MLA_Q_GAIN) < 0.1
    assert abs(f32("mla_layers", "wo", "kernel").std() * np.sqrt(model.num_heads * model.v_head_dim)
               - family.MLA_OUT_GAIN) < 0.05
    bias = f32("layers", "mlp", "router_bias")
    assert bias.any() and abs(bias.std() - family.ROUTER_BIAS_STD) < 0.03
    assert abs(f32("experts", "w_gate").std() * np.sqrt(D) - family.EXPERT_GAIN) < 0.05
    assert abs(f32("layers", "mlp", "shared", "w_down", "kernel").std() * np.sqrt(model.moe_intermediate_size)
               - family.EXPERT_GAIN) < 0.05
    assert abs(f32("lead_0", "mlp", "w_gate", "kernel").std() * np.sqrt(D) - family.DENSE_GAIN) < 0.1


def test_the_configuration_is_the_published_one_cut_to_a_share_at_its_whole_depth():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert (model.hidden_size, model.num_heads, model.kda_num_heads, model.kda_head_dim) == (2304, 32, 32, 128)
    assert (model.intermediate_size, model.moe_intermediate_size, model.short_conv_kernel_size) == (9216, 1024, 4)
    assert (model.num_experts, model.num_experts_per_token, model.experts_held) == (256, 8, 16)
    assert model.first_held == 16 * cfg["ep_rank"] and cfg["ep_size"] == 16
    assert (model.vocab_size, model.num_layers, model.first_k_dense, model.num_moe_layers) == (20480, 27, 1, 26)
    assert (model.num_kda_layers, model.num_mla_layers, model.q_lora_rank, model.mla_use_nope) == (20, 7, None, True)
    assert (model.kv_lora_rank, model.qk_nope_head_dim, model.qk_rope_head_dim, model.v_head_dim) == (512, 128, 64, 128)
    assert (model.routed_scaling_factor, model.n_group, model.topk_group, model.norm_topk_eps) == (2.446, 1, 1, 1e-20)
    assert cfg["reduced"] == ["ep_size", "vocab_size"] and cfg["num_nextn_predict_layers"] == 0
    assert "one of 16 chips that share each layer" in cfg["deployment"] and "all 27 layers" in cfg["deployment"]
    assert any("A_log" in a and "dt_bias" in a for a in cfg["assumed"]) and any("tokenizer" in a for a in cfg["assumed"])
    assert any("percentile" in a for a in cfg["assumed"]) and any("weights_seed" in a for a in cfg["assumed"])
    assert any("speculative" in a for a in cfg["assumed"])
    assert cfg["serving"]["engine"]["prompt_buckets"] == [2048, 4096]
    assert cfg["serving"]["tokenizer_vocab"] == 20480 == model.vocab_size
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models import delta_moe as dm

    shapes = jax.eval_shape(lambda: dm.init_delta_moe_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    count = sum(s.size for s in jax.tree.leaves(shapes))
    # the arithmetic of the file's ``assumed``, mixer by mixer
    D, W, F, V = 2304, 4096, 1024, 20480
    kda = 3 * D * W + W * D + 2 * (D * 128 + 128 * W) + D * 32 + 4 * 3 * W + W + 32 + 128
    mla = D * 6144 + D * 576 + 512 + 512 * 8192 + W * D
    sparse_ffn = 16 * 3 * D * F + 3 * D * F + D * 256 + 256
    assert (kda, mla, sparse_ffn) == (39514272, 29114880, 120914176)
    body = 20 * kda + 7 * mla + 3 * D * 9216 + 26 * sparse_ffn + 27 * 2 * D + D + V * D
    assert count == body + D * V == 4296057728
    assert any("4,296,057,728" in a for a in cfg["assumed"])
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 8.5e9 < nbytes < 8.7e9, nbytes  # every leaf bf16 but A_log, dt_bias and the routers' bias
    cache = jax.eval_shape(lambda: dm.make_delta_cache(model, 1, 4352))
    assert cache.state.size * 4 == 41943040 and cache.conv.size * 2 == 1474560  # 41.9 MB and 1.5 MB a row
    assert (cache.c_kv.size + cache.k_rope.size) * 2 == 35094528  # the seven latent planes: 35.1 MB a row
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert differs == ["vocab_size"]  # ep_size, the other reduced key, is not a published one
    assert cfg["linear_attn_config"] == row["config"]["linear_attn_config"] and cfg["num_hidden_layers"] == 27


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/kimi_linear.py`` against tier 1's ``tests/kimi_linear_reference.py``
    on one seeded input; each control moves the reading, and the structural
    ones are the faults tier 1's reference can make."""
    import jax
    import numpy as np

    import kimi_linear_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    reference = serve.load_reference("kimi_linear")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 300)], [int(t) for t in rng.integers(3, 512, 9)]
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0])
    pick = lambda logits: np.asarray(logits)[len(prompt) - 1:-1]  # noqa: E731
    logits = pick(tier1.forward(params, model, np.asarray(prompt + emitted)))
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        if control == "no_l2norm":  # a key longer than sqrt(2 / beta) makes the correction diverge: no number at all,
            assert not np.isfinite(faulty["chosen_logit"]).all()  # which run.py's ``<=`` refuses as it does a large one
            continue
        assert np.isfinite(faulty["chosen_logit"]).all(), control
        moved = np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max()
        assert moved > 1e-3, (control, moved)
    for control in tier1.FAULTS:
        if control == "no_l2norm":
            continue
        wrong = pick(tier1.forward(params, model, np.asarray(prompt + emitted), control))
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        np.testing.assert_allclose(faulty["chosen_logit"], wrong[np.arange(9), emitted], atol=2e-4)
    routed, alphas = [], []
    reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], route_log=routed, alpha_log=alphas)
    assert len(routed) == model.num_moe_layers and all(r["prefill"].sum() == 300 * 4 for r in routed)
    assert all(r["decode"].sum() == 8 * 4 for r in routed)  # every delivered token but the last is fed back
    assert all(r["prefill"].shape == (16,) for r in routed)  # over ALL the experts, for every rank's load
    assert len(alphas) == model.num_kda_layers
    assert all(0 < a["alpha_p5_p50_p95"][0] <= a["alpha_p5_p50_p95"][1] <= a["alpha_p5_p50_p95"][2] <= 1 for a in alphas)
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    path = tmp_path / "unknown.json"
    path.write_text(json.dumps({**cfg, "sliding_window": 512}), encoding="utf-8")
    with pytest.raises(ValueError, match="sliding_window"):
        serve.load_config(str(path))
    path.write_text(json.dumps({**cfg, "num_nextn_predict_layers": 1}), encoding="utf-8")
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    with pytest.raises(ValueError, match="q_lora_rank"):
        family.model_config({**loaded, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="num_key_value_heads"):
        family.model_config({**loaded, "num_key_value_heads": 8})
    with pytest.raises(ValueError, match="head_dim"):
        family.model_config({**loaded, "head_dim": 128})
    with pytest.raises(ValueError, match="every layer"):
        family.model_config({**loaded, "num_hidden_layers": 28})
    with pytest.raises(ValueError, match="linear_attn_config"):
        family.model_config({**loaded, "linear_attn_config": {**loaded["linear_attn_config"], "window": 4}})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="delta_moe"):
        serve.load_family("kimi_linear")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry  # appended
    assert family.layer_loop_trips(cfg) == 26
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "traffic", "solo-lfm2.json"), encoding="utf-8") as f:
        solo = json.load(f)
    same = ("loop", "clients", "question_pool", "zipf_a", "corpus_pages", "words_per_page", "max_new_tokens",
            "lead_in_requests")
    assert {k: mix[k] for k in same} == {k: solo[k] for k in same} and set(mix) == set(solo)
    assert mix["content_seed"] != solo["content_seed"]  # a corpus of its own
    assert (mix["loop"], mix["clients"], mix["max_new_tokens"], mix["lead_in_requests"]) == ("closed", 1, 150, 3)
    # 0.85 of the window at the finished change's measured median answer (PERF.md section 4)
    assert mix["plan_requests"] == int(0.85 * 51 / MEDIAN_ANSWER_S) and f"{mix['plan_requests']} questions" in cell["why"]
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert e2e == {"setup_s", "latency_p50_ms"}  # ~60 answers put a p90 on the sixth largest, which probes set
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    names = {x["name"] for x in mine}
    assert set(NEW_READERS) <= names and set(LISTED) <= names
    assert "latent_attn_decode_ms_per_step" not in names  # it reads all of decode/attn, here mostly linear layers
    assert "verify_step_device_ms" not in names  # the window does not speculate: its steps are decode steps
    assert [x["name"] for x in bench["per_layer"][-len(NEW_READERS):]] == list(NEW_READERS)  # appended
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-len(NEW_READERS):])
    for x in bench["per_layer"]:
        if "workloads" in x and CELL in x["workloads"]:
            assert x["workloads"][-1] == CELL, x["name"]  # appended to each list
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


MEDIAN_ANSWER_S = 1.2715  # the finished change's median answer on the chip (my chip runs, PR 49: 1271 to 1273 ms)


def test_readers_read_the_new_scopes_and_find_nothing_without_them():
    from benchmark.lib import kda_scopes, stats

    path = "jit(gen)/{}/DeltaMoEModel/while/body/closed_call/layers/attn/cond/branch_0_fun/DeltaAttention/kda/{}mul"
    decode, prefill, verify = "decode/while/body", "prefill/rows1", "verify/while/body"
    assert kda_scopes.fine_scope(path.format(decode, "delta/")) == ("decode", "kda/delta")
    assert kda_scopes.fine_scope(path.format(prefill, "conv/")) == ("prefill", "kda/conv")
    assert kda_scopes.fine_scope(path.format(verify, "gate/f_a/")) == ("verify", "kda/gate")
    assert kda_scopes.fine_scope(path.format(decode, "wqkv/")) == ("decode", "kda/other")  # a projection
    assert kda_scopes.fine_scope("jit(gen)/verify/while/body/attn/kda/delta/dot") == ("verify", "kda/delta")  # commit
    assert kda_scopes.fine_scope("jit(gen)/decode/while/body/attn/LatentAttention/latent/dot") is None
    assert kda_scopes.fine_scope("jit(gen)/decode/while/body/mlp/experts/mul") is None
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": path.format(decode, "delta/"), "b": path.format(decode, "wqkv/"), "c": "",
                                "d": path.format(prefill, "delta/"), "e": path.format(prefill, "conv/"),
                                "f": "jit(gen)/decode/while/body/attn/LatentAttention/latent/x"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 50.0, 5.0],
                    ["d f32[8]", 60.0, 20.0], ["e f32[8]", 80.0, 2.0], ["f f32[8]", 90.0, 4.0]]}
    split = kda_scopes.seconds_by_fine_scope(data)
    assert split == {"decode": {"kda/delta": 1e-8, "kda/other": 3e-8},
                     "prefill": {"kda/delta": 2e-8, "kda/conv": pytest.approx(2e-9)}}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counted = lambda n: {"tpu_rag_engine_kda_prefill_positions": n,  # noqa: E731
                         "tpu_rag_engine_kda_prefill_positions_bucketed": 4096.0 * 20 * n / (3584.0 * 20)}
    cfg, _ = serve.load_config(CONFIG)
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0}, "kda_scopes": split, "config": cfg,
           "peaks": peaks, "stats": stats, "before": counted(0.0), "after": counted(3584.0 * 20 * 10),
           "requests": [{"status": 200}] * 10}
    assert _reader("kda_decode_ms_per_step").read(ctx) == pytest.approx(4e-8 / 2 * 1e3)
    assert _reader("kda_prefill_ms_per_row").read(ctx) == pytest.approx(2.2e-8 / 4 * 1e3)
    assert _reader("kda_prefill_live_position_share").read(ctx) == pytest.approx(87.5)
    roof = _reader("kda_chunk_prefill_roofline")
    positions = 3584.0 * 20 * 4  # the slice's four rows
    least = max(roof.flops(positions, 32, 128) / 197e12, roof.bytes_moved(positions, 4 * 20, 32, 128) / 819e9)
    assert roof.read(ctx) == pytest.approx(least / 2e-8 * 100.0)
    assert roof.flops(1, 1, 128) == 2 * 64 * 128 + 32.5 * 128 + 2 * 63 * 128 + 6 * 128 * 128 + 65 * 128
    # a window that speculates: the steps are the verify loop's
    spec = {**ctx, "phases": {"steps": {"verify": 5, "decode": 1}, "prefill_rows": 4.0},
            "kda_scopes": {"verify": {"kda/delta": 5e-8}}}
    assert _reader("kda_decode_ms_per_step").read(spec) == pytest.approx(5e-8 / 5 * 1e3)
    # a program that opens no such scope (another family's trace, the parent), or no trace
    other = {**ctx, "kda_scopes": {}, "before": {}, "after": {}}
    for name in NEW_READERS:
        assert _reader(name).read(other) is None, name
    for name in NEW_READERS[:3]:
        assert _reader(name).read({"trace": None}) is None, name


def test_rehearsal_walks_to_its_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--allow-cpu-rehearsal", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "12", "--trace", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["moe_dropped_assignment_share"]["value"] == 0.0
    assert 0 < metrics["kda_prefill_live_position_share"]["value"] <= 100.0
    # the XLA forms of the rehearsal run no kernel: no slot to count, no kernel to time
    assert "small_expert_grouped_matmul_roofline" not in metrics and "decode_streamed_slot_share" not in metrics
    split = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "kda_scopes"' in line)
    assert split["seconds_by_fine_scope"]["prefill"]["kda/delta"] > 0
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/kimi_linear.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"kimi_linear": digests()}, sort_keys=True))

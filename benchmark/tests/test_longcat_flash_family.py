"""Tests of the ``longcat_flash`` family's benchmark files
(``families/longcat_flash.py``, ``references/longcat_flash.py``, the
configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_longcat_flash_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_longcat_flash_family.py`` prints the weight digests
that ``recorded_weights_longcat_flash.json`` pins (the family is served at tp
1 in bf16 only, so its digests are made here, as ``test_dots_vlm.py`` makes
its own).
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

NAME = "longcat-flash-bf16-ep32-share"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_longcat_flash.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "longcat-flash-ep32.closed8"


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
        want = json.load(f)["longcat_flash"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_rehearsal_model_keeps_the_family_s_shape():
    cfg, family, model, params = toy()
    assert model.sublayers_per_layer == 2 and model.num_cache_planes == 2 * model.num_layers >= 4
    assert model.zero_expert_num > 0 and model.scoring_func == "softmax" and model.n_shared_experts == 0
    assert 1 < model.experts_held < model.n_routed_experts and model.first_held > 0
    assert family.layer_loop_trips(cfg) == model.num_layers
    assert "shared" not in params["layers"]["mlp"] and {"attn_0", "attn_1", "ffn_0", "ffn_1"} <= set(params["layers"])
    assert params["layers"]["mlp"]["router"]["kernel"].shape[-1] == model.router_width


def test_the_configuration_is_the_published_one_but_for_what_it_lists():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert model.experts_held == 16 and model.first_held == 16 * cfg["ep_rank"] and model.num_layers == 4
    assert model.vocab_size * 8 == 131072 and model.router_width == 768 and model.num_cache_planes == 8
    assert sorted(cfg["reduced"]) == ["ep_size", "num_layers", "vocab_size"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models.latent_moe import init_latent_moe_params

    shapes = jax.eval_shape(lambda: init_latent_moe_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 10.2e9 < nbytes < 10.5e9, nbytes  # 10.35 GB of bf16: 65% of the chip
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == ["num_layers", "vocab_size"]  # ep_size is added, not changed


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/longcat_flash.py`` against tier 1's
    ``tests/longcat_flash_reference.py`` on one seeded input; each control
    moves the reading."""
    import jax
    import numpy as np

    import longcat_flash_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    reference = serve.load_reference("longcat_flash")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 40)], [int(t) for t in rng.integers(3, 512, 9)]
    log = []
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], route_log=log)
    logits = tier1.forward(params, model, prompt + emitted)[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    # who was chosen: moe_topk a token-layer over routed + zero outputs
    assert len(log) == model.num_layers and log[0]["zero_experts"] == model.zero_expert_num
    for entry in log:
        assert entry["prefill"].shape == (model.router_width,)
        assert entry["prefill"].sum() == len(prompt) * model.num_experts_per_tok
        assert entry["decode"].sum() == (len(emitted) - 1) * model.num_experts_per_tok
    moved = {}
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        moved[control] = np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max()
        assert moved[control] > 1e-3, control
    assert moved["fp8_matmuls"] > moved["int8_matmuls"] and moved["fp8_matmuls"] > moved["fp8_dense_path"]
    # a fault in ONE dense-path sublayer shows: the second sublayer left out, or
    # rebuilding its keys and values from the first sublayer's latent
    assert min(moved["drop_second_sublayer"], moved["shared_plane"]) > 10 * 1e-3
    early = tier1.forward(params, model, prompt + emitted, join_after=0)[len(prompt) - 1:-1]
    (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="early_join")
    np.testing.assert_allclose(faulty["chosen_logit"], early[np.arange(9), emitted], atol=2e-4)
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_a_zero_expert_that_is_not_the_identity_is_refused(tmp_path):
    """The program serves identity zero experts only and has no field for
    another kind: the family's ``FIXED`` is where a file that asks is refused."""
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    cfg["zero_expert_type"] = "copy"
    path = tmp_path / "copy.json"
    path.write_text(json.dumps(cfg), encoding="utf-8")
    with pytest.raises(ValueError, match="zero_expert_type"):
        serve.load_config(str(path))


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1
    model = family.model_config(cfg)
    assert family.layer_loop_trips(cfg) == model.num_layers == cfg["num_layers"]
    assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert {"setup_s", "latency_p50_ms", "output_tok_per_s"} <= e2e
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    assert {"zero_expert_assignment_share", "sparse_branch_decode_ms_per_step",
            "sparse_branch_prefill_ms_per_row"} <= {x["name"] for x in mine}
    assert "grouped_matmul_roofline" not in {x["name"] for x in mine}  # it reads a key this family lacks
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_fine_scopes_and_find_nothing_without_them():
    from benchmark.lib import stats

    from benchmark.lib import fine_scopes

    path = "jit(gen)/{}/layers/while/body/mlp/{}/dot_general"
    decode, prefill = "decode/while/body", "prefill/rows8"
    assert [fine_scopes.fine_scope(path.format(decode, s)) for s in ("router", "experts", "zero", "dense")] == [
        ("decode", "router"), ("decode", "experts"), ("decode", "zero"), ("decode", "dense")]
    assert fine_scopes.fine_scope("jit(gen)/decode/while/body/layers/while/body/mlp/add") == ("decode", "")
    assert fine_scopes.fine_scope("jit(gen)/decode/while/body/attn/latent/dot") is None
    assert fine_scopes.fine_scope("jit(gen)/prefill/rows8/mlp/experts/dot") == ("prefill", "experts")
    assert fine_scopes.fine_scope("jit(gen)/verify/while/body/mlp/experts/dot") is None
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": path.format(decode, "experts"), "b": path.format(decode, "dense"), "c": "",
                                "d": path.format(prefill, "experts"), "e": path.format(prefill, "zero")}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 50.0, 5.0],
                    ["d f32[8]", 60.0, 20.0], ["e f32[8]", 80.0, 2.0]]}
    split = fine_scopes.seconds_by_fine_scope(data)
    assert split == {"decode": {"experts": 1e-8, "dense": 3e-8}, "prefill": {"experts": 2e-8, "zero": 2e-9}}
    # the readers share one split through ctx, and find nothing without a trace
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0}, "fine_scopes": split}
    assert _reader("sparse_branch_decode_ms_per_step").read(ctx) == pytest.approx(1e-8 / 2 * 1e3)
    assert _reader("sparse_branch_prefill_ms_per_row").read(ctx) == pytest.approx(2.2e-8 / 4 * 1e3)
    assert _reader("sparse_branch_prefill_ms_per_row").read({**ctx, "fine_scopes": {}}) is None
    for name in ("sparse_branch_decode_ms_per_step", "sparse_branch_prefill_ms_per_row"):
        assert _reader(name).read({"trace": None}) is None
    zero = _reader("zero_expert_assignment_share")
    name = "tpu_rag_engine_moe_{}_assignments_zero".format
    after = {name("prefill"): 300.0, name("decode"): 90.0, name("chunk"): 10.0,
             "tpu_rag_engine_moe_tokens_routed": 100.0}
    ctx = {"before": {}, "after": after, "stats": stats, "config": {"moe_topk": 12}}
    assert zero.read(ctx) == pytest.approx(100.0 / 3)
    # a program without the counters (the parent's), or a family without the key
    assert zero.read({**ctx, "after": {"tpu_rag_engine_moe_tokens_routed": 100.0}}) is None
    assert zero.read({**ctx, "config": {}}) is None


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
    assert 25.0 < metrics["zero_expert_assignment_share"]["value"] < 40.0  # 8 of 24 outputs
    assert "unscoped_device_time_share" in metrics
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer split reads them too
        assert metrics["sparse_branch_decode_ms_per_step"]["value"] > 0
        assert metrics["moe_ffn_decode_ms_per_step"]["value"] >= metrics["sparse_branch_decode_ms_per_step"]["value"]
    if "prefill_device_ms_per_row" in metrics:
        assert 0 < metrics["sparse_branch_prefill_ms_per_row"]["value"] < metrics["prefill_device_ms_per_row"]["value"]
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/longcat_flash.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"longcat_flash": digests()}, sort_keys=True))

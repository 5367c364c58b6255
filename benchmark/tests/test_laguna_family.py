"""Tests of the ``laguna`` family's benchmark files (``families/laguna.py``,
``references/laguna.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_laguna_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_laguna_family.py`` prints the weight digests
that ``recorded_weights_laguna.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_dots_vlm.py`` makes its own).
The controls' walk over the cell's own requests is ``controls_laguna.py`` (chip).
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

NAME = "laguna-s-2.1-bf16-ep16-share"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_laguna.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "laguna-s-ep16.closed8"
NEW_READERS = ("window_attn_decode_ms_per_step", "full_attn_decode_ms_per_step", "window_flash_prefill_roofline",
               "window_layer_decode_slot_share", "small_expert_grouped_matmul_roofline")
PER_LAYER = ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer")


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
        want = json.load(f)["laguna"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_rehearsal_model_keeps_the_family_s_shape():
    cfg, family, model, params = toy()
    assert (model.num_lead, model.period, model.num_periods) == (1, 3, 2)
    assert set(model.layer_types) == {"full_attention", "sliding_attention"}
    assert sorted(set(h // model.num_kv_heads for h in model.num_attention_heads_per_layer)) == [6, 9]
    assert 1 < model.experts_held < model.num_experts and model.first_held > 0
    assert family.layer_loop_trips(cfg) == model.num_periods
    assert set(params["periods"]) == {"l0", "l1", "l2"} and "lead_0" in params
    assert params["periods"]["l0"]["attn"]["wq"]["kernel"].shape[-1] != params["periods"]["l2"]["attn"]["wq"]["kernel"].shape[-1]
    assert params["periods"]["l0"]["mlp"]["router"]["kernel"].shape[-1] == model.num_experts
    assert model.rope_of("full_attention").partial_rotary_factor == 0.5


def test_the_configuration_is_the_published_one_but_for_what_it_lists():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert model.experts_held == 16 and model.first_held == 16 * cfg["ep_rank"] and model.num_layers == 17
    assert model.vocab_size * 8 == 100352 and model.num_experts == 256 and model.num_experts_per_tok == 10
    assert (model.num_lead, model.period, model.num_periods, model.num_sliding_layers) == (1, 4, 4, 12)
    assert model.sliding_window == 512 and set(model.num_attention_heads_per_layer) == {48, 72}
    assert sorted(cfg["reduced"]) == sorted(("num_hidden_layers", "vocab_size", "ep_size") + PER_LAYER)
    assert len(cfg["assumed"]) >= 6 and "16 chips" in cfg["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models.windowed_moe import init_windowed_moe_params

    shapes = jax.eval_shape(lambda: init_windowed_moe_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 7.45e9 < nbytes < 7.55e9, nbytes  # 7.497 GB of bf16: 47% of the chip before the cache
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(("num_hidden_layers", "vocab_size") + PER_LAYER)  # ep_size is added, not changed
    for key in PER_LAYER:  # cut to the depth, nothing else
        assert cfg[key] == row["config"][key][:17]


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/laguna.py`` against tier 1's ``tests/laguna_reference.py``
    on one seeded input three windows long; each control moves the reading,
    and the two structural ones are the faults tier 1's reference can make."""
    import jax
    import numpy as np

    import laguna_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    reference = serve.load_reference("laguna")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 200)], [int(t) for t in rng.integers(3, 512, 9)]
    assert len(prompt) > 3 * model.sliding_window
    log = []
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], route_log=log)
    logits = tier1.forward(params, model, prompt + emitted)[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    assert len(log) == model.num_moe_layers
    for entry in log:
        assert entry["prefill"].shape == (model.num_experts,)
        assert entry["prefill"].sum() == len(prompt) * model.num_experts_per_tok
        assert entry["decode"].sum() == (len(emitted) - 1) * model.num_experts_per_tok
    moved = {}
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        moved[control] = np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max()
        assert moved[control] > 1e-3, control
    for control, fault in (("sliding_as_full", dict(sliding_as_full=True)), ("no_gate", dict(gate=False))):
        wrong = tier1.forward(params, model, prompt + emitted, **fault)[len(prompt) - 1:-1]
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        np.testing.assert_allclose(faulty["chosen_logit"], wrong[np.arange(9), emitted], atol=2e-4)
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    for key, value, match in (("gating", "per-layer", "gating"), ("decoder_sparse_step", 2, "decoder_sparse_step"),
                              ("attention_bias", True, "attention_bias")):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps({**cfg, key: value}), encoding="utf-8")
        with pytest.raises(ValueError, match=match):
            serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    for key, value, match in (("moe_router_logit_softcapping", 30.0, "softcapping"),
                              ("moe_apply_router_weight_on_input", True, "OUTPUT"),
                              ("gating_types", ["per_head"] * 16 + ["per_layer"], "gating_types"),
                              ("mlp_only_layers", [0, 1], "mlp_only_layers")):
        with pytest.raises(ValueError, match=match):
            family.model_config({**loaded, key: value})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="windowed_moe"):
        serve.load_family("laguna")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200
    assert family.layer_loop_trips(cfg) == 4
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "traffic", "closed8-dots.json"), encoding="utf-8") as f:
        dots = json.load(f)
    content = ("loop", "clients", "content_seed", "question_pool", "zipf_a", "corpus_pages", "words_per_page",
               "max_new_tokens", "lead_in_requests")
    assert {k: mix[k] for k in content} == {k: dots[k] for k in content}
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert {"setup_s", "latency_p50_ms", "output_tok_per_s"} <= e2e
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    assert set(NEW_READERS) <= {x["name"] for x in mine}
    assert [x["name"] for x in bench["per_layer"][-5:]] == list(NEW_READERS)  # appended, and for this cell alone
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-5:])
    assert "grouped_matmul_roofline" not in {x["name"] for x in mine}  # it reads a key this family lacks
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_new_scopes_and_counters_and_find_nothing_without_them():
    from benchmark.lib import attn_scopes, stats

    path = "jit(gen)/{}/periods/while/body/l1/attn/{}/dot_general"
    decode, prefill = "decode/while/body", "prefill/rows8"
    assert [attn_scopes.fine_scope(path.format(decode, s)) for s in ("window", "global", "gate")] == [
        ("decode", "window"), ("decode", "global"), ("decode", "gate")]
    assert attn_scopes.fine_scope("jit(gen)/decode/while/body/periods/while/body/l0/attn/wq/dot") == ("decode", "")
    assert attn_scopes.fine_scope("jit(gen)/decode/while/body/mlp/experts/dot") is None
    assert attn_scopes.fine_scope("jit(gen)/prefill/rows8/attn/window/flash") == ("prefill", "window")
    assert attn_scopes.fine_scope("jit(gen)/verify/while/body/attn/window/dot") is None
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": path.format(decode, "window"), "b": path.format(decode, "global"), "c": "",
                                "d": path.format(prefill, "window"), "e": "jit(gen)/decode/while/body/attn/latent/x"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 50.0, 5.0],
                    ["d f32[8]", 60.0, 20.0], ["e f32[8]", 80.0, 2.0]]}
    split = attn_scopes.seconds_by_fine_scope(data)
    assert split == {"decode": {"window": 1e-8, "global": 3e-8, "": 2e-9}, "prefill": {"window": 2e-8}}
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0}, "attn_scopes": split}
    assert _reader("window_attn_decode_ms_per_step").read(ctx) == pytest.approx(1e-8 / 2 * 1e3)
    assert _reader("full_attn_decode_ms_per_step").read(ctx) == pytest.approx(3e-8 / 2 * 1e3)
    # a program that opens no such scope (the latent family's trace above), or no trace
    other = {**ctx, "attn_scopes": {"decode": {"": 2e-9}}}
    for name in ("window_attn_decode_ms_per_step", "full_attn_decode_ms_per_step"):
        assert _reader(name).read(other) is None and _reader(name).read({"trace": None}) is None

    share = _reader("window_layer_decode_slot_share")
    after = {share.STREAMED: 3 * 256 * 12 * 8.0, share.ALLOCATED: 4352 * 12 * 8.0}
    ctx = {"before": {}, "after": after, "stats": stats}
    assert share.read(ctx) == pytest.approx(100 * 768 / 4352)
    assert share.read({**ctx, "after": {}}) is None  # the parent's program: no such counters

    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flash = _reader("window_flash_prefill_roofline")
    assert flash.live_pairs(100, 512) == 100 * 101 / 2 and flash.live_pairs(3600, 512) == 512 * 513 / 2 + 3088 * 512
    row = flash.flops(3600, 512, 72, 128) / peaks["bf16_flops_per_s"]
    assert row > flash.bytes_moved(4096, 72, 8, 128) / peaks["hbm_bytes_per_s"]  # bound by compute
    tr = {"kernels": {"flash_attention_window bf16[576,4096,128]": (24, 24 * 8 * row * 2.5),
                      "flash_attention_window bf16[72,4096,128]": (3, 3 * row * 2.5),
                      "flash_attention bf16[384,4096,128]": (5, 1.0)}}
    ctx = {"trace": tr, "config": cfg, "prompt_tokens": [3600] * 8, "peaks": peaks, "stats": stats}
    assert flash.read(ctx) == pytest.approx(40.0)
    assert flash.read({**ctx, "trace": {"kernels": {"flash_attention bf16[384,4096,128]": (5, 1.0)}}}) is None
    assert flash.read({**ctx, "config": {"hidden_size": 7168}}) is None and flash.read({**ctx, "trace": None}) is None

    gmm = _reader("small_expert_grouped_matmul_roofline")
    name = "tpu_rag_engine_moe_{}".format
    after = {name("decode_layer_steps"): 16.0, name("prefill_layer_calls"): 16.0,
             name("decode_assignments_computed"): 16 * 5.0, name("decode_experts_hit"): 16 * 4.0,
             name("prefill_assignments_computed"): 16 * 20480.0}
    least_up = max(gmm.flops(20480, 3072, 1024) / peaks["bf16_flops_per_s"],
                   gmm.bytes_moved(20480, 16, 3072, 1024) / peaks["hbm_bytes_per_s"])
    tr = {"kernels": {"grouped_matmul bf16[40960,1024]": (32, 32 * least_up * 2), "fusion.1 bf16[8]": (1, 1.0)}}
    ctx = {"trace": tr, "config": cfg, "before": {}, "after": after, "stats": stats, "peaks": peaks}
    assert gmm.read(ctx) == pytest.approx(50.0)
    # never more rows than the buffer the trace shows: two passes of a skewed layer stay under 100
    tr = {"kernels": {"grouped_matmul bf16[10240,1024]": (64, 64 * max(
        gmm.flops(10240, 3072, 1024) / peaks["bf16_flops_per_s"],
        gmm.bytes_moved(10240, 16, 3072, 1024) / peaks["hbm_bytes_per_s"]))}}
    assert gmm.read({**ctx, "trace": tr}) == pytest.approx(100.0)
    assert gmm.read({**ctx, "config": {"n_routed_experts": 256, "moe_intermediate_size": 2048}}) is None
    assert gmm.read({**ctx, "after": {}}) is None and gmm.read({**ctx, "trace": None}) is None


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
    assert "unscoped_device_time_share" in metrics
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer split reads them too
        assert 0 < metrics["window_attn_decode_ms_per_step"]["value"] < metrics["decode_step_device_ms"]["value"]
        assert 0 < metrics["full_attn_decode_ms_per_step"]["value"] < metrics["decode_step_device_ms"]["value"]
    # the XLA forms of the rehearsal run no kernel: no walk to count, no kernel to time
    for name in ("window_layer_decode_slot_share", "decode_streamed_slot_share", "window_flash_prefill_roofline",
                 "small_expert_grouped_matmul_roofline"):
        assert name not in metrics
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/laguna.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"laguna": digests()}, sort_keys=True))

"""Tests of the ``lfm2_moe`` family's benchmark files (``families/lfm2_moe.py``,
``references/lfm2_moe.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_lfm2_moe_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_lfm2_moe_family.py`` prints the weight digests
that ``recorded_weights_lfm2_moe.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_jamba_family.py`` makes its
own). The controls' walk over the cell's own requests is ``controls_lfm2_moe.py`` (chip).
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

NAME = "lfm2-24b-a2b-bf16-pp4-stage"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_lfm2_moe.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "lfm2-24b-a2b-pp4.solo"
NEW_READERS = ("short_conv_prefill_ms_per_row", "short_conv_decode_ms_per_step", "decode_experts_hit_per_layer_step")
# the accepted metrics whose readers take this configuration's published names as they are
LISTED = ("spec_tokens_per_verify", "prefill_device_ms_per_row", "decode_step_device_ms",
          "retrieve_device_ms_per_answer", "full_attn_decode_ms_per_step", "moe_ffn_decode_ms_per_step",
          "moe_dropped_assignment_share", "held_experts_prefill_ms_per_row", "router_prefill_ms_per_row",
          "small_expert_grouped_matmul_roofline", "decode_streamed_slot_share", "prefill_computed_token_share")


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
        want = json.load(f)["lfm2_moe"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_the_head_is_the_reciting_head_and_the_gains_are_the_familys():
    import jax
    import numpy as np
    from flax import traverse_util

    cfg, family, model, params = toy()
    assert not model.tie_word_embeddings and family.layer_loop_trips(cfg) == 2 == model.num_periods
    flat = traverse_util.flatten_dict(params)
    key = jax.random.fold_in(serve.prng_key(SEED, 0), len(flat))
    (want,) = serve.draw_head(key, params["embedding"], model.eos_token_ids, 5.0, params["lm_head"].dtype)
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32), np.asarray(want, np.float32))
    f32 = lambda *path: np.asarray(flat[path], np.float32)  # noqa: E731
    D = model.hidden_size
    taps = f32("lead_0", "shortconv", "conv_w")  # [3, D]: three different numbers a channel, not a flat mean
    assert taps.shape == (3, D) and abs(taps.std() * np.sqrt(3) - family.CONV_GAIN) < 0.15
    assert np.abs(taps - taps.mean(0)).min(0).max() > 0.05
    assert abs(f32("lead_0", "shortconv", "in_proj", "kernel").std() * np.sqrt(D) - family.IN_GAIN) < 0.1
    assert abs(f32("lead_0", "shortconv", "out_proj", "kernel").std() * np.sqrt(D) - family.OUT_GAIN) < 0.05
    for name in ("q_norm", "k_norm"):  # bf16's 1.203125
        assert np.abs(f32("periods", "l0", "attn", name, "scale") - family.QK_SCALE).max() < 0.01
    assert (f32("periods", "l0", "operator_norm", "scale") == 1).all() and (f32("embedding_norm", "scale") == 1).all()
    bias = f32("periods", "l0", "mlp", "router_bias")
    assert bias.any() and abs(bias.std() - family.ROUTER_BIAS_STD) < 0.03
    assert abs(f32("experts", "w_gate").std() * np.sqrt(D) - family.EXPERT_GAIN) < 0.05
    assert abs(f32("experts", "w_down").std() * np.sqrt(model.moe_intermediate_size) - family.EXPERT_GAIN) < 0.05
    assert abs(f32("lead_0", "mlp", "w_gate", "kernel").std() * np.sqrt(D) - family.DENSE_GAIN) < 0.1


def test_the_configuration_is_the_published_one_cut_to_a_stage():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads, model.head_dim) == (2048, 32, 8, 64)
    assert (model.intermediate_size, model.moe_intermediate_size, model.conv_L_cache) == (11776, 1536, 3)
    assert (model.num_experts, model.num_experts_per_tok, model.experts_held, model.first_held) == (64, 4, 64, 0)
    assert (model.vocab_size, model.num_layers, model.num_lead, model.period, model.num_periods) == (65536, 10, 2, 4, 2)
    assert (model.num_conv_layers, model.num_attention_layers, model.norm_topk_eps) == (8, 2, 1e-6)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"] and not model.tie_word_embeddings
    assert "stage 0 of a four-stage pipeline" in cfg["deployment"] and "all 64 experts" in cfg["deployment"]
    assert "FIVE-stage" in cfg["deployment"]  # the fallback the issue fixed, stated and not taken
    assert sum("a later PR that learns otherwise changes one line" in a for a in cfg["assumed"]) == 5
    assert any("untied" in a for a in cfg["assumed"]) and any("tokenizer" in a for a in cfg["assumed"])
    assert any("speculative=auto" in a for a in cfg["assumed"]) and "engine" in cfg["serving"]
    assert cfg["serving"]["engine"] == {"prompt_buckets": [2048, 4096]}
    assert cfg["serving"]["tokenizer_vocab"] == 65536 == model.vocab_size
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models import conv_moe as cm

    shapes = jax.eval_shape(lambda: cm.init_conv_moe_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    count = sum(s.size for s in jax.tree.leaves(shapes))
    # the arithmetic of the file's ``assumed``, operator by operator
    D, F, E, V = 2048, 1536, 64, 65536
    conv, attention = 3 * D * D + D * D + 3 * D, 2 * D * D + 2 * D * 512 + 2 * 64
    dense_ffn, sparse_ffn = 3 * D * 11776, E * 3 * D * F + D * E + E
    assert (conv, attention, dense_ffn, sparse_ffn) == (16783360, 10485888, 72351744, 603979776 + 131136)
    body = 8 * conv + 2 * attention + 2 * dense_ffn + 8 * sparse_ffn + 10 * 2 * D + D + V * D
    assert body == 5267090176 and count == body + D * V == 5401307904
    assert any("5,401,307,904" in a and "5,267,090,176" in a for a in cfg["assumed"])
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 10.80e9 < nbytes < 10.81e9, nbytes  # every leaf bf16 but the routers' float32 selection bias
    cache = jax.eval_shape(lambda: cm.make_conv_cache(model, 1, 4352))
    assert cache.k.shape == (2, 1, 8, 4352, 64) and cache.conv.shape == (8, 1, 2, 2048)
    assert 2 * cache.k.size * 2 == 17825792 and cache.conv.size * 2 == 65536  # K/V 17.8 MB, state 65 KB a row
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"])
    assert cfg["layer_types"] == row["config"]["layer_types"][:10] and cfg["num_hidden_layers"] == 10


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/lfm2_moe.py`` against tier 1's ``tests/lfm2_moe_reference.py``
    on one seeded input; each control moves the reading, and the structural
    ones are the faults tier 1's reference can make."""
    import jax
    import numpy as np

    import lfm2_moe_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    cfg["serving"] = dict(cfg["serving"], engine={"prompt_buckets": [512, 640]})
    reference = serve.load_reference("lfm2_moe")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 600)], [int(t) for t in rng.integers(3, 512, 9)]
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0])
    pick = lambda logits: np.asarray(logits)[len(prompt) - 1:-1]  # noqa: E731
    logits = pick(tier1.forward(params, model, prompt + emitted))
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        assert np.isfinite(faulty["chosen_logit"]).all(), control
        assert np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max() > 1e-3, control
    n = len(prompt)
    for control, fault in (("no_conv_handover", dict(drop_conv_at=n)), ("pads_unmasked", dict(pads=40)),
                           ("taps_reversed", dict(taps_reversed=True)), ("no_qk_norm", dict(qk_norm=False)),
                           ("bias_in_weights", dict(bias_in_weights=True)), ("unnormed_topk", dict(normed=False))):
        wrong = pick(tier1.forward(params, model, prompt + emitted, **fault))
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        np.testing.assert_allclose(faulty["chosen_logit"], wrong[np.arange(9), emitted], atol=2e-4)
    routed = []
    reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], route_log=routed)
    assert len(routed) == model.num_moe_layers and all(r["prefill"].sum() == 600 * 2 for r in routed)
    assert all(r["decode"].sum() == 8 * 2 for r in routed)  # every delivered token but the last is fed back
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    path = tmp_path / "unknown.json"
    path.write_text(json.dumps({**cfg, "sliding_window": 512}), encoding="utf-8")
    with pytest.raises(ValueError, match="sliding_window"):
        serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    with pytest.raises(ValueError, match="no bias"):
        family.model_config({**loaded, "conv_bias": True})
    with pytest.raises(ValueError, match="selection bias"):
        family.model_config({**loaded, "use_expert_bias": False})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.model_config({**loaded, "num_hidden_layers": 40})
    with pytest.raises(ValueError, match="rope_type"):
        family.model_config({**loaded, "rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}})
    with pytest.raises(ValueError, match="layer_types"):
        family.model_config({**loaded, "layer_types": ["conv"] * 9 + ["sliding_attention"]})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="conv_moe"):
        serve.load_family("lfm2_moe")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry  # appended
    assert family.layer_loop_trips(cfg) == 2
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "traffic", "solo.json"), encoding="utf-8") as f:
        solo = json.load(f)
    same = ("loop", "clients", "content_seed", "question_pool", "zipf_a", "corpus_pages", "words_per_page",
            "max_new_tokens", "lead_in_requests")
    assert {k: mix[k] for k in same} == {k: solo[k] for k in same} and set(mix) == set(solo)
    assert (mix["loop"], mix["clients"], mix["max_new_tokens"], mix["lead_in_requests"]) == ("closed", 1, 150, 3)
    # 0.85 of the window at the finished change's measured median answer (0.4225 s: PERF.md section 6)
    assert mix["plan_requests"] == int(0.85 * 51 / 0.4225) == 102 and f"{mix['plan_requests']} questions" in cell["why"]
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert e2e == {"setup_s", "latency_p50_ms", "latency_p90_ms"}  # no one-caller cell reports tokens a second
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    names = {x["name"] for x in mine}
    assert set(NEW_READERS) <= names and set(LISTED) <= names
    assert "verify_step_device_ms" not in names and "flash_prefill_roofline" not in names
    assert [x["name"] for x in bench["per_layer"][-3:]] == list(NEW_READERS)  # appended, and for this cell alone
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-3:])
    for x in bench["per_layer"]:
        if "workloads" in x and CELL in x["workloads"]:
            assert x["workloads"][-1] == CELL, x["name"]  # appended to each list
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_new_scopes_and_find_nothing_without_them():
    from benchmark.lib import ssm_scopes, stats

    path = "jit(gen)/{}/ConvMoEModel/periods/while/body/closed_call/attn/shortconv/{}mul"
    decode, prefill = "decode/while/body", "prefill/rows1"
    assert ssm_scopes.fine_scope(path.format(decode, "conv/")) == ("decode", "conv")
    assert ssm_scopes.fine_scope(path.format(prefill, "conv/")) == ("prefill", "conv")
    # the operator's projections carry the module's name, which is not the scope's
    assert ssm_scopes.fine_scope(path.format(decode, "in_proj/")) == ("decode", "")
    assert ssm_scopes.fine_scope("jit(gen)/verify/while/body/attn/shortconv/conv/mul") is None
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": path.format(decode, "conv/"), "b": path.format(decode, "in_proj/"), "c": "",
                                "d": path.format(prefill, "conv/"), "e": "jit(gen)/decode/while/body/attn/attn/global/x"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 50.0, 5.0],
                    ["d f32[8]", 60.0, 20.0], ["e f32[8]", 80.0, 2.0]]}
    split = ssm_scopes.seconds_by_fine_scope(data, names=("conv",))
    assert split == {"decode": {"conv": 1e-8, "": 3.2e-8}, "prefill": {"conv": 2e-8}}
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0}, "ssm_scopes": split}
    assert _reader("short_conv_decode_ms_per_step").read(ctx) == pytest.approx(1e-8 / 2 * 1e3)
    assert _reader("short_conv_prefill_ms_per_row").read(ctx) == pytest.approx(2e-8 / 4 * 1e3)
    # a program that opens no such scope (another family's trace), or no trace
    other = {**ctx, "ssm_scopes": {"decode": {"": 2e-9}, "prefill": {"": 1e-9}}}
    for name in NEW_READERS[:2]:
        assert _reader(name).read(other) is None and _reader(name).read({"trace": None}) is None
    hit = _reader("decode_experts_hit_per_layer_step")
    counted = lambda h, s: {"tpu_rag_engine_moe_decode_experts_hit": h, "tpu_rag_engine_moe_decode_layer_steps": s}  # noqa: E731
    ctx = {"stats": stats, "before": counted(100.0, 20.0), "after": counted(448164.0, 112036.0)}
    assert hit.read(ctx) == pytest.approx(4.0)
    assert hit.read({**ctx, "after": counted(100.0, 20.0)}) is None  # a window of verify steps only
    assert hit.read({"stats": stats, "before": {}, "after": {}}) is None  # a program without the counters


def test_rehearsal_walks_to_its_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--allow-cpu-rehearsal", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "12", "--trace", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["decode_experts_hit_per_layer_step"]["value"] == 2.0  # the toy's top-2, at batch 1
    assert metrics["moe_dropped_assignment_share"]["value"] == 0.0
    if "prefill_device_ms_per_row" in metrics and "short_conv_prefill_ms_per_row" in metrics:
        assert 0 < metrics["short_conv_prefill_ms_per_row"]["value"] < metrics["prefill_device_ms_per_row"]["value"]
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer split reads them too
        assert 0 < metrics["short_conv_decode_ms_per_step"]["value"] < metrics["decode_step_device_ms"]["value"]
    # the XLA forms of the rehearsal run no kernel: no slot to count, no kernel to time
    assert "small_expert_grouped_matmul_roofline" not in metrics and "decode_streamed_slot_share" not in metrics
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/lfm2_moe.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"lfm2_moe": digests()}, sort_keys=True))

"""Tests of the ``jamba`` family's benchmark files (``families/jamba.py``,
``references/jamba.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_jamba_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_jamba_family.py`` prints the weight digests
that ``recorded_weights_jamba.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_laguna_family.py`` makes its
own). The controls' walk over the cell's own requests is ``controls_jamba.py`` (chip).
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

NAME = "jamba2-3b-bf16-tp1"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_jamba.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "jamba2-3b.closed8"
NEW_READERS = ("ssm_scan_prefill_ms_per_row", "ssm_decode_ms_per_step", "selective_scan_prefill_roofline")


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

    out = {}
    for gain in (0.0, 5.0):
        params = toy(recite_gain=gain)[3]
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
        want = json.load(f)["jamba"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_the_head_is_the_reciting_head_and_the_gains_are_the_familys():
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, family, model, params = toy()
    assert not model.tie_word_embeddings and family.layer_loop_trips(cfg) == 4
    key = jax.random.fold_in(serve.prng_key(SEED, 0), len(params))
    (want,) = serve.draw_head(key, params["embedding"], model.eos_token_ids, 5.0, params["lm_head"].dtype)
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32), np.asarray(want, np.float32))
    f32 = lambda n: np.asarray(params[n], np.float32)  # noqa: E731
    assert all(params[n].dtype == jnp.float32 for n in ("ssm_A_log", "ssm_D", "ssm_dt_bias"))
    assert params["ssm_in_proj"].dtype == jnp.bfloat16
    a_log = f32("ssm_A_log")  # [state layers, d_state, d_inner]: log(1..16) a channel
    np.testing.assert_allclose(np.exp(a_log[1, :, 5]), np.arange(1, 17), rtol=1e-6)
    dt = np.log1p(np.exp(f32("ssm_dt_bias")))  # softplus of the bias: the log-uniform draw
    assert family.DT_MIN * 0.99 <= dt.min() and dt.max() <= family.DT_MAX * 1.01 and dt.max() / dt.min() > 30
    assert (f32("ssm_b_norm") == family.BC_SCALE).all() and (f32("ssm_dt_norm") == 1).all()
    assert (f32("ssm_D") == 1).all() and not f32("ssm_conv_b").any()
    D, Di = model.hidden_size, model.d_inner
    assert abs(f32("ssm_out_proj").std() * np.sqrt(Di) - family.OUT_GAIN) < 0.05
    assert abs(f32("attn_wq").std() * np.sqrt(D) - family.QK_GAIN) < 0.1
    assert abs(f32("layers_w_gate").std() * np.sqrt(D) - serve.LAYER_GAIN) < 0.02


def test_the_configuration_is_the_published_one_and_nothing_is_cut():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads, model.head_dim) == (2560, 20, 1, 128)
    assert (model.intermediate_size, model.d_inner, model.mamba_d_state, model.mamba_d_conv) == (8192, 5120, 16, 4)
    assert (model.mamba_dt_rank, model.vocab_size, model.num_layers) == (160, 65536, 28)
    assert model.attention_layers == (7, 21) and model.num_state_layers == 26
    assert cfg["reduced"] == [] and cfg["tie_word_embeddings"] is True and not model.tie_word_embeddings
    assert "whole model" in cfg["deployment"] and "all 28 layers" in cfg["deployment"]
    assert sum("a later PR that learns otherwise changes one line" in a for a in cfg["assumed"]) == 3
    assert any("untied" in a for a in cfg["assumed"]) and any("tokenizer" in a for a in cfg["assumed"])
    assert cfg["serving"]["engine"] == {"prompt_buckets": [2048, 4096]}
    assert cfg["serving"]["tokenizer_vocab"] == 65536 == model.vocab_size
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models import hybrid_ssm as hs

    shapes = jax.eval_shape(lambda: hs.init_hybrid_ssm_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 6.39e9 < nbytes < 6.41e9, nbytes  # 6.399 GB as served (the untied head's 335 MB among them)
    cache = jax.eval_shape(lambda: hs.make_hybrid_cache(model, 8, 4352))
    assert cache.k.shape == (2, 8, 1, 4352, 128) and cache.ssm.shape == (26, 8, 16, 5120)
    assert cache.conv.shape == (26, 8, 3, 5120) and cache.ssm.dtype == "float32"
    state = cache.ssm.size * 4 + cache.conv.size * 2
    assert 2 * cache.k.size * 2 == 35651584 and state == 74547200  # KV 36 MB, state 75 MB for 8 rows
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k, "absent") != v] == []


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/jamba.py`` against tier 1's ``tests/jamba_reference.py`` on
    one seeded input; each control moves the reading, and the structural ones
    are the faults tier 1's reference can make."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import jamba_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    cfg["serving"] = dict(cfg["serving"], engine={"prompt_buckets": [512, 640]})
    reference = serve.load_reference("jamba")
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
    for control, fault in (("no_state_handover", dict(drop_state_at=n)), ("no_conv_handover", dict(drop_conv_at=n)),
                           ("no_inner_norms", dict(inner_norms=False)), ("no_softplus", dict(softplus=False)),
                           ("state_bf16", dict(state_dtype=jnp.bfloat16)), ("attn_window_512", dict(attn_window=512))):
        wrong = pick(tier1.forward(params, model, prompt + emitted, **fault))
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        np.testing.assert_allclose(faulty["chosen_logit"], wrong[np.arange(9), emitted], atol=2e-4)
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    for key, value in (("num_experts", 16), ("num_experts_per_tok", 2), ("sliding_window", 4096),
                       ("hidden_act", "gelu"), ("use_mamba_kernels", False)):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps({**cfg, key: value}), encoding="utf-8")
        with pytest.raises(ValueError, match=key):
            serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    with pytest.raises(ValueError, match="bias"):
        family.model_config({**loaded, "mamba_proj_bias": True})
    with pytest.raises(ValueError, match="attn_layer_offset"):
        family.model_config({**loaded, "attn_layer_offset": 14})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="hybrid_ssm"):
        serve.load_family("jamba")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200
    assert family.layer_loop_trips(cfg) == 28
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["content_seed"], mix["question_pool"], mix["zipf_a"],
            mix["corpus_pages"], mix["words_per_page"], mix["lead_in_requests"], mix["max_new_tokens"]) == (
        "closed", 8, 2147483659, 64, 1.1, 400, 500, 2, 150)
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert {"setup_s", "latency_p50_ms", "output_tok_per_s"} <= e2e
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    assert set(NEW_READERS) <= {x["name"] for x in mine}
    assert [x["name"] for x in bench["per_layer"][-3:]] == list(NEW_READERS)  # appended, and for this cell alone
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-3:])
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_new_scopes_and_find_nothing_without_them():
    from benchmark.lib import ssm_scopes, stats

    path = "jit(gen)/{}/HybridSSMModel/while/body/closed_call/attn/cond/branch_0_fun/{}/mul"
    decode, prefill = "decode/while/body", "prefill/rows8"
    assert [ssm_scopes.fine_scope(path.format(decode, s)) for s in ("scan", "conv")] == [
        ("decode", "scan"), ("decode", "conv")]
    assert ssm_scopes.fine_scope("jit(gen)/decode/while/body/while/body/attn/cond/branch_1_fun/global/x") == ("decode", "")
    assert ssm_scopes.fine_scope("jit(gen)/decode/while/body/mlp/dot") is None
    assert ssm_scopes.fine_scope("jit(gen)/verify/while/body/attn/scan/mul") is None
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": path.format(decode, "scan"), "b": path.format(decode, "conv"), "c": "",
                                "d": path.format(prefill, "scan"), "e": "jit(gen)/decode/while/body/attn/ring/x"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 50.0, 5.0],
                    ["d f32[8]", 60.0, 20.0], ["e f32[8]", 80.0, 2.0]]}
    split = ssm_scopes.seconds_by_fine_scope(data)
    assert split == {"decode": {"scan": 1e-8, "conv": 3e-8, "": 2e-9}, "prefill": {"scan": 2e-8}}
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0}, "ssm_scopes": split}
    assert _reader("ssm_decode_ms_per_step").read(ctx) == pytest.approx(4e-8 / 2 * 1e3)
    assert _reader("ssm_scan_prefill_ms_per_row").read(ctx) == pytest.approx(2e-8 / 4 * 1e3)
    # a program that opens no such scope (another family's trace), or no trace
    other = {**ctx, "ssm_scopes": {"decode": {"": 2e-9}, "prefill": {"": 1e-9}}}
    for name in NEW_READERS[:2]:
        assert _reader(name).read(other) is None and _reader(name).read({"trace": None}) is None

    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = _reader("selective_scan_prefill_roofline")
    moved = roof.bytes_moved(8, 4096, 5120, 16)
    assert moved == 8 * (4096 * (3 * 5120 * 2 + 2 * 16 * 4) + 5120 * 16 * 4) + (5120 * 16 + 5120) * 4
    assert roof.state_updates(8, 4096, 5120, 16) == 8 * 4096 * 5120 * 16
    least = moved / peaks["hbm_bytes_per_s"]
    tr = {"kernels": {"selective_scan (bf16[8,4096,40,128]{3,2,1,0},": (52, 52 * least * 10),
                      "flash_attention bf16[160,4096,128]": (4, 1.0)}}
    ctx = {"trace": tr, "config": cfg, "peaks": peaks, "stats": stats}
    assert roof.read(ctx) == pytest.approx(10.0)
    assert roof.read({**ctx, "trace": {"kernels": {"selective_scan bf16[8,4096,40,128]": (1, least * 4)}}}) == pytest.approx(25.0)
    assert roof.read({**ctx, "trace": {"kernels": {"flash_attention bf16[160,4096,128]": (4, 1.0)}}}) is None
    assert roof.read({**ctx, "config": {"hidden_size": 4096}}) is None and roof.read({**ctx, "trace": None}) is None


def test_rehearsal_walks_to_its_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--allow-cpu-rehearsal", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "12", "--trace", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert "coalesce_wait_ms" in metrics and "selective_scan_prefill_roofline" not in metrics
    if "prefill_device_ms_per_row" in metrics and "ssm_scan_prefill_ms_per_row" in metrics:  # the slice held a prefill
        assert 0 < metrics["ssm_scan_prefill_ms_per_row"]["value"] < metrics["prefill_device_ms_per_row"]["value"]
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer split reads them too
        assert 0 < metrics["ssm_decode_ms_per_step"]["value"] < metrics["decode_step_device_ms"]["value"]
    # the XLA forms of the rehearsal run no kernel: no walk to count, no kernel to time
    assert "selective_scan_prefill_roofline" not in metrics and "decode_streamed_slot_share" not in metrics
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/jamba.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"jamba": digests()}, sort_keys=True))

"""Tests of the ``phi4flash`` family's benchmark files (``families/phi4flash.py``,
``references/phi4flash.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_phi4flash_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_phi4flash_family.py`` prints the weight digests
that ``recorded_weights_phi4flash.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_jamba_family.py`` makes its
own). The controls' walk over the cell's own requests is
``controls_phi4flash.py`` (chip).
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

NAME = "phi4-mini-flash-reasoning-bf16-tp1"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_phi4flash.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "phi4-mini-flash.solo-12chunk"
NEW_READERS = ("cross_decoder_prefill_ms_per_row", "cross_decoder_prefill_position_share",
               "shared_kv_decode_ms_per_step", "gmu_decode_ms_per_step", "diff_attn_epilogue_ms_per_step",
               "shared_plane_flash_prefill_roofline", "diff_window_flash_prefill_roofline")


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
        want = json.load(f)["phi4flash"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_the_head_is_the_reciting_head_and_the_gains_are_the_familys():
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, family, model, params = toy()
    assert not model.tie_word_embeddings and family.layer_loop_trips(cfg) == 2
    key = jax.random.fold_in(serve.prng_key(SEED, 0), len(params))
    (want,) = serve.draw_head(key, params["embedding"], model.eos_token_ids, 5.0, params["lm_head"].dtype)
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32), np.asarray(want, np.float32))
    f32 = lambda n: np.asarray(params[n], np.float32)  # noqa: E731
    floats = ("ssm_A_log", "ssm_D", "ssm_dt_bias", "attn_lambda_q1", "cross_lambda_k2")
    assert all(params[n].dtype == jnp.float32 for n in floats) and params["ssm_in_proj"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.exp(f32("ssm_A_log")[1, :, 5]), np.arange(1, 17), rtol=1e-6)
    dt = np.log1p(np.exp(f32("ssm_dt_bias")))  # softplus of the bias: the log-uniform draw
    assert family.DT_MIN * 0.99 <= dt.min() and dt.max() <= family.DT_MAX * 1.01 and dt.max() / dt.min() > 30
    assert (f32("ssm_D") == 1).all() and not f32("ssm_conv_b").any() and (f32("attn_subln") == 1).all()
    assert (f32("layers_input_norm") == 1).all() and not f32("layers_ff_norm_b").any() and not f32("final_norm_b").any()
    D, Di, R = model.hidden_size, model.d_inner, model.mamba_dt_rank
    x = f32("ssm_x_proj") * np.sqrt(Di)  # the time step's columns, then B's and C's
    assert abs(x[..., :R].std() - family.X_GAIN) < 0.1 and abs(x[..., R:].std() - family.BC_GAIN) < 0.2
    assert abs(f32("gmu_out_proj").std() * np.sqrt(Di) - family.OUT_GAIN) < 0.05
    assert abs(f32("cross_wq").std() * np.sqrt(D) - family.QK_GAIN) < 0.1
    assert abs(f32("attn_lambda_q1").std() - family.LAMBDA_STD) < 0.03 and abs(f32("attn_bq").std() - 0.1) < 0.03
    assert abs(f32("layers_w_gate").std() * np.sqrt(D) - serve.LAYER_GAIN) < 0.02


def test_the_configuration_is_the_published_one_and_nothing_is_cut():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads, model.head_dim) == (2560, 40, 20, 64)
    assert (model.intermediate_size, model.d_inner, model.mamba_d_state, model.mamba_d_conv) == (10240, 5120, 16, 4)
    assert (model.mamba_dt_rank, model.vocab_size, model.num_layers, model.sliding_window) == (160, 200064, 32, 512)
    assert (model.num_state_layers, model.num_window_layers, model.num_cross_layers) == (9, 8, 7)
    assert cfg["reduced"] == [] and cfg["tie_word_embeddings"] is True and not model.tie_word_embeddings
    assert "whole model" in cfg["deployment"] and "all 32 layers" in cfg["deployment"]
    assert sum("a later PR that learns otherwise changes one line" in a for a in cfg["assumed"]) >= 3
    assert any("untied" in a for a in cfg["assumed"]) and any("tokenizer" in a for a in cfg["assumed"])
    assert cfg["serving"]["retrieval"] == {"k": 12, "context_top_n": 9}
    engine = cfg["serving"]["engine"]
    bucket = max(engine["prompt_buckets"])
    assert bucket + 150 <= engine["max_seq_len"]
    from rag_llm_k8s_tpu.ops.attention import flash_window_step

    # the bucket is the longest at which a window layer's prefill takes its window in ONE step (the strips resident)
    assert flash_window_step(bucket, 4, 128, 128, 512) == (64, 576) and flash_window_step(bucket + 1024, 4, 128, 128, 512) is None
    assert cfg["serving"]["tokenizer_vocab"] == 200064 == model.vocab_size
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models import cross_decoder as cd

    shapes = jax.eval_shape(lambda: cd.init_cross_decoder_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    n = sum(s.size for s in jax.tree.leaves(shapes))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert round(n / 1e6) == 4365 and 8.72e9 < nbytes < 8.74e9, (n, nbytes)  # as served; 3853 M tied
    cache = jax.eval_shape(lambda: cd.make_cross_cache(model, 1, bucket + 256))
    assert cache.k.shape == (9, 1, 10, bucket + 256, 128) and cache.ssm.shape == (9, 1, 16, 5120)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k, "absent") != v] == []


def test_the_references_controls_each_move_the_reading():
    """``references/phi4flash.py score`` against its own ``forward`` (tier 1
    holds that to the program) on one seeded input; each control moves it."""
    import jax
    import numpy as np

    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    cfg["serving"] = dict(cfg["serving"], engine={"prompt_buckets": [256, 320]})
    reference = serve.load_reference("phi4flash")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 300)], [int(t) for t in rng.integers(3, 512, 9)]
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0])
    logits = np.asarray(reference.forward(params, cfg, prompt + emitted))[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        assert np.isfinite(faulty["chosen_logit"]).all(), control
        assert np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max() > 1e-3, control
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")
    with open(os.path.join(BENCH, "references", "phi4flash.py"), "rb") as a, \
            open(os.path.join(REPO, "tests", "phi4flash_reference.py"), "rb") as b:
        assert a.read() == b.read()  # tier 1 holds the program to this very file


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    for key, value in (("hidden_act", "gelu"), ("mlp_bias", True), ("lm_head_bias", True), ("resid_pdrop", 0.1)):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps({**cfg, key: value}), encoding="utf-8")
        with pytest.raises(ValueError, match=key):
            serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    with pytest.raises(ValueError, match="mb_per_layer"):
        family.model_config({**loaded, "mb_per_layer": 4})
    with pytest.raises(ValueError, match="fours"):
        family.model_config({**loaded, "num_hidden_layers": 30})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="cross_decoder"):
        serve.load_family("phi4flash")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert family.layer_loop_trips(cfg) == 8
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["content_seed"], mix["question_pool"], mix["zipf_a"],
            mix["corpus_pages"], mix["words_per_page"], mix["lead_in_requests"], mix["max_new_tokens"]) == (
        "closed", 1, 2147483693, 64, 1.1, 400, 500, 3, 150)
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert e2e == {"setup_s", "latency_p50_ms"}
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    assert set(NEW_READERS) <= {x["name"] for x in mine}
    assert [x["name"] for x in bench["per_layer"][-len(NEW_READERS):]] == list(NEW_READERS)  # appended, for this cell alone
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-len(NEW_READERS):])
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_new_scopes_and_find_nothing_without_them():
    from benchmark.lib import cross_scopes, stats

    decode = "jit(gen)/decode/while/body/CrossDecoderModel/cross/while/body/attn/{}/mul"
    assert cross_scopes.in_cross_decoder(decode.format("gmu")) == "decode"
    assert cross_scopes.in_cross_decoder("jit(gen)/prefill/rows1/CrossDecoderModel/cross/mlp/dot") == "prefill"
    assert cross_scopes.in_cross_decoder("jit(gen)/prefill/rows1/CrossDecoderModel/while/body/mlp/dot") is None
    assert cross_scopes.in_cross_decoder("jit(embed)/cross/attn") is None  # no phase in front of it
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": decode.format("cross"), "b": decode.format("gmu"), "c": decode.format("diff"),
                                "d": "jit(gen)/decode/while/body/M/attn/global/dot",
                                "e": "jit(gen)/prefill/rows1/M/cross/mlp/dot",
                                "f": "jit(gen)/prefill/rows1/M/while/body/attn/scan/x"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 40.0, 5.0],
                    ["d f32[8]", 50.0, 20.0], ["e f32[8]", 70.0, 8.0], ["f f32[8]", 80.0, 2.0]]}
    half = cross_scopes.half_seconds(data)
    assert half == {"decode": pytest.approx(4.5e-8), "prefill": pytest.approx(8e-9)}
    fine = cross_scopes.ssm_scopes.seconds_by_fine_scope(data, cross_scopes.FINE)
    assert fine["decode"] == {"cross": 1e-8, "gmu": 3e-8, "diff": 5e-9, "global": 2e-8}
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0},
           "cross_scopes": {"fine": fine, "half": half}}
    assert _reader("shared_kv_decode_ms_per_step").read(ctx) == pytest.approx(3e-8 / 2 * 1e3)
    assert _reader("gmu_decode_ms_per_step").read(ctx) == pytest.approx(3e-8 / 2 * 1e3)
    assert _reader("diff_attn_epilogue_ms_per_step").read(ctx) == pytest.approx(5e-9 / 2 * 1e3)
    assert _reader("cross_decoder_prefill_ms_per_row").read(ctx) == pytest.approx(8e-9 / 4 * 1e3)
    # a program that opens no such scope (another family's trace: ``global`` alone is not this family's), or no trace
    other = {**ctx, "cross_scopes": {"fine": {"decode": {"global": 2e-8, "": 1e-9}}, "half": {}}}
    for name in NEW_READERS[:5]:
        if name != "cross_decoder_prefill_position_share":
            assert _reader(name).read(other) is None and _reader(name).read({"trace": None}) is None

    share = _reader("cross_decoder_prefill_position_share")
    before = {share.COMPUTED: 10.0, share.FED: 1000.0}
    after = {share.COMPUTED: 12.0, share.FED: 1000.0 + 2 * 13312}
    assert share.read({"stats": stats, "before": before, "after": after}) == pytest.approx(100.0 / 13312)
    assert share.read({"stats": stats, "before": {}, "after": {}}) is None

    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    full, window = _reader("shared_plane_flash_prefill_roofline"), _reader("diff_window_flash_prefill_roofline")
    n = 12000.0
    assert full.flops(n, 40, 64) == 40 * 384 * n * (n + 1) / 2  # a score of 64, a value pair of 128, a pair and head
    assert window.flops(n, 512, 40, 64) == 40 * 384 * (512 * 513 / 2 + (n - 512) * 512)
    least = full.flops(n, 40, 64) / peaks["bf16_flops_per_s"]
    assert least > full.bytes_moved(13312, 40, 10, 128) / peaks["hbm_bytes_per_s"]  # bound by compute at this length
    tr = {"kernels": {"flash_attention bf16[40,13312,128]": (3, 3 * least * 4),
                      "flash_attention_window bf16[40,13312,128]": (24, 1.0),
                      "flash_attention bf16[16,512,64]": (9, 1.0)}}  # the encoder's: another shape
    ctx = {"trace": tr, "config": cfg, "peaks": peaks, "stats": stats, "prompt_tokens": [n, n]}
    assert full.read(ctx) == pytest.approx(25.0)
    w_least = max(window.flops(n, 512, 40, 64) / peaks["bf16_flops_per_s"],
                  window.bytes_moved(13312, 40, 10, 128) / peaks["hbm_bytes_per_s"])
    assert window.read(ctx) == pytest.approx(24 * w_least * 100.0)
    for reader in (full, window):
        assert reader.read({**ctx, "trace": None}) is None and reader.read({**ctx, "prompt_tokens": None}) is None
        assert reader.read({**ctx, "config": {"model_type": "jamba"}}) is None
        assert reader.read({**ctx, "trace": {"kernels": {"flash_attention bf16[16,512,64]": (9, 1.0)}}}) is None


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
    assert metrics["cross_decoder_prefill_position_share"]["value"] < 1.0  # one position of a bucket of 512
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer splits read them too
        for name in ("shared_kv_decode_ms_per_step", "gmu_decode_ms_per_step", "diff_attn_epilogue_ms_per_step",
                     "ssm_decode_ms_per_step", "window_attn_decode_ms_per_step"):
            assert 0 < metrics[name]["value"] < metrics["decode_step_device_ms"]["value"], name
    if "prefill_device_ms_per_row" in metrics:
        assert 0 < metrics["cross_decoder_prefill_ms_per_row"]["value"] < metrics["prefill_device_ms_per_row"]["value"]
    # the XLA forms of the rehearsal run no kernel: no walk to count, no kernel to time
    assert "shared_plane_flash_prefill_roofline" not in metrics and "decode_streamed_slot_share" not in metrics
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/phi4flash.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"phi4flash": digests()}, sort_keys=True))

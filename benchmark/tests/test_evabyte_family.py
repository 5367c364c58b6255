"""Tests of the ``evabyte`` family's benchmark files (``families/evabyte.py``,
``references/evabyte.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_evabyte_family.py -q -p no:cacheprovider

``python3 benchmark/tests/test_evabyte_family.py`` prints the weight digests
that ``recorded_weights_evabyte.json`` pins (the family is served at tp 1 in
bf16 only, so its digests are made here, as ``test_laguna_family.py`` makes its
own). The controls' walk over the cell's own requests is ``controls_evabyte.py`` (chip).
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

NAME = "evabyte-6.5b-bf16-pp4-stage"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_evabyte.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "evabyte-pp4.closed8"
NEW_READERS = ("ring_summary_attn_decode_ms_per_step", "window_summary_prefill_roofline",
               "ring_summary_decode_roofline", "summary_served_position_share")


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
        want = json.load(f)["evabyte"]
    got = digests()
    assert got == want
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_head_zero_is_the_reciting_head_and_the_others_are_plain():
    import jax
    import numpy as np

    cfg, family, model, params = toy()
    V, D = model.vocab_size, model.hidden_size
    head = np.asarray(params["lm_head"], np.float32)
    assert head.shape == (D, model.num_pred_heads * V) and family.layer_loop_trips(cfg) == 2
    key = jax.random.split(jax.random.fold_in(serve.prng_key(SEED, 0), len(params)))[0]
    (want,) = serve.draw_head(key, params["embedding"], model.eos_token_ids, 5.0, params["lm_head"].dtype)
    np.testing.assert_array_equal(head[:, :V], np.asarray(want, np.float32))  # serve.draw_head CALLED for head 0
    assert not head[:, model.eos_token_ids[0]].any() and head[:, V + model.eos_token_ids[0]].any()
    assert 0.8 < head[:, V:].std() * np.sqrt(D) < 1.2  # heads 1..: unit-std logits, no recitation
    assert not np.asarray(params["final_norm"], np.float32).any()  # unit-offset scales: offsets 0
    mu = np.asarray(params["layers_mu"], np.float32)
    assert mu.shape == (2, 4, 16) and 0.7 < mu.std() * 4 / family.POOL_GAIN < 1.3
    wq, wv = np.asarray(params["layers_wq"], np.float32), np.asarray(params["layers_wv"], np.float32)
    assert abs(wq.std() / wv.std() - family.QK_GAIN / family.VO_GAIN) < 0.05


def test_the_configuration_is_the_published_one_but_for_what_it_lists():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert (model.hidden_size, model.num_heads, model.head_dim, model.intermediate_size) == (4096, 32, 128, 11008)
    assert (model.window_size, model.chunk_size, model.num_pred_heads, model.vocab_size) == (2048, 16, 8, 320)
    assert model.num_layers == 8 and cfg["reduced"] == ["num_hidden_layers"]
    assert "four-stage pipeline" in cfg["deployment"] and "no layer is shared" in cfg["deployment"]
    assert sum("a later PR that learns otherwise changes one line" in a for a in cfg["assumed"]) == 3
    eng = cfg["serving"]["engine"]
    assert eng["prompt_buckets"] == [10240, 20480] and eng["max_seq_len"] == 20992 and eng["max_seq_len"] % 128 == 0
    assert cfg["serving"]["tokenizer_vocab"] == 259 <= model.vocab_size
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[NAME]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    import jax

    from rag_llm_k8s_tpu.core.config import DTypePolicy
    from rag_llm_k8s_tpu.models import block_window as bwm

    shapes = jax.eval_shape(lambda: bwm.init_block_window_params(jax.random.PRNGKey(0), model, DTypePolicy()))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 3.25e9 < nbytes < 3.28e9, nbytes  # 3.262 GB of bf16
    cache = jax.eval_shape(lambda: bwm.make_block_window_cache(model, 8, 20992))
    assert cache.k.shape == (8, 8, 32, 1408 + 2048, 128)  # no plane as long as the context
    assert 2 * cache.k.size * 2 == 3623878656  # 3.62 GB for 8 rows: 56.6 MB a row-layer
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert cfg["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v) == ["num_hidden_layers"]


def test_the_merge_free_byte_tokenizer_is_one_id_a_byte(tmp_path):
    """The trainer and the native bpe library take a table of 3 special ids +
    the 256-byte alphabet and no merge as it is."""
    from rag_llm_k8s_tpu.native.build import load_library
    from rag_llm_k8s_tpu.tokenizer import load_tokenizer

    path = str(tmp_path / "bpe_259.json")
    serve._train_bpe(path, ["def f(x):\n    return x + 1\n" * 40, "naïve café ✓"], 259)
    with open(path, encoding="utf-8") as f:
        model = json.load(f)["model"]
    assert len(model["vocab"]) == 259 and model["merges"] == []
    tok = load_tokenizer(path)
    text = "Question: naïve café ✓?\n\nContext: x = 1"
    ids = tok.encode(text)
    assert len(ids) == len(text.encode("utf-8")) and max(ids) < 259 and min(ids) >= 3
    assert tok.decode(ids) == text
    assert load_library("bpe") is not None


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/evabyte.py`` against tier 1's ``tests/evabyte_reference.py``
    on one seeded input three windows long; each control moves the reading,
    and the four structural ones are the faults tier 1's reference can make."""
    import jax
    import numpy as np

    import evabyte_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    reference = serve.load_reference("evabyte")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 390)], [int(t) for t in rng.integers(3, 512, 9)]
    assert len(prompt) > 3 * model.window_size
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0])
    logits = np.asarray(tier1.forward(params, model, prompt + emitted))[len(prompt) - 1:-1, 0]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        assert np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max() > 1e-3, control
    for control, fault in (("no_summaries", dict(summaries=False)), ("swap_mu_phi", dict(swap_mu_phi=True)),
                           ("mean_pool", dict(mean_pool=True)),
                           ("own_window_summaries", dict(own_window_summaries=True))):
        wrong = np.asarray(tier1.forward(params, model, prompt + emitted, **fault))[len(prompt) - 1:-1, 0]
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        np.testing.assert_allclose(faulty["chosen_logit"], wrong[np.arange(9), emitted], atol=2e-4)
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_what_the_decoder_does_not_run_is_refused(tmp_path):
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    for key, value in (("attention_class", "softmax"), ("fp32_skip_add", False), ("norm_add_unit_offset", False),
                       ("hidden_act", "gelu"), ("attention_bias", True), ("rope_scaling", {"factor": 2})):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps({**cfg, key: value}), encoding="utf-8")
        with pytest.raises(ValueError, match=key):
            serve.load_config(str(path))
    loaded, family = serve.load_config(CONFIG)
    with pytest.raises(ValueError, match="multi-head"):
        family.model_config({**loaded, "num_key_value_heads": 8})
    with pytest.raises(ValueError, match="whole number of chunks"):
        family.model_config({**loaded, "chunk_size": 24})


def test_a_checkout_without_the_family_s_module_fails_at_once(tmp_path, monkeypatch):
    """What the parent commit does on this cell: the family file is found,
    the program's module is not, and the import says so before any device."""
    monkeypatch.setattr(serve, "REPO", str(tmp_path))
    with pytest.raises(ImportError, match="block_window"):
        serve.load_family("evabyte")


def test_the_cell_resolves_to_files_that_parse():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1 and len(cell["why"]) <= 200
    assert family.layer_loop_trips(cfg) == 8
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["question_pool"], mix["zipf_a"], mix["corpus_pages"],
            mix["words_per_page"], mix["lead_in_requests"], mix["max_new_tokens"]) == (
        "closed", 8, 64, 1.1, 400, 500, 2, 384)
    assert mix["max_new_tokens"] + max(cfg["serving"]["engine"]["prompt_buckets"]) <= cfg["serving"]["engine"]["max_seq_len"]
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert {"setup_s", "latency_p50_ms", "output_tok_per_s"} <= e2e
    mine = [x for x in bench["per_layer"] if CELL in x.get("workloads", [CELL])]
    assert set(NEW_READERS) <= {x["name"] for x in mine}
    assert [x["name"] for x in bench["per_layer"][-4:]] == list(NEW_READERS)  # appended, and for this cell alone
    assert all(x["workloads"] == [CELL] for x in bench["per_layer"][-4:])
    # the traced slice of a 7.5 s round holds no ``retrieve`` span (run.py starts it on the
    # answers of the round before the last), so the accepted reader finds nothing here
    assert "retrieve_device_ms_per_answer" not in {x["name"] for x in mine}
    for x in mine:
        assert x["moves"] in e2e, x["name"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def test_readers_read_the_new_scopes_and_counters_and_find_nothing_without_them():
    from benchmark.lib import ring_scopes, stats

    path = "jit(gen)/{}/BlockWindowModel/while/body/attn/{}/dot_general"
    decode, prefill = "decode/while/body", "prefill/rows8"
    assert [ring_scopes.fine_scope(path.format(decode, s)) for s in ("ring", "pool")] == [
        ("decode", "ring"), ("decode", "pool")]
    assert ring_scopes.fine_scope("jit(gen)/decode/while/body/while/body/attn/dynamic_update_slice") == ("decode", "")
    assert ring_scopes.fine_scope("jit(gen)/decode/while/body/mlp/dot") is None
    assert ring_scopes.fine_scope("jit(gen)/verify/while/body/attn/ring/dot") is None
    data = {"modules": [["m(1)", 0.0, 100.0]], "host": [],
            "scopes": {"m(1)": {"a": path.format(decode, "ring"), "b": path.format(decode, "pool"), "c": "",
                                "d": path.format(prefill, "ring"), "e": "jit(gen)/decode/while/body/attn/window/x"}},
            "ops": [["a f32[8]", 0.0, 10.0], ["b f32[8]", 10.0, 30.0], ["c f32[8]", 50.0, 5.0],
                    ["d f32[8]", 60.0, 20.0], ["e f32[8]", 80.0, 2.0]]}
    split = ring_scopes.seconds_by_fine_scope(data)
    assert split == {"decode": {"ring": 1e-8, "pool": 3e-8, "": 2e-9}, "prefill": {"ring": 2e-8}}
    ctx = {"trace": {}, "phases": {"steps": {"decode": 2}, "prefill_rows": 4.0}, "ring_scopes": split}
    assert _reader("ring_summary_attn_decode_ms_per_step").read(ctx) == pytest.approx(4e-8 / 2 * 1e3)
    # a program that opens no such scope (the windowed family's trace above), or no trace
    other = {**ctx, "ring_scopes": {"decode": {"": 2e-9}}}
    name = "ring_summary_attn_decode_ms_per_step"
    assert _reader(name).read(other) is None and _reader(name).read({"trace": None}) is None

    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    share = _reader("summary_served_position_share")
    after = {share.POOLED: 1152.0 * 8, share.POSITIONS: (1152 * 16 + 2048) * 8.0}
    ctx = {"before": {}, "after": after, "stats": stats, "config": cfg}
    assert share.read(ctx) == pytest.approx(90.0)
    assert share.read({**ctx, "after": {}}) is None  # a program without the counters

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flash = _reader("window_summary_prefill_roofline")
    brute = sum(t % 2048 + 1 + 128 * (t // 2048) for t in range(18500))
    assert flash.keys_seen(18500, 2048, 16) == brute and flash.keys_seen(0, 2048, 16) == 0
    row = flash.flops(18500, 2048, 16, 32, 128) / peaks["bf16_flops_per_s"]
    assert row > flash.bytes_moved(20480, 2048, 16, 32, 128) / peaks["hbm_bytes_per_s"]  # bound by compute
    tr = {"kernels": {"window_summary_flash_attention bf16[32,20480,128]": (64, 64 * row * 2.5),
                      "flash_attention bf16[384,4096,128]": (5, 1.0)}}
    ctx = {"trace": tr, "config": cfg, "prompt_tokens": [18500] * 8, "peaks": peaks, "stats": stats, "new_tokens": 384}
    assert flash.read(ctx) == pytest.approx(40.0)
    assert flash.read({**ctx, "trace": {"kernels": {"flash_attention bf16[384,4096,128]": (5, 1.0)}}}) is None
    assert flash.read({**ctx, "config": {"hidden_size": 7168}}) is None and flash.read({**ctx, "trace": None}) is None

    walk = _reader("ring_summary_decode_roofline")
    slots = walk.live_slots([18500] * 8, 384, 2048, 16)
    assert slots == pytest.approx(sum((18500 + s) % 2048 + 1 + 128 * ((18500 + s) // 2048) for s in range(384)) / 384)
    least = walk.bytes_moved(8, slots, 32, 128) / peaks["hbm_bytes_per_s"]
    assert least > walk.flops(8, slots, 32, 128) / peaks["bf16_flops_per_s"]  # bound by bytes
    tr = {"kernels": {"ring_summary_decode_attention bf16[8,32,1,128]": (800, 800 * least * 4),
                      "decode_attention bf16[8,8,4,128]": (9, 1.0)}}
    rode = 'rag_generate_dispatch_rows_total{path="batched",rows="%d"}'
    whole = {**ctx, "trace": tr, "before": {rode % 8: 16.0}, "after": {rode % 8: 64.0, rode % 1: 2.0}}
    assert walk.padded_dispatches(whole) == 0 and walk.read(whole) == pytest.approx(25.0)
    # a round that split seven and one: the program built for eight rows served seven, and the trace cannot tell
    split = {**whole, "after": {rode % 8: 56.0, rode % 7: 7.0, rode % 1: 1.0}}
    assert walk.padded_dispatches(split) == 7 and walk.read(split) is None
    assert walk.read({**whole, "trace": {"kernels": {"decode_attention bf16[8,8,4,128]": (9, 1.0)}}}) is None
    assert walk.read({**whole, "config": {"hidden_size": 7168}}) is None


def test_rehearsal_walks_to_its_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--allow-cpu-rehearsal", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "12", "--trace", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert "unscoped_device_time_share" in metrics and "prefill_device_ms_per_row" in metrics
    if "decode_step_device_ms" in metrics:  # the slice held decode steps: the finer split reads them too
        assert 0 < metrics["ring_summary_attn_decode_ms_per_step"]["value"] < metrics["decode_step_device_ms"]["value"]
    # the XLA forms of the rehearsal run no kernel: no walk to count, no kernel to time
    for name in ("summary_served_position_share", "window_summary_prefill_roofline", "ring_summary_decode_roofline"):
        assert name not in metrics
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/evabyte.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"evabyte": digests()}, sort_keys=True))

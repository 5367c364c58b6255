"""Tests of the ``dots_vlm`` family's benchmark files (``families/dots_vlm.py``,
``references/dots_vlm.py``, the configuration, its per-layer readers). Not tier 1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_dots_vlm.py -q -p no:cacheprovider

``python3 benchmark/tests/test_dots_vlm.py`` prints the weight digests that
``recorded_weights_dots_vlm.json`` pins (``weight_digests.py`` prints a
family's over tp 1 and 4, bf16 and int8; this family is served at tp 1 in
bf16 only, so its digests are made here, the same way).
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

CONFIG = os.path.join(BENCH, "configs", "dots-vlm1-bf16-ep16-share.json")
RECORDED = os.path.join(BENCH, "tests", "recorded_weights_dots_vlm.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 11
CELL = "dots-vlm1-ep16.closed8"


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


def test_the_family_draws_the_weights_it_drew():
    """Leaf by leaf: the cell's numbers are properties of one weight draw."""
    with open(RECORDED, encoding="utf-8") as f:
        want = json.load(f)["dots_vlm"]
    got = digests()
    for case in want:
        assert sorted(got[case]) == sorted(want[case])
        assert got[case] == want[case]
    heads = {case: leaves.pop("lm_head") for case, leaves in got.items()}
    assert got["tp1.bf16.recite0"] == got["tp1.bf16.recite5"] and len(set(heads.values())) == 2


def test_rehearsal_model_keeps_the_family_s_shape():
    cfg, family, model, _ = toy()
    assert model.first_k_dense >= 1 and model.num_moe_layers >= 2 and model.n_group > 1
    assert 1 < model.experts_held < model.n_routed_experts and model.first_held > 0
    assert family.layer_loop_trips(cfg) == model.num_moe_layers


def test_the_configuration_is_the_published_one_but_for_what_it_lists():
    cfg, family = serve.load_config(CONFIG)
    model = family.model_config(cfg)
    assert model.experts_held == 16 and model.first_held == 16 * cfg["ep_rank"] and model.num_moe_layers >= 4
    assert model.vocab_size * 8 == 129280 and model.n_routed_experts == 256
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["dots-vlm1-bf16-ep16-share"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) and entry["source"] == cfg["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots.vlm1.inst")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"])


def test_the_two_references_agree_and_the_controls_do_not():
    """``references/dots_vlm.py`` against tier 1's ``tests/latent_moe_reference.py``
    on one seeded input; each control moves the reading."""
    import jax
    import numpy as np

    import latent_moe_reference as tier1
    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    reference = serve.load_reference("dots_vlm")
    rng = np.random.default_rng(0)
    prompt, emitted = [int(t) for t in rng.integers(3, 512, 40)], [int(t) for t in rng.integers(3, 512, 9)]
    (got,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0])
    logits = tier1.forward(params, model, prompt + emitted)[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(9), emitted], atol=2e-4)
    moved = {}
    for control in reference.CONTROLS:
        (faulty,) = reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control=control)
        moved[control] = np.abs(faulty["chosen_logit"] - got["chosen_logit"]).max()
        assert moved[control] > 1e-3, control
    # the whole reference one precision down moves more than its experts' inputs alone
    assert moved["fp8_matmuls"] > moved["int8_matmuls"] > moved["int8_expert_inputs"]
    with pytest.raises(ValueError, match="control"):
        reference.score(params, cfg, [(prompt, emitted)], jax.devices()[0], control="fp4")


def test_the_reference_says_who_was_chosen():
    """``route_log``: every token chooses ``num_experts_per_tok`` experts a MoE
    layer, counted apart for the tokens the program prefills and decodes."""
    import jax
    import numpy as np

    from rag_llm_k8s_tpu.core.config import DTypePolicy

    cfg, _, model, params = toy(DTypePolicy.fp32())
    reference = serve.load_reference("dots_vlm")
    rng = np.random.default_rng(1)
    sample = [([int(t) for t in rng.integers(3, 512, n)], [int(t) for t in rng.integers(3, 512, 5)])
              for n in (30, 12)]
    log = []
    reference.score(params, cfg, sample, jax.devices()[0], route_log=log)
    assert len(log) == len(sample) * model.num_moe_layers
    for entry in log:
        prompt, emitted = sample[entry["sequence"]]
        assert entry["prefill_tokens"] == len(prompt) and entry["decode_tokens"] == len(emitted) - 1
        assert entry["prefill"].shape == (model.n_routed_experts,)
        assert entry["prefill"].sum() == len(prompt) * model.num_experts_per_tok
        assert entry["decode"].sum() == (len(emitted) - 1) * model.num_experts_per_tok


def test_the_cell_resolves_to_files_that_parse():
    """``test_benchmark.py test_every_cell_resolves_to_files_that_parse`` as it
    has to read for a family with a layer outside the loop (that test holds
    ``layer_loop_trips`` to the depth and may not be edited by this PR: PERF.md
    section 7): the trips are the MoE layers, never more than the depth."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg, family = serve.load_config(os.path.join(REPO, entry["file"]))
    assert cfg["serving"]["tp"] == cell["chips"] == 1
    model = family.model_config(cfg)
    assert 0 < family.layer_loop_trips(cfg) <= model.num_layers == cfg["num_hidden_layers"]
    assert model.num_heads % cell["chips"] == 0 and model.num_kv_heads % cell["chips"] == 0
    assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    e2e = {x["name"] for x in bench["end_to_end"] if CELL in x.get("workloads", [CELL])}
    assert {"setup_s", "latency_p50_ms", "output_tok_per_s"} <= e2e
    for x in bench["per_layer"]:
        if CELL in x.get("workloads", [CELL]):
            assert x["moves"] in e2e, x["name"]
            assert os.path.exists(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_roofline_arithmetic():
    flash = _reader("mla_flash_attention_roofline")
    assert flash.flops(4096, 128, 192, 128) == 2 * 128 * 320 * 4096 * 4097 / 2
    assert flash.bytes_moved(4096, 128, 192, 128) == 128 * 4096 * 640 * 2
    dec = _reader("mla_decode_attention_roofline")
    assert dec.bytes_moved(8, 3000, 128, 512, 64) == 8 * (3000 * 576 + 128 * 1088) * 2
    gmm = _reader("grouped_matmul_roofline")
    assert gmm.flops(4, 7168, 2048) == 2 * 4 * 7168 * 2048
    assert gmm.bytes_moved(4, 3, 7168, 2048) == (3 * 7168 * 2048 + 4 * 9216) * 2
    ctx = {"trace": None, "config": {}, "stats": None}
    assert all(_reader(n).read(ctx) is None for n in (
        "mla_flash_attention_roofline", "mla_decode_attention_roofline", "grouped_matmul_roofline"))


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's program has no ``engine_moe_*`` series: the line leaves
    the metric out and nothing raises."""
    from benchmark.lib import stats

    ctx = {"before": {}, "after": {"tpu_rag_engine_generate_calls": 3.0}, "stats": stats,
           "trace": {"kernels": {}}, "config": {"moe_intermediate_size": 2048}}
    assert _reader("moe_dropped_assignment_share").read(ctx) is None
    assert _reader("grouped_matmul_roofline").read(ctx) is None


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
        assert metrics["latent_attn_decode_ms_per_step"]["value"] > 0
        assert metrics["moe_ffn_decode_ms_per_step"]["value"] > 0
    audit = next(json.loads(line) for line in p.stdout.splitlines() if '"event": "audit"' in line)
    assert audit["reference"] == "references/dots_vlm.py"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps({"dots_vlm": digests()}, sort_keys=True))

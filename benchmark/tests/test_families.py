"""Tests of the seam a decoder family arrives through: ``lib/serve.py
load_config / load_family / load_reference``, ``families/``, ``references/``.
Not tier 1:

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

from benchmark.lib import serve, stats  # noqa: E402

MISTRAL_7B = os.path.join(BENCH, "configs", "mistral-7b-v0.3-int8-tp1.json")
with open(os.path.join(BENCH, "tests", "recorded_weights.json"), encoding="utf-8") as f:
    RECORDED_WEIGHTS = json.load(f)


# ---- the weights are the parent's ------------------------------------------------


@pytest.fixture(scope="module")
def mistral_digests():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "weight_digests.py"), MISTRAL_7B],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(RECORDED_WEIGHTS["mistral"]))
def test_mistral_draws_the_weights_the_parent_drew(mistral_digests, case):
    """Every leaf, bit for bit, of what ``lib/serve.py make_llama_params`` drew
    at PR 26's parent: ``solo``'s tokens a verify are one draw's."""
    want = RECORDED_WEIGHTS["mistral"][case]
    assert sorted(mistral_digests[case]) == sorted(want)  # the same leaves ...
    assert mistral_digests[case] == want  # ... holding the same bits
    assert want == RECORDED_WEIGHTS["mistral"][case.replace("tp4", "tp1")]  # whatever the mesh


# ---- a second family is files, and no edit ---------------------------------------

# The Mistral block under another model_type and other key names.
OTHER_KEYS = {"d_model": "hidden_size", "d_ff": "intermediate_size", "n_layers": "num_hidden_layers",
              "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
              "d_head": "head_dim", "n_vocab": "vocab_size", "n_positions": "max_position_embeddings",
              "norm_eps": "rms_norm_eps", "rope_base": "rope_theta"}
OTHER_FAMILY = f'''
from benchmark.lib import serve

KEYS = {OTHER_KEYS!r}
PUBLISHED_KEYS = tuple(KEYS)
FIXED = {{"activation": "swiglu"}}
SERVING_KEYS = ("expert_placement",)
REHEARSAL_MODEL = dict(d_model=32, d_ff=64, n_layers=3, n_heads=2, n_kv_heads=1, d_head=16,
                       n_vocab=256, n_positions=1024)


def as_mistral(cfg):
    return {{KEYS.get(k, k): v for k, v in cfg.items()}}


def model_config(cfg):
    return serve.load_family("mistral").model_config(as_mistral(cfg))


def make_params(model, dtypes, seed, quant, mesh, recite_gain):
    return serve.load_family("mistral").make_params(model, dtypes, seed, quant, mesh, recite_gain)


def layer_loop_trips(cfg):
    return cfg["n_layers"] - 1  # as if the first layer sat outside the loop
'''
OTHER_REFERENCE = f'''
from benchmark.lib import serve

KEYS = {OTHER_KEYS!r}
HALF_GAP_TOL = 0.02
LOGIT_TOL = 0.04


def score(params, cfg, sequences, device):
    mistral = serve.load_reference("mistral")
    return mistral.score(params, {{KEYS.get(k, k): v for k, v in cfg.items()}}, sequences, device)
'''


@pytest.fixture
def other_root(tmp_path):
    """A directory shaped like ``benchmark/`` that holds one more family."""
    for kind, text in (("families", OTHER_FAMILY), ("references", OTHER_REFERENCE)):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / "otherblock.py").write_text(text)
    cfg = {"model_type": "otherblock", "source": "https://example.org/otherblock/config.json",
           "activation": "swiglu", "eos_token_id": 2, "d_model": 4096, "d_ff": 14336, "n_layers": 32,
           "n_heads": 32, "n_kv_heads": 8, "d_head": 128, "n_vocab": 32768, "n_positions": 32768,
           "norm_eps": 1e-5, "rope_base": 1e6, "deployment": "none", "assumed": [], "reduced": [],
           "serving": {"tp": 1, "weight_quant": "int8", "kv_quant": "int8", "encoder": "bge_m3",
                       "recite_gain": 5.0, "weights_seed": 2**31 + 11, "tokenizer_vocab": 256,
                       "expert_placement": "none", "engine": {"prompt_buckets": [512]}}}
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "otherblock-toy.json"
    path.write_text(json.dumps(cfg))
    return str(tmp_path), str(path), cfg


def test_a_second_family_goes_from_a_file_to_a_verdict_with_no_edit(other_root):
    """Through the loader ``run.py`` uses, its search directory an argument:
    the family and its reference are two files outside ``benchmark/``."""
    import jax
    import numpy as np

    from rag_llm_k8s_tpu.core.config import DTypePolicy, MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh

    root, path, _ = other_root
    cfg, family = serve.load_config(path, root)
    reference = serve.load_reference(cfg["model_type"], root)
    assert family.__file__ == os.path.join(root, "families", "otherblock.py")
    assert reference.__file__ == os.path.join(root, "references", "otherblock.py")
    cfg.update(family.REHEARSAL_MODEL)
    model = family.model_config(cfg)
    assert (model.num_layers, model.num_heads, model.num_kv_heads, model.hidden_size) == (3, 2, 1, 32)
    assert family.layer_loop_trips(cfg) == 2  # the family's statement, not the depth
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=jax.devices()[:1])
    s = cfg["serving"]
    params = family.make_params(model, DTypePolicy(), s["weights_seed"], s["weight_quant"], mesh,
                                s["recite_gain"])
    assert params["layers"]["mlp"]["w_down"]["kernel_q"].shape == (3, 64, 32)
    # the served configuration is built from the same file (sections by name)
    config = serve.app_config(cfg, model, os.path.join(root, "work"), 8, attn_impl="xla")
    assert config.model is model and config.engine.prompt_buckets == (512,)

    # the reference's own greedy continuation of a prompt is what a sound
    # program would deliver: every verdict number is 0, inside the family's limits
    prompt, emitted = [5, 9, 200, 31, 77, 2, 140], []
    device = jax.devices()[0]
    for _ in range(4):
        out = reference.score(params, cfg, [(prompt, emitted + [0])], device)[0]
        emitted.append(int(out["argmax"][-1]))
    ref = reference.score(params, cfg, [(prompt, emitted)], device)[0]
    assert list(ref["argmax"]) == emitted
    assert stats.half_gap_max(ref) == 0.0 <= reference.HALF_GAP_TOL
    assert stats.logit_err_max(ref, ref) == 0.0 <= reference.LOGIT_TOL
    # and a stream that is not the reference's choice is told apart
    wrong = emitted[:-1] + [(emitted[-1] + 1) % 256]
    bad = reference.score(params, cfg, [(prompt, wrong)], device)[0]
    assert stats.half_gap_max(bad) > 0.0
    np.testing.assert_array_equal(bad["argmax"][:-1], ref["argmax"][:-1])


def test_the_second_familys_keys_are_its_own(other_root, tmp_path):
    root, path, cfg = other_root
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(dict(cfg, hidden_size=4096)))  # a Mistral key is unknown here
    with pytest.raises(ValueError, match=r"unknown keys \['hidden_size'\]"):
        serve.load_config(str(bad), root)
    bad.write_text(json.dumps(dict(cfg, activation="gelu")))
    with pytest.raises(ValueError, match="activation='gelu'"):
        serve.load_config(str(bad), root)
    bad.write_text(json.dumps(dict(cfg, serving=dict(cfg["serving"], surprise=1))))
    with pytest.raises(ValueError, match=r"unknown serving keys \['surprise'\]"):
        serve.load_config(str(bad), root)
    bad.write_text(json.dumps({k: v for k, v in cfg.items() if k != "model_type"}))
    with pytest.raises(ValueError, match="no model_type"):
        serve.load_config(str(bad), root)
    # and Mistral's file does not load against a directory without its family
    with pytest.raises(FileNotFoundError, match="families/mistral.py"):
        serve.load_config(MISTRAL_7B, root)


# ---- a missing file, or one short of the contract, fails by name before JAX -------

PROBE = '''
import sys
sys.path.insert(0, {repo!r})
from benchmark.lib import serve
try:
    serve.{loader}({model_type!r}, {root!r})
except Exception as e:
    print(type(e).__name__, e)
print("jax" in sys.modules)
'''


@pytest.mark.parametrize("loader, model_type, text, error, names", [
    ("load_family", "nope", None, "FileNotFoundError", ["'nope'", "families/nope.py"]),
    ("load_reference", "nope", None, "FileNotFoundError", ["'nope'", "references/nope.py"]),
    ("load_family", "short", OTHER_FAMILY.replace("def layer_loop_trips", "def trips"),
     "AttributeError", ["families/short.py", "['layer_loop_trips']"]),
    ("load_reference", "short", OTHER_REFERENCE.replace("LOGIT_TOL", "TOLERANCE"),
     "AttributeError", ["references/short.py", "['LOGIT_TOL']"]),
    ("load_family", "../lib/serve", None, "ValueError", ["not a plain name"]),
])
def test_a_missing_or_short_file_fails_by_name_before_any_jax_import(
        tmp_path, loader, model_type, text, error, names):
    if text is not None:
        kind = "families" if loader == "load_family" else "references"
        (tmp_path / kind).mkdir()
        (tmp_path / kind / (model_type + ".py")).write_text(text)
    p = subprocess.run(
        [sys.executable, "-c", PROBE.format(repo=REPO, loader=loader, model_type=model_type,
                                            root=str(tmp_path))],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    said, jax_loaded = p.stdout.strip().splitlines()
    assert said.startswith(error + " "), said
    assert all(n in said for n in names), said
    assert jax_loaded == "False"


def test_every_family_here_has_its_reference_and_keys_of_its_own():
    for name in os.listdir(os.path.join(BENCH, "families")):
        if name.endswith(".py"):
            family = serve.load_family(name[:-3])
            reference = serve.load_reference(name[:-3])  # a family without a reference is half of one
            assert callable(family.model_config) and callable(reference.score)
            assert 0.0 < reference.HALF_GAP_TOL < reference.LOGIT_TOL
            assert not set(family.FIXED) & set(family.PUBLISHED_KEYS)
            assert not (set(family.FIXED) | set(family.PUBLISHED_KEYS)) & serve.COMMON_KEYS


# ---- serving: sections of the program's AppConfig, nested by field name ------------


def test_a_dict_for_a_dataclass_field_is_overrides_of_that_dataclass():
    from rag_llm_k8s_tpu.core.config import EngineConfig, PrefixCacheConfig

    engine = serve._replace(EngineConfig(), {"prefix_cache": {"enabled": True},
                                             "prompt_buckets": [2048, 4096]}, "serving.engine")
    assert isinstance(engine.prefix_cache, PrefixCacheConfig)  # not the dict it was handed
    assert engine.prefix_cache.enabled is True and EngineConfig().prefix_cache.enabled is False
    assert engine.prefix_cache == PrefixCacheConfig(enabled=True)  # the other fields keep their defaults
    assert engine.prompt_buckets == (2048, 4096)
    with pytest.raises(ValueError, match=r"serving\.engine\.prefix_cache: no such field \['enable'\]"):
        serve._replace(EngineConfig(), {"prefix_cache": {"enable": True}}, "serving.engine")
    with pytest.raises(ValueError, match=r"serving\.engine: no such field \['prefix_cash'\]"):
        serve._replace(EngineConfig(), {"prefix_cash": {"enabled": True}}, "serving.engine")


def test_serving_may_override_any_section_of_the_app_config(tmp_path):
    from rag_llm_k8s_tpu.core.config import AppConfig

    cfg, family = serve.load_config(MISTRAL_7B)
    work = str(tmp_path)
    plain = serve.app_config(cfg, family.model_config(cfg), work, 150)
    assert plain.engine.prompt_buckets == (2048, 4096) and plain.sampling.do_sample is False
    assert plain.shadow.sample_rate == 0.0 and plain.flight.spool_dir == os.path.join(work, "incidents")
    cfg["serving"].update(
        engine={"prompt_buckets": [4096], "prefix_cache": {"enabled": True}},
        shadow={"sample_rate": 0.05}, flight={"enabled": False},
        retrieval={"context_top_n": 2})
    config = serve.app_config(cfg, family.model_config(cfg), work, 150)
    assert config.engine.prefix_cache.enabled is True and config.engine.prompt_buckets == (4096,)
    assert config.engine.weight_quant == "int8" and config.sampling.max_new_tokens == 150
    assert config.shadow.sample_rate == 0.05 and config.retrieval.context_top_n == 2
    assert config.flight.enabled is False and config.flight.spool_dir == plain.flight.spool_dir
    assert config.slo == AppConfig().slo  # a section the file does not name keeps the program's
    cfg["serving"]["telemetry"] = {"enabled": True}
    with pytest.raises(ValueError, match=r"serving: no such field \['telemetry'\]"):
        serve.app_config(cfg, family.model_config(cfg), work, 150)

"""The executable store in front of tracing (``core/compile_cache.py``,
``obs/tracing.py build_span``): a build whose site hands an identity, with a
compile cache directory placed, is keyed without tracing; an entry found is
loaded, an entry not found is built and kept; anything wrong with an entry is a
miss. Toy programs on the CPU, a ``tmp_path`` cache directory."""

import dataclasses
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core import compile_cache
from rag_llm_k8s_tpu.core.config import DTypePolicy, EngineConfig, LlamaConfig, SamplingConfig
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import tracing

FP32 = DTypePolicy.fp32()
IDENTITY = ("toy", LlamaConfig.tiny(), 3)


@pytest.fixture
def placed(tmp_path):
    """A compile cache directory placed for one test, the store inside it."""
    from jax._src import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        yield os.path.join(str(tmp_path), compile_cache.STORE_SUBDIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def _toy(shape=(8, 16), dtype=jnp.float32, scale=2.0, kernel=None):
    """``make`` of a build: a FRESH function every call (nothing of the last
    build is in JAX's in-memory tables), a donated tree in and a tree out."""
    def make():
        def f(state, x):
            if kernel is not None:  # what model code does where it is traced
                tracing.count_kernel_build("decode", kernel)
                tracing.count_kernel_build("prefill", kernel)
                tracing.count_kernel_build("prefill", kernel)
            return {"acc": state["acc"] + jnp.sin(x) * scale, "n": state["n"] + 1}, x.sum()

        state = {"acc": jax.ShapeDtypeStruct(shape, dtype), "n": jax.ShapeDtypeStruct((), jnp.int32)}
        return jax.jit(f, donate_argnums=(0,)), (state, jax.ShapeDtypeStruct(shape, dtype))
    return make


def _build(make=None, identity=IDENTITY, key=(1, 16, 4)):
    """One ``build_span`` under a trace: ``(executable, span attrs, events gained)``."""
    before = tracing.compile_census()[1]
    tr = tracing.start_trace()
    fn = tracing.build_span("generate", key, make or _toy(), identity=identity, rows=1, bucket=16)
    tracing.finish_trace(tr)
    after = tracing.compile_census()[1]
    (sp,) = [s for s in tr.spans if s.name == "build/generate"]
    return fn, sp.attrs, {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)}


def _run(fn, shape=(8, 16)):
    state = {"acc": jnp.full(shape, 0.5, jnp.float32), "n": jnp.int32(7)}
    out = fn(state, jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape))
    return jax.tree.map(np.asarray, out), state["acc"].is_deleted()


def _entries(store):
    return sorted(n for n in os.listdir(store) if n.endswith(".rexe")) if os.path.isdir(store) else []


def test_a_second_build_loads_what_the_first_kept(placed):
    cold, cold_attrs, cold_events = _build()
    assert cold_attrs["trace_s"] > 0 and cold_attrs["lower_s"] > 0 and cold_attrs["cache_hit"] < 2
    assert ("generate", "stored") not in cold_events and len(_entries(placed)) == 1
    warm, attrs, events = _build()  # a fresh function: only the store can know it
    assert attrs["trace_s"] == attrs["lower_s"] == 0.0 and attrs["compile_s"] > 0
    assert attrs["cache_hit"] == 2.0 and events == {("generate", "stored"): 1}
    (want, want_n), donated_cold = _run(cold)
    (got, got_n), donated_warm = _run(warm)
    assert np.array_equal(want["acc"], got["acc"]) and want["n"] == got["n"] == 8
    assert want_n == got_n and donated_cold == donated_warm is True
    assert len(_entries(placed)) == 1 and compile_cache.store_bytes() > 0


@pytest.mark.parametrize("what", [
    "shape", "dtype", "identity", "key", "source", "version", "config", "flags"])
def test_a_changed_input_of_the_key_misses(placed, monkeypatch, what):
    _build()
    changed = {"shape": dict(make=_toy(shape=(8, 32))),
               "dtype": dict(make=_toy(dtype=jnp.bfloat16)),
               "identity": dict(identity=("toy", dataclasses.replace(LlamaConfig.tiny(), rope_theta=1e4), 3)),
               "key": dict(key=(1, 16, 5))}.get(what, {})
    if what == "source":
        monkeypatch.setattr(compile_cache, "_source_hash", "0" * 64)
    elif what == "version":
        real = compile_cache._versions
        monkeypatch.setattr(compile_cache, "_versions", lambda: {**real(), "jaxlib": "0.0.1"})
    elif what == "flags":
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_some_flag=true")
    was = jax.config.jax_default_matmul_precision
    try:
        if what == "config":
            jax.config.update("jax_default_matmul_precision", "highest")
        _, attrs, events = _build(**changed)
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    assert attrs["trace_s"] > 0 and ("generate", "stored") not in events
    assert len(_entries(placed)) == 2  # the new key's entry beside the old one
    assert len({n[:12] for n in _entries(placed)}) == (2 if what == "source" else 1)


def test_no_cache_directory_or_no_identity_reads_and_writes_nothing(tmp_path, placed, monkeypatch):
    for _ in range(2):  # no identity: as the parent builds, both times
        _, attrs, events = _build(identity=None)
        assert attrs["trace_s"] > 0 and ("generate", "stored") not in events
    assert _entries(placed) == []
    asked = []
    monkeypatch.setattr(compile_cache.StoreEntry, "load", lambda self: asked.append(self) or None)
    jax.config.update("jax_compilation_cache_dir", None)  # ``placed`` restores it
    assert compile_cache.store_dir() is None
    for _ in range(2):
        _, attrs, events = _build()
        assert attrs["trace_s"] > 0 and ("generate", "stored") not in events
    assert asked == [] and _entries(placed) == [] and os.listdir(tmp_path) == []


def test_an_identity_that_names_an_address_is_never_stored(placed):
    _build(identity=("toy", object()))
    assert _entries(placed) == []


@pytest.mark.parametrize("damage", ["truncated", "garbage", "payload", "empty"])
def test_a_bad_entry_is_a_miss_that_is_rebuilt_and_replaced(placed, damage):
    _build()
    (name,) = _entries(placed)
    path = os.path.join(placed, name)
    with open(path, "rb") as f:
        whole = f.read()
    bad = {"truncated": whole[: len(whole) // 2], "garbage": os.urandom(4096), "empty": b"",
           "payload": whole[:-64] + bytes(64)}[damage]  # a flipped tail under a whole manifest
    with open(path, "wb") as f:
        f.write(bad)
    fn, attrs, events = _build()
    assert attrs["trace_s"] > 0 and ("generate", "stored") not in events
    assert _run(fn)[0][0]["n"] == 8
    assert compile_cache.read_manifest(path)["program"] == "generate"  # replaced by a whole entry
    assert _build()[2] == {("generate", "stored"): 1}


def test_two_threads_that_miss_together_leave_one_readable_entry(placed):
    gate, out = threading.Barrier(2), []

    def worker():
        gate.wait()
        out.append(tracing.build_span("generate", (1, 16, 4), _toy(), identity=IDENTITY))

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == 2 and len(_entries(placed)) == 1
    assert [n for n in os.listdir(placed) if n.endswith(".tmp")] == []
    fn, _, events = _build()
    assert events == {("generate", "stored"): 1} and _run(fn)[0][0]["n"] == 8


def test_kernel_builds_read_the_same_after_a_warm_build_as_after_a_cold_one(placed):
    def gained(before):
        return {k: n - before.get(k, 0) for k, n in tracing.kernel_builds().items()
                if n != before.get(k, 0)}

    before = tracing.kernel_builds()
    _build(make=_toy(kernel="toy_kernel_pallas"))
    cold = gained(before)
    assert cold == {("decode", "toy_kernel_pallas"): 1, ("prefill", "toy_kernel_pallas"): 2}
    before = tracing.kernel_builds()
    _, _, events = _build(make=_toy(kernel="toy_kernel_pallas"))
    assert events == {("generate", "stored"): 1} and gained(before) == cold
    # an increment outside any build is noted on none
    tracing.count_kernel_build("decode", "toy_kernel_pallas")
    assert gained(before)[("decode", "toy_kernel_pallas")] == 2


def test_the_store_never_holds_more_than_its_budget(placed, monkeypatch):
    _build(key=(1, 16, 1))
    size = compile_cache.store_bytes()
    monkeypatch.setattr(compile_cache, "STORE_BUDGET_BYTES", int(size * 3.5))
    monkeypatch.setattr(compile_cache, "_source_hash", "1" * 64)  # an older image's entry
    _build(key=(1, 16, 2))
    monkeypatch.undo()
    monkeypatch.setattr(compile_cache, "STORE_BUDGET_BYTES", int(size * 3.5))
    first = _entries(placed)
    _build(key=(1, 16, 3))
    os.utime(os.path.join(placed, [n for n in _entries(placed) if n not in first][0]), (1, 1))
    assert len(_entries(placed)) == 3 and compile_cache.store_bytes() <= size * 3.5
    _build(key=(1, 16, 4))  # no room: the other source's entry goes first
    assert len(_entries(placed)) == 3 and not [n for n in _entries(placed) if n.startswith("1" * 12)]
    _build(key=(1, 16, 1))  # read: now the newest
    _build(key=(1, 16, 5))  # no room: the one read longest ago goes (key 3, touched back)
    assert compile_cache.store_bytes() <= size * 3.5
    assert _build(key=(1, 16, 1))[2] == {("generate", "stored"): 1}
    assert ("generate", "stored") not in _build(key=(1, 16, 3))[2]
    monkeypatch.setattr(compile_cache, "STORE_BUDGET_BYTES", size // 2)  # one entry is too many
    held = _entries(placed)
    assert ("generate", "stored") not in _build(key=(1, 16, 6))[2] and _entries(placed) == held


# ---------------------------------------------------------------------------
# the key covers the program: the toy Llama engine's ``generate``
# ---------------------------------------------------------------------------

GREEDY = SamplingConfig(do_sample=False, max_new_tokens=4)
SMALL = EngineConfig(prompt_buckets=(16, 32), max_batch_size=2, speculative="off")
TINY = LlamaConfig.tiny()
# (model configuration, engine options, sampling, dtypes, pad id): the first is
# the base, the second the base again, the rest each change one thing the
# program closes over or is built from
VARIANTS = [
    (TINY, SMALL, GREEDY, FP32, 0),
    (TINY, SMALL, GREEDY, FP32, 0),
    (dataclasses.replace(TINY, rope_theta=10000.0), SMALL, GREEDY, FP32, 0),
    (dataclasses.replace(TINY, rms_norm_eps=1e-6), SMALL, GREEDY, FP32, 0),
    (dataclasses.replace(TINY, eos_token_ids=(2, 5)), SMALL, GREEDY, FP32, 0),
    (dataclasses.replace(TINY, num_layers=1), SMALL, GREEDY, FP32, 0),
    (TINY, dataclasses.replace(SMALL, kv_quant="int8"), GREEDY, FP32, 0),
    (TINY, dataclasses.replace(SMALL, fuse_matmuls=False), GREEDY, FP32, 0),
    (TINY, dataclasses.replace(SMALL, attn_impl="xla"), GREEDY, FP32, 0),  # same text on the CPU
    (TINY, SMALL, SamplingConfig(do_sample=True, temperature=0.5, max_new_tokens=4), FP32, 0),
    (TINY, SMALL, SamplingConfig(do_sample=True, top_p=0.5, max_new_tokens=4), FP32, 0),
    (TINY, SMALL, GREEDY, DTypePolicy(), 0),
    (TINY, SMALL, GREEDY, FP32, 9),
]


_params = {}


def _engine(variant):
    cfg, engine_config, sampling, dtypes, pad_id = variant
    # seeded weights in the initializer's shapes without compiling it: the
    # variants change what is traced, and only depth and dtype change the tree
    if (cfg.num_layers, dtypes) not in _params:
        rng = np.random.default_rng(0)
        _params[cfg.num_layers, dtypes] = jax.tree.map(
            lambda a: jnp.asarray(rng.standard_normal(a.shape) * 0.05, a.dtype),
            jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), cfg, dtypes)))
    params = _params[cfg.num_layers, dtypes]
    return InferenceEngine(cfg, params, sampling=sampling, engine_config=engine_config,
                           dtypes=dtypes, pad_id=pad_id)


def test_the_engines_key_differs_wherever_its_lowered_text_does(placed):
    key, digests, texts = (1, 16, 4, None), [], []
    for variant in VARIANTS:
        eng = _engine(variant)
        jitted, avals = eng._build_generate(1, 16, 4)
        digests.append(compile_cache.entry_for("generate", key, avals, eng._build_identity).digest)
        texts.append(compile_cache.lowered_text_sha256(jitted.trace(*avals).lower()))
    assert digests[0] == digests[1] and texts[0] == texts[1]  # two engines of one configuration
    assert len(set(texts)) >= len(VARIANTS) - 3  # the list does change the program
    for i in range(len(VARIANTS)):
        for j in range(i):
            assert texts[i] == texts[j] or digests[i] != digests[j], (i, j)
    # and a build through the engine records the text it compiled
    eng = _engine(VARIANTS[0])
    eng._get_compiled(1, 16, 4)
    (name,) = _entries(placed)
    manifest = compile_cache.read_manifest(os.path.join(placed, name))
    assert manifest["digest"] == digests[0] and manifest["lowered_sha256"] == texts[0]
    assert manifest["program"] == "generate" and manifest["devices"] == [0]
    again = _engine(VARIANTS[1])
    before = tracing.compile_census()[1]
    fn = again._get_compiled(1, 16, 4)
    assert tracing.compile_census()[1].get(("generate", "stored"), 0) \
        == before.get(("generate", "stored"), 0) + 1
    assert again.generate([[3, 17, 42, 7, 99]]) == eng.generate([[3, 17, 42, 7, 99]]) and fn is not None

"""The single-token decode kernels walk each row's live window (PR 32).

``decode_attention``, ``decode_attention_q8`` and ``mla_decode_attention``
fetch, a row, the steps ``ops/attention.py decode_block_plan`` names and
nothing else of the cache; the steps need not divide the cache length. The
kernels meet their dense oracles in interpret mode on windows that start
inside a step, on a step's edge and at slot 0, end at the cache's last slot
or hold one slot, with every dead slot poisoned; the plan is checked against
brute force; the models' counters against the plan; the benchmark's reader
against a recorded ``/metrics`` pair. The chunk kernels keep the old block
rule (``_decode_block``): the pin at the end is for the benchmark's ``solo``
cell, whose verify program must not drift with the new one.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import DTypePolicy, EngineConfig, LlamaConfig, SamplingConfig
from rag_llm_k8s_tpu.ops import attention as A
from rag_llm_k8s_tpu.ops import mla as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAN = float("nan")


# ---------------------------------------------------------------------------
# the kernels against their oracles
# ---------------------------------------------------------------------------

# (T, step): 17 steps' worth and a quarter (4352 = 8.5 x 512's analogue), so
# the last step of a full window is fetched from T - step; and a cache of
# 17 x 32 walked in steps of 128 (4.25 steps)
SIZES = {"T272_step64": (272, 64), "T544_step128": (544, 128)}


def _windows(T: int, step: int):
    """Rows of ONE batch with different windows, by name."""
    return {
        "inside_a_step_to_the_end": (step + step // 2 + 5, T),
        "on_a_step_edge": (2 * step, T - 3),
        "from_slot_0": (0, step + 7),
        "whole_cache": (0, T),
        "one_slot_at_0": (0, 1),
        "one_slot_mid": (step + 1, step + 2),
        "one_slot_last": (T - 1, T),
        "across_the_clamped_step": (T - step - 9, T),
    }


def _poison(x, ok, axis):
    """NaN wherever ``ok`` (over ``axis``) is False."""
    shape = [1] * x.ndim
    shape[1], shape[axis] = ok.shape[0], ok.shape[1]  # [.., B, .., T, ..]
    return jnp.where(ok.reshape(shape), x, NAN)


def _gqa(q8: bool, T: int, step: int, kv_start, kv_len, seed: int):
    B, K, G, hd, L, lay = len(kv_start), 2, 4, 32, 2, 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, K * G, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
    ok = (np.arange(T)[None] >= np.asarray(kv_start)[:, None]) & (np.arange(T)[None] < np.asarray(kv_len)[:, None])
    ok = jnp.asarray(ok)
    st, ln = jnp.asarray(kv_start, jnp.int32), jnp.asarray(kv_len, jnp.int32)
    if q8:  # the int8 payload is finite by construction: the scales are what can be NaN
        (kq, ksc), (vq, vsc) = A.quantize_kv(kc), A.quantize_kv(vc)
        ksc, vsc = _poison(ksc, ok, 3), _poison(vsc, ok, 3)
        args = (q, kq, vq, ksc, vsc, st, ln, jnp.int32(lay))
        return A.decode_attention_q8(*args, bk=step, interpret=True), A.decode_attention_xla_q8(*args)
    kc, vc = _poison(kc, ok, 3), _poison(vc, ok, 3)
    args = (q, kc, vc, st, ln, jnp.int32(lay))
    want = A.decode_attention_xla(q, jnp.nan_to_num(kc), jnp.nan_to_num(vc), st, ln, jnp.int32(lay))
    return A.decode_attention(*args, bk=step, interpret=True), want


def _latent(T: int, step: int, kv_start, kv_len, seed: int):
    B, H, C, R, L, lay = len(kv_start), 8, 32, 16, 2, 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    ql = jax.random.normal(ks[0], (B, 1, H, C), jnp.float32)
    qr = jax.random.normal(ks[1], (B, 1, H, R), jnp.float32)
    cc = jax.random.normal(ks[2], (L, B, T, C), jnp.float32)
    rc = jax.random.normal(ks[3], (L, B, T, R), jnp.float32)
    ok = jnp.asarray((np.arange(T)[None] >= np.asarray(kv_start)[:, None])
                     & (np.arange(T)[None] < np.asarray(kv_len)[:, None]))
    st, ln = jnp.asarray(kv_start, jnp.int32), jnp.asarray(kv_len, jnp.int32)
    got = M.mla_decode_attention(ql, qr, _poison(cc, ok, 2), _poison(rc, ok, 2), st, ln, jnp.int32(lay),
                                 scale=0.2, bk=step, interpret=True)
    # the oracle's decode query sits at the row's last live slot; rows differ, so a row at a time
    want = jnp.concatenate([
        M.latent_attention_xla(ql[b:b + 1], qr[b:b + 1], cc[:, b:b + 1], rc[:, b:b + 1], st[b:b + 1],
                               ln[b:b + 1], jnp.int32(lay), ln[b] - 1, scale=0.2)
        for b in range(B)])
    return got, want


KERNELS = {
    "decode_attention": lambda *a: _gqa(False, *a),
    "decode_attention_q8": lambda *a: _gqa(True, *a),
    "mla_decode_attention": _latent,
}
BATCHES = {
    "mixed_rows": ("inside_a_step_to_the_end", "on_a_step_edge", "from_slot_0", "one_slot_last"),
    "edges": ("whole_cache", "one_slot_at_0", "one_slot_mid", "across_the_clamped_step"),
}


@pytest.mark.parametrize("batch", sorted(BATCHES))
@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_matches_its_oracle_on_poisoned_caches(kernel, size, batch):
    T, step = SIZES[size]
    rows = [_windows(T, step)[name] for name in BATCHES[batch]]
    got, want = KERNELS[kernel](T, step, [r[0] for r in rows], [r[1] for r in rows], 7)
    assert np.isfinite(np.asarray(got)).all(), "a dead slot's NaN reached the output"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_an_empty_window_is_zeros():
    """``kv_len <= kv_start``: the walk still makes its one fetch, every slot
    of it masked."""
    got, _ = _gqa(False, 272, 64, [100, 0], [100, 272], 3)
    assert np.asarray(got[0] == 0).all() and np.isfinite(np.asarray(got)).all()


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,step", [(4352, 512), (4352, 1024), (4352, 2048), (272, 64), (544, 128),
                                    (256, 256), (200, 200), (1024, 48)])
def test_plan_names_exactly_the_steps_that_touch_the_window(T, step):
    rng = np.random.default_rng(T + step)
    starts = np.minimum(np.concatenate([[0, 0, T - 1, step, step - 1, step + 1], rng.integers(0, T, 200)]), T - 1)
    lens = np.concatenate([[T, 1, T, T, step, step + 2], rng.integers(1, T + 1, 200)])
    lens = np.maximum(lens, starts + 1)  # a live window; the empty one is below
    origin, n = (np.asarray(x) for x in A.decode_block_plan(starts, lens, T, step))
    align = np.gcd(step, A.DECODE_ALIGN)
    assert (origin % align == 0).all() and (origin <= starts).all() and (starts - origin < align).all()
    for s, e, o, k in zip(starts, lens, origin, n):
        # every step from the origin to the end of the cache: named iff it holds a live slot
        for j in range(-(-(T - o) // step)):
            lo, hi = o + j * step, o + (j + 1) * step
            assert (j < k) == (lo < e and hi > s), (s, e, o, k, j)
        # what is fetched stays inside the cache, and covers the window once
        covered = np.zeros(T, bool)
        for j in range(k):
            first, nominal = (int(x) for x in A.decode_step_bounds(o, j, T, step))
            assert 0 <= first and first + step <= T and first <= nominal
            live = np.arange(first, first + step)
            live = live[(live >= max(s, nominal)) & (live < e)]
            assert not covered[live].any()
            covered[live] = True
        assert covered[s:e].all() and not covered[:s].any() and not covered[e:].any()
    empty = A.decode_block_plan(np.array([50, T - 1]), np.array([50, 3]), T, step)
    assert np.asarray(empty[1]).tolist() == [1, 1]
    assert int(A.decode_slots_streamed(starts, lens, T, step)) == int(n.sum()) * step


def test_the_step_follows_the_shape():
    """The four serving shapes (T = 4352: the 4096 bucket + 150 new tokens,
    rounded), as swept on the chip; and a cache too short or oddly sized to
    walk is one step."""
    assert A.gqa_decode_step(4352, 8, 4, 128, jnp.int8) == 512  # Mistral-7B, int8 KV, one chip
    assert A.gqa_decode_step(4352, 2, 4, 128, jnp.bfloat16) == 1024  # Nemo's 2 local heads under tp=4
    assert M.latent_decode_step(4352, 128, 512, jnp.bfloat16) == 2048  # 128 heads over a rank-512 latent
    assert M.latent_decode_step(4352, 64, 512, jnp.bfloat16) == 2048
    assert A.gqa_decode_step(4352, 8, 4, 128, jnp.bfloat16) == 256  # 1 MiB of a bf16 cache of 8 heads
    assert A.decode_step(128, 1024, 8, 128) == 128 and A.decode_step(200, 1024, 8, 128) == 200
    # a step never outgrows half the cache, nor the VMEM a step may take
    assert A.decode_step(1024, 64, 8, 128) == 512
    assert A.decode_step(1 << 20, 4096, 128, 4096) * (4 * 4096 + 12 * 128) <= A._DECODE_VMEM


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------

CFG = LlamaConfig.tiny(vocab_size=64)
NEW = 5


@pytest.fixture(scope="module")
def llama_params():
    from rag_llm_k8s_tpu.models.llama import init_llama_params

    return init_llama_params(jax.random.PRNGKey(0), CFG, DTypePolicy.fp32())


def _expected(lens, bucket, T, step, new):
    """What ``new`` tokens cost: the first comes of the prefill, each later
    one of a decode step whose window is the row's prompt plus what it has
    written; the plan says what each step fetches."""
    starts = np.array([bucket - n for n in lens])
    streamed = sum(int(A.decode_slots_streamed(starts, np.full(len(lens), bucket + t + 1), T, step))
                   for t in range(new - 1))
    return streamed, (new - 1) * len(lens) * T


@pytest.mark.parametrize("kv_quant", ["bf16", "int8"])
def test_llama_counts_what_the_plan_fetches(llama_params, kv_quant):
    from rag_llm_k8s_tpu.engine import InferenceEngine

    ec = EngineConfig(prompt_buckets=(256,), max_batch_size=2, max_seq_len=512, attn_impl="pallas_interpret",
                      kv_quant=kv_quant)
    eng = InferenceEngine(CFG, llama_params, sampling=SamplingConfig(do_sample=False, max_new_tokens=NEW),
                          engine_config=ec, dtypes=DTypePolicy.fp32())
    lens = (40, 200)
    eng.generate([list(range(3, 3 + n)) for n in lens])
    T = 256 + -(-NEW // 128) * 128
    dtype = jnp.int8 if kv_quant == "int8" else jnp.float32
    step = A.gqa_decode_step(T, CFG.num_kv_heads, CFG.num_heads // CFG.num_kv_heads, CFG.head_dim, dtype)
    streamed, allocated = _expected(lens, 256, T, step, NEW)
    got = eng.stats.family_counters
    assert (got["decode_slots_streamed"], got["decode_slots_allocated"]) == (streamed, allocated)
    assert 0 < streamed < allocated  # the short row's left pad is not fetched

    xla = InferenceEngine(CFG, llama_params, sampling=SamplingConfig(do_sample=False, max_new_tokens=NEW),
                          engine_config=EngineConfig(prompt_buckets=(256,), max_batch_size=2, max_seq_len=512,
                                                     attn_impl="xla", kv_quant=kv_quant),
                          dtypes=DTypePolicy.fp32())
    xla.generate([list(range(3, 3 + n)) for n in lens])
    assert xla.stats.family_counters["decode_slots_allocated"] == 0  # no kernel, no plan, nothing counted


def test_under_a_tp_mesh_the_plan_is_the_local_kernel_s(llama_params):
    """tp=2 shards the kernel over heads (``shard_map``: one local KV head a
    device): the same tokens, and the counters read the step of the LOCAL
    shapes, once a step and not once a device."""
    from rag_llm_k8s_tpu.core.config import MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.engine import InferenceEngine
    from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

    ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
    ec = EngineConfig(prompt_buckets=(256,), max_batch_size=2, max_seq_len=512, attn_impl="pallas_interpret")
    greedy = SamplingConfig(do_sample=False, max_new_tokens=NEW)
    prompts = [list(range(3, 43)), list(range(3, 203))]
    sharded = InferenceEngine(CFG, shard_llama_params(llama_params, ctx), sampling=greedy, engine_config=ec,
                              dtypes=DTypePolicy.fp32(), mesh=ctx)
    single = InferenceEngine(CFG, llama_params, sampling=greedy, engine_config=ec, dtypes=DTypePolicy.fp32())
    assert sharded.generate(prompts) == single.generate(prompts)
    T = 256 + 128
    step = A.gqa_decode_step(T, CFG.num_kv_heads // 2, CFG.num_heads // CFG.num_kv_heads, CFG.head_dim, jnp.float32)
    streamed, allocated = _expected((40, 200), 256, T, step, NEW)
    got = sharded.stats.family_counters
    assert (got["decode_slots_streamed"], got["decode_slots_allocated"]) == (streamed, allocated)


def test_the_latent_family_counts_too():
    from rag_llm_k8s_tpu.core.config import LatentMoEConfig
    from rag_llm_k8s_tpu.engine import InferenceEngine
    from rag_llm_k8s_tpu.models.latent_moe import init_latent_moe_params

    cfg = LatentMoEConfig.tiny(vocab_size=64)
    params = init_latent_moe_params(jax.random.PRNGKey(1), cfg, DTypePolicy.fp32())
    ec = EngineConfig(prompt_buckets=(256,), max_batch_size=2, max_seq_len=512, attn_impl="pallas_interpret")
    eng = InferenceEngine(cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=NEW),
                          engine_config=ec, dtypes=DTypePolicy.fp32())
    lens = (30, 150)
    eng.generate([list(range(3, 3 + n)) for n in lens])
    T = 256 + 128
    step = M.latent_decode_step(T, cfg.num_heads, cfg.kv_lora_rank, jnp.float32)
    streamed, allocated = _expected(lens, 256, T, step, NEW)
    got = eng.stats.family_counters
    assert (got["decode_slots_streamed"], got["decode_slots_allocated"]) == (streamed, allocated)
    assert got["moe_decode_layer_steps"] > 0  # the older fields kept their places


# ---------------------------------------------------------------------------
# the benchmark's reader
# ---------------------------------------------------------------------------


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# two scrapes of ``/metrics`` around a window of ``mistral-7b-int8.closed8``
# as the chip served it (PR 32): 80 answers of 149 decode steps at batch 8
BEFORE = {"tpu_rag_engine_decode_slots_streamed": 4153344.0, "tpu_rag_engine_decode_slots_allocated": 5187584.0,
          "tpu_rag_engine_prefill_tokens_bucketed": 32768.0}
AFTER = {"tpu_rag_engine_decode_slots_streamed": 45686784.0, "tpu_rag_engine_decode_slots_allocated": 57063424.0,
         "tpu_rag_engine_prefill_tokens_bucketed": 360448.0}


@pytest.mark.parametrize("case,before,after,want", [
    ("a_recorded_pair", BEFORE, AFTER, 100.0 * (45686784 - 4153344) / (57063424 - 5187584)),
    ("no_such_counters", {"tpu_rag_engine_prefill_tokens_bucketed": 1.0},
     {"tpu_rag_engine_prefill_tokens_bucketed": 9.0}, None),
    ("counters_that_did_not_move", AFTER, AFTER, None),
])
def test_reader_of_decode_streamed_slot_share(case, before, after, want):
    reader = _load("benchmark/layer_metrics/decode_streamed_slot_share.py", "decode_streamed_slot_share")
    stats = _load("benchmark/lib/stats.py", "bench_stats")
    got = reader.read({"stats": stats, "before": before, "after": after})
    assert got == want if want is None else got == pytest.approx(want)
    if want is not None:
        assert 0.0 < got < 100.0


def test_the_benchmark_lists_the_metric_for_the_four_batched_cells():
    """And, appended by PR 33, for the windowed family's cell, where it is a
    FULL layer's share (the sliding layers' is a metric of its own); by PR 40
    for the hybrid state-space family's, whose two attention layers walk; by
    PR 45 for the gated-convolution family's, whose heads of 64 the walk
    refuses: its steps take the chunk form and the share reads 100; by PR 53
    for the decoder-hybrid-decoder family's: the full layer's own walk of the
    plane seven cross layers also read; by PR 55 for the state-space-duality
    family's one attention layer of eleven (32 query heads over 2 KV heads)."""
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}["decode_streamed_slot_share"]
    assert entry["better"] == "lower"
    assert entry["source"] == "program_counter" and entry["moves"] == "latency_p50_ms"
    assert entry["workloads"] == ["mistral-7b-int8.closed8", "mistral-nemo-tp4.closed4",
                                  "dots-vlm1-ep16.closed8", "longcat-flash-ep32.closed8",
                                  "laguna-s-ep16.closed8", "jamba2-3b.closed8", "lfm2-24b-a2b-pp4.solo",
                                  "kimi-linear-ep16.solo", "phi4-mini-flash.solo-12chunk",
                                  "nemotron-3-super-ep4.solo"]


# ---------------------------------------------------------------------------
# the control: the chunk kernels keep the old block rule
# ---------------------------------------------------------------------------


def _grids(fn, *args, **kw):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr if hasattr(sub.jaxpr, "eqns") else sub.jaxpr.jaxpr)

    walk(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args).jaxpr)
    return found


def _cache_avals(q8: bool, B: int, T: int = 4352, K: int = 8, hd: int = 128, L: int = 2):
    sd = jax.ShapeDtypeStruct
    payload = sd((L, B, K, T, hd), jnp.int8 if q8 else jnp.bfloat16)
    scales = [sd((L, B, K, T), jnp.float32)] * 2 if q8 else []
    return [payload, payload, *scales]


def _chunk_args(q8: bool, S: int):
    sd, i32 = jax.ShapeDtypeStruct, jnp.int32
    return [sd((1, S, 32, 128), jnp.bfloat16), *_cache_avals(q8, 1), sd((1,), i32), sd((1,), i32),
            sd((), i32), sd((), i32)]


CHUNK_KERNELS = {
    # name -> (function, S, grid at the serving shape T = 4352): 17 blocks of
    # 256 a row, as before PR 32. The grouped kernel at S = 16 is `solo`'s
    # verify step
    "chunk_attention_grouped_q8": (A.chunk_attention_grouped_q8, True, 16, (1, 17)),
    "chunk_attention_grouped": (A.chunk_attention_grouped, False, 16, (1, 17)),
    "chunk_prefill_attention_q8": (A.chunk_prefill_attention_q8, True, 512, (32, 1, 17)),
    "chunk_prefill_attention": (A.chunk_prefill_attention, False, 512, (32, 1, 17)),
}


def test_the_old_block_rule_still_reads_256_at_the_serving_length():
    assert A._decode_block(4352, 512) == 256
    assert A._decode_block(4096, 512) == 512 and A._decode_block(256, 512) == 256


@pytest.mark.parametrize("name", sorted(CHUNK_KERNELS))
def test_chunk_kernels_keep_their_grid(name, monkeypatch):
    fn, q8, S, grid = CHUNK_KERNELS[name]
    assert _grids(fn, *_chunk_args(q8, S), interpret=True) == [(name, grid)]
    # and they take the block from `_decode_block`, not from the decode kernels' rule
    asked = []
    monkeypatch.setattr(A, "_decode_block", lambda T, bk: asked.append((T, bk)) or 128)
    jax.clear_caches()
    try:
        (_, regrid), = _grids(fn, *_chunk_args(q8, S), interpret=True)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert asked and asked[0][0] == 4352 and regrid[-1] == 34


def test_the_decode_kernels_left_that_rule(monkeypatch):
    """One grid cell a row, whatever ``_decode_block`` says."""
    monkeypatch.setattr(A, "_decode_block", lambda T, bk: pytest.fail("the decode kernels asked the chunk rule"))
    sd, i32 = jax.ShapeDtypeStruct, jnp.int32
    tail = [sd((8,), i32), sd((8,), i32), sd((), i32)]
    q = sd((8, 1, 32, 128), jnp.bfloat16)
    assert _grids(A.decode_attention_q8, q, *_cache_avals(True, 8), *tail) == [("decode_attention_q8", (8,))]
    assert _grids(A.decode_attention, q, *_cache_avals(False, 8), *tail) == [("decode_attention", (8,))]
    lat = [sd((8, 1, 128, 512), jnp.bfloat16), sd((8, 1, 128, 64), jnp.bfloat16),
           sd((2, 8, 4352, 512), jnp.bfloat16), sd((2, 8, 4352, 64), jnp.bfloat16)]
    assert _grids(M.mla_decode_attention, *lat, *tail, scale=0.1) == [("mla_decode_attention", (8,))]

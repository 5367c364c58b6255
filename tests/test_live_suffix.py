"""Prefill computes the live tokens, not the bucket (ISSUE 28).

A fresh prompt call of which one position's logits leave
(``last_logit_only``) runs its layers' matmuls on the token suffix behind
the batch's smallest left pad, at the granularity of ``live_offsets(S)``
(eighths of the bucket).
The reference here is the same model asked for every position's logits,
which takes no branch: the suffix prefill must give the same last-position
logits and the same cache on every live slot, and count what it did.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    MeshConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import llama
from rag_llm_k8s_tpu.models.llama import (
    LlamaModel,
    fuse_llama_params,
    init_llama_params,
    live_offsets,
    make_kv_cache,
    mask_window,
    quantize_llama_params,
)
from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

FP32 = DTypePolicy.fp32()
S = 1280  # the smallest kind of bucket with rungs: eighths of it up to half
TILE = S // 8
CFG = LlamaConfig.tiny(vocab_size=300)


@pytest.fixture(scope="module")
def params():
    return init_llama_params(jax.random.PRNGKey(0), CFG, FP32)


def _prompts(lens, seed=0):
    """Left-padded ``tokens, pad_mask [B, S]`` of rows ``lens`` tokens long."""
    rng = np.random.default_rng(seed)
    tokens = np.zeros((len(lens), S), np.int32)
    mask = np.zeros((len(lens), S), np.int32)
    for i, n in enumerate(lens):
        tokens[i, S - n:] = rng.integers(3, CFG.vocab_size, n)
        mask[i, S - n:] = 1
    return jnp.asarray(tokens), jnp.asarray(mask)


def _prefill(model, tree, tokens, mask, *, last: bool, kv_quant="bf16"):
    """The engine's prompt call: ``(logits, cache, kv_start)``."""
    B = tokens.shape[0]
    kv_start, _ = mask_window(mask)
    positions = jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0)

    @jax.jit
    def call(tree, tokens):
        cache = make_kv_cache(CFG, B, S + 128, jnp.float32, quant=kv_quant, counters=True)
        return model.apply(
            {"params": tree}, tokens, positions, cache, kv_start,
            jnp.full((B,), S, jnp.int32), jnp.int32(0), last_logit_only=last,
        )

    logits, cache = call(tree, tokens)
    return logits, cache, np.asarray(kv_start)


def _assert_same_on_live_slots(got, want, kv_start, atol=2e-5):
    """int8 payloads may round a float32 ulp apart: one step; scales are floats."""
    q8 = got.k_scale is not None
    for name in ("k", "v") + (("k_scale", "v_scale") if q8 else ()):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        tol = 1 if a.dtype == np.int8 else atol
        for row, start in enumerate(kv_start):
            # [L, B, K, T(, hd)]: the slots of the row's own tokens
            np.testing.assert_allclose(
                a[:, row, :, start:S].astype(np.float32),
                b[:, row, :, start:S].astype(np.float32), atol=tol, err_msg=name)


def _rung_offset(kv_start) -> int:
    return min(int(min(kv_start)) // TILE, len(live_offsets(S)) - 1) * TILE


def test_the_rungs_are_eighths_of_the_bucket_up_to_half():
    assert live_offsets(4096) == (0, 512, 1024, 1536)
    assert live_offsets(2048) == (0, 256, 512, 768)
    assert live_offsets(S) == (0, 160, 320, 480)
    # a bucket too small to gain gets no branches, nor does a decode step
    assert live_offsets(1024) == live_offsets(512) == live_offsets(1) == ()
    assert live_offsets(2056) == ()  # eighths that are no whole 8-row tiles


@pytest.mark.parametrize("kv_quant", ["bf16", "int8"])
@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("lens", [(900,), (700, 1000, 1279)], ids=["batch1", "mixed_pads"])
def test_suffix_prefill_equals_full_prefill(params, lens, fused, kv_quant):
    """Same last-position logits, same cache on every live slot; the batch's
    SMALLEST pad governs (a row of 1279 tokens beside one of 700 skips
    nothing of its own)."""
    tree = fuse_llama_params(params) if fused else params
    model = LlamaModel(CFG, FP32, attn_impl="xla", fused_qkv=fused, kv_quant=kv_quant)
    tokens, mask = _prompts(lens)
    got, cache, kv_start = _prefill(model, tree, tokens, mask, last=True, kv_quant=kv_quant)
    want, ref, _ = _prefill(model, tree, tokens, mask, last=False, kv_quant=kv_quant)
    np.testing.assert_allclose(np.asarray(got[:, -1]), np.asarray(want[:, -1]), atol=2e-5)
    _assert_same_on_live_slots(cache, ref, kv_start)
    off = _rung_offset(kv_start)
    assert off == {(900,): 320, (700, 1000, 1279): 0}[lens]
    B = len(lens)
    assert cache.counters.tolist()[:2] == [B * (S - off), B * S]
    assert ref.counters.tolist()[:2] == [B * S, B * S]  # every position's logits leave: no branch


def test_suffix_prefill_with_int8_weights(params):
    tree = quantize_llama_params(params)
    model = LlamaModel(CFG, FP32, attn_impl="xla", quantized=True)
    tokens, mask = _prompts((640, 800))
    got, cache, kv_start = _prefill(model, tree, tokens, mask, last=True)
    want, ref, _ = _prefill(model, tree, tokens, mask, last=False)
    np.testing.assert_allclose(np.asarray(got[:, -1]), np.asarray(want[:, -1]), atol=2e-5)
    _assert_same_on_live_slots(cache, ref, kv_start)
    assert cache.counters.tolist()[:2] == [2 * (S - 480), 2 * S]


@pytest.mark.parametrize("lens, off", [
    ((100,), 480),  # shorter than the shortest suffix: the rung clamps, still right
    ((S,), 0),  # kv_start 0 (a full bucket; right-padded callers): the full branch
    ((0, 900), 0),  # mask_window reads 0 for a row with no valid slot: the safe side
    ((1, 900), 320),  # a batch-padding row (one token at slot S - 1) does not move the minimum
], ids=["short_prompt_clamps", "kv_start_0_full_branch", "empty_row_full_branch", "padding_row"])
def test_which_rung_a_batch_takes(params, lens, off):
    model = LlamaModel(CFG, FP32, attn_impl="xla")
    tokens, mask = _prompts(lens)
    got, cache, kv_start = _prefill(model, params, tokens, mask, last=True)
    want, ref, _ = _prefill(model, params, tokens, mask, last=False)
    assert _rung_offset(kv_start) == off
    assert cache.counters.tolist()[:2] == [len(lens) * (S - off), len(lens) * S]
    live = [i for i, n in enumerate(lens) if n]  # an empty row's logits are nobody's
    np.testing.assert_allclose(np.asarray(got[live, -1]), np.asarray(want[live, -1]), atol=2e-5)
    _assert_same_on_live_slots(cache, ref, [kv_start[i] if i in live else S for i in range(len(lens))])


def test_only_a_fresh_prompt_call_branches(params):
    """A bucket with no rungs, a decode step and a chunk over the cache trace
    no conditional; a bucket with rungs does (q/k/v, the output projection, the FFN)."""
    def conds(model, s, **kw):
        cache = make_kv_cache(CFG, 1, 2 * S, jnp.float32, counters=True)
        z = jnp.zeros((1, s), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda p: model.apply(
            {"params": p}, z, z, cache, jnp.zeros((1,), jnp.int32),
            jnp.full((1,), s, jnp.int32), jnp.int32(0), **kw))(params)
        return str(jaxpr).count(" cond[")

    plain = LlamaModel(CFG, FP32, attn_impl="xla")
    assert conds(plain, S, last_logit_only=True) >= 3
    assert conds(plain, S) == 0  # training, logit evaluation: every position leaves
    assert conds(plain, 1024, last_logit_only=True) == 0
    assert conds(plain, 1, last_logit_only=True) == 0
    assert conds(plain.copy(chunked=True), S, last_logit_only=True) == 0


# ---------------------------------------------------------------------------
# through the engine: the counters ride the one fetch into EngineStats
# ---------------------------------------------------------------------------

GREEDY = SamplingConfig(do_sample=False, max_new_tokens=4)


def _engine(tree, mesh=None, **kw):
    ec = EngineConfig(prompt_buckets=(S,), max_batch_size=4, max_seq_len=S + 128, **kw)
    return InferenceEngine(CFG, tree, sampling=GREEDY, engine_config=ec, dtypes=FP32, mesh=mesh)


def _prefill_counters(eng):
    """The counters of this file (the decode kernel's, PR 32, ride the same
    row and stay 0 on the XLA path these engines take)."""
    return {k: v for k, v in eng.stats.family_counters.items() if k.startswith("prefill_")}


def _no_rungs(monkeypatch):
    """The parent's program, for a reference: steered here, in the test; the
    program has no option for it."""
    monkeypatch.setattr(llama, "live_offsets", lambda S: ())


PROMPTS = [list(range(5, 705)), list(range(9, 809)), list(range(7, 907))]


def test_engine_counts_what_the_branch_did(params, monkeypatch):
    """Three prompts ride a batch of four: the padding row (one BOS at slot
    S - 1) does not move the minimum, 1280 - 900 = 380 -> the rung at 320."""
    eng = _engine(params)
    got = eng.generate(PROMPTS)
    assert _prefill_counters(eng) == {
        "prefill_tokens_computed": 4 * (S - 320), "prefill_tokens_bucketed": 4 * S}
    got1 = eng.generate(PROMPTS[:1])  # 1280 - 700 = 580 -> the last rung, 480
    assert _prefill_counters(eng) == {
        "prefill_tokens_computed": 4 * (S - 320) + (S - 480), "prefill_tokens_bucketed": 5 * S}
    _no_rungs(monkeypatch)
    ref = _engine(params)
    assert ref.generate(PROMPTS) == got and ref.generate(PROMPTS[:1]) == got1
    assert _prefill_counters(ref) == {
        "prefill_tokens_computed": 5 * S, "prefill_tokens_bucketed": 5 * S}


def test_the_speculative_program_counts_too(params, monkeypatch):
    prompt = [[5, 9, 2] * 250]
    eng = _engine(params, speculative="prompt_lookup", kv_quant="int8")
    got = eng.generate(prompt)
    assert eng.stats.spec_verify_steps > 0
    assert _prefill_counters(eng) == {
        "prefill_tokens_computed": S - 480, "prefill_tokens_bucketed": S}
    _no_rungs(monkeypatch)
    assert _engine(params, speculative="prompt_lookup", kv_quant="int8").generate(prompt) == got


def test_under_a_tp_mesh(params):
    ctx = make_mesh(MeshConfig(dp=2, sp=1, tp=4))
    sharded = _engine(shard_llama_params(params, ctx), mesh=ctx)
    single = _engine(params)
    assert sharded.generate(PROMPTS) == single.generate(PROMPTS)
    assert _prefill_counters(sharded) == _prefill_counters(single) == {
        "prefill_tokens_computed": 4 * (S - 320), "prefill_tokens_bucketed": 4 * S}

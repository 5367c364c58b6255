"""The hybrid state-space family (models/hybrid_ssm.py over ops/ssm.py and
models/llama.py's attention seam) against its plain reference
(tests/jamba_reference.py), at a toy size on the CPU with seeded weights under
the fp32 policy: hidden 64, d_inner 128, 16 states, dt rank 4, 8 layers of
which 1 and 5 are attention (4 query heads over 1 KV head of 16).

Tolerances. The program and the reference compute the same float32 numbers in
different orders (the cache round-trips nothing in fp32; a running softmax
against one softmax over a masked row; the recurrence is the same chain of
multiply-adds either way), so logits of magnitude ~1-3 agree to a few 1e-6;
``ATOL`` is 1e-4, some thirty times that. The faults the comparison must see
are far above it: a state or a convolution's history lost at the hand-over
from prefill to decode, the inner norms left out, the softplus or ``A``'s
``exp`` dropped, a window of 16 on the attention layers and a bf16 state each
move a logit by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jamba_reference as ref
from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    HybridSSMConfig,
    LlamaConfig,
    MeshConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import families, hybrid_ssm as hs
from rag_llm_k8s_tpu.models.llama import live_offsets
from rag_llm_k8s_tpu.ops import ssm

FP32 = DTypePolicy.fp32()
ATOL = 1e-4
V = 48
CFG = HybridSSMConfig.tiny(vocab_size=V)
UNTIED = dataclasses.replace(CFG, tie_word_embeddings=False)
NEW = 6
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)


def seeded_params(cfg, seed=0):
    """Weights with statistics that make every part matter: kernels of std
    1/sqrt(fan_in), norm scales near 1, ``A_log = log(1..N)`` a channel and a
    time-step bias that is the inverse softplus of a log-uniform draw over
    [1e-2, 1] (a state that forgets over a few to a hundred positions)."""
    shapes = jax.eval_shape(lambda: hs.init_hybrid_ssm_params(jax.random.PRNGKey(0), cfg, FP32))
    rng = np.random.default_rng(seed)
    out = {}
    for name, leaf in shapes.items():
        if name == "ssm_A_log":
            value = np.broadcast_to(np.log(np.arange(1, leaf.shape[1] + 1))[None, :, None], leaf.shape)
        elif name == "ssm_dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-2), 0.0, leaf.shape))
            value = dt + np.log(-np.expm1(-dt))
        elif "norm" in name or name == "ssm_D":
            value = 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        elif name == "ssm_conv_b":
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif name == "embedding":
            value = rng.standard_normal(leaf.shape)
        else:
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        out[name] = jnp.asarray(value, jnp.float32)
    return out


PARAMS = {True: seeded_params(CFG), False: seeded_params(UNTIED)}


@pytest.fixture(scope="module")
def params():
    return PARAMS[True]


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, V, size=n)]


_REF, _FORWARD = {}, {}


def forward(tokens, tied=True, **faults):
    """The reference's logits of ``tokens``, computed at a padded length (a
    pad behind the sequence changes nothing in front of it: every mixer is
    causal), so that one compiled program serves every length up to it."""
    n = -(-len(tokens) // 64) * 64
    key = (n, tied, tuple(sorted((k, str(v)) for k, v in faults.items())))
    if key not in _FORWARD:
        cfg = CFG if tied else UNTIED
        _FORWARD[key] = jax.jit(lambda params, ids: ref.forward(params, cfg, ids, **faults))
    ids = jnp.asarray(list(tokens) + [0] * (n - len(tokens)), jnp.int32)
    return np.asarray(_FORWARD[key](PARAMS[tied], ids))[:len(tokens)]


def reference(tokens, tied=True):
    key = (tuple(tokens), tied)
    if key not in _REF:
        _REF[key] = forward(tokens, tied)
    return _REF[key]


def greedy_reference(prompt, n):
    tokens = list(prompt)
    for _ in range(n):
        tokens.append(int(np.argmax(forward(tokens)[-1])))
    return tokens[len(prompt):]


def through_the_cache(rows, S, lengths, impl="xla", tied=True):
    """Logits of ``rows`` (left-padded to ``S``, of which ``lengths`` are
    prefilled at once and the rest decoded a token at a time), and the cache."""
    cfg, params = (CFG, PARAMS[True]) if tied else (UNTIED, PARAMS[False])
    B, lens = len(rows), np.asarray(lengths)
    T = S + max(len(r) - n for r, n in zip(rows, lens))
    model = hs.HybridSSMModel(cfg, FP32, attn_impl=impl)
    call = jax.jit(lambda *a: model.apply({"params": params}, *a))
    cache = hs.make_hybrid_cache(cfg, B, T, jnp.float32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    padded = np.zeros((B, S), np.int32)
    for b, row in enumerate(rows):
        padded[b, S - lens[b]:] = row[:lens[b]]
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    logits, cache = call(jnp.asarray(padded), positions, cache, kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0))
    out = [[np.asarray(logits[b, S - lens[b] + t]) for t in range(lens[b])] for b in range(B)]
    for t in range(min(len(r) - n for r, n in zip(rows, lens))):
        tok = jnp.asarray([[r[n + t]] for r, n in zip(rows, lens)], jnp.int32)
        logits, cache = call(tok, jnp.asarray(lens + t)[:, None].astype(jnp.int32), cache, kv_start,
                             jnp.full((B,), S + t + 1, jnp.int32), jnp.int32(S + t))
        for b in range(B):
            out[b].append(np.asarray(logits[b, 0]))
    return [np.stack(o) for o in out], cache


# ---- (a) prefill, then decode through both kinds of state ----


@pytest.mark.parametrize("impl,S,prompt_len,tied", [
    ("xla", 40, 33, True), ("xla", 40, 33, False), ("xla", 40, 40, True), ("xla", 40, 40, False),
    ("pallas_interpret", 128, 101, True),
], ids=lambda v: {True: "tied", False: "untied"}.get(v, str(v)))
def test_prefill_then_decode_matches_reference_at_every_position(impl, S, prompt_len, tied):
    tokens = prompt_of(prompt_len + 12, prompt_len)
    (got,), cache = through_the_cache([tokens], S, [prompt_len], impl=impl, tied=tied)
    np.testing.assert_allclose(got, reference(tokens, tied), atol=ATOL)
    counted = hs.fold_counters(np.asarray(cache.counters))
    assert counted["prefill_tokens_computed"] == counted["prefill_tokens_bucketed"] == S
    assert counted["ssm_positions_scanned"] == S and counted["ssm_state_updates"] == 12 * CFG.num_state_layers
    assert (counted["decode_slots_streamed"] > 0) == (impl != "xla")
    # two kinds of state in one cache: planes by position for layers 1 and 5, a state without positions for the rest
    assert cache.k.shape == (2, 1, 1, S + 12, 16) and cache.ssm.shape == (6, 1, 16, 128)
    assert cache.conv.shape == (6, 1, 3, 128) and cache.ssm.dtype == jnp.float32


def test_two_rows_of_one_bucket_with_different_left_padding():
    """Each row equals the reference, and the shorter row's state is what it
    is alone in a bucket it fills (to rounding: matmuls of another shape sum
    in another order; ``test_pads_leave_the_state_bit_for_bit`` is exact)."""
    rows = [prompt_of(44, 2), prompt_of(29, 3), prompt_of(9, 4)]
    lengths = [40, 25, 2]  # the last row is shorter than the convolution
    got, cache = through_the_cache(rows, 40, lengths)
    for row, g in zip(rows, got):
        np.testing.assert_allclose(g, reference(row)[:len(g)], atol=ATOL)
    _, alone = through_the_cache([rows[1][:25 + 4]], 25, [25])
    np.testing.assert_allclose(np.asarray(cache.ssm[:, 1]), np.asarray(alone.ssm[:, 0]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(cache.conv[:, 1]), np.asarray(alone.conv[:, 0]), atol=ATOL)


# ---- (b) a chunk over the cache; the verify step and what it commits ----


def chunk_call(params, tokens, start, n, S=32, keep_steps=False, impl="xla"):
    """``tokens[:start]`` prefilled (left-padded to ``S``), then ``n``
    positions from ``start`` in ONE chunk call; returns its logits and cache."""
    model = hs.HybridSSMModel(CFG, FP32, attn_impl=impl)
    mc = model.copy(chunked=True, keep_steps=keep_steps)
    cache = hs.make_hybrid_cache(CFG, 1, S + max(64, n), jnp.float32)
    pad = S - start
    padded = np.zeros((1, S), np.int32)
    padded[0, pad:] = tokens[:start]
    ks = jnp.asarray([pad], jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None] - pad, 0)
    _, cache = model.apply({"params": params}, jnp.asarray(padded), positions, cache, ks,
                           jnp.full((1,), S, jnp.int32), jnp.int32(0))
    fed = jnp.asarray([tokens[start:start + n]], jnp.int32)
    return mc.apply({"params": params}, fed, (start + jnp.arange(n))[None], cache, ks,
                    jnp.full((1,), S + n, jnp.int32), jnp.int32(S)), ks


@pytest.mark.parametrize("kept,why", [(8, "accepted in full"), (3, "accepted in part"), (1, "none accepted")])
def test_a_verify_step_commits_the_state_it_kept(params, kept, why):
    """A verify step feeds 8 positions of which only the first ``kept`` are
    the sequence's; ``commit`` leaves the state behind them, and the steps
    that follow equal the reference on the sequence."""
    tokens = prompt_of(60, 7)
    start, n = 27, 8
    junk = tokens[:start + kept] + prompt_of(n - kept, 99)  # rejected proposals behind the kept ones
    (logits, cache), ks = chunk_call(params, junk, start, n, keep_steps=True)
    np.testing.assert_allclose(np.asarray(logits[0, :kept]), reference(tokens[:start + kept])[start:], atol=ATOL)
    assert cache.ssm_steps.shape == (6, 1, n, 16, 128) and cache.conv_steps.shape == (6, 1, 3 + n, 128)
    cache = hs.commit(cache, jnp.int32(kept))
    assert cache.ssm_steps is None and cache.conv_steps is None
    counted = hs.fold_counters(np.asarray(cache.counters))
    assert (counted["verify_positions_fed"], counted["verify_positions_kept"]) == (n, kept)
    model = hs.HybridSSMModel(CFG, FP32, attn_impl="xla")
    S = 32
    for at in range(start + kept, start + kept + 5):  # the frontier stands behind the kept positions
        slot = S + at - start
        step, cache = model.apply({"params": params}, jnp.asarray([[tokens[at]]], jnp.int32), jnp.asarray([[at]]),
                                  cache, ks, jnp.full((1,), slot + 1, jnp.int32), jnp.int32(slot))
        np.testing.assert_allclose(np.asarray(step[0, 0]), reference(tokens[:at + 1])[-1], atol=ATOL)


def test_an_uncommitted_verify_step_is_the_fault_commit_cures(params):
    tokens = prompt_of(60, 7)
    junk = tokens[:28] + prompt_of(7, 99)
    (_, cache), ks = chunk_call(params, junk, 27, 8)  # the chunk form leaves the state behind ALL it fed
    model = hs.HybridSSMModel(CFG, FP32, attn_impl="xla")
    step, _ = model.apply({"params": params}, jnp.asarray([[tokens[28]]], jnp.int32), jnp.asarray([[28]]),
                          cache, ks, jnp.full((1,), 34, jnp.int32), jnp.int32(33))
    assert np.abs(np.asarray(step[0, 0]) - reference(tokens[:29])[-1]).max() > 100 * ATOL


@pytest.mark.parametrize("impl,S,start,n", [("xla", 32, 20, 11), ("pallas_interpret", 128, 90, 128)])
def test_a_chunk_over_the_cache_starts_from_the_state_it_is_handed(params, impl, S, start, n):
    tokens = prompt_of(start + n + 1, 5)
    (logits, cache), ks = chunk_call(params, tokens, start, n, S=S, impl=impl)
    np.testing.assert_allclose(np.asarray(logits[0]), reference(tokens[:start + n])[start:], atol=ATOL)


# ---- (c) the kernel against the XLA form ----


def scan_inputs(R, S, Di, N, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    return (normal(ks[0], (R, S, Di)).astype(dtype), (normal(ks[1], (R, S, Di)) - 2).astype(dtype),
            normal(ks[2], (R, S, Di)).astype(dtype), -jnp.exp(normal(ks[3], (N, Di))), normal(ks[4], (R, S, N)),
            normal(ks[5], (R, S, N)), normal(ks[6], (Di,)), 0.1 * normal(ks[7], (Di,)), normal(ks[8], (R, N, Di)))


@pytest.mark.parametrize("R,S,Di,N,start,why", [
    (2, 128, 128, 16, (0, 37), "one time chunk; a row that starts inside it"),
    (2, 384, 1152, 4, (128, 255), "three chunks of 128 and two channel tiles (one padded): a first chunk of nothing "
                                  "but pads, a row that starts on a chunk's last position"),
])
def test_the_scan_kernel_is_the_xla_form(R, S, Di, N, start, why):
    """Interpret mode against the ``lax.scan``, from a non-zero state: the
    outputs at every live position, and the state handed on."""
    args = scan_inputs(R, S, Di, N, jnp.float32)
    first = jnp.asarray(start, jnp.int32)
    want_y, want_h = ssm.selective_scan_xla(*args, first)
    got_y, got_h = ssm.selective_scan_pallas(*args, first, interpret=True)
    live = np.arange(S)[None, :, None] >= np.asarray(start)[:, None, None]
    np.testing.assert_allclose(np.where(live, got_y, 0), np.where(live, want_y, 0), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), atol=2e-5)
    assert ssm.scan_form(S, "pallas") == ssm.KERNEL and ssm.scan_form(S, "xla") == "selective_scan_xla"
    assert ssm.scan_form(16, "pallas") == "selective_scan_xla" == ssm.scan_form(4100, "pallas")


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
def test_pads_leave_the_state_bit_for_bit(form):
    """A left-padded row's state, and its outputs at its real positions, are
    bit for bit what the row gives alone from the same state: a pad's time
    step is 0 (``exp(0) = 1``, ``0 * u * B = 0``) and its convolution input 0."""
    S, pad = 256, 128
    u, delta, z, A, B, C, D, bias, h0 = scan_inputs(1, S, 128, 16, jnp.float32, seed=2)
    first = jnp.asarray([pad], jnp.int32)
    scan = (lambda *a: ssm.selective_scan_pallas(*a, interpret=True)) if form != "xla" else ssm.selective_scan_xla
    y, h = scan(u, delta, z, A, B, C, D, bias, h0, first)
    cut = lambda a: a[:, pad:]  # noqa: E731
    y1, h1 = scan(cut(u), cut(delta), cut(z), A, cut(B), cut(C), D, bias, h0, jnp.zeros((1,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h1))
    np.testing.assert_array_equal(np.asarray(y[:, pad:]), np.asarray(y1))
    w, b, history = jnp.ones((4, 128)) * 0.3, jnp.zeros((128,)), jnp.zeros((1, 3, 128))
    masked = jnp.where(jnp.arange(S)[None, :, None] >= pad, u, 0)
    out, run = ssm.causal_conv(masked, history, w, b)
    out1, run1 = ssm.causal_conv(cut(u), history, w, b)
    np.testing.assert_array_equal(np.asarray(out[:, pad:]), np.asarray(out1))
    np.testing.assert_array_equal(np.asarray(run[:, -3:]), np.asarray(run1[:, -3:]))


def test_the_scan_keeps_every_positions_state_where_asked():
    args = scan_inputs(1, 8, 128, 16, jnp.float32, seed=1)
    y, last, steps = ssm.selective_scan_xla(*args, jnp.zeros((1,), jnp.int32), keep_steps=True)
    assert steps.shape == (1, 8, 16, 128)
    np.testing.assert_array_equal(np.asarray(steps[:, -1]), np.asarray(last))
    _, mid = ssm.selective_scan_xla(*(a[:, :5] if a.ndim == 3 and a.shape[1] == 8 else a for a in args),
                                    jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(steps[:, 4]), np.asarray(mid), atol=1e-6)


# ---- (d) the faults the comparison must see ----


@pytest.mark.parametrize("fault", [
    dict(inner_norms=False), dict(softplus=False), dict(a_exp=False), dict(attn_window=16),
    dict(drop_state_at=40), dict(drop_conv_at=40), dict(state_dtype=jnp.bfloat16),
], ids=lambda f: next(iter(f)))
def test_a_fault_fails_the_tolerance(params, fault):
    tokens = prompt_of(52, 1)
    sound = reference(tokens)
    bad = forward(tokens, **fault)
    assert np.abs(bad - sound).max() > 100 * ATOL
    (got,), _ = through_the_cache([tokens], 40, [40])
    assert np.abs(got - bad).max() > 100 * ATOL  # and the program is on the sound side


# ---- (e) every one-shot program of the engine ----


def engine_for(params, cfg=CFG, **kw):
    ec = EngineConfig(**{**dict(prompt_buckets=(32, 64), max_batch_size=4, max_seq_len=128,
                                speculative="off", attn_impl="xla", max_chunked_prompt=256,
                                prefix_cache=PrefixCacheConfig(enabled=False)), **kw})
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


def test_batched_rows_of_unequal_length(params):
    prompts = [prompt_of(n, 10 + n) for n in (61, 40, 35)]
    engine = engine_for(params)
    assert engine.generate(prompts) == [greedy_reference(p, NEW) for p in prompts]
    # the three rows ride the batch ladder's rung of four
    assert engine.stats.family_counters["ssm_state_updates"] == (NEW - 1) * 4 * CFG.num_state_layers


def test_a_prompt_past_the_largest_bucket_prefills_in_chunks(params):
    prompt = prompt_of(150, 21)  # three chunks of 64, left-padded by 42
    assert engine_for(params).generate([prompt]) == [greedy_reference(prompt, NEW)]


def repeating(n, period, seed):
    return [prompt_of(period, seed)[i % period] for i in range(n)]


@pytest.mark.parametrize("prompt,why", [
    (repeating(50, 7, 31), "a prompt that repeats: proposals accepted in full and in part"),
    (prompt_of(50, 32), "no repeat: nothing accepted"),
])
def test_the_verify_loop_is_the_vanilla_loop(params, prompt, why):
    """Prompt-lookup speculation commits the state of what it kept: the
    stream is the vanilla greedy stream, which is the reference's."""
    sampling = SamplingConfig(do_sample=False, max_new_tokens=16)
    ec = dict(speculative="prompt_lookup", spec_tokens=5, spec_ngram=2)
    engine = InferenceEngine(CFG, params, sampling=sampling, dtypes=FP32, engine_config=EngineConfig(
        prompt_buckets=(32, 64), max_batch_size=4, max_seq_len=128, attn_impl="xla", **ec))
    got = engine.generate([prompt])
    assert got == [greedy_reference(prompt, 16)]
    counted = engine.stats.family_counters
    assert counted["verify_positions_fed"] == 6 * engine.stats.spec_verify_steps
    assert counted["verify_positions_kept"] == engine.stats.spec_emitted_tokens


def test_score_exact_is_the_reference(params):
    prompt = prompt_of(45, 41)
    emitted = greedy_reference(prompt, NEW)
    got = engine_for(params).score_exact(prompt, emitted)
    logits = reference(prompt + emitted)[len(prompt) - 1:-1]
    np.testing.assert_array_equal(got["argmax"], np.argmax(logits, axis=-1))
    np.testing.assert_allclose(got["max_logit"], logits.max(axis=-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(NEW), emitted], atol=ATOL)


# ---- (f) what the family cannot be served with yet ----


@pytest.mark.parametrize("kw,engine,names", [
    (dict(batching="continuous"), "one-shot", "continuous"),
    (dict(), "continuous", "paged KV pool"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "one-shot", "prefix cache"),
    (dict(kv_quant="int8"), "one-shot", "kv_quant='int8'"),
    (dict(weight_quant="int8"), "one-shot", "weight_quant='int8'"),
])
def test_refusals_name_the_mechanism(kw, engine, names):
    ec = EngineConfig(**{**dict(prefix_cache=PrefixCacheConfig(enabled=False)), **kw})
    with pytest.raises(NotImplementedError, match="hybrid state-space family") as e:
        families.refuse_unsupported(CFG, ec, None, engine=engine)
    assert names in str(e.value)


def test_tensor_parallel_is_refused_by_name():
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    ec = EngineConfig(prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(NotImplementedError, match="tp=2"):
        families.refuse_unsupported(CFG, ec, mesh)
    family = families.of(CFG)
    assert family.commit is hs.commit and family.verify_span is None
    assert "name map" in family.checkpoint_loader_refusal
    assert families.of(LlamaConfig.tiny()).commit is None  # a frontier does the job there


# ---- (g) a fresh prompt call computes the live suffix of a left-padded bucket ----

LIVE_S = 1280  # the smallest kind of bucket with rungs (``hs.live_rungs``: every second of llama's eighths): 0, 320


def _no_rungs(monkeypatch):
    """The parent's program, for a reference: steered here, in the test; the
    program has no option for it."""
    monkeypatch.setattr(hs, "live_offsets", lambda S: ())


def _rung_offset(kv_start) -> int:
    offsets = live_offsets(LIVE_S)[::2]  # from llama's own: ``_no_rungs`` patches the name this family reads
    return offsets[min(int(min(kv_start)) // offsets[1], len(offsets) - 1)]


def live_prefill(lens, impl="xla", tied=True, steps=0, seed=0):
    """The engine's fresh prompt call on rows of ``lens`` tokens left-padded
    to ``LIVE_S``, then ``steps`` decode steps on tokens of the seed:
    ``(last-position logits, cache, kv_start, [a step's logits])``."""
    cfg, params = (CFG, PARAMS[True]) if tied else (UNTIED, PARAMS[False])
    B, lens = len(lens), np.asarray(lens)
    rng = np.random.default_rng(seed)
    tokens = np.zeros((B, LIVE_S), np.int32)
    for b, n in enumerate(lens):
        tokens[b, LIVE_S - n:] = rng.integers(3, V, n)
    kv_start = jnp.asarray(np.where(lens > 0, LIVE_S - lens, 0), jnp.int32)  # mask_window reads 0 for an empty row
    positions = jnp.maximum(jnp.arange(LIVE_S)[None, :] - kv_start[:, None], 0)
    model = hs.HybridSSMModel(cfg, FP32, attn_impl=impl)

    @jax.jit
    def prompt(tokens):
        cache = hs.make_hybrid_cache(cfg, B, LIVE_S + 128, jnp.float32)
        return model.apply({"params": params}, tokens, positions, cache, kv_start,
                           jnp.full((B,), LIVE_S, jnp.int32), jnp.int32(0), last_logit_only=True)

    step = jax.jit(lambda tok, pos, cache, t: model.apply(
        {"params": params}, tok, pos, cache, kv_start, jnp.broadcast_to(LIVE_S + t + 1, (B,)), LIVE_S + t))
    logits, cache = prompt(jnp.asarray(tokens))
    stepped = []
    for t in range(steps):
        tok = jnp.asarray(rng.integers(3, V, (B, 1)), jnp.int32)
        out, cache = step(tok, jnp.asarray(lens + t, jnp.int32)[:, None], cache, jnp.int32(t))
        stepped.append(np.asarray(out[:, 0]))
    return np.asarray(logits[:, -1]), cache, np.asarray(kv_start), stepped


def _assert_same_behind_kv_start(got, want, kv_start, live):
    """The state, and the attention planes on every slot a row can ever read."""
    for name in ("conv", "ssm"):
        np.testing.assert_allclose(np.asarray(getattr(got, name))[:, live], np.asarray(getattr(want, name))[:, live],
                                   atol=ATOL, err_msg=name)
    for name in ("k", "v"):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        for row in live:  # [attention layers, B, K, T, hd]
            np.testing.assert_allclose(a[:, row, :, kv_start[row]:], b[:, row, :, kv_start[row]:], atol=ATOL, err_msg=name)


@pytest.mark.parametrize("lens,impl,tied", [
    ((900,), "xla", True), ((900,), "xla", False),
    ((700, 1000, 1279), "xla", True), ((700, 1000, 1279), "xla", False),
    ((900, 850), "pallas_interpret", True),
], ids=lambda v: {True: "tied", False: "untied"}.get(v, str(v)))
def test_suffix_prefill_equals_full_prefill(lens, impl, tied, monkeypatch):
    """The same last-position logits, the same state and planes behind every
    row's ``kv_start``, the same four decode steps after it; the batch's
    SMALLEST pad governs (a row of 1279 tokens beside one of 700 skips
    nothing), and the counters say what was computed."""
    got, cache, kv_start, steps = live_prefill(lens, impl, tied, steps=4)
    _no_rungs(monkeypatch)
    want, ref, _, ref_steps = live_prefill(lens, impl, tied, steps=4)
    np.testing.assert_allclose(got, want, atol=ATOL)
    _assert_same_behind_kv_start(cache, ref, kv_start, range(len(lens)))
    for a, b in zip(steps, ref_steps):
        np.testing.assert_allclose(a, b, atol=ATOL)
    off = _rung_offset(kv_start)
    assert off == {(900,): 320, (700, 1000, 1279): 0, (900, 850): 320}[lens]
    B = len(lens)
    counted, plain = hs.fold_counters(np.asarray(cache.counters)), hs.fold_counters(np.asarray(ref.counters))
    assert (counted["prefill_tokens_computed"], counted["prefill_tokens_bucketed"]) == (B * (LIVE_S - off), B * LIVE_S)
    assert counted["ssm_positions_scanned"] == B * LIVE_S  # the scan is handed the bucket and passes the pads' chunks
    assert plain["prefill_tokens_computed"] == plain["prefill_tokens_bucketed"] == plain["ssm_positions_scanned"] == B * LIVE_S


@pytest.mark.parametrize("lens, off", [
    ((100,), 320),  # shorter than the shortest suffix: the rung clamps, still right
    ((LIVE_S,), 0),  # kv_start 0 (a full bucket): the full branch
    ((0, 900), 0),  # mask_window reads 0 for a row with no valid slot: the safe side
    ((1, 900), 320),  # a batch-padding row (one token at slot S - 1) does not move the minimum
], ids=["short_prompt_clamps", "kv_start_0_full_branch", "empty_row_full_branch", "padding_row"])
def test_which_rung_a_batch_takes(lens, off, monkeypatch):
    got, cache, kv_start, _ = live_prefill(lens)
    assert _rung_offset(kv_start) == off
    counted = hs.fold_counters(np.asarray(cache.counters))
    assert (counted["prefill_tokens_computed"], counted["prefill_tokens_bucketed"]) == (
        len(lens) * (LIVE_S - off), len(lens) * LIVE_S)
    _no_rungs(monkeypatch)
    want, ref, _, _ = live_prefill(lens)
    live = [i for i, n in enumerate(lens) if n]  # an empty row's logits and state are nobody's
    np.testing.assert_allclose(got[live], want[live], atol=ATOL)
    _assert_same_behind_kv_start(cache, ref, kv_start, live)


def test_only_a_fresh_prompt_call_branches(params):
    """The scorer (every position's logits leave), a chunk over the cache, a
    verify step (``keep_steps``), a decode step and a bucket without rungs
    trace the mixers' ``cond`` and no other: the programs the parent built.
    A fresh prompt call at a bucket with rungs traces the rungs too."""
    def conds(model, s, **kw):
        cache = hs.make_hybrid_cache(CFG, 1, 2 * LIVE_S, jnp.float32)
        z = jnp.zeros((1, s), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda p: model.apply(
            {"params": p}, z, z, cache, jnp.zeros((1,), jnp.int32),
            jnp.full((1,), s, jnp.int32), jnp.int32(0), **kw))(params)
        return str(jaxpr).count(" cond[")

    plain = hs.HybridSSMModel(CFG, FP32, attn_impl="xla")
    assert conds(plain, LIVE_S, last_logit_only=True) > 1
    assert conds(plain, LIVE_S) == 1  # the scorer: every position leaves
    assert conds(plain, 1024, last_logit_only=True) == 1
    assert conds(plain, 1, last_logit_only=True) == 1
    assert conds(plain.copy(chunked=True), LIVE_S, last_logit_only=True) == 1
    assert conds(plain.copy(chunked=True, keep_steps=True), LIVE_S, last_logit_only=True) == 1
    assert conds(plain.copy(keep_steps=True), LIVE_S, last_logit_only=True) == 1


def test_engine_counts_what_the_suffix_prefill_did(params, monkeypatch):
    """Three prompts ride a batch of four: the padding row (one token at slot
    S - 1) does not move the minimum, 1280 - 900 = 380 -> the rung at 320;
    the speculative program opens with the same call."""
    ec = dict(prompt_buckets=(LIVE_S,), max_seq_len=LIVE_S + 128, max_chunked_prompt=2 * LIVE_S)
    prompts = [prompt_of(n, n) for n in (700, 800, 900)]
    repeat = [repeating(600, 7, 31)]  # 1280 - 600 = 680 -> the last rung, 320
    spec = dict(ec, speculative="prompt_lookup", spec_tokens=5, spec_ngram=2)

    def served():
        engine = engine_for(params, **ec)
        out = [engine.generate(prompts), engine.generate(prompts[:1])]
        counted = {k: v for k, v in engine.stats.family_counters.items() if k.startswith("prefill_")}
        lookup = engine_for(params, **spec)
        out.append(lookup.generate(repeat))
        assert lookup.stats.spec_verify_steps > 0
        return out, counted, lookup.stats.family_counters["prefill_tokens_computed"]

    got, counted, spec_computed = served()
    assert counted == {"prefill_tokens_computed": 4 * (LIVE_S - 320) + (LIVE_S - 320),
                       "prefill_tokens_bucketed": 5 * LIVE_S}
    assert spec_computed == LIVE_S - 320
    _no_rungs(monkeypatch)
    want, plain, spec_plain = served()
    assert got == want
    assert plain == {"prefill_tokens_computed": 5 * LIVE_S, "prefill_tokens_bucketed": 5 * LIVE_S}
    assert spec_plain == LIVE_S

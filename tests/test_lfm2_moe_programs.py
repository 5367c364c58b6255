"""The gated short-convolution, sparse-expert family, second half: a chunk over
the cache and the verify step's commit (b), the router's rule and the share
that adds up (c), and every one-shot program of the engine (e). The reference,
the seeded weights and the tolerances are ``tests/test_lfm2_moe.py``'s, which
holds sections (a), (d) and (f); a file of its own so that ``--dist loadfile``
can run the two halves side by side."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lfm2_moe_reference as ref
from rag_llm_k8s_tpu.core.config import EngineConfig, PrefixCacheConfig, SamplingConfig
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import conv_moe as cm
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.ops import moe
from test_lfm2_moe import (  # noqa: F401
    ATOL,
    CFG,
    FP32,
    GREEDY,
    NEW,
    greedy_reference,
    params,
    prompt_of,
    reference,
    seeded_params,
)


# ---- (b) a chunk over the cache; the verify step and what it commits ----


def chunk_call(params, tokens, start, n, S=32, keep_steps=False, impl="xla"):
    """``tokens[:start]`` prefilled (left-padded to ``S``), then ``n``
    positions from ``start`` in ONE chunk call; returns its logits and cache."""
    model = cm.ConvMoEModel(CFG, FP32, attn_impl=impl)
    mc = model.copy(chunked=True, keep_steps=keep_steps)
    cache = cm.make_conv_cache(CFG, 1, -(-(S + max(64, n)) // 128) * 128, jnp.float32)
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
    the sequence's; ``commit`` leaves the two gated inputs in front of the
    first rejected position, and the steps that follow equal the reference."""
    tokens = prompt_of(60, 7)
    start, n = 27, 8
    junk = tokens[:start + kept] + prompt_of(n - kept, 99)  # rejected proposals behind the kept ones
    (logits, cache), ks = chunk_call(params, junk, start, n, keep_steps=True)
    np.testing.assert_allclose(np.asarray(logits[0, :kept]), reference(tokens[:start + kept])[start:], atol=ATOL)
    assert cache.conv_steps.shape == (8, 1, 2 + n, 64)
    cache = cm.commit(cache, jnp.int32(kept))
    assert cache.conv_steps is None
    counted = cm.fold_counters(np.asarray(cache.counters))
    assert (counted["verify_positions_fed"], counted["verify_positions_kept"]) == (n, kept)
    assert counted["moe_chunk_assignments_held"] == n * 4 * CFG.num_moe_layers  # the verify step's count under ``chunk``
    model = cm.ConvMoEModel(CFG, FP32, attn_impl="xla")
    S = 32
    for at in range(start + kept, start + kept + 5):  # the frontier stands behind the kept positions
        slot = S + at - start
        step, cache = model.apply({"params": params}, jnp.asarray([[tokens[at]]], jnp.int32), jnp.asarray([[at]]),
                                  cache, ks, jnp.full((1,), slot + 1, jnp.int32), jnp.int32(slot))
        np.testing.assert_allclose(np.asarray(step[0, 0]), reference(tokens[:at + 1])[-1], atol=ATOL)


def test_an_uncommitted_verify_step_is_the_fault_commit_cures(params):
    tokens = prompt_of(60, 7)
    junk = tokens[:28] + prompt_of(7, 99)
    (_, cache), ks = chunk_call(params, junk, 27, 8)  # the chunk form leaves the inputs behind ALL it fed
    model = cm.ConvMoEModel(CFG, FP32, attn_impl="xla")
    step, _ = model.apply({"params": params}, jnp.asarray([[tokens[28]]], jnp.int32), jnp.asarray([[28]]),
                          cache, ks, jnp.full((1,), 34, jnp.int32), jnp.int32(33))
    assert np.abs(np.asarray(step[0, 0]) - reference(tokens[:29])[-1]).max() > 100 * ATOL


@pytest.mark.parametrize("impl,S,start,n", [("xla", 32, 20, 11), ("pallas_interpret", 128, 90, 16),
                                             ("pallas_interpret", 128, 90, 128)])
def test_a_chunk_over_the_cache_starts_from_the_state_it_is_handed(params, impl, S, start, n):
    """Head 16 through the chunk forms: the grouped kernel (16 positions x 2
    heads a KV head) and the per-head one (a prompt chunk of 128)."""
    tokens = prompt_of(start + n + 1, 5)
    (logits, cache), ks = chunk_call(params, tokens, start, n, S=S, impl=impl)
    np.testing.assert_allclose(np.asarray(logits[0]), reference(tokens[:start + n])[start:], atol=ATOL)


@pytest.mark.parametrize("head_dim,form", [(64, "chunk_attention_grouped"), (128, "decode_attention")])
def test_a_step_at_a_head_the_walk_refuses_is_a_chunk_of_one(head_dim, form):
    """The decode walk copies whole 128-lane tiles out of a plane: a head of
    64 takes the grouped chunk kernel for its single-token step, a head of
    128 the walk; both equal the XLA form."""
    cfg = dataclasses.replace(CFG, hidden_size=4 * head_dim, layer_types=("conv", "full_attention"), num_dense_layers=1)
    assert cfg.head_dim == head_dim and cm.walks("pallas", head_dim) == (head_dim == 128) and cm.walks("xla", 64)
    params = seeded_params(cfg, seed=3)
    tokens = prompt_of(141, 8)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        model = cm.ConvMoEModel(cfg, FP32, attn_impl=impl)
        cache = cm.make_conv_cache(cfg, 1, 2048, jnp.float32)
        ks = jnp.zeros((1,), jnp.int32)
        _, cache = model.apply({"params": params}, jnp.asarray([tokens[:128]]), jnp.arange(128)[None], cache, ks,
                               jnp.full((1,), 128, jnp.int32), jnp.int32(0))
        steps = []
        for t in range(128, 132):
            step, cache = model.apply({"params": params}, jnp.asarray([[tokens[t]]]), jnp.asarray([[t]]), cache, ks,
                                      jnp.full((1,), t + 1, jnp.int32), jnp.int32(t))
            steps.append(np.asarray(step[0, 0]))
        out[impl] = np.stack(steps)
        if impl != "xla":
            counted = cm.fold_counters(np.asarray(cache.counters))
            assert counted["decode_slots_allocated"] == 4 * 2048
            # the walk fetches its window's steps; the chunk form every slot of the plane
            assert (counted["decode_slots_streamed"] == 4 * 2048) == (head_dim == 64), form
    np.testing.assert_allclose(out["pallas_interpret"], out["xla"], atol=ATOL)


# ---- (c) the router's rule, and the share that adds up ----


def test_choice_is_by_score_plus_bias_weight_by_score_and_the_epsilon_is_the_published_one():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]], jnp.float32)
    bias = jnp.asarray([-1.0, 0.0, 0.0, 0.0, 2.0, 0.0], jnp.float32)  # drops the best score, lifts a poor one
    rule = dict(top_k=2, n_group=1, topk_group=1, scaling=1.0)
    experts, weights = moe.route(logits, bias, eps=1e-6, **rule)
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    assert sorted(np.asarray(experts)[0].tolist()) == [1, 4]  # chosen by s + b ...
    got = dict(zip(np.asarray(experts)[0].tolist(), np.asarray(weights)[0].tolist()))
    for e in (1, 4):  # ... weighed by s, over (their sum + 1e-6)
        assert got[e] == pytest.approx(s[e] / (s[1] + s[4] + 1e-6), rel=1e-6)
    # where the chosen scores are tiny the two epsilons part: today's default stays 1e-20
    tiny = jnp.full((1, 6), -18.0, jnp.float32)
    _, published = moe.route(tiny, jnp.zeros((6,)), eps=1e-6, **rule)
    _, default = moe.route(tiny, jnp.zeros((6,)), **rule)
    assert float(np.asarray(default).sum()) == pytest.approx(1.0, rel=1e-5)
    assert float(np.asarray(published).sum()) == pytest.approx(2 * float(jax.nn.sigmoid(-18.0)) / (
        2 * float(jax.nn.sigmoid(-18.0)) + 1e-6), rel=1e-4) and float(np.asarray(published).sum()) < 0.05
    assert CFG.norm_topk_eps == 1e-6
    # the kernel form takes the same epsilon (128 outputs, 1024 tokens: ``route_blocks`` says kernel)
    wide = jax.random.normal(jax.random.PRNGKey(0), (1024, 128)) - 12.0
    zero = jnp.zeros((128,))
    wide_rule = dict(top_k=4, n_group=1, topk_group=1, scaling=1.0, eps=1e-6)
    e0, w0 = moe.route(wide, zero, **wide_rule)
    e1, w1 = moe.route(wide, zero, impl="pallas_interpret", **wide_rule)
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    np.testing.assert_allclose(np.asarray(w0), np.asarray(w1), rtol=1e-5)
    assert float(np.asarray(w0).sum(-1).max()) < 0.999


def test_the_eight_shares_of_the_expert_layer_sum_to_the_uncut_layer(params):
    """With the 16 experts split into 8 shares of 2 (``first_held`` 0, 2, ...),
    each share routes over all 16, drops no assignment of its own, and the
    shares' expert terms sum to the uncut reference's layer."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    layer = ref.layer_params(params, CFG, 2)  # the first sparse layer
    stacks = tuple(params["experts"][name][0] for name in ("w_gate", "w_up", "w_down"))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.moe(x[0], layer["mlp"], stacks, range(16), CFG))
    total, routed = np.zeros_like(whole), 0
    for rank in range(8):
        share = dataclasses.replace(CFG, ep_size=8, ep_rank=rank)
        assert (share.first_held, share.experts_held) == (2 * rank, 2)
        held = tuple(w[None, 2 * rank:2 * rank + 2] for w in stacks)  # [1 layer, 2 held, ...]
        y, counts = lm.SparseMLP(share, FP32, "xla").apply({"params": layer["mlp"]}, x, held, jnp.int32(0))
        assert int(counts.routed) == int(counts.computed) == int(counts.combined)  # no assignment dropped
        total, routed = total + np.asarray(y[0]), routed + int(counts.routed)
    assert routed == 24 * 4  # every assignment is some share's
    np.testing.assert_allclose(total, whole, atol=ATOL)
    # and the uncut program layer is the uncut reference's
    full = tuple(w[None] for w in stacks)
    y, counts = lm.SparseMLP(CFG, FP32, "xla").apply({"params": layer["mlp"]}, x, full, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(y[0]), whole, atol=ATOL)
    assert int(counts.routed) == 24 * 4 == int(counts.computed)


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
    counted = engine.stats.family_counters
    # the three rows ride the batch ladder's rung of four; the filler row's assignments are computed too
    assert counted["moe_decode_layer_steps"] == (NEW - 1) * CFG.num_moe_layers
    assert counted["moe_decode_assignments_computed"] == counted["moe_decode_assignments_held"] == (NEW - 1) * 4 * 4 * 8
    assert counted["prefill_tokens_computed"] == 4 * 64


def test_a_prompt_past_the_largest_bucket_prefills_in_chunks(params):
    prompt = prompt_of(150, 21)  # three chunks of 64, left-padded by 42: the first chunk's pads leave zeros
    assert engine_for(params).generate([prompt]) == [greedy_reference(prompt, NEW)]


def repeating(n, period, seed):
    return [prompt_of(period, seed)[i % period] for i in range(n)]


@pytest.mark.parametrize("prompt,why", [
    (repeating(50, 7, 31), "a prompt that repeats: proposals accepted in full and in part"),
    (prompt_of(50, 32), "no repeat: nothing accepted"),
])
def test_the_verify_loop_is_the_vanilla_loop(params, prompt, why):
    """Prompt-lookup speculation commits the kept inputs of what it kept: the
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

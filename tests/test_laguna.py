"""The windowed-attention sparse-expert family (models/windowed_moe.py over
models/llama.py's attention seam, models/latent_moe.py's sparse FFN,
ops/attention.py, ops/moe.py) against its plain reference
(tests/laguna_reference.py), at a toy size on the CPU with seeded weights under
the fp32 policy. The toy model has the published block's traits at once: a
dense full-attention lead layer, then two periods of (two sliding layers of 9
query heads a KV head, one full layer of 6), a window of 8 tokens (every prompt
here is longer), half of a full layer's head rotated under YaRN and the whole
of a sliding layer's, the per-head gate, 16 experts of which rank 1 of 2 holds
8, top-4, a shared expert.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (grouped query heads in one matmul against a loop over heads;
the experts as grouped matmuls over gathered rows; the cache round-trips
nothing in fp32), so logits of magnitude ~1-3 agree to a few 1e-5; ``ATOL`` is
3e-4, ten times that. The faults the comparison must see are far above it:
a sliding layer run as a full one moves a logit by 1e-2 or more on a prompt
longer than the window, the gate left out by 1e-1. The share test adds the
same float32 terms in another order: 2e-5.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import laguna_reference as ref
from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    MeshConfig,
    PrefixCacheConfig,
    RopeParameters,
    SamplingConfig,
    WindowedMoEConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import families, latent_moe as lm, windowed_moe as wm

FP32 = DTypePolicy.fp32()
ATOL = 3e-4
CFG = WindowedMoEConfig.tiny(vocab_size=300)
NEW = 5
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)


def seeded_params(cfg, seed=0):
    """Weights with statistics that make every part matter: kernels of std
    1/sqrt(fan_in), norm weights near 1, a router bias that moves choices."""
    shapes = jax.eval_shape(lambda: wm.init_windowed_moe_params(jax.random.PRNGKey(0), cfg, FP32))
    rng = np.random.default_rng(seed)
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = tuple(k.key for k in path)
        if any("norm" in n for n in names):
            value = 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        elif names[-1] == "router_bias":
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif names[-1] == "embedding":
            value = rng.standard_normal(leaf.shape)
        else:
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[names[-1]] = jnp.asarray(value, jnp.float32)
    return out


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def engine_for(params, cfg=CFG, **kw):
    ec = EngineConfig(**{**dict(prompt_buckets=(32, 64), max_batch_size=4, max_seq_len=160,
                                speculative="off", attn_impl="xla", max_chunked_prompt=256), **kw})
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


def greedy_reference(params, cfg, prompt, n):
    """The reference's greedy continuation. Sequences are padded on the right
    to a multiple of 16 (causal: a pad changes nothing in front of it), so the
    eager operations compile for a few lengths and not for every one."""
    tokens = list(prompt)
    for _ in range(n):
        padded = tokens + [0] * (-len(tokens) % 16)
        tokens.append(int(np.argmax(ref.forward(params, cfg, padded)[len(tokens) - 1])))
    return tokens[len(prompt):]


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 300, size=n)]


def through_the_cache(params, tokens, S, T, impl="xla", lengths=None):
    """Logits of ``tokens`` (one row, or rows left-padded to ``S`` where
    ``lengths`` says how much of ``S`` each prompt fills): ``S`` slots
    prefilled at once, the rest decoded a token at a time."""
    rows = [tokens] if lengths is None else tokens
    B = len(rows)
    lens = np.asarray([S] * B if lengths is None else lengths)
    model = wm.WindowedMoEModel(CFG, FP32, attn_impl=impl)
    call = jax.jit(lambda *a: model.apply({"params": params}, *a))  # one trace for the prompt, one for every step
    cache = wm.make_windowed_cache(CFG, B, T, jnp.float32)
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


# ---- (a) prefill logits, then decode through the K/V planes step by step ----


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_prefill_then_decode_matches_reference(params, impl):
    tokens = prompt_of(24, 1)
    want = ref.forward(params, CFG, tokens)
    (got,), cache = through_the_cache(params, tokens, 16, 32, impl)
    np.testing.assert_allclose(got, want, atol=ATOL)
    counted = wm.fold_counters(np.asarray(cache.counters))
    assert counted["moe_tokens_routed"] == 24 * CFG.num_moe_layers
    assert counted["moe_decode_layer_steps"] == 8 * CFG.num_moe_layers
    for mode in ("prefill", "decode"):
        assert counted[f"moe_{mode}_assignments_held"] == counted[f"moe_{mode}_assignments_computed"] > 0
    assert (counted["decode_slots_streamed_window"] > 0) == (impl != "xla")


def test_batched_rows_with_different_left_pads(params):
    """Rows whose windows start at different slots, one of them shorter than
    the sliding window: the window bound is per row and counts from the
    query, never from the bucket's slot 0."""
    rows = [prompt_of(30, 2), prompt_of(17, 3), prompt_of(11, 4)]
    lengths = [24, 11, 5]
    got, _ = through_the_cache(params, rows, 24, 48, lengths=lengths)
    for row, n, g in zip(rows, lengths, got):
        np.testing.assert_allclose(g, ref.forward(params, CFG, row)[:len(g)], atol=ATOL)


# ---- (b) the faults the comparison must see ----


def test_a_sliding_layer_run_as_full_fails_the_tolerance(params):
    tokens = prompt_of(24, 1)  # three windows long
    sound = ref.forward(params, CFG, tokens)
    full = ref.forward(params, CFG, tokens, sliding_as_full=True)
    np.testing.assert_allclose(full[:CFG.sliding_window], sound[:CFG.sliding_window], atol=1e-5)
    assert np.abs(full[CFG.sliding_window:] - sound[CFG.sliding_window:]).max() > 30 * ATOL
    (got,), _ = through_the_cache(params, tokens, 16, 32)
    assert np.abs(got - full).max() > 30 * ATOL  # and the program is on the windowed side


def test_the_gate_dropped_fails_the_tolerance(params):
    tokens = prompt_of(24, 1)
    assert np.abs(ref.forward(params, CFG, tokens, gate=False) - ref.forward(params, CFG, tokens)).max() > 100 * ATOL


def test_head_counts_differ_by_layer_in_one_model(params):
    K = CFG.num_kv_heads
    assert sorted(set(CFG.num_attention_heads_per_layer)) == [6 * K, 9 * K]
    assert params["periods"]["l0"]["attn"]["wq"]["kernel"].shape == (2, 64, 9 * K * 16)
    assert params["periods"]["l2"]["attn"]["wq"]["kernel"].shape == (2, 64, 6 * K * 16)
    assert params["periods"]["l0"]["attn"]["wg"]["kernel"].shape == (2, 64, 9 * K)
    assert params["lead_0"]["attn"]["wg"]["kernel"].shape == (64, 6 * K)
    cache = families.make_cache(CFG, 2, 32, jnp.float32)
    assert cache.k.shape == (CFG.num_layers, 2, K, 32, 16) and cache.counters.shape == (wm.N_COUNTERS,)


# ---- (c) every one-shot program of the engine ----


def test_batched_rows_of_unequal_length(params):
    prompts = [prompt_of(n, 10 + n) for n in (20, 31, 7)]
    got = engine_for(params).generate(prompts)
    assert got == [greedy_reference(params, CFG, p, NEW) for p in prompts]


def test_verify_16_drafts_is_the_vanilla_stream(params):
    base = prompt_of(6, 3)
    prompt = (base * 5)[:28]  # repeats: prompt lookup has something to draft
    e = engine_for(params, speculative="prompt_lookup", spec_tokens=16)
    assert e.generate([prompt]) == [greedy_reference(params, CFG, prompt, NEW)]
    counted = e.stats.family_counters
    assert e.stats.spec_verify_steps > 0 and counted["moe_chunk_assignments_held"] > 0
    assert counted["moe_chunk_assignments_held"] == counted["moe_chunk_assignments_computed"]


def test_chunked_prefill_past_the_largest_bucket(params):
    prompt = prompt_of(100, 4)  # > 64: two chunks of 64 through the cache, the window across their seam
    assert engine_for(params).generate([prompt]) == [greedy_reference(params, CFG, prompt, NEW)]


def test_score_exact_matches_reference_logits(params):
    prompt, emitted = prompt_of(20, 5), prompt_of(6, 6)
    got = engine_for(params).score_exact(prompt, emitted)
    logits = ref.forward(params, CFG, prompt + emitted)[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(6), emitted], atol=ATOL)


def test_fused_single_fetch_path(params):
    e = engine_for(params)
    a_ids, b_ids = np.asarray(prompt_of(5, 7), np.int32), np.asarray(prompt_of(4, 8), np.int32)
    store = np.zeros((8, 12), np.int32)
    lens = np.asarray([12, 9, 12, 5, 12, 12, 12, 12], np.int32)
    for i in range(8):
        store[i, :lens[i]] = prompt_of(int(lens[i]), 20 + i)
    packed = jnp.asarray([[0.1, 0.2, 0.3, 3.0, 1.0, 6.0]], jnp.float32)  # dists | ids
    got = e.generate_rag(a_ids, b_ids, packed, jnp.asarray(store), jnp.asarray(lens), n_chunks=2)
    prompt = list(a_ids) + list(store[3, :5]) + list(store[1, :9]) + list(b_ids)
    assert got == greedy_reference(params, CFG, [int(t) for t in prompt], NEW)


def test_pallas_path_is_the_xla_path(params):
    """The windowed flash prefill, the decode walk on the shortened window
    and the grouped expert matmul (all in interpret mode) give the stream
    the XLA forms give, and the program counts what the kernels did."""
    from rag_llm_k8s_tpu.obs import tracing

    prompts = [prompt_of(n, 30 + n) for n in (20, 9)]
    e = engine_for(params, attn_impl="pallas_interpret")
    assert e.generate(prompts) == engine_for(params).generate(prompts)
    built = tracing.kernel_builds()
    assert built.get(("prefill", "flash_attention_window"), 0) > 0 and built.get(("prefill", "flash_attention"), 0) > 0
    counted = e.stats.family_counters
    assert 0 < counted["decode_slots_streamed_window"] <= counted["decode_slots_allocated_window"]
    assert 0 < counted["decode_slots_streamed"] <= counted["decode_slots_allocated"]
    assert counted["decode_slots_allocated_window"] == CFG.num_sliding_layers * counted["decode_slots_allocated"]


# ---- (d) the share ties to the model ----


def test_shares_add_up_to_the_uncut_layer():
    """16 ranks' held-expert parts, the shared expert counted once, equal
    the uncut layer: the program's expert layer under every ``ep_rank``
    against the reference's layer over all experts."""
    cfg = WindowedMoEConfig.tiny(vocab_size=300, num_experts=32, num_experts_per_tok=5, ep_size=16, ep_rank=0)
    whole = dataclasses.replace(cfg, ep_size=1)
    p_whole = seeded_params(whole, seed=3)
    layer = jax.tree.map(lambda a: a[1], p_whole["periods"]["l1"]["mlp"])
    moe_layer = cfg.period + 1  # period 1, sublayer 1, behind no sparse lead layer
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 24, cfg.hidden_size)), jnp.float32)
    stack = tuple(p_whole["experts"][n] for n in ("w_gate", "w_up", "w_down"))
    held = cfg.experts_held

    def share(rank):
        c = dataclasses.replace(cfg, ep_rank=rank)
        y, _ = lm.SparseMLP(c, FP32, "xla").apply(
            {"params": layer}, x, tuple(w[:, rank * held:(rank + 1) * held] for w in stack), jnp.int32(moe_layer))
        return np.asarray(y, np.float64)

    with jax.default_matmul_precision("highest"):
        shares = [share(r) for r in range(16)]
        flat = x.reshape(-1, cfg.hidden_size)
        uncut = np.asarray(ref.moe(flat, layer, tuple(w[moe_layer] for w in stack), list(range(32)), whole),
                           np.float64).reshape(x.shape)
        sh = layer["shared"]
        shared = np.asarray(ref._swiglu(x, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                                        sh["w_down"]["kernel"]), np.float64)
    # every share counts the shared expert; the uncut layer counts it once
    np.testing.assert_allclose(sum(shares) - 15 * shared, uncut, atol=2e-5)
    assert sum(np.abs(s - shared).max() > 1e-2 for s in shares) >= 12  # the parts are no rounding


# ---- (e) the rotary tables ----


def test_rotary_tables_are_the_closed_form():
    big = WindowedMoEConfig()  # the published block
    full, sliding = big.rope_of("full_attention"), big.rope_of("sliding_attention")
    assert wm.rotary_dim(big, full) == 64 and wm.rotary_dim(big, sliding) == 128
    pos = jnp.asarray([[0, 1, 4095, 4501]])  # float32 phases: the positions a bucket serves
    for rope in (full, sliding):
        cos, sin = wm.rope_table(pos, big, rope)
        inv = ref.inv_freq(rope, big.head_dim)
        amp = rope.attention_factor if rope.rope_type == "yarn" else 1.0
        want = np.asarray(pos, np.float64)[..., None] * inv
        np.testing.assert_allclose(np.asarray(cos), np.cos(want) * amp, atol=2e-3)
        np.testing.assert_allclose(np.asarray(sin), np.sin(want) * amp, atol=2e-3)
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    inv = ref.inv_freq(full, 128)
    assert np.allclose(inv[:4], base[:4]) and np.allclose(inv[-8:], base[-8:] / 128)
    np.testing.assert_allclose(full.attention_factor, 0.1 * np.log(128.0) + 1.0, rtol=1e-9)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 4, 2, 128)), jnp.float32)
    cos, sin = wm.rope_table(pos, big, full)
    assert np.array_equal(np.asarray(wm.rotate(x, cos, sin))[..., 64:], np.asarray(x)[..., 64:])  # untouched half


# ---- (f) the configuration refuses what the loop cannot run ----


def test_config_refuses_what_is_not_whole_periods():
    kinds = CFG.layer_types
    with pytest.raises(ValueError, match="repeat one pattern"):
        dataclasses.replace(CFG, layer_types=kinds[:-1] + ("sliding_attention",))
    with pytest.raises(ValueError, match="leading run"):
        dataclasses.replace(CFG, mlp_layer_types=("dense", "sparse", "dense") + ("sparse",) * 4)
    with pytest.raises(ValueError, match="softcapping"):
        dataclasses.replace(CFG, moe_router_logit_softcapping=30.0)
    with pytest.raises(ValueError, match="OUTPUT"):
        dataclasses.replace(CFG, moe_apply_router_weight_on_input=True)
    with pytest.raises(ValueError, match="no table"):
        dataclasses.replace(CFG, rope_parameters=(("full_attention", RopeParameters()),))
    big = WindowedMoEConfig()
    assert (big.num_lead, big.period, big.num_periods, big.num_sliding_layers) == (1, 4, 1, 3)


# ---- (g) what the family cannot be served with refuses by name ----


@pytest.mark.parametrize("overrides,mechanism", [
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(weight_quant="int8"), "weight_quant"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "prefix cache"),
    (dict(batching="continuous"), "continuous"),
])
def test_refusals_name_the_mechanism(params, overrides, mechanism):
    with pytest.raises(NotImplementedError, match=mechanism):
        engine_for(params, **overrides)


def test_continuous_engine_and_tp_refuse(params):
    from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine

    with pytest.raises(NotImplementedError, match="continuous engine"):
        ContinuousEngine(CFG, params, sampling=GREEDY, engine_config=EngineConfig(), dtypes=FP32)
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="tp=2"):
        InferenceEngine(CFG, params, sampling=GREEDY, engine_config=EngineConfig(), dtypes=FP32, mesh=mesh)


def test_the_family_row_and_the_other_two_s(params):
    fam = families.of(CFG)
    assert fam.counters_width == wm.N_COUNTERS == lm.N_COUNTERS + 4
    assert fam.counter_names[-4:] == ("decode_slots_streamed_window", "decode_slots_allocated_window",
                                      "prefill_window_pairs_multiplied", "prefill_window_pairs_live")
    assert fam.counter_names[:-4] == tuple(lm.COUNTER_STATS)  # the latent family's, under their names
    assert set(fam.counter_names) == set(wm.fold_counters(np.zeros(wm.N_COUNTERS)))
    assert fam.checkpoint_loader_refusal and "name map" in fam.checkpoint_loader_refusal
    from rag_llm_k8s_tpu.core.config import LatentMoEConfig, LlamaConfig

    assert families.of(LlamaConfig.tiny()).counters_width == 4
    assert families.of(LatentMoEConfig.tiny()).counters_width == lm.N_COUNTERS

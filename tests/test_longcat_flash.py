"""The shortcut-connected block of the latent-attention sparse-expert family
(two attention sublayers a layer, a dense FFN each, ONE expert layer that
branches off sublayer 0 and joins after the last; softmax routing over routed
and zero-computation experts; both LoRA scales) against its plain reference
(tests/longcat_flash_reference.py), at a toy size on the CPU with seeded
weights under the fp32 policy.

Tolerances. As tests/test_latent_moe.py: the program and the reference
compute the same float32 numbers in other orders (absorbed against expanded
attention, grouped matmuls over gathered rows, the zero experts' weights
summed before the product), so logits of magnitude ~1-3 agree to a few 1e-5;
``ATOL`` is 3e-4, and each fault a test plants (the branch joined a sublayer
early, a LoRA scale left out, a cache plane swapped, the zero term dropped)
moves them by ``FAULT`` = 1e-2 or more. The share test adds the same float32
terms in another order: 5e-5.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import longcat_flash_reference as ref
from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LatentMoEConfig,
    MeshConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import families
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.models.llama import rope_cos_sin
from rag_llm_k8s_tpu.ops import moe

FP32 = DTypePolicy.fp32()
ATOL, FAULT = 3e-4, 1e-2
CFG = ref.tiny_config(vocab_size=300)
NEW = 6
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)


def seeded_params(cfg, seed=0):
    """Kernels of std 1/sqrt(fan_in), norm weights near 1, and a router bias
    on the softmax's own scale (scores sit near 1 / 24): it moves choices."""
    shapes = jax.eval_shape(lambda: lm.init_latent_moe_params(jax.random.PRNGKey(0), cfg, FP32))
    rng = np.random.default_rng(seed)
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = tuple(k.key for k in path)
        if any("norm" in n for n in names):
            value = 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        elif names[-1] == "router_bias":
            value = 0.02 * rng.standard_normal(leaf.shape)
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


def greedy_reference(params, cfg, prompt, n=NEW):
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


def prefill_then_decode(params, cfg, tokens, S, spoil=None):
    """Logits ``[len(tokens), V]`` of a prefill of ``S`` tokens and one decode
    step a token after it, through the latent cache; ``spoil(cache)`` may
    damage the cache between the two."""
    model = lm.LatentMoEModel(cfg, FP32, attn_impl="xla")
    cache = lm.make_latent_cache(cfg, 1, 32, jnp.float32)
    zero, i32 = jnp.zeros((1,), jnp.int32), jnp.int32
    logits, cache = model.apply(
        {"params": params}, jnp.asarray([tokens[:S]]), jnp.arange(S)[None], cache, zero,
        jnp.full((1,), S, i32), i32(0))
    rows = [np.asarray(logits[0])]
    if spoil:
        cache = spoil(cache)
    for t in range(S, len(tokens)):
        logits, cache = model.apply(
            {"params": params}, jnp.asarray([[tokens[t]]]), jnp.asarray([[t]]), cache, zero,
            jnp.full((1,), t + 1, i32), i32(t))
        rows.append(np.asarray(logits[0]))
    return np.concatenate(rows), cache


# ---- (a) prefill logits, then decode through the latent cache step by step ----


def test_prefill_then_decode_matches_reference(params):
    tokens = prompt_of(22, 1)
    want = ref.forward(params, CFG, tokens)
    got, cache = prefill_then_decode(params, CFG, tokens, 16)
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert cache.c_kv.shape[0] == cache.k_rope.shape[0] == 2 * CFG.num_layers == 4
    c = {f: np.asarray(cache.counters).reshape(len(lm.COUNTER_MODES), -1)[:, i]
         for i, f in enumerate(lm.COUNTER_FIELDS)}
    assert c["tokens"][0] == 16 * CFG.num_layers and c["layer_calls"][1] == 6 * CFG.num_layers
    assert (c["routed"] == c["computed"]).all()  # routed to held == computed: nothing dropped
    # every token-layer makes top_k choices: to held experts, to zero experts, or to absent chips
    assert 0 < c["zero"][0] < c["tokens"][0] * CFG.num_experts_per_tok
    assert (c["routed"] + c["zero"] <= c["tokens"] * CFG.num_experts_per_tok).all()
    # the reference's own routing says how many went to zero experts
    assert c["zero"][2] == 0 and c["zero"][1] > 0


# ---- (b) every one-shot program of the engine ----


def test_batched_rows_of_unequal_length(params):
    prompts = [prompt_of(n, 10 + n) for n in (20, 31, 7)]
    e = engine_for(params)
    assert e.generate(prompts) == [greedy_reference(params, CFG, p) for p in prompts]
    assert families.of(CFG) is families.of(LatentMoEConfig.tiny())  # the latent family's one row
    counted = e.stats.family_counters
    assert counted["moe_prefill_assignments_zero"] > 0 and counted["moe_decode_assignments_zero"] > 0


def test_verify_16_drafts_is_the_vanilla_stream(params):
    prompt = (prompt_of(6, 3) * 5)[:28]  # repeats: prompt lookup has something to draft
    e = engine_for(params, speculative="prompt_lookup", spec_tokens=16)
    assert e.generate([prompt]) == [greedy_reference(params, CFG, prompt)]
    counted = e.stats.family_counters
    assert e.stats.spec_verify_steps > 0 and counted["moe_chunk_assignments_zero"] > 0
    assert counted["moe_chunk_assignments_held"] == counted["moe_chunk_assignments_computed"]


def test_chunked_prefill_past_the_largest_bucket(params):
    prompt = prompt_of(100, 4)  # > 64: two chunks of 64 through the cache
    assert engine_for(params).generate([prompt]) == [greedy_reference(params, CFG, prompt)]


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
    assert got == greedy_reference(params, CFG, [int(t) for t in prompt])


def test_pallas_path_is_the_xla_path(params):
    prompts = [prompt_of(n, 30 + n) for n in (20, 9)]
    assert engine_for(params, attn_impl="pallas_interpret").generate(prompts) == engine_for(params).generate(prompts)


def test_rowwise_prefill_changes_nothing(params, monkeypatch):
    """At the served widths (64 heads, a 4096 bucket, batch 8) attention and
    both dense FFNs go a row at a time, the branch live across them."""
    big = dataclasses.replace(ref.tiny_config(), num_heads=64, qk_nope_head_dim=128,
                              qk_rope_head_dim=64, v_head_dim=128)
    assert lm.rowwise(big, 8, 4096, jnp.bfloat16) and not lm.rowwise(big, 1, 4096, jnp.bfloat16)
    prompts = [prompt_of(n, 40 + n) for n in (20, 9, 14)]
    want = engine_for(params).generate(prompts)
    monkeypatch.setattr(lm, "ROWWISE_BYTES", 1)
    assert engine_for(params).generate(prompts) == want


# ---- (c) routing ----

ROUTE = dict(top_k=2, n_group=1, topk_group=1, scaling=6.0, normalize=False, scoring="softmax")


def test_route_softmax_bias_scaling_ties_and_zero_indices():
    logits = jnp.zeros((1, 12)).at[0, [3, 7]].set(2.0).at[0, 10].set(1.0)  # 8 routed + 4 zero
    p = np.asarray(jax.nn.softmax(logits[0]))
    experts, weights = moe.route(logits, jnp.zeros(12), **ROUTE)
    assert experts.tolist() == [[3, 7]]  # ties to the lower index first
    # the weight is the softmax over ALL 12 outputs, times 6, NOT renormalised over the chosen
    np.testing.assert_allclose(np.asarray(weights), [[6 * p[3], 6 * p[7]]], rtol=1e-6)
    assert float(weights.sum()) < 6.0
    # the bias moves the CHOICE (zero expert 10, index >= routed) and never the WEIGHT
    experts, weights = moe.route(logits, jnp.zeros(12).at[10].set(0.5), **ROUTE)
    assert experts.tolist() == [[10, 3]]
    np.testing.assert_allclose(np.asarray(weights), [[6 * p[10], 6 * p[3]]], rtol=1e-6)
    # asked to, it normalises over the chosen
    _, normed = moe.route(logits, jnp.zeros(12), **{**ROUTE, "normalize": True})
    np.testing.assert_allclose(float(normed.sum()), 6.0, rtol=1e-6)
    # all equal: the lowest indices
    assert moe.route(jnp.zeros((1, 12)), jnp.zeros(12), **ROUTE)[0].tolist() == [[0, 1]]


def test_route_matches_reference_on_random_scores():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    w_g = jnp.asarray(rng.standard_normal((32, 24)) / 4, jnp.float32)
    bias = jnp.asarray(0.02 * rng.standard_normal(24), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.route(x, w_g, bias, CFG)
        experts, weights = moe.route(x @ w_g, bias, top_k=6, n_group=1, topk_group=1, scaling=6.0,
                                     normalize=False, scoring="softmax")
    got = np.zeros((64, 24))
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert (np.asarray(experts) >= 16).any() and (np.asarray(experts) < 16).any()


# ---- (d) the faults the comparison must catch ----


def test_the_branch_joins_after_the_last_sublayer(params):
    """A block whose expert branch joins after sublayer 0 (an ordinary MoE
    layer followed by a dense one) is another model: the comparison fails."""
    tokens = prompt_of(22, 1)
    got, _ = prefill_then_decode(params, CFG, tokens, 16)
    early = ref.forward(params, CFG, tokens, join_after=0)
    assert np.abs(got - early).max() > FAULT
    np.testing.assert_allclose(got, ref.forward(params, CFG, tokens, join_after=1), atol=ATOL)


@pytest.mark.parametrize("flag", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_a_model_without_a_lora_scale_fails(params, flag):
    tokens = prompt_of(22, 1)
    want = ref.forward(params, CFG, tokens)
    got, _ = prefill_then_decode(params, dataclasses.replace(CFG, **{flag: False}), tokens, 16)
    assert np.abs(got[:16] - want[:16]).max() > FAULT  # expanded prefill
    assert np.abs(got[16:] - want[16:]).max() > FAULT  # absorbed decode over the cache
    assert np.abs(want - ref.forward(params, CFG, tokens, lora_scales=False)).max() > FAULT


def test_each_attention_sublayer_reads_its_own_plane(params):
    """Decode over a cache whose planes 0 and 1 (layer 0's two sublayers) are
    swapped is wrong: the write index advances twice a layer."""
    tokens = prompt_of(22, 1)
    want = ref.forward(params, CFG, tokens)

    def swap(cache):
        order = jnp.asarray([1, 0, 2, 3])
        return cache.replace(c_kv=cache.c_kv[order], k_rope=cache.k_rope[order])

    got, _ = prefill_then_decode(params, CFG, tokens, 16, spoil=swap)
    np.testing.assert_allclose(got[:16], want[:16], atol=ATOL)
    assert np.abs(got[16:] - want[16:]).max() > FAULT


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_imbalance_loses_nothing(impl):
    """All six choices of every token on ONE held expert (six times the
    balanced buffer: several passes), then all six on zero experts: no
    grouped matmul runs, ``computed`` 0, and the output is ``sum(w) * u``."""
    rng = np.random.default_rng(2)
    N, D, F, held, top_k, n_routed = 128, 32, 16, 4, 6, 16
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) / 4, jnp.float32)
         for s in ((1, held, D, F), (1, held, D, F), (1, held, F, D))]
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (N, top_k)), jnp.float32)
    one = jnp.full((N, top_k), 9, jnp.int32)
    assert moe.rows_per_pass(N, top_k, 24, held) < N * top_k
    with jax.default_matmul_precision("highest"):
        y, counts = moe.held_expert_ffn(x, one, weights, *w, jnp.int32(0), 8, 24, impl=impl)
        want = weights.sum(-1, keepdims=True) * ref.swiglu(x, w[0][0, 1], w[1][0, 1], w[2][0, 1])
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
        assert int(counts.routed) == int(counts.computed) == N * top_k and int(counts.experts_hit) == 1
        assert int(moe.zero_expert_term(x, one, weights, n_routed)[1]) == 0
        zeros = jnp.asarray(rng.integers(n_routed, 24, (N, top_k)), jnp.int32)
        y, counts = moe.held_expert_ffn(x, zeros, weights, *w, jnp.int32(0), 8, 24, impl=impl)
        term, n_zero = moe.zero_expert_term(x, zeros, weights, n_routed)
    assert int(counts.routed) == int(counts.computed) == int(counts.experts_hit) == 0
    assert not np.asarray(y).any() and int(n_zero) == N * top_k
    np.testing.assert_allclose(np.asarray(term), np.asarray(weights.sum(-1, keepdims=True) * x), rtol=1e-6)


# ---- (e) the share ties to the model ----


def test_shares_add_up_to_the_uncut_layer():
    """Over ``ep_rank`` 0..ep-1 the layers' outputs, with what every chip
    computes alike (residual, attention, both dense FFNs, the zero experts'
    term) counted once, equal the uncut reference's layer."""
    whole = dataclasses.replace(CFG, ep_size=1, ep_rank=0)
    p = seeded_params(whole, seed=3)
    layer = jax.tree.map(lambda a: a[1], p["layers"])
    stack = tuple(p["experts"][n] for n in ("w_gate", "w_up", "w_down"))
    S = 24
    h = jnp.asarray(np.random.default_rng(4).standard_normal((1, S, CFG.hidden_size)), jnp.float32)
    cos, sin = rope_cos_sin(jnp.arange(S)[None], lm.yarn_frequencies(CFG.qk_rope_head_dim, CFG.rope_theta, None))
    window = (jnp.zeros((1,), jnp.int32), jnp.full((1,), S, jnp.int32), cos, sin, jnp.int32(0))

    def run(cfg, stack):
        cache = lm.make_latent_cache(cfg, 1, S, jnp.float32)
        carry = (h, (cache.c_kv, cache.k_rope), cache.counters, jnp.int32(2))  # layer 1: planes 2 and 3
        (out, planes, _, plane), _ = lm.Block(cfg, FP32, "xla").apply({"params": layer}, carry, *window, stack)
        assert int(plane) == 4 and np.asarray(planes[0][2:]).any() and not np.asarray(planes[0][:2]).any()
        return np.asarray(out[0], np.float64)

    held = CFG.n_routed_experts // 2
    with jax.default_matmul_precision("highest"):
        shares = [run(dataclasses.replace(CFG, ep_size=2, ep_rank=r),
                      tuple(w[:, r * held:(r + 1) * held] for w in stack)) for r in range(2)]
        ex = tuple(w[1] for w in stack)
        uncut = np.asarray(ref.layer(h[0], layer, ex, list(range(CFG.n_routed_experts)), whole), np.float64)
        alike = np.asarray(ref.layer(h[0], layer, ex, [], whole), np.float64)  # no routed expert at all
    np.testing.assert_allclose(shares[0] + shares[1] - alike, uncut, atol=5e-5)
    assert np.abs(shares[0] - alike).max() > FAULT and np.abs(shares[1] - alike).max() > FAULT
    np.testing.assert_allclose(run(whole, stack), uncut, atol=5e-5)


# ---- (f) what is not served refuses by name ----


@pytest.mark.parametrize("overrides,said", [
    (dict(zero_expert_num=-1), "zero_expert_num"),
    (dict(sublayers_per_layer=3), "sublayers_per_layer"),
    (dict(first_k_dense=1), "leading dense"),
    (dict(n_group=4, topk_group=2), "without groups"),
    (dict(scoring_func="tanh"), "scoring_func"),
])
def test_the_configuration_refuses_what_is_not_served(overrides, said):
    with pytest.raises(ValueError, match=said):
        ref.tiny_config(**overrides)


@pytest.mark.parametrize("overrides,mechanism", [
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(weight_quant="int8"), "weight_quant"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "prefix cache"),
    (dict(batching="continuous"), "continuous"),
])
def test_refusals_name_the_mechanism(params, overrides, mechanism):
    with pytest.raises(NotImplementedError, match=mechanism):
        engine_for(params, **overrides)


def test_tp_refuses_and_the_roofline_counts_the_block(params):
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="tp=2"):
        InferenceEngine(CFG, params, sampling=GREEDY, engine_config=EngineConfig(), dtypes=FP32, mesh=mesh)
    plain = dataclasses.replace(CFG, sublayers_per_layer=1, zero_expert_num=0, n_shared_experts=1)
    one_flops, _, one_kv = plain.roofline_terms()
    two_flops, _, two_kv = CFG.roofline_terms()
    # a plane an attention sublayer; two attentions and two dense FFNs a layer
    assert two_kv == 2 * one_kv
    assert two_flops > one_flops

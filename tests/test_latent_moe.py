"""The latent-attention sparse-expert family (models/latent_moe.py, ops/mla.py,
ops/moe.py) against its plain reference (tests/latent_moe_reference.py), at a
toy size on the CPU with seeded weights under the fp32 policy.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (the absorbed form folds W_UK into the query and takes W_UV
after the sum; the experts run as grouped matmuls over gathered rows; the
cache round-trips nothing in fp32), so logits of magnitude ~1-3 agree to a
few 1e-5; ``ATOL`` is 3e-4, ten times that, and a wrong scale, a missed
rotation or a dropped expert moves them by 1e-2 or more. The share test (e)
adds the same float32 terms in another order: 2e-5.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_moe_reference as ref
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
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.ops import mla, moe

FP32 = DTypePolicy.fp32()
ATOL = 3e-4
CFG = LatentMoEConfig.tiny(vocab_size=300)
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)


def seeded_params(cfg, seed=0):
    """Weights with statistics that make every part matter: kernels of std
    1/sqrt(fan_in), norm weights near 1, a router bias that moves choices."""
    shapes = jax.eval_shape(lambda: lm.init_latent_moe_params(jax.random.PRNGKey(0), cfg, FP32))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    rng = np.random.default_rng(seed)
    out = {}
    for path, leaf in flat:
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


# ---- (a) prefill logits, then decode through the latent cache step by step ----


def test_prefill_then_decode_matches_reference(params):
    tokens = prompt_of(24, 1)
    want = ref.forward(params, CFG, tokens)
    model = lm.LatentMoEModel(CFG, FP32, attn_impl="xla")
    S, T = 16, 32
    cache = lm.make_latent_cache(CFG, 1, T, jnp.float32)
    zero, i32 = jnp.zeros((1,), jnp.int32), jnp.int32
    logits, cache = model.apply(
        {"params": params}, jnp.asarray([tokens[:S]]), jnp.arange(S)[None], cache, zero,
        jnp.full((1,), S, i32), i32(0))
    np.testing.assert_allclose(np.asarray(logits[0]), want[:S], atol=ATOL)
    for t in range(S, len(tokens)):  # one token at a time, against the full forward
        logits, cache = model.apply(
            {"params": params}, jnp.asarray([[tokens[t]]]), jnp.asarray([[t]]), cache, zero,
            jnp.full((1,), t + 1, i32), i32(t))
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t], atol=ATOL)
    counters = np.asarray(cache.counters).reshape(len(lm.COUNTER_MODES), -1)
    assert counters[0, 0] == S * CFG.num_moe_layers and counters[1, 4] == 8 * CFG.num_moe_layers
    assert (counters[:, 1] == counters[:, 2]).all()  # routed to held == computed


# ---- (b) every one-shot program of the engine: tests/test_latent_moe_engine.py ----


# ---- (c) absorbed against expanded attention ----


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_absorbed_equals_expanded(impl):
    rng = np.random.default_rng(0)
    B, S, H, C, R, dn, dv = 2, 128, 4, 16, 8, 16, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q_nope, q_rope, c_kv, k_rope, w = f(B, S, H, dn), f(B, S, H, R), f(B, S, C), f(B, S, R), f(C, H, dn + dv)
    kv_start, kv_len = jnp.asarray([0, 5], jnp.int32), jnp.asarray([S, S - 3], jnp.int32)
    kv = jnp.einsum("bsc,chd->bshd", c_kv, w)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None], (B, S, H, R))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    expanded = mla.mla_prefill_attention_xla(q, k, kv[..., dn:], kv_start, kv_len, scale=0.2)
    if impl != "xla":
        flash = mla.mla_flash_attention(q, k, kv[..., dn:], kv_start, kv_len, scale=0.2,
                                        bq=64, bk=64, interpret=True)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(expanded), atol=2e-5)
    q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w[..., :dn])
    o_lat = mla.latent_attention_xla(q_lat, q_rope, c_kv[None], k_rope[None], kv_start, kv_len,
                                     jnp.int32(0), jnp.int32(0), scale=0.2)
    absorbed = jnp.einsum("bshc,chv->bshv", o_lat, w[..., dn:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=2e-5)
    if impl != "xla":  # the decode kernel: the last valid query of each row
        last = S - 4
        one = mla.mla_decode_attention(
            q_lat[:, last:last + 1], q_rope[:, last:last + 1], c_kv[None], k_rope[None], kv_start,
            jnp.full((B,), last + 1, jnp.int32), jnp.int32(0), scale=0.2, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(one[:, 0]), np.asarray(o_lat[:, last]), atol=2e-5)


@pytest.mark.parametrize("kv_start", [(0, 37), (402, 100), (255, 256)], ids=["first-block", "402", "block-edge"])
def test_mla_flash_attention_at_the_published_head_widths(kv_start):
    """The flash prefill at key width 192 (128 nope + 64 rope) against value
    width 128, one head a group, windows that start inside the first block, at
    a block's edge and beyond it: the kernel's visits follow each row's own
    triangle, and the caller's scale (YaRN's correction) is applied in float32."""
    rng = np.random.default_rng(3)
    B, S, H, dq, dv = 2, 512, 2, 192, 128
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v = f(B, S, H, dq), f(B, S, H, dq), f(B, S, H, dv)
    kv_start, kv_len = jnp.asarray(kv_start, jnp.int32), jnp.asarray([S, S - 3], jnp.int32)
    scale = 0.1147 * 1.37  # not a power of two: a q pre-scaled in bf16 would round
    want = mla.mla_prefill_attention_xla(q, k, v, kv_start, kv_len, scale=scale)
    got = mla.mla_flash_attention(q, k, v, kv_start, kv_len, scale=scale, bq=128, bk=256, interpret=True)
    live = (jnp.arange(S)[None, :] >= kv_start[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(jnp.where(live, got, 0)), np.asarray(jnp.where(live, want, 0)), atol=2e-5)


# ---- (d) routing ----

ROUTE = dict(top_k=2, n_group=4, topk_group=2, scaling=2.5)


def test_route_group_limiting_bias_normalisation_scaling_and_ties():
    logits = jnp.full((1, 16), -3.0).at[0, [0, 1]].set(2.0).at[0, [4, 5]].set(1.0).at[0, 8].set(2.5)
    # group scores (top-2 sums): g0 = 2 s(2), g1 = 2 s(1), g2 = s(2.5) + s(-3): g2 is the lowest of
    # the three, so expert 8, the single best, is NOT eligible; ties in g0 go to the lower index
    experts, weights = moe.route(logits, jnp.zeros(16), **ROUTE)
    assert experts.tolist() == [[0, 1]]
    np.testing.assert_allclose(np.asarray(weights), [[1.25, 1.25]], rtol=1e-6)
    # the bias moves the CHOICE (expert 5 over 1) and never the WEIGHT (sigmoid(1) and sigmoid(2))
    bias = jnp.zeros(16).at[5].set(0.5).at[4].set(0.5)
    experts, weights = moe.route(logits, bias, **ROUTE)
    assert sorted(experts[0].tolist()) == [4, 5]
    np.testing.assert_allclose(float(weights.sum()), 2.5, rtol=1e-6)
    experts, weights = moe.route(logits, bias.at[4].set(0.0).at[0].set(0.3), **ROUTE)
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0]))
    got = dict(zip(experts[0].tolist(), np.asarray(weights[0])))
    np.testing.assert_allclose([got[0], got[5]], np.asarray(s / s.sum() * 2.5), rtol=1e-6)
    assert np.allclose(moe.route(logits, bias, **ROUTE, normalize=False)[1].sum(),
                       2.5 * 2 * float(jax.nn.sigmoid(1.0)), rtol=1e-6)


def test_route_matches_reference_on_random_scores():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    w_g = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    bias = jnp.asarray(0.2 * rng.standard_normal(16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.route(x, w_g, bias, CFG)
        experts, weights = moe.route(x @ w_g, bias, top_k=4, n_group=4, topk_group=2, scaling=2.5)
    got = np.zeros((64, 16))
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---- (d') the router's kernel against the jnp body ----

# the routers the three sparse-expert cells serve: outputs, then route's rule
ROUTERS = {
    "dots_sigmoid_top8_of_4_of_8_groups": (256, dict(
        top_k=8, n_group=8, topk_group=4, scaling=2.5, normalize=True, scoring="sigmoid")),
    "longcat_softmax_top12_of_768": (768, dict(
        top_k=12, n_group=1, topk_group=1, scaling=6.0, normalize=False, scoring="softmax")),
    "laguna_sigmoid_top10_of_256": (256, dict(
        top_k=10, n_group=1, topk_group=1, scaling=2.5, normalize=True, scoring="sigmoid")),
}
ROUTER_CASES = {  # case -> tokens
    "a_decode_step_of_8": 8, "one_tile": 128, "a_tile_and_3": 131, "several_tiles_in_steps_of_two": 1280,
    "ties_inside_and_across_the_cut": 256, "a_bias_that_moves_the_choice": 128,
    "minus_inf_beside_the_kept": 256, "logits_of_bf16_operands": 384,
}


def router_inputs(case: str, E: int, scoring: str):
    """``(logits [N, E] float32, bias [E])`` of a case."""
    N = ROUTER_CASES[case]
    key = jax.random.PRNGKey(len(case) + E)
    logits = 2.0 * jax.random.normal(key, (N, E), jnp.float32)
    std = 1.0 / E if scoring == "softmax" else 0.05  # the families' own draws: it moves choices among near scores
    bias = std * jax.random.normal(jax.random.fold_in(key, 1), (E,), jnp.float32)
    if case == "ties_inside_and_across_the_cut":
        # every score of a row equal; logits on a grid of whole numbers; two values a row; no bias to part them
        logits = logits.at[:64].set(0.25).at[64:128].set(jnp.round(logits[64:128]))
        logits = logits.at[128:192].set(jnp.where(logits[128:192] > 0, 1.0, -1.0))
        bias = jnp.zeros(E)
    if case == "a_bias_that_moves_the_choice":
        bias = jnp.zeros(E).at[jnp.arange(5, E, 7)].set(2.0)  # above any difference of two scores
    if case == "minus_inf_beside_the_kept":
        # one finite score a group of 32, none at all in every fourth group and past 256: inside a
        # kept group the finite score's neighbours are -inf, and the choice runs on into them by index
        at = jnp.arange(E)
        bias = jnp.where((at % 32 >= 1) | (at // 32 % 4 == 1) | (at >= 256), -jnp.inf, bias)
    if case == "logits_of_bf16_operands":
        x = jax.random.normal(key, (N, 64), jnp.bfloat16)
        w = (jax.random.normal(jax.random.fold_in(key, 2), (64, E), jnp.float32) / 8).astype(jnp.bfloat16)
        logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return logits, bias


@pytest.mark.parametrize("case", sorted(ROUTER_CASES))
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_route_kernel_is_the_jnp_body(router, case, monkeypatch):
    """``route_topk`` (interpret mode) against ``route``'s jnp body under the
    three served rules: the same experts in the same order, element for
    element, and the same weights to float32 rounding."""
    E, rule = ROUTERS[router]
    logits, bias = router_inputs(case, E, rule["scoring"])
    monkeypatch.setattr(moe, "ROUTE_KERNEL_TOKENS", 128)  # the served rule's 1024 at sizes the interpreter walks
    N = logits.shape[0]
    want = jax.jit(functools.partial(moe.route, **rule, impl="xla"))(logits, bias)
    kernel = functools.partial(moe.route, **rule, impl="pallas_interpret")
    got = jax.jit(kernel)(logits, bias)
    assert ("route_topk" in str(jax.make_jaxpr(kernel)(logits, bias))) == (N >= 128)
    for mine, its, dtype in zip(got, want, (jnp.int32, jnp.float32)):
        assert mine.dtype == its.dtype == dtype and mine.shape == its.shape == (N, rule["top_k"])
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=4e-7, atol=0.0)
    experts = np.asarray(got[0])
    if case == "several_tiles_in_steps_of_two":
        assert moe.route_blocks(N, E, rule["n_group"]) == 2  # ten columns: five grid steps
    if case == "ties_inside_and_across_the_cut":
        assert experts[0].tolist() == sorted(experts[0].tolist()) and experts[0, 0] == 0  # all equal: the lowest indices
    if case == "a_bias_that_moves_the_choice":
        plain = np.asarray(jax.jit(kernel)(logits, jnp.zeros(E))[0])
        favoured = np.asarray(bias)[experts] > 0
        assert favoured.all() and (plain != experts).any()  # the bias alone decided ...
        s = np.asarray(jax.nn.softmax(logits) if rule["scoring"] == "softmax" else jax.nn.sigmoid(logits))
        w = np.take_along_axis(s, experts, axis=1)  # ... and weighs nothing
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20) if rule["normalize"] else w
        np.testing.assert_allclose(np.asarray(got[1]), w * rule["scaling"], rtol=2e-6)
    if case == "minus_inf_beside_the_kept":
        finite = np.isfinite(np.asarray(bias))[experts]
        assert (~finite).any(axis=1).all() and finite[:, 0].all()  # every row runs past its finite scores
        assert all((np.diff(row[~f]) > 0).all() for row, f in zip(experts, finite))  # the tail goes by index
        assert np.isfinite(np.asarray(got[1])).all()


def test_route_rule_for_the_served_shapes():
    """Every prefill of a bucket and the scorer's lengths take the kernel, 8
    columns of 128 tokens a step at 256 outputs and 2 at 768 where they tile;
    a decode step, a verify chunk and a 512-token prefill chunk keep
    ``lax.top_k``; so does a router whose outputs or groups fill no whole tile."""
    for E, rule in ROUTERS.values():
        g, most = rule["n_group"], 8 if E == 256 else 2
        assert {N: moe.route_blocks(N, E, g) for N in (8, 16, 128, 512)} == {8: None, 16: None, 128: None, 512: None}
        assert {N: moe.route_blocks(N, E, g) for N in (1024, 2048, 2304, 4352, 16384, 32768)} == {
            1024: most, 2048: most, 2304: 2, 4352: 2, 16384: most, 32768: most}
    assert moe.ROUTE_KERNEL_TOKENS == 1024
    assert moe.route_blocks(4100, 256, 1) == 1  # 33 columns, the last one padded
    assert moe.route_blocks(4096, 16, 4) is None and moe.route_blocks(4096, 192, 1) is None  # no 128-lane tile of outputs
    assert moe.route_blocks(4096, 256, 16) is None and moe.route_blocks(4096, 128, 32) is None  # groups past one row / of 4


@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_a_prefill_router_holds_no_sort_gather_or_scatter(router):
    """At the cells' prefill shape the jaxpr of ``route`` (the kernel's body
    included) names the kernel and no ``sort``, ``top_k``, ``gather`` or
    ``scatter``; at a decode step's shape it is the jnp body under either
    ``impl``: ``top_k`` and no kernel."""
    E, rule = ROUTERS[router]
    args = lambda N: (jax.ShapeDtypeStruct((N, E), jnp.float32), jax.ShapeDtypeStruct((E,), jnp.float32))  # noqa: E731
    text = str(jax.make_jaxpr(functools.partial(moe.route, **rule, impl="pallas_interpret"))(*args(32768)))
    assert "route_topk" in text
    assert not re.search(r"\b(sort|top_k|gather|scatter[-_\w]*)\b", text), re.findall(r"\b(?:sort|top_k|gather|scatter\S*)\b", text)
    step = str(jax.make_jaxpr(functools.partial(moe.route, **rule, impl="pallas_interpret"))(*args(8)))
    assert "route_topk" not in step and "top_k" in step
    assert step == str(jax.make_jaxpr(functools.partial(moe.route, **rule, impl="xla"))(*args(8)))


def test_the_model_routes_a_prefill_through_the_kernel(monkeypatch):
    """``SparseMLP`` hands ``route`` its ``impl``: a prefill whose tokens reach
    the rule runs the kernel in every routed layer, the decode step behind it
    keeps ``lax.top_k``, the logits are the XLA path's and nothing is dropped."""
    monkeypatch.setattr(moe, "ROUTE_KERNEL_TOKENS", 128)
    cfg = LatentMoEConfig.tiny(n_routed_experts=128, n_group=8, topk_group=4, num_experts_per_tok=4,
                               ep_size=8, ep_rank=1, max_seq_len=256)
    params = lm.init_latent_moe_params(jax.random.PRNGKey(0), cfg, FP32)
    S, i32 = 128, jnp.int32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S + 1), 3, cfg.vocab_size)
    zero = jnp.zeros((1,), i32)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        model = lm.LatentMoEModel(cfg, FP32, attn_impl=impl)
        prefill = functools.partial(model.apply, {"params": params}, tokens[:, :S], jnp.arange(S)[None])
        decode = functools.partial(model.apply, {"params": params}, tokens[:, S:], jnp.asarray([[S]]))
        cache = lm.make_latent_cache(cfg, 1, 256, jnp.float32)
        first = (cache, zero, jnp.full((1,), S, i32), i32(0))
        assert ("route_topk" in str(jax.make_jaxpr(prefill)(*first))) == (impl != "xla")
        logits, cache = prefill(*first)
        then = (cache, zero, jnp.full((1,), S + 1, i32), i32(S))
        assert "route_topk" not in str(jax.make_jaxpr(decode)(*then))
        step, cache = decode(*then)
        out[impl] = (np.asarray(logits), np.asarray(step), lm.fold_counters(np.asarray(cache.counters)))
    np.testing.assert_allclose(out["pallas_interpret"][0], out["xla"][0], atol=ATOL)
    np.testing.assert_allclose(out["pallas_interpret"][1], out["xla"][1], atol=ATOL)
    counted = out["pallas_interpret"][2]
    assert counted["moe_prefill_assignments_held"] == counted["moe_prefill_assignments_computed"] > 0
    assert counted["moe_prefill_assignments_held"] == out["xla"][2]["moe_prefill_assignments_held"]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_imbalance_loses_nothing(impl):
    """Every token sends all its choices to held experts, half of them to ONE:
    four times the row buffer, so the layer takes several passes."""
    rng = np.random.default_rng(2)
    N, D, F, held, top_k = 256, 32, 16, 4, 2
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) / 4, jnp.float32)
         for s in ((1, held, D, F), (1, held, D, F), (1, held, F, D))]
    experts = jnp.stack([jnp.full((N,), 9), 8 + jnp.asarray(rng.integers(0, 4, N))], 1).astype(jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (N, top_k)), jnp.float32)
    assert moe.rows_per_pass(N, top_k, 64, held) < N * top_k
    with jax.default_matmul_precision("highest"):
        y, counts = moe.held_expert_ffn(x, experts, weights, *w, jnp.int32(0), 8, 64, impl=impl)
        want = sum(jnp.where(experts[:, j:j + 1] == 8 + e, weights[:, j:j + 1], 0.0)
                   * ref._swiglu(x, w[0][0, e], w[1][0, e], w[2][0, e])
                   for e in range(held) for j in range(top_k))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    assert int(counts.routed) == int(counts.computed) == N * top_k and int(counts.experts_hit) >= 1


# ---- (e) the share ties to the model ----


def test_shares_add_up_to_the_uncut_layer(params):
    whole = dataclasses.replace(CFG, ep_size=1, ep_rank=0)
    p_whole = seeded_params(whole, seed=3)
    layer = jax.tree.map(lambda a: a[0], p_whole["layers"]["mlp"])
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 24, CFG.hidden_size)), jnp.float32)

    def run(cfg, stack):
        y, _ = lm.SparseMLP(cfg, FP32, "xla").apply({"params": layer}, x, stack, jnp.int32(1))
        return np.asarray(y, np.float64)

    stack = tuple(p_whole["experts"][n] for n in ("w_gate", "w_up", "w_down"))
    with jax.default_matmul_precision("highest"):
        uncut = run(whole, stack)
        held = CFG.n_routed_experts // 2
        shares = [run(dataclasses.replace(CFG, ep_size=2, ep_rank=r),
                      tuple(w[:, r * held:(r + 1) * held] for w in stack)) for r in range(2)]
        sh = layer["shared"]
        shared = np.asarray(ref._swiglu(x, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                                        sh["w_down"]["kernel"]), np.float64)
    # every share counts the shared expert; the uncut layer counts it once
    np.testing.assert_allclose(shares[0] + shares[1] - shared, uncut, atol=2e-5)
    assert np.abs(shares[0] - shared).max() > 1e-2 and np.abs(shares[1] - shared).max() > 1e-2


# ---- (f) YaRN ----


def test_yarn_frequencies_and_softmax_scale_are_the_closed_form():
    big = LatentMoEConfig()  # the published block
    inv = np.asarray(lm.yarn_frequencies(big.qk_rope_head_dim, big.rope_theta, big.rope_scaling))
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(big), rtol=1e-6)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(inv[:10], base[:10], rtol=1e-6) and np.allclose(inv[-8:], base[-8:] / 40, rtol=1e-6)
    m = 0.1 * np.log(40.0) + 1.0
    assert abs(m - 1.369) < 1e-3
    np.testing.assert_allclose(lm.softmax_scale(big), 192 ** -0.5 * m * m, rtol=1e-9)
    assert lm.rope_amplitude(big) == 1.0
    assert lm.softmax_scale(dataclasses.replace(big, rope_scaling=None)) == 192 ** -0.5
    np.testing.assert_allclose(lm.softmax_scale(CFG), ref.softmax_scale(CFG), rtol=1e-9)


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


def test_llama_tree_is_untouched_by_the_seam():
    from rag_llm_k8s_tpu.core.config import LlamaConfig
    from rag_llm_k8s_tpu.models import families
    from rag_llm_k8s_tpu.models.llama import KVCache, LlamaModel

    cfg = LlamaConfig.tiny()
    llama = families.of(cfg)
    assert isinstance(llama.build_model(cfg, FP32, EngineConfig(), None, fused=False, quantized=False),
                      LlamaModel)
    assert isinstance(families.make_cache(cfg, 1, 128, jnp.float32), KVCache)
    # its cache counts the token rows its prefills computed (PR 28) and the cache
    # slots its decode kernel fetched (PR 32, appended), nothing of a router's
    assert llama.counter_names == ("prefill_tokens_computed", "prefill_tokens_bucketed",
                                   "decode_slots_streamed", "decode_slots_allocated")
    assert llama.counters_width == 4 and llama.checkpoint_loader_refusal is None
    assert families.of(CFG).counters_width == lm.N_COUNTERS
    assert set(families.of(CFG).counter_names) == set(lm.fold_counters(np.zeros(lm.N_COUNTERS)))
    with pytest.raises(TypeError, match="no decoder family"):
        families.of(object())


def test_build_service_refuses_the_family_s_checkpoint_by_name(monkeypatch, tmp_path):
    """A deployment's entry point loads safetensors through a name map this
    family does not have: it says so instead of misreading a tree."""
    import dataclasses as dc

    from rag_llm_k8s_tpu.core.config import AppConfig
    from rag_llm_k8s_tpu.server import main as server_main

    base = AppConfig.from_env()
    app = dc.replace(base, model=CFG, server=dc.replace(base.server, model_path=str(tmp_path)))
    monkeypatch.setattr(AppConfig, "from_env", classmethod(lambda cls: app))
    with pytest.raises(NotImplementedError, match="name map"):
        server_main.build_service()


# ---- the grouped kernel counts the rows it stores, and its tiles follow its groups ----


def _tiles_visited(sizes, tm):
    """(row tile, group) pairs that hold rows: what the grid's middle dimension is."""
    lo, visits = 0, 0
    for size in sizes:
        if size:
            visits += (lo + size - 1) // tm - lo // tm + 1
        lo += size
    return visits


@pytest.mark.parametrize("sizes", [(0, 0, 0, 0), (5, 0, 130, 1), (128, 128, 0, 0), (300, 3, 3, 206)])
def test_grouped_matmul_reports_the_rows_it_stored(sizes):
    rng = np.random.default_rng(5)
    m, k, n = 512, 128, 256
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2, 4, k, n)) / 8, jnp.float32)
    tm = moe.grouped_blocks(m, 4, k, n, 4)[0]
    with jax.default_matmul_precision("highest"):
        out, stored, tile_rows = moe.grouped_matmul(
            lhs, rhs, jnp.asarray(sizes, jnp.int32), jnp.int32(1), interpret=True)
        assert int(stored) == sum(sizes)
        assert int(tile_rows) == _tiles_visited(sizes, tm) * tm  # num_tiles * tm
        lo = 0
        for g, size in enumerate(sizes):
            np.testing.assert_allclose(np.asarray(out[lo:lo + size]), np.asarray(lhs[lo:lo + size] @ rhs[1, g]),
                                       atol=1e-4)
            lo += size


def test_a_tile_the_grid_never_reaches_shows_in_the_count(monkeypatch):
    """The count is the kernel's own: cut the grid one (row tile, group) pair
    short, as a wrong bound would, and fewer rows are reported than routed."""
    import importlib

    gmm = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    real = gmm.make_group_metadata

    def short(**kw):
        meta, tiles = real(**kw)
        return meta, tiles - 1

    monkeypatch.setattr(gmm, "make_group_metadata", short)
    lhs = jnp.ones((256, 128), jnp.float32)
    rhs = jnp.ones((1, 2, 128, 128), jnp.float32)
    sizes = jnp.asarray([128, 100], jnp.int32)
    _, stored, _ = moe.grouped_matmul.__wrapped__(lhs, rhs, sizes, jnp.int32(0), interpret=True)
    assert int(stored) == 128 < int(sizes.sum())


TODAY = (512, 1024)  # the row tile and the cut of ``k`` every shape had before the plan read the groups


@pytest.mark.parametrize("case,m,G,k,n,want", [
    # (tokens x top-k in rows_per_pass's buffer, experts held, k, n) of the four sparse cells
    ("lfm2 prefill, gate and up", 16384, 64, 2048, 1536, (256, 2048, 512)),
    ("lfm2 prefill, down", 16384, 64, 1536, 2048, (256, 1536, 1024)),
    ("lfm2 decode, up", 128, 64, 2048, 1536, (128, 2048, 512)),
    ("lfm2 decode, down", 128, 64, 1536, 2048, (128, 1536, 1024)),
    ("lfm2 verify chunk (16 positions), up", 128, 64, 2048, 1536, (128, 2048, 512)),
    ("lfm2, a 512-token prefill chunk, up", 2048, 64, 2048, 1536, (128, 2048, 512)),
    ("lfm2, eight callers' prefill, up", 131072, 64, 2048, 1536, TODAY + (512,)),
    ("lfm2, eight callers' prefill, down", 131072, 64, 1536, 2048, (512, 512, 1024)),
    ("dots prefill, up", 32768, 16, 7168, 2048, TODAY + (1024,)),
    ("dots prefill, down", 32768, 16, 2048, 7168, TODAY + (1024,)),
    ("dots decode, up", 128, 16, 7168, 2048, (128, 1024, 1024)),
    ("dots decode, down", 128, 16, 2048, 7168, (128, 1024, 1024)),
    ("dots, a 512-token prefill chunk, down", 512, 16, 2048, 7168, TODAY + (1024,)),
    ("longcat prefill, up", 16384, 16, 6144, 2048, TODAY + (1024,)),
    ("longcat prefill, down", 16384, 16, 2048, 6144, TODAY + (1024,)),
    ("longcat decode, up", 128, 16, 6144, 2048, (128, 1024, 1024)),
    ("longcat decode, down", 128, 16, 2048, 6144, (128, 1024, 1024)),
    ("laguna prefill, up", 40960, 16, 3072, 1024, TODAY + (1024,)),
    ("laguna prefill, down", 40960, 16, 1024, 3072, TODAY + (1024,)),
    ("laguna decode, up", 128, 16, 3072, 1024, (128, 1024, 1024)),
    ("laguna decode, down", 128, 16, 1024, 3072, (128, 1024, 1024)),
    ("32 held of lfm2's, one row: groups of 512", 16384, 32, 2048, 1536, (512, 2048, 512)),
    ("small groups whose whole-K block does not fit", 16384, 64, 7168, 2048, TODAY + (1024,)),
])
def test_the_grouped_kernel_s_tiles_follow_the_groups(case, m, G, k, n, want):
    """Small mean groups (``m / G`` under ``SMALL_GROUP_ROWS``) whose ``[k,
    tn]`` block fits take the whole ``k`` and a row tile of the mean group's
    size; every other shape, the three controls' every buffer among them,
    takes exactly what it took before the plan read ``G``."""
    got = moe.grouped_blocks(m, G, k, n, 2)
    assert got == want
    today = (min(512, m), 1024 if k % 1024 == 0 else 512, 1024 if n % 1024 == 0 else 512)
    if not case.startswith(("lfm2", "32 held")) or m // G >= moe.SMALL_GROUP_ROWS:
        assert got == today


@pytest.mark.parametrize("cells,held,want", [
    ("lfm2 prefill", (4096, 4, 64, 64), 16384), ("lfm2 decode", (1, 4, 64, 64), 128),
    ("lfm2 verify chunk", (16, 4, 64, 64), 128), ("lfm2, eight callers", (8 * 4096, 4, 64, 64), 131072),
])
def test_the_shape_table_s_buffers_are_rows_per_pass_s(cells, held, want):
    assert moe.rows_per_pass(*held) == want


@pytest.mark.parametrize("case,m,sizes", [
    ("empty groups between full ones", 1024, (200, 0, 0, 130, 0, 90, 0, 60)),
    ("every group under a tile", 1024, (100, 90, 70, 50, 30, 20, 10, 5)),
    ("a tile spanning five groups", 1024, (120, 2, 3, 1, 2, 300, 0, 40)),
    ("no rows at all", 1024, (0,) * 8),
    ("the whole buffer", 1024, (128,) * 8),
    ("one group takes nearly all", 1024, (1, 0, 1000, 0, 0, 1, 0, 0)),
    ("256-row tiles, three groups in one", 2048, (250, 3, 2, 700, 0, 130, 1, 260)),
])
def test_grouped_matmul_under_the_small_group_plan(case, m, sizes):
    """8 groups of a mean 128 or 256 rows: a row tile of that size and one K
    tile. Against ``ragged_dot``; rows past the groups are never written, and
    both counts are the kernel's own."""
    rng = np.random.default_rng(11)
    k, n, G = 256, 128, len(sizes)
    tm, tk, tn = moe.grouped_blocks(m, G, k, n, 4)
    assert (tm, tk, tn) == (m // G, k, n)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2, G, k, n)) / 8, jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        out, stored, tile_rows = moe.grouped_matmul(lhs, rhs, group_sizes, jnp.int32(1), interpret=True)
        want, given, _ = moe._grouped_xla(lhs, rhs, group_sizes, jnp.int32(1))
    total = sum(sizes)
    assert int(stored) == int(given) == total
    assert int(tile_rows) == _tiles_visited(sizes, tm) * tm
    np.testing.assert_allclose(np.asarray(out[:total]), np.asarray(want[:total]), atol=1e-4)
    # rows past the groups are never written: they keep what interpret mode allocates (NaN)
    assert np.isnan(np.asarray(out[total:])).all()


@pytest.mark.parametrize("field,at", [
    ("tokens", 0), ("routed", 1), ("computed", 2), ("experts_hit", 3), ("layer_calls", 4), ("zero", 5),
    ("slots_streamed", 6), ("slots_allocated", 7), ("combined", 8), ("tile_rows", 9),
])
def test_the_counter_block_keeps_its_older_fields_in_place(field, at):
    assert lm.COUNTER_FIELDS.index(field) == at and len(lm.COUNTER_FIELDS) == 10


@pytest.mark.parametrize("mode", lm.COUNTER_MODES)
def test_fold_counters_carries_the_tile_rows(mode):
    row = np.arange(lm.N_COUNTERS) * 3
    at = lm.COUNTER_MODES.index(mode) * len(lm.COUNTER_FIELDS)
    got = lm.fold_counters(row)
    assert got[f"moe_{mode}_tile_rows"] == row[at + lm.COUNTER_FIELDS.index("tile_rows")]
    assert got[f"moe_{mode}_assignments_computed"] == row[at + 2]
    assert got[f"moe_{mode}_assignments_combined"] == row[at + 8]


# ---- a large batch prefills a row at a time, by shape ----


def test_rowwise_is_decided_by_shape_and_changes_nothing(params, monkeypatch):
    big = LatentMoEConfig()  # the published block: a row of a 4096 bucket expands to 0.5 GiB
    assert not lm.rowwise(big, 1, 4096, jnp.bfloat16) and lm.rowwise(big, 2, 4096, jnp.bfloat16)
    assert not lm.rowwise(big, 2, 2048, jnp.bfloat16) and lm.rowwise(big, 8, 2048, jnp.bfloat16)
    assert not lm.rowwise(CFG, 8, 64, jnp.float32)
    prompts = [prompt_of(n, 40 + n) for n in (20, 9, 14)]
    want = engine_for(params).generate(prompts)
    monkeypatch.setattr(lm, "ROWWISE_BYTES", 1)
    assert lm.rowwise(CFG, 3, 32, jnp.float32)
    assert engine_for(params).generate(prompts) == want


# ---- the combine: rows back into their tokens as a one-hot matmul ----


RUNS = 4  # a pass is so many runs (an expert each) of rows whose tokens rise


def _combine_case(N, C, D, n_valid, spread, seed=7):
    """``C`` rows of which the first ``n_valid`` have a token among the first
    ``spread`` of ``N``, run by run as a pass holds them (the rest none: NaN
    where the grouped kernel wrote nothing), and the scatter-add as oracle."""
    rng = np.random.default_rng(seed)
    token, group = np.full(C, N, np.int32), np.full(C, RUNS, np.int32)
    t, g = rng.integers(0, spread, n_valid), np.sort(rng.integers(0, RUNS, n_valid))
    by = np.lexsort((t, g))
    token[:n_valid], group[:n_valid] = t[by], g[by]
    y = rng.standard_normal((C, D)).astype(np.float32)
    y[n_valid:] = np.nan
    weight = rng.uniform(0.1, 1.0, C).astype(np.float32)
    acc = rng.standard_normal((N, D)).astype(np.float32)
    want = acc.copy()
    np.add.at(want, token[:n_valid], weight[:n_valid, None] * y[:n_valid])
    return tuple(map(jnp.asarray, (acc, y, weight, token, group))), want


@pytest.mark.parametrize("N,C,D,n_valid,spread,blocks", [
    (64, 256, 128, 200, 4, (16, 128, 128)),  # a token with fifty rows; twelve tiles' worth of tokens with none
    (64, 256, 128, 40, 64, (16, 128, 128)),  # an invalid tail five times the valid rows
    (64, 512, 128, 512, 16, (16, 128, 128)),  # one tile's range crosses three steps of R
    (64, 256, 128, 0, 64, (16, 128, 128)),  # no row at all: every tile writes what it held
    (8, 128, 256, 5, 8, (8, 128, 128)),  # the decode shape, through the kernel too
    (32, 256, 3072, 90, 32, (16, 128, 3072)),  # the served widths' column blocks
    (32, 256, 6144, 90, 32, (16, 128, 3072)),
    (32, 256, 7168, 90, 32, (16, 128, 1792)),
], ids=["many-a-token", "long-tail", "range-crosses-steps", "no-rows", "decode-8x128", "D3072", "D6144", "D7168"])
def test_combine_is_the_scatter_add(N, C, D, n_valid, spread, blocks):
    args, want = _combine_case(N, C, D, n_valid, spread)
    for got, hot in (moe._combine_dense(*args[:4]),
                     moe.expert_combine(*args, groups=RUNS, blocks=blocks, interpret=True)):
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
        assert int(hot) == n_valid
    if blocks[2] > 128:  # the rule's column block at this width, under the cell's VMEM
        assert moe.combine_blocks(32768, 32768, D, 2)[2] == blocks[2]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_passes_combine_every_assignment(impl, monkeypatch):
    """``total > C``: the second pass adds into what the first left, through
    the kernel where the shape asks for it (the boundary lowered to force it)."""
    monkeypatch.setattr(moe, "COMBINE_DENSE", 1 << 10)
    rng = np.random.default_rng(3)
    N, D, F, held, top_k = 256, 128, 16, 4, 2
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) / 4, jnp.float32)
         for s in ((1, held, D, F), (1, held, D, F), (1, held, F, D))]
    experts = jnp.stack([jnp.full((N,), 9), 8 + jnp.asarray(rng.integers(0, 4, N))], 1).astype(jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (N, top_k)), jnp.float32)
    C = moe.rows_per_pass(N, top_k, 64, held)
    assert C < N * top_k and moe.combine_blocks(N, C, D, 4) == (256, 128, 128)  # impl "xla" keeps the dot
    with jax.default_matmul_precision("highest"):
        y, counts = moe.held_expert_ffn(x, experts, weights, *w, jnp.int32(0), 8, 64, impl=impl)
        want = sum(jnp.where(experts[:, j:j + 1] == 8 + e, weights[:, j:j + 1], 0.0)
                   * ref._swiglu(x, w[0][0, e], w[1][0, e], w[2][0, e])
                   for e in range(held) for j in range(top_k))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    assert int(counts.combined) == int(counts.routed) == int(counts.computed) == N * top_k


def test_held_expert_ffn_holds_no_scatter():
    """Neither the prefill shape (the kernel) nor the decode shape (the dense
    dot) adds by scatter: the jaxpr of the whole function, sub-jaxprs included."""
    held, D, F = 16, 256, 128
    w = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in ((1, held, D, F), (1, held, D, F), (1, held, F, D))]
    for N, tiled in ((8192, True), (8, False)):
        C = moe.rows_per_pass(N, 8, 256, held)
        assert (moe.combine_blocks(N, C, D, 2) is not None) == tiled
        text = str(jax.make_jaxpr(functools.partial(
            moe.held_expert_ffn, first_held=16, n_experts=256, impl="pallas_interpret"))(
            jax.ShapeDtypeStruct((N, D), jnp.bfloat16), jax.ShapeDtypeStruct((N, 8), jnp.int32),
            jax.ShapeDtypeStruct((N, 8), jnp.float32), *w, jax.ShapeDtypeStruct((), jnp.int32)))
        assert ("expert_combine" in text) == tiled
        # what is left adds into vectors of a few entries (the grouped kernel's
        # metadata: histograms over experts and row tiles), never into rows
        adds = re.findall(r"\w+\[([\d,]*)\] = scatter[-_]add", text)
        few = C // moe.ROW_ALIGN + held  # the smallest row tile's count of tiles, and the groups
        assert adds and all("," not in shape and int(shape) <= few for shape in adds), adds


def test_combine_rule_for_the_served_shapes():
    """The three cells' prefill (batch 8 of a 4096 bucket) takes the kernel at
    these tiles; decode, a verify chunk and a 512-token prefill chunk the dot."""
    served = {"dots": (8, 256, 7168), "longcat": (12, 768, 6144), "laguna": (10, 256, 3072)}
    rows = {k: moe.rows_per_pass(32768, top_k, E, 16) for k, (top_k, E, _) in served.items()}
    assert rows == {"dots": 32768, "longcat": 16384, "laguna": 40960}
    assert {k: moe.combine_blocks(32768, rows[k], D, 2) for k, (_, _, D) in served.items()} == {
        "dots": (256, 128, 1792), "longcat": (256, 128, 3072), "laguna": (256, 128, 3072)}
    for top_k, E, D in served.values():
        for N in (8, 16, 512):
            assert moe.combine_blocks(N, moe.rows_per_pass(N, top_k, E, 16), D, 2) is None
    assert moe.combine_blocks(1024, 512, 7168, 2) is None and moe.combine_blocks(1024, 1024, 7168, 2) is not None


# ---- the benchmark's readers of the held experts' and the router's prefill time ----


@pytest.mark.parametrize("case,by,rows,want", [
    ("a_slice_with_the_scope", {"prefill": {"experts": 0.24, "router": 0.12}, "decode": {"experts": 9.0, "router": 7.0}},
     24.0, {"experts": 10.0, "router": 5.0}),
    ("a_program_without_the_scope", {"prefill": {"dense": 1.0}}, 24.0, None),
    ("no_prefill_row_in_the_slice", {"prefill": {"experts": 0.24, "router": 0.12}}, 0.0, None),
    ("no_trace", None, 24.0, None),
])
@pytest.mark.parametrize("metric,scope", [("held_experts_prefill_ms_per_row", "experts"),
                                          ("router_prefill_ms_per_row", "router")])
def test_reader_of_a_fine_scope_s_prefill_ms_per_row(metric, scope, case, by, rows, want):
    """``benchmark/layer_metrics/held_experts_prefill_ms_per_row.py`` and
    ``router_prefill_ms_per_row.py`` divide ``prefill/.../mlp/experts`` and
    ``prefill/.../mlp/router`` by the slice's prefill rows, return None (and
    do not raise) where either is missing, and ``BENCHMARK.json`` lists each
    for the six sparse-expert cells (PR 55: the latent experts' cell)."""
    import importlib.util
    import json
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        spec = importlib.util.spec_from_file_location(
            metric + "_reader", os.path.join(repo, f"benchmark/layer_metrics/{metric}.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(repo)
    ctx = {"trace": None} if by is None else {"trace": {}, "fine_scopes": by, "phases": {"prefill_rows": rows, "steps": {}}}
    got = reader.read(ctx)
    assert got is None if want is None else got == pytest.approx(want[scope])
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[metric]
    assert entry == {"name": metric, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "model step", "moves": "latency_p50_ms",
                     "workloads": ["dots-vlm1-ep16.closed8", "longcat-flash-ep32.closed8", "laguna-s-ep16.closed8",
                                   "lfm2-24b-a2b-pp4.solo", "kimi-linear-ep16.solo", "nemotron-3-super-ep4.solo"]}

"""The gated short-convolution, sparse-expert family (models/conv_moe.py over
ops/ssm.py causal_conv, ops/moe.py, models/latent_moe.py's sparse FFN and
models/llama.py's attention seam) against its plain reference
(tests/lfm2_moe_reference.py), at a toy size on the CPU with seeded weights
under the fp32 policy: hidden 64, two dense conv layers, then two periods of
(attention, conv, conv, conv); 4 query heads over 2 KV heads of 16; 16 experts
of 32, all held, top 4.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (a running softmax against one softmax over a masked row; the
grouped experts against a loop over every expert), so logits of magnitude ~5
agree to a few 1e-5; ``ATOL`` is 5e-4. The faults the comparison must see are
far above it: the kept inputs lost at the hand-over from prefill to decode,
pads run through the convolution, the taps reversed, q and k not normed (or
normed behind the rotation), weights from score plus bias, the chosen scores
not normalised, an activation behind the taps each move a logit by 5e-2 or
more. Router inputs are float32 on both sides: no expert is swapped at the
top-4's edge here (on the chip, under bf16, some are: PERF.md section 6).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

import lfm2_moe_reference as ref
from rag_llm_k8s_tpu.core.config import (
    ConvMoEConfig,
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    MeshConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.models import conv_moe as cm
from rag_llm_k8s_tpu.models import families
from rag_llm_k8s_tpu.ops import moe, ssm

FP32 = DTypePolicy.fp32()
ATOL = 5e-4
V = 48
CFG = ConvMoEConfig.tiny(vocab_size=V)
UNTIED = dataclasses.replace(CFG, tie_word_embeddings=False)
# two dense layers, one whole period and half of another: what the published depth leaves behind its loop
TAILED = dataclasses.replace(CFG, layer_types=CFG.layer_types[:8])
NEW = 6
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)


def seeded_params(cfg, seed=0):
    """Weights with statistics that make every part matter: kernels of std
    1/sqrt(fan_in), norm scales near 1 (1.2 on q and k), taps of std
    1/sqrt(3), a selection bias of std 0.1, a unit-std embedding."""
    shapes = traverse_util.flatten_dict(
        jax.eval_shape(lambda: cm.init_conv_moe_params(jax.random.PRNGKey(0), cfg, FP32)))
    rng = np.random.default_rng(seed)
    out = {}
    for path, leaf in sorted(shapes.items()):
        if any("norm" in part for part in path):
            value = (1.2 if path[-2] in ("q_norm", "k_norm") else 1.0) + 0.1 * rng.standard_normal(leaf.shape)
        elif path[-1] == "router_bias":
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif path[-1] == "embedding":
            value = rng.standard_normal(leaf.shape)
        else:  # the taps ``[3, hidden]`` too: std 1/sqrt(3)
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        out[path] = jnp.asarray(value, jnp.float32)
    return traverse_util.unflatten_dict(out)


CONFIGS = {"tied": CFG, "untied": UNTIED, "tailed": TAILED}
PARAMS = {name: seeded_params(cfg) for name, cfg in CONFIGS.items()}


@pytest.fixture(scope="module")
def params():
    return PARAMS["tied"]


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, V, size=n)]


_REF, _FORWARD = {}, {}


def forward(tokens, which="tied", **faults):
    """The reference's logits of ``tokens``, computed at a padded length (a
    pad behind the sequence changes nothing in front of it: every operator is
    causal), so that one compiled program serves every length up to it."""
    n = -(-len(tokens) // 64) * 64
    key = (n, which, tuple(sorted((k, str(v)) for k, v in faults.items())))
    if key not in _FORWARD:
        cfg = CONFIGS[which]
        _FORWARD[key] = jax.jit(lambda params, ids: ref.forward(params, cfg, ids, **faults))
    ids = jnp.asarray(list(tokens) + [0] * (n - len(tokens)), jnp.int32)
    return np.asarray(_FORWARD[key](PARAMS[which], ids))[:len(tokens)]


def reference(tokens, which="tied"):
    key = (tuple(tokens), which)
    if key not in _REF:
        _REF[key] = forward(tokens, which)
    return _REF[key]


def greedy_reference(prompt, n):
    tokens = list(prompt)
    for _ in range(n):
        tokens.append(int(np.argmax(forward(tokens)[-1])))
    return tokens[len(prompt):]


def through_the_cache(rows, S, lengths, impl="xla", which="tied"):
    """Logits of ``rows`` (left-padded to ``S``, of which ``lengths`` are
    prefilled at once and the rest decoded a token at a time), and the cache."""
    cfg, params = CONFIGS[which], PARAMS[which]
    B, lens = len(rows), np.asarray(lengths)
    T = S + max(len(r) - n for r, n in zip(rows, lens))
    if impl != "xla":  # the kernels take planes of whole 128-slot tiles
        T = -(-T // 128) * 128
    model = cm.ConvMoEModel(cfg, FP32, attn_impl=impl)
    call = jax.jit(lambda *a: model.apply({"params": params}, *a))
    cache = cm.make_conv_cache(cfg, B, T, jnp.float32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    padded = np.zeros((B, S), np.int32)
    for b, row in enumerate(rows):
        padded[b, S - lens[b]:] = row[:lens[b]]
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    logits, cache = call(jnp.asarray(padded), positions, cache, kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0))
    out = [[np.asarray(logits[b, S - lens[b] + t]) for t in range(lens[b])] for b in range(B)]
    after_prefill = cache
    for t in range(min(len(r) - n for r, n in zip(rows, lens))):
        tok = jnp.asarray([[r[n + t]] for r, n in zip(rows, lens)], jnp.int32)
        logits, cache = call(tok, jnp.asarray(lens + t)[:, None].astype(jnp.int32), cache, kv_start,
                             jnp.full((B,), S + t + 1, jnp.int32), jnp.int32(S + t))
        for b in range(B):
            out[b].append(np.asarray(logits[b, 0]))
    return [np.stack(o) for o in out], cache, after_prefill


# ---- (a) prefill, then decode through both kinds of state ----


@pytest.mark.parametrize("impl,S,prompt_len,which", [
    ("xla", 40, 33, "tied"), ("xla", 40, 33, "untied"), ("xla", 40, 40, "tied"), ("xla", 40, 40, "untied"),
    ("pallas_interpret", 128, 101, "tied"), ("pallas_interpret", 128, 101, "untied"),
    ("xla", 40, 33, "tailed"),
])
def test_prefill_then_decode_matches_reference_at_every_position(impl, S, prompt_len, which):
    tokens = prompt_of(prompt_len + 12, prompt_len)
    (got,), cache, _ = through_the_cache([tokens], S, [prompt_len], impl=impl, which=which)
    np.testing.assert_allclose(got, reference(tokens, which), atol=ATOL)
    cfg = CONFIGS[which]
    counted = cm.fold_counters(np.asarray(cache.counters))
    assert counted["prefill_tokens_computed"] == counted["prefill_tokens_bucketed"] == S
    sparse = cfg.num_moe_layers
    assert counted["moe_prefill_assignments_held"] == counted["moe_prefill_assignments_computed"] == S * 4 * sparse
    # a batch-1 step streams its sparsity and nothing more: 4 experts a layer-step, every assignment computed
    assert counted["moe_decode_layer_steps"] == 12 * sparse and counted["moe_decode_experts_hit"] == 4 * 12 * sparse
    assert counted["moe_decode_assignments_computed"] == counted["moe_decode_assignments_combined"] == 4 * 12 * sparse
    # a step at a head of 16 lanes takes the chunk form: every slot of the plane is fetched
    kernel = impl != "xla"
    assert counted["decode_slots_streamed"] == counted["decode_slots_allocated"] == (12 * cache.k.shape[3] if kernel else 0)
    # two kinds of state in one cache: planes by position for the attention layers, two kept inputs for the rest
    Na, Nc = cfg.num_attention_layers, cfg.num_conv_layers
    assert cache.k.shape == (Na, 1, 2, cache.k.shape[3], 16) and cache.conv.shape == (Nc, 1, 2, 64)
    assert (Na, Nc) == ((2, 6) if which == "tailed" else (2, 8)) and cache.conv_steps is None


def test_the_published_depth_is_two_dense_nine_periods_and_a_half():
    published = ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 9 + ("full_attention", "conv")
    cfg = ConvMoEConfig(layer_types=published)
    assert (cfg.num_layers, cfg.num_lead, cfg.period, cfg.num_periods, cfg.num_tail) == (40, 2, 4, 9, 2)
    assert (cfg.num_conv_layers, cfg.num_attention_layers, cfg.head_dim) == (30, 10, 64)
    assert (TAILED.period, TAILED.num_periods, TAILED.num_tail) == (4, 1, 2)
    stage = ConvMoEConfig()  # layers 0-9: the benchmark's cut
    assert (stage.num_layers, stage.num_periods, stage.num_tail, stage.experts_held, stage.first_held) == (10, 2, 0, 64, 0)
    flops, weight_bytes, kv_bytes = stage.roofline_terms()
    # a token's matmuls count 4 experts a sparse layer, and a step's bytes the experts it hits, never the 64 held
    D, F = 2048, 1536
    body = 8 * 4 * D * D + 2 * (2 * D * 2048 + 2 * D * 512) + 2 * 3 * D * 11776 + 8 * (64 * D + 4 * 3 * D * F)
    assert flops == 2.0 * (body + 65536 * D)
    assert weight_bytes == flops + 8 * 2 * 2 * 2 * D and kv_bytes == 2.0 * 2 * 2 * 8 * 64
    assert weight_bytes < 0.2 * 2 * 5.4e9


def test_two_rows_of_one_bucket_with_different_left_padding():
    """Each row equals the reference, and a shorter row's kept inputs are
    what it keeps alone in a bucket it fills."""
    rows = [prompt_of(44, 2), prompt_of(29, 3), prompt_of(5, 4)]
    lengths = [40, 25, 1]  # the last row is shorter than the convolution
    got, _, cache = through_the_cache(rows, 40, lengths)
    for row, g in zip(rows, got):
        np.testing.assert_allclose(g, reference(row)[:len(g)], atol=ATOL)
    _, _, alone = through_the_cache([rows[1][:25 + 4]], 25, [25])
    np.testing.assert_allclose(np.asarray(cache.conv[:, 1]), np.asarray(alone.conv[:, 0]), atol=ATOL)
    assert not np.asarray(cache.conv[:, 2, 0]).any()  # one real token: the older kept input is a pad's, exactly zero


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_pads_leave_the_kept_positions_bit_for_bit_zero(impl):
    """A row of nothing but pads (the batch ladder's filler; a prompt chunk in
    front of a long prompt's first token) leaves both kept inputs of every
    conv layer exactly zero, and a padded row's kept inputs are bit for bit
    the row's alone from the same state."""
    S = 128
    tokens = prompt_of(70, 6)
    model = cm.ConvMoEModel(CFG, FP32, attn_impl=impl)
    cache = cm.make_conv_cache(CFG, 2, 2 * S, jnp.float32)
    padded = np.zeros((2, S), np.int32)
    padded[0, S - 70:] = tokens
    padded[1] = prompt_of(S, 7)  # tokens that are all in front of kv_start: pads
    kv_start = jnp.asarray([S - 70, S], jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    _, cache = model.apply({"params": PARAMS["tied"]}, jnp.asarray(padded), positions, cache, kv_start,
                           jnp.full((2,), S, jnp.int32), jnp.int32(0))
    assert not np.asarray(cache.conv[:, 1]).any()
    z = jax.random.normal(jax.random.PRNGKey(0), (1, S, 64))
    w, history = jax.random.normal(jax.random.PRNGKey(1), (3, 64)), jnp.zeros((1, 2, 64))
    masked = jnp.where(jnp.arange(S)[None, :, None] >= 58, z, 0)
    out, run = ssm.causal_conv(masked, history, w, None, activate=False)
    out1, run1 = ssm.causal_conv(z[:, 58:], history, w, None, activate=False)
    np.testing.assert_array_equal(np.asarray(out[:, 58:]), np.asarray(out1))
    np.testing.assert_array_equal(np.asarray(run[:, -2:]), np.asarray(run1[:, -2:]))
    # the activation is the caller's to drop, and the state-space family's call keeps it
    acted, _ = ssm.causal_conv(z, history, w, jnp.zeros((64,)))
    plain, _ = ssm.causal_conv(z, history, w, None, activate=False)
    np.testing.assert_allclose(np.asarray(acted), np.asarray(jax.nn.silu(plain)), atol=1e-6)


# ---- (b) a chunk over the cache and the verify step, (c) the router's rule and the share,
# (e) every one-shot program of the engine: tests/test_lfm2_moe_programs.py ----


# ---- (d) the faults the comparison must see ----


@pytest.mark.parametrize("fault", [
    dict(drop_conv_at=40), dict(pads=24), dict(taps_reversed=True), dict(qk_norm=False), dict(norm_after_rope=True),
    dict(bias_in_weights=True), dict(normed=False), dict(conv_silu=True), dict(eps=1e-1),
    dict(fp8="experts"), dict(fp8="all"),
], ids=lambda f: "-".join(map(str, next(iter(f.items())))) if "fp8" in f else next(iter(f)))
def test_a_fault_fails_the_tolerance(params, fault):
    tokens = prompt_of(52, 1)
    sound = reference(tokens)
    bad = forward(tokens, **fault)
    assert np.abs(bad - sound).max() > 100 * ATOL
    (got,), _, _ = through_the_cache([tokens], 40, [40])
    assert np.abs(got - bad).max() > 100 * ATOL  # and the program is on the sound side


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
    with pytest.raises(NotImplementedError, match="gated-convolution sparse-expert family") as e:
        families.refuse_unsupported(CFG, ec, None, engine=engine)
    assert names in str(e.value)


def test_tensor_parallel_is_refused_by_name():
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    ec = EngineConfig(prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(NotImplementedError, match="tp=2"):
        families.refuse_unsupported(CFG, ec, mesh)
    family = families.of(CFG)
    assert family.commit is cm.commit and family.verify_span is None
    assert "name map" in family.checkpoint_loader_refusal
    assert family.counter_names == cm.COUNTER_NAMES and family.counters_width == cm.N_COUNTERS
    assert families.of(LlamaConfig.tiny()).commit is None  # a frontier does the job there


@pytest.mark.parametrize("bad,says", [
    (dict(layer_types=("conv", "window")), "layer_types"), (dict(conv_bias=True), "no bias"),
    (dict(use_expert_bias=False), "selection bias"), (dict(conv_L_cache=1), "conv_L_cache"),
    (dict(ep_size=3), "ep_size"), (dict(num_dense_layers=11), "num_dense_layers"),
    (dict(num_attention_heads=3), "heads"), (dict(num_experts_per_tok=17), "num_experts_per_tok"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_configuration_refuses_what_the_block_does_not_run(bad, says):
    with pytest.raises(ValueError, match=says):
        ConvMoEConfig.tiny(**bad)

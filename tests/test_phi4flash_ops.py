"""What the decoder-hybrid-decoder family adds beneath its model: the scan's
un-gated output (ops/ssm.py, kernel and XLA form), differential attention as
grouped-query attention over zero-padded pair heads (models/cross_decoder.py)
against four plain softmaxes, the window's edge, the configuration's derived
layer kinds and sizes, and the counters' arithmetic."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import phi4flash_reference as ref
from rag_llm_k8s_tpu.core.config import CrossDecoderConfig, DTypePolicy
from rag_llm_k8s_tpu.models import cross_decoder as cd, hybrid_ssm as hs
from rag_llm_k8s_tpu.models.llama import attend
from rag_llm_k8s_tpu.ops import ssm
from rag_llm_k8s_tpu.ops.attention import attention_xla, decode_slots_streamed, gqa_decode_step

HERE = os.path.dirname(os.path.abspath(__file__))


def scan_inputs(R, S, Di, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    return (normal(ks[0], (R, S, Di)), normal(ks[1], (R, S, Di)) - 2, normal(ks[2], (R, S, Di)),
            -jnp.exp(normal(ks[3], (N, Di))), normal(ks[4], (R, S, N)), normal(ks[5], (R, S, N)),
            normal(ks[6], (Di,)), 0.1 * normal(ks[7], (Di,)), normal(ks[8], (R, N, Di)))


@pytest.mark.parametrize("R,S,Di,N,start", [(2, 128, 128, 16, (0, 37)), (2, 384, 1152, 4, (128, 255))])
def test_the_scan_hands_back_its_output_in_front_of_the_gate(R, S, Di, N, start):
    """``ungated``: ``m`` beside ``y``, ``y = m * silu(z)``, the kernel's
    (interpret mode) the XLA form's; and the gated call is what it was."""
    args = scan_inputs(R, S, Di, N)
    first = jnp.asarray(start, jnp.int32)
    y0, h0 = ssm.selective_scan_xla(*args, first)
    y, h, m = ssm.selective_scan_xla(*args, first, ungated=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h0))
    z = args[2]
    np.testing.assert_allclose(np.asarray(m * z * jax.nn.sigmoid(z)), np.asarray(y), atol=1e-5)
    ky, kh, km = ssm.selective_scan_pallas(*args, first, interpret=True, ungated=True)
    ky0, kh0 = ssm.selective_scan_pallas(*args, first, interpret=True)
    np.testing.assert_array_equal(np.asarray(ky), np.asarray(ky0))
    np.testing.assert_array_equal(np.asarray(kh), np.asarray(kh0))
    live = np.arange(S)[None, :, None] >= np.asarray(start)[:, None, None]
    np.testing.assert_allclose(np.where(live, km, 0), np.where(live, m, 0), atol=2e-5)
    np.testing.assert_allclose(np.where(live, ky, 0), np.where(live, y, 0), atol=2e-5)
    # every position's state AND the memory, as the verify step of the memory's layer asks
    y2, h2, steps, m2 = ssm.selective_scan_xla(*args, first, keep_steps=True, ungated=True)
    assert steps.shape == (R, S, N, Di)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(m))
    out = ssm.selective_scan(*args, first, impl="pallas_interpret", ungated=True)
    assert len(out) == 3 and len(ssm.selective_scan(*args, first, impl="xla")) == 2


def plain_differential(q, k, v, lam, lam_init, g, eps, window=None):
    """Four plain softmaxes a (query pair, key pair): numpy, float64."""
    S, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    out = np.zeros((S, H // 2, 2 * hd))
    mask = np.tril(np.ones((S, S), bool))
    if window:
        mask &= ~np.tril(np.ones((S, S), bool), -window)
    for p in range(H // 2):
        r = p // G
        vv = np.concatenate([v[:, 2 * r], v[:, 2 * r + 1]], axis=-1)
        a = []
        for j in (0, 1):
            s = q[:, 2 * p + j] @ k[:, 2 * r + j].T / math.sqrt(hd)
            s = np.where(mask, s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            a.append(w / w.sum(-1, keepdims=True) @ vv)
        d = a[0] - lam * a[1]
        out[:, p] = d / np.sqrt((d * d).mean(-1, keepdims=True) + eps) * g * (1 - lam_init)
    return out.reshape(S, -1)


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window8"])
def test_zero_padded_pair_heads_are_four_plain_softmaxes(window):
    """A pair of key heads as one head of twice the width, a query head
    zero-padded on the other half, grouped-query attention at scale
    ``(2 hd)^-1/2`` with the queries carrying ``sqrt 2``, then the epilogue:
    the published rule with four softmaxes a pair written out."""
    rng = np.random.default_rng(0)
    S, H, K, hd = 24, 8, 4, 16
    q, k, v = (rng.standard_normal((S, n, hd)) for n in (H, K, K))
    g = 1 + 0.1 * rng.standard_normal(2 * hd)
    lam, lam_init, eps = 0.37, 0.55, 1e-5
    want = plain_differential(q, k, v, lam, lam_init, g, eps, window)
    qp = cd.pad_query_pairs(jnp.asarray(q * math.sqrt(2.0), jnp.float32)[None])
    assert qp.shape == (1, S, H, 2 * hd)
    assert not np.asarray(qp[0, :, 0, hd:]).any() and not np.asarray(qp[0, :, 1, :hd]).any()
    kp, vp = (jnp.asarray(a, jnp.float32).reshape(1, S, K // 2, 2 * hd) for a in (k, v))
    zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), S, jnp.int32)
    o = attend(qp, kp, vp, zero, full, 0, mode="prefill", impl="xla", window=window)
    got = cd.differential(o, lam, lam_init, jnp.asarray(g, jnp.float32), eps, jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
    # the reference's own form is the same rule (it pads nothing)
    p = {"lambda_q1": jnp.zeros(hd), "lambda_k1": jnp.zeros(hd), "lambda_q2": jnp.zeros(hd),
         "lambda_k2": jnp.zeros(hd), "subln": jnp.asarray(g, jnp.float32)}
    depth = -math.log((0.8 - lam_init) / 0.6) / 0.3  # the depth whose lambda_init this is; lambda = lam_init here
    mine = ref._differential(*(jnp.asarray(a, jnp.float32) for a in (q, k, v)), p, 0, depth, window, "", eps)
    np.testing.assert_allclose(np.asarray(mine), plain_differential(q, k, v, lam_init, lam_init, g, eps, window),
                               atol=2e-5)


def test_the_window_ends_at_exactly_512_keys():
    """A window layer's query at ``t`` sees keys ``t - 511 .. t``: moving key
    ``t - 512`` changes nothing at ``t``, moving key ``t - 511`` does."""
    rng = np.random.default_rng(1)
    S, W = 640, 512
    q, k, v = (jnp.asarray(rng.standard_normal((1, S, 2, 8)), jnp.float32) for _ in range(3))
    base = np.asarray(attention_xla(q, k, v, causal=True, window=W))
    t = 600
    for back, moved in ((W, False), (W - 1, True)):
        k2 = k.at[0, t - back].add(3.0)
        out = np.asarray(attention_xla(q, k2, v, causal=True, window=W))
        assert (np.abs(out[0, t] - base[0, t]).max() > 1e-6) == moved, back


def test_the_layer_kinds_follow_from_the_depth():
    c = CrossDecoderConfig()
    kinds = [c.kind_of(i) for i in range(32)]
    assert [i for i, k in enumerate(kinds) if k == "mamba"] == list(range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "window"] == list(range(1, 16, 2))
    assert [i for i, k in enumerate(kinds) if k == "full"] == [17] == [c.shared_layer]
    assert [i for i, k in enumerate(kinds) if k == "gmu"] == list(range(18, 31, 2))
    assert [i for i, k in enumerate(kinds) if k == "cross"] == list(range(19, 32, 2))
    assert kinds == [ref.kind_of(i, 32) for i in range(32)] and c.memory_layer == 16
    assert (c.num_state_layers, c.num_window_layers, c.num_plane_layers, c.num_cross_layers) == (9, 8, 9, 7)
    assert (c.head_dim, c.num_pair_heads, c.pair_dim, c.d_inner) == (64, 10, 128, 5120)
    assert float(cd.lambda_init(0)) == pytest.approx(0.2) and float(cd.lambda_init(17)) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))


def test_the_published_sizes_are_the_published_parameter_count():
    """3852 M tied ("3.8B"), 4364 M with the served untied head; 14 of 32
    layers own no state; a row's cache at 12.4 k positions."""
    c = CrossDecoderConfig(tie_word_embeddings=False)
    shapes = jax.eval_shape(lambda: cd.init_cross_decoder_params(jax.random.PRNGKey(0), c, DTypePolicy()))
    n = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert round(n / 1e6) == 4365 and round((n - c.hidden_size * c.vocab_size) / 1e6) == 3853
    assert shapes["layers_w_gate"].shape == (32, 2560, 10240) and shapes["ssm_in_proj"].shape == (9, 2560, 10240)
    assert shapes["attn_wk"].shape == (9, 2560, 1280) and shapes["cross_wq"].shape == (7, 2560, 2560)
    assert shapes["gmu_in_proj"].shape == (7, 2560, 5120) and "cross_wk" not in shapes
    assert all(shapes[f"attn_lambda_{x}"].dtype == jnp.float32 for x in ("q1", "k1", "q2", "k2"))
    cache = jax.eval_shape(lambda: cd.make_cross_cache(c, 1, 12416))
    assert cache.k.shape == (9, 1, 10, 12416, 128) and cache.ssm.shape == (9, 1, 16, 5120)
    assert cache.conv.shape == (9, 1, 3, 5120) and cache.counters.shape == (15,)
    assert 2 * cache.k.size * 2 // 9 == 63569920  # ONE plane of 64 MB grows with the context; eight more are windows
    flops, weight_bytes, kv_bytes = c.roofline_terms()
    # a token's matmuls: every matrix but the embedding (looked up, not multiplied); the vectors are 0.03% more
    assert 0 < (2.0 * n - 2.0 * c.hidden_size * c.vocab_size) / flops - 1 < 5e-4
    assert kv_bytes == 2.0 * 2 * 10 * 128 * 8  # a position's keys and values, read by the full layer and 7 cross layers
    assert weight_bytes > flops  # the states and the window layers' 512 slots ride the weights' bytes


def test_the_counters_arithmetic():
    assert cd.COUNTER_NAMES[:8] == hs.COUNTER_NAMES  # ``commit`` counts at the hybrid family's places
    assert cd.COUNTER_NAMES[8:12] == ("decode_slots_streamed_window", "decode_slots_allocated_window",
                                      "prefill_window_pairs_multiplied", "prefill_window_pairs_live")
    c = CrossDecoderConfig.tiny(vocab_size=32, num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
                                hidden_size=512, sliding_window=128)  # pair heads of 128: what the decode kernel tiles
    params = jax.eval_shape(lambda: cd.init_cross_decoder_params(jax.random.PRNGKey(0), c, DTypePolicy.fp32()))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
    model = cd.CrossDecoderModel(c, DTypePolicy.fp32(), attn_impl="pallas_interpret")
    B, T = 2, 512
    cache = cd.make_cross_cache(c, B, T, jnp.float32)
    kv_start, kv_len = jnp.asarray([0, 100], jnp.int32), jnp.asarray([300, 300], jnp.int32)
    _, cache = model.apply({"params": params}, jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, 1), jnp.int32), cache,
                           kv_start, kv_len, jnp.int32(299))
    counted = cd.fold_counters(np.asarray(cache.counters))
    step = gqa_decode_step(T, c.num_pair_heads, c.num_heads // c.num_pair_heads, c.pair_dim, jnp.float32)
    full = int(decode_slots_streamed(kv_start, kv_len, T, step))
    window = int(decode_slots_streamed(jnp.maximum(kv_start, kv_len - 128), kv_len, T, step))
    assert counted["decode_slots_streamed"] == full and counted["decode_slots_allocated"] == B * T
    assert counted["shared_plane_slots_streamed"] == (1 + c.num_cross_layers) * full  # the full layer and every cross layer
    assert counted["decode_slots_streamed_window"] == c.num_window_layers * window
    assert counted["decode_slots_allocated_window"] == c.num_window_layers * B * T
    assert counted["ssm_state_updates"] == B * c.num_state_layers
    assert counted["cross_positions_fed"] == 0 == counted["prefill_window_pairs_live"]  # a step is no prompt call


def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(HERE, "phi4flash_reference.py"), "rb") as a, \
            open(os.path.join(HERE, "..", "benchmark", "references", "phi4flash.py"), "rb") as b:
        assert a.read() == b.read()

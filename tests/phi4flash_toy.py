"""What the decoder-hybrid-decoder family's tier-1 files share: the toy
configuration (hidden 64, 12 layers: three (Mamba, window 8) pairs, the
memory's layer 6 and the full layer 7, two (memory unit, cross) pairs; 4 query
heads over 2 KV heads of 16, so two query pairs over one key pair; d_inner
128, 16 states, dt rank 4), seeded weights under the fp32 policy in which
every leaf matters (norm biases, projection biases and lambdas included), the
plain reference's logits (``tests/phi4flash_reference.py``) and the walk of a
batch through the cache.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (a running softmax against one softmax over a masked row, a
zero-padded 32-wide score against a 16-wide one, the recurrence the same chain
either way), so logits of magnitude ~1-3 agree to a few 1e-5 (3e-5 the largest
seen); ``ATOL`` is 2e-4. The faults the comparison must see (``CONTROLS``) each
move a logit by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import phi4flash_reference as ref
from rag_llm_k8s_tpu.core.config import CrossDecoderConfig, DTypePolicy
from rag_llm_k8s_tpu.models import cross_decoder as cd

FP32 = DTypePolicy.fp32()
ATOL = 2e-4
V = 48
CFG = CrossDecoderConfig.tiny(vocab_size=V)
UNTIED = dataclasses.replace(CFG, tie_word_embeddings=False)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads."""
    return dict(num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
                num_key_value_heads=cfg.num_key_value_heads, sliding_window=cfg.sliding_window,
                layer_norm_eps=cfg.layer_norm_eps)


def seeded_params(cfg, seed=0):
    shapes = jax.eval_shape(lambda: cd.init_cross_decoder_params(jax.random.PRNGKey(0), cfg, FP32))
    rng = np.random.default_rng(seed)
    out = {}
    for name, leaf in shapes.items():
        last = name.rsplit("_", 1)[-1]
        if name == "ssm_A_log":
            value = np.broadcast_to(np.log(np.arange(1, leaf.shape[1] + 1))[None, :, None], leaf.shape)
        elif name == "ssm_dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-2), 0.0, leaf.shape))
            value = dt + np.log(-np.expm1(-dt))
        elif "_lambda_" in name:
            value = 0.3 * rng.standard_normal(leaf.shape)
        elif last in ("b", "bq", "bk", "bv", "bo"):  # the norms' biases, the convolution's, the projections'
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif "norm" in name or last in ("subln", "D"):
            value = 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        elif name == "embedding":
            value = rng.standard_normal(leaf.shape)
        else:
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        out[name] = jnp.asarray(value, jnp.float32)
    return out


PARAMS = {True: seeded_params(CFG), False: seeded_params(UNTIED)}


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, V, size=n)]


_FORWARD, _REF = {}, {}


def forward(tokens, tied=True, control="", pads=0, handover=ref.NEVER):
    """The reference's logits of ``tokens`` at a padded length (a pad behind
    the sequence changes nothing in front of it: every mixer is causal), so
    that one compiled program serves every length up to it."""
    n = -(-len(tokens) // 64) * 64
    cfg = CFG if tied else UNTIED
    key = (n, tied, control, pads, handover)
    if key not in _FORWARD:
        _FORWARD[key] = jax.jit(lambda params, ids: ref.forward(
            params, sizes_of(cfg), ids, control=control, pads=pads, handover=handover))
    ids = jnp.asarray(list(tokens) + [0] * (n - len(tokens)), jnp.int32)
    with jax.default_matmul_precision("highest"):
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


_CALLS = {}


def calls(tied=True, impl="xla", chunked=False, keep_steps=False):
    """``(call, first)`` of one model, jitted ONCE a (configuration, form):
    every position's logits, and the engine's prompt call
    (``last_logit_only``: the cross-decoder at the last position only)."""
    key = (tied, impl, chunked, keep_steps)
    if key not in _CALLS:
        cfg, params = (CFG, PARAMS[True]) if tied else (UNTIED, PARAMS[False])
        model = cd.CrossDecoderModel(cfg, FP32, attn_impl=impl, chunked=chunked, keep_steps=keep_steps)
        _CALLS[key] = (jax.jit(lambda *a: model.apply({"params": params}, *a)),
                       jax.jit(lambda *a: model.apply({"params": params}, *a, last_logit_only=True)))
    return _CALLS[key]


def cache_length(n, impl):
    """Slots for ``n`` positions: the kernels tile a cache in 128s."""
    return n if impl == "xla" else -(-n // 128) * 128


def through_the_cache(rows, S, lengths, impl="xla", tied=True, fresh=False):
    """Logits of ``rows`` (left-padded to ``S``, of which ``lengths`` are
    prefilled at once and the rest decoded a token at a time), and the cache.
    ``fresh``: the prompt call as the engine makes it, its one position's
    logits standing for the prompt's."""
    B, lens = len(rows), np.asarray(lengths)
    call, first = calls(tied, impl)
    cache = cd.make_cross_cache(CFG, B, cache_length(S + max(len(r) - n for r, n in zip(rows, lens)), impl),
                                jnp.float32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    padded = np.zeros((B, S), np.int32)
    for b, row in enumerate(rows):
        padded[b, S - lens[b]:] = row[:lens[b]]
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    logits, cache = (first if fresh else call)(
        jnp.asarray(padded), positions, cache, kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0))
    if fresh:
        out = [[np.asarray(logits[b, 0])] for b in range(B)]
    else:
        out = [[np.asarray(logits[b, S - lens[b] + t]) for t in range(lens[b])] for b in range(B)]
    for t in range(min(len(r) - n for r, n in zip(rows, lens))):
        tok = jnp.asarray([[r[n + t]] for r, n in zip(rows, lens)], jnp.int32)
        logits, cache = call(tok, jnp.asarray(lens + t)[:, None].astype(jnp.int32), cache, kv_start,
                             jnp.full((B,), S + t + 1, jnp.int32), jnp.int32(S + t))
        for b in range(B):
            out[b].append(np.asarray(logits[b, 0]))
    return [np.stack(o) for o in out], cache


def chunk_call(tokens, start, n, S=32, keep_steps=False, impl="xla"):
    """``tokens[:start]`` prefilled (left-padded to ``S``, the engine's fresh
    call), then ``n`` positions from ``start`` in ONE chunk call; returns its
    ``(logits, cache)`` and ``kv_start``."""
    cache = cd.make_cross_cache(CFG, 1, cache_length(S + max(64, n), impl), jnp.float32)
    pad = S - start
    padded = np.zeros((1, S), np.int32)
    padded[0, pad:] = tokens[:start]
    ks = jnp.asarray([pad], jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None] - pad, 0)
    _, cache = calls(impl=impl)[1](jnp.asarray(padded), positions, cache, ks, jnp.full((1,), S, jnp.int32),
                                   jnp.int32(0))
    fed = jnp.asarray([tokens[start:start + n]], jnp.int32)
    return calls(impl=impl, chunked=True, keep_steps=keep_steps)[0](
        fed, (start + jnp.arange(n))[None], cache, ks, jnp.full((1,), S + n, jnp.int32), jnp.int32(S)), ks

"""What the state-space-duality latent-expert family's tier-1 files share:
the toy configuration (``SSDMoEConfig.tiny``: hidden 128, seven layers
``MEM*EME``, 8 Mamba-2 heads of 16 in 2 groups at state 16 and chunks of 8,
4 query heads over 2 KV heads of 16, 16 experts top-3 in a latent of 64, of
which rank 1 of 2 holds 8), seeded weights under the fp32 policy in which every
leaf matters (the convolution's bias, ``D``, the correction bias and the norm
scales included), the plain reference's logits
(``tests/nemotron_h_reference.py``) and the walk of a batch through the cache.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (the chunked matmul form against a position at a time, a
running softmax against one softmax over a masked row, rows gathered by expert
against a loop over experts), so logits of magnitude ~1-3 agree to a few 1e-5;
``ATOL`` is 2e-4. The faults the comparison must see (``CONTROLS``) each move a
logit by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import nemotron_h_reference as ref
from rag_llm_k8s_tpu.core.config import DTypePolicy, SSDMoEConfig
from rag_llm_k8s_tpu.models import ssd_moe as sm

FP32 = DTypePolicy.fp32()
ATOL = 2e-4
V = 48
CFG = SSDMoEConfig.tiny(vocab_size=V)
M, CHUNK = CFG.num_mamba_layers, CFG.chunk_size


def sizes_of(cfg) -> dict:
    """The published keys the reference reads."""
    keys = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
            "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "n_shared_experts", "layer_norm_epsilon", "ep_rank")
    return {k: getattr(cfg, k) for k in keys}


def seeded_params(cfg, seed=0):
    shapes = jax.eval_shape(lambda: sm.init_ssd_moe_params(jax.random.PRNGKey(0), cfg, FP32))
    rng = np.random.default_rng(seed)
    out = {}
    for name, leaf in shapes.items():
        if name == "mamba_A_log":
            value = np.log(rng.uniform(1.0, 16.0, leaf.shape))
        elif name == "mamba_dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-2), 0.0, leaf.shape))
            value = dt + np.log(-np.expm1(-dt))
        elif name in ("mamba_conv_b", "moe_router_bias"):
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif "norm" in name or name == "mamba_D":
            value = 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        elif name == "embedding":
            value = rng.standard_normal(leaf.shape)
        elif name == "moe_router":  # scores across (0, 1): the choice and the weights both matter
            value = 2.0 * rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        else:
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        out[name] = jnp.asarray(value, jnp.float32)
    return out


PARAMS = seeded_params(CFG)


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, V, size=n)]


_REF = {}


def forward(tokens, control="", cfg=CFG, params=None):
    """The reference's logits of ``tokens`` at a padded length (a pad behind
    the sequence changes nothing in front of it: every layer is causal), so
    that the reference's jitted layers serve every length up to it."""
    n = -(-len(tokens) // 64) * 64
    ids = jnp.asarray(list(tokens) + [0] * (n - len(tokens)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(PARAMS if params is None else params, sizes_of(cfg), ids, control=control)
    return np.asarray(logits)[:len(tokens)]


def reference(tokens):
    key = tuple(tokens)
    if key not in _REF:
        _REF[key] = forward(tokens)
    return _REF[key]


def greedy_reference(prompt, n):
    tokens = list(prompt)
    for _ in range(n):
        tokens.append(int(np.argmax(forward(tokens)[-1])))
    return tokens[len(prompt):]


_CALLS = {}


def calls(impl="xla", chunked=False, keep_steps=False):
    """``(call, first)`` of one model, jitted ONCE a form: every position's
    logits, and the engine's prompt call (``last_logit_only``)."""
    key = (impl, chunked, keep_steps)
    if key not in _CALLS:
        model = sm.SSDMoEModel(CFG, FP32, attn_impl=impl, chunked=chunked, keep_steps=keep_steps)
        _CALLS[key] = (jax.jit(lambda *a: model.apply({"params": PARAMS}, *a)),
                       jax.jit(lambda *a: model.apply({"params": PARAMS}, *a, last_logit_only=True)))
    return _CALLS[key]


def cache_length(n, impl):
    """Slots for ``n`` positions: the kernels tile a cache in 128s."""
    return n if impl == "xla" else -(-n // 128) * 128


def through_the_cache(rows, S, lengths, impl="xla"):
    """Logits of ``rows`` (left-padded to ``S``, of which ``lengths`` are
    prefilled at once and the rest decoded a token at a time), and the cache."""
    B, lens = len(rows), np.asarray(lengths)
    call, _ = calls(impl)
    cache = sm.make_ssd_cache(CFG, B, cache_length(S + max(len(r) - n for r, n in zip(rows, lens)), impl),
                              jnp.float32)
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


def chunk_call(tokens, start, n, S=32, keep_steps=False, impl="xla"):
    """``tokens[:start]`` prefilled (left-padded to ``S``, the engine's fresh
    call), then ``n`` positions from ``start`` in ONE chunk call; returns its
    ``(logits, cache)`` and ``kv_start``."""
    cache = sm.make_ssd_cache(CFG, 1, cache_length(S + max(64, n), impl), jnp.float32)
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


def uncut(cfg=CFG):
    """``cfg`` with every expert held (one rank), and ``PARAMS`` with the held
    stacks of both ranks side by side: the model no share was cut from. The
    other rank's experts are drawn from the next seed."""
    whole = dataclasses.replace(cfg, ep_size=1, ep_rank=0)
    other = seeded_params(cfg, seed=1)
    ranks = [other, PARAMS] if cfg.ep_rank else [PARAMS, other]
    params = dict(PARAMS)
    for name in ("experts_w_up", "experts_w_down"):
        params[name] = jnp.concatenate([r[name] for r in ranks], axis=1)
    return whole, params

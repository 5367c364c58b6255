"""The plain reference of the shortcut-connected latent-attention decoder
(LongCat-Flash's block), where tier 1 can import it
(``benchmark/references/longcat_flash.py`` is the benchmark's own copy;
``benchmark/tests/test_longcat_flash_family.py`` holds the two to each other).

One function, ``forward``: the whole sequence at once in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, the EXPANDED
attention (per-head keys and values rebuilt from the latents) under a causal
mask, no cache, no batching, a Python loop over heads and over the held
experts. It takes nothing from the program but the parameter tree.

A layer, as published (``LongcatFlashDecoderLayer``)::

    for i in (0, 1):
        x   = x + MLA_i(RMSNorm_in_i(x))
        u_i = RMSNorm_post_i(x)
        if i == 0:  m = MoE(u_0)
        x   = x + FFN_i(u_i)
    x = x + m

``MLA``: ``q = (RMSNorm(x W_DQ) W_UQ) * (D / q_rank) ** 0.5``; ``c_kv =
RMSNorm(c) * (D / kv_rank) ** 0.5``; ``k_rope`` not scaled. ``MoE``: softmax
over routed + zero outputs, choice by ``p + bias``, weight ``p * scaling``
(not renormalised), zero experts the identity.

Departures from the publisher's code, both noted where they act: RoPE pairs
dimension ``i`` with ``i + R/2`` (a permutation of the rope columns of
``W_UQ`` and ``W_DKV``, which are random here); the SHARE: only the experts in
``held`` are summed (``None``: what ``ep_size`` / ``ep_rank`` say), the zero
experts' term and everything else whole.

``join_after`` and ``lora_scales`` exist for the tests that must FAIL: the
branch joined after another sublayer, the scales left out. ``tiny_config`` is
the miniature block the tests compare at.
"""

import jax
import jax.numpy as jnp
import numpy as np


def tiny_config(vocab_size: int = 256, **overrides):
    """The shortcut-connected block at a toy size, as overrides of
    ``LatentMoEConfig.tiny``: two layers of two sublayers (4 cache planes),
    16 routed + 8 zero experts, top-6, softmax scores, both LoRA scales, rank
    1 of 2 holds 8."""
    from rag_llm_k8s_tpu.core.config import LatentMoEConfig

    base = dict(
        num_layers=2, first_k_dense=0, sublayers_per_layer=2, n_shared_experts=0,
        n_group=1, topk_group=1, num_experts_per_tok=6, zero_expert_num=8,
        scoring_func="softmax", norm_topk_prob=False, routed_scaling_factor=6.0,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, rope_scaling=None,
        rope_theta=1e7, rms_norm_eps=1e-5,
    )
    base.update(overrides)
    return LatentMoEConfig.tiny(vocab_size, **base)


def _f(w):
    return jnp.asarray(w, jnp.float32)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(scale)


def _rope(x, theta):
    """``x [S, R]`` at positions 0..S-1, dimension i paired with i + R/2."""
    dim = x.shape[-1]
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    phase = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    a, b = x[:, :dim // 2], x[:, dim // 2:]
    return jnp.concatenate([a * jnp.cos(phase) - b * jnp.sin(phase), b * jnp.cos(phase) + a * jnp.sin(phase)], -1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def route(x, w_g, bias, cfg) -> np.ndarray:
    """``[S, routed + zero]`` routing weights, zero where not chosen."""
    logits = np.asarray(x @ _f(w_g), np.float64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    choice = p + np.asarray(bias, np.float64)[None, :]
    weights = np.zeros_like(p)
    for t in range(p.shape[0]):
        chosen = np.argsort(-choice[t], kind="stable")[:cfg.num_experts_per_tok]
        w = p[t, chosen]
        if cfg.norm_topk_prob:
            w = w / (w.sum() + 1e-20)
        weights[t, chosen] = w * cfg.routed_scaling_factor
    return weights


def attention(x, a, cfg, lora_scales=True):
    """``MLA(x)`` for the normed stream ``x [S, D]``."""
    eps, D = cfg.rms_norm_eps, cfg.hidden_size
    H, C, dn, R, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    S = x.shape[0]
    q = _norm(x @ _f(a["wq_a"]["kernel"]), a["q_norm"]["scale"], eps) @ _f(a["wq_b"]["kernel"])
    latent = x @ _f(a["wkv_a"]["kernel"])
    c_kv = _norm(latent[:, :C], a["kv_norm"]["scale"], eps)
    if lora_scales and cfg.mla_scale_q_lora:
        q = q * (D / cfg.q_lora_rank) ** 0.5
    if lora_scales and cfg.mla_scale_kv_lora:
        c_kv = c_kv * (D / C) ** 0.5
    q = q.reshape(S, H, dn + R)
    k_rope = _rope(latent[:, C:], cfg.rope_theta)  # one for all heads, not scaled
    kv = (c_kv @ _f(a["wkv_b"]["kernel"])).reshape(S, H, dn + dv)
    causal = jnp.tril(jnp.ones((S, S), bool))
    heads = []
    for i in range(H):
        qi = jnp.concatenate([q[:, i, :dn], _rope(q[:, i, dn:], cfg.rope_theta)], -1)
        ki = jnp.concatenate([kv[:, i, :dn], k_rope], -1)
        scores = jnp.where(causal, qi @ ki.T * (dn + R) ** -0.5, -jnp.inf)
        heads.append(jax.nn.softmax(scores, axis=-1) @ kv[:, i, dn:])
    return jnp.concatenate(heads, -1) @ _f(a["wo"]["kernel"])


def moe(u, mlp, experts, held, cfg):
    """``sum_{e in held, chosen} w_e E_e(u) + (sum_{zero e chosen} w_e) u``;
    ``experts`` are one layer's three ``[len(held), ...]`` stacks."""
    w = route(u, mlp["router"]["kernel"], mlp["router_bias"], cfg)
    y = jnp.asarray(w[:, cfg.n_routed_experts:].sum(-1, keepdims=True), jnp.float32) * u
    for j, e in enumerate(held):
        y = y + jnp.asarray(w[:, e:e + 1], jnp.float32) * swiglu(u, experts[0][j], experts[1][j], experts[2][j])
    return y


def layer(h, p, experts, held, cfg, join_after=None, lora_scales=True):
    n = cfg.sublayers_per_layer
    join_after = n - 1 if join_after is None else join_after
    for i in range(n):
        h = h + attention(_norm(h, p[f"input_norm_{i}"]["scale"], cfg.rms_norm_eps), p[f"attn_{i}"], cfg,
                          lora_scales)
        u = _norm(h, p[f"post_attn_norm_{i}"]["scale"], cfg.rms_norm_eps)
        if i == 0:
            m = moe(u, p["mlp"], experts, held, cfg)
        f = p[f"ffn_{i}"]
        h = h + swiglu(u, f["w_gate"]["kernel"], f["w_up"]["kernel"], f["w_down"]["kernel"])
        if i == join_after:
            h = h + m
    return h


def forward(params, cfg, tokens, held=None, join_after=None, lora_scales=True) -> np.ndarray:
    """Logits ``[S, V]`` of every position of ``tokens``."""
    if held is None:
        held = list(range(cfg.first_held, cfg.first_held + cfg.experts_held))
    with jax.default_matmul_precision("highest"):
        h = _f(params["embedding"])[jnp.asarray(tokens)]
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            ex = tuple(params["experts"][n][i] for n in ("w_gate", "w_up", "w_down"))
            h = layer(h, p, ex, held, cfg, join_after, lora_scales)
        h = _norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return np.asarray(h @ _f(params["lm_head"]))

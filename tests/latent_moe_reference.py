"""The plain reference of the latent-attention sparse-expert decoder, where
tier 1 can import it (``benchmark/references/dots_vlm.py`` is the benchmark's
own copy; ``benchmark/tests/test_dots_vlm.py`` holds the two to each other).

One function, ``forward``: the whole sequence at once in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, the EXPANDED
attention (per-head keys and values rebuilt from the latents) under a causal
mask, no cache, no batching, a Python loop over heads and over the held
experts. It takes nothing from the program but the parameter tree, and a list
of the published expert indices that are held (``None``: what the
configuration's ``ep_size``/``ep_rank`` say).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * jnp.asarray(scale, jnp.float32)


def yarn_inv_freq(cfg) -> np.ndarray:
    """Closed form: ``theta_i`` below the dimension that turns ``beta_fast``
    times in the original context, ``theta_i / factor`` above the one that
    turns ``beta_slow`` times, a linear blend by dimension index between."""
    dim, theta, s = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if s is None:
        return inv
    def dim_turning(turns):
        return dim * math.log(s.original_max_position_embeddings / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim_turning(s.beta_fast)), 0)
    high = min(math.ceil(dim_turning(s.beta_slow)), dim - 1)
    blend = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv * (1 - blend) + inv / s.factor * blend


def softmax_scale(cfg) -> float:
    s = cfg.rope_scaling
    m = 1.0 if s is None or s.factor <= 1 else 0.1 * s.mscale_all_dim * math.log(s.factor) + 1.0
    return m * m / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _rope(x, inv_freq):
    """``x [S, R]`` at positions 0..S-1, dimension i paired with i + R/2."""
    half = x.shape[-1] // 2
    phase = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * jnp.cos(phase) - b * jnp.sin(phase), b * jnp.cos(phase) + a * jnp.sin(phase)], -1)


def _swiglu(x, gate, up, down):
    f = lambda w: jnp.asarray(w, jnp.float32)  # noqa: E731
    return (jax.nn.silu(x @ f(gate)) * (x @ f(up))) @ f(down)


def route(x, w_g, bias, cfg):
    """``[S, E]`` routing weights, zero where an expert is not chosen."""
    s = np.asarray(jax.nn.sigmoid(x @ jnp.asarray(w_g, jnp.float32)), np.float64)
    choice = s + np.asarray(bias, np.float64)[None, :]
    n, e = s.shape
    per = e // cfg.n_group
    weights = np.zeros_like(s)
    for t in range(n):
        groups = choice[t].reshape(cfg.n_group, per)
        score = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score, kind="stable")[:cfg.topk_group]
        allowed = np.full(e, -np.inf)
        for g in kept:
            allowed[g * per:(g + 1) * per] = choice[t, g * per:(g + 1) * per]
        chosen = np.argsort(-allowed, kind="stable")[:cfg.num_experts_per_tok]
        w = s[t, chosen]
        if cfg.norm_topk_prob:
            w = w / (w.sum() + 1e-20)
        weights[t, chosen] = w * cfg.routed_scaling_factor
    return weights


def attention(h, p, cfg):
    a, eps = p["attn"], cfg.rms_norm_eps
    f = lambda w: jnp.asarray(w, jnp.float32)  # noqa: E731
    H, C, dn, R, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    S = h.shape[0]
    x = _norm(h, p["input_norm"]["scale"], eps)
    q = (_norm(x @ f(a["wq_a"]["kernel"]), a["q_norm"]["scale"], eps) @ f(a["wq_b"]["kernel"])).reshape(S, H, dn + R)
    latent = x @ f(a["wkv_a"]["kernel"])
    c_kv = _norm(latent[:, :C], a["kv_norm"]["scale"], eps)
    inv = yarn_inv_freq(cfg)
    k_rope = _rope(latent[:, C:], inv)
    kv = (c_kv @ f(a["wkv_b"]["kernel"])).reshape(S, H, dn + dv)
    causal = jnp.tril(jnp.ones((S, S), bool))
    heads = []
    for i in range(H):
        qi = jnp.concatenate([q[:, i, :dn], _rope(q[:, i, dn:], inv)], -1)
        ki = jnp.concatenate([kv[:, i, :dn], k_rope], -1)
        scores = jnp.where(causal, qi @ ki.T * softmax_scale(cfg), -jnp.inf)
        heads.append(jax.nn.softmax(scores, axis=-1) @ kv[:, i, dn:])
    return h + jnp.concatenate(heads, -1) @ f(a["wo"]["kernel"])


def moe(x, mlp, experts, held, cfg):
    """``sum_{i in held, chosen} w_i E_i(x) + E_shared(x)``; ``experts`` are the
    three ``[len(held), ...]`` stacks of one layer, in ``held``'s order."""
    w = route(x, mlp["router"]["kernel"], mlp["router_bias"], cfg)
    y = jnp.zeros_like(x)
    for j, e in enumerate(held):
        y = y + jnp.asarray(w[:, e:e + 1], jnp.float32) * _swiglu(x, experts[0][j], experts[1][j], experts[2][j])
    sh = mlp["shared"]
    return y + _swiglu(x, sh["w_gate"]["kernel"], sh["w_up"]["kernel"], sh["w_down"]["kernel"])


def forward(params, cfg, tokens, held=None) -> np.ndarray:
    """Logits ``[S, V]`` of every position of ``tokens``."""
    if held is None:
        held = list(range(cfg.first_held, cfg.first_held + cfg.experts_held))
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embedding"], jnp.float32)[jnp.asarray(tokens)]
        for i in range(cfg.num_layers):
            dense = i < cfg.first_k_dense
            p = params[f"dense_{i}"] if dense else jax.tree.map(lambda a: a[i - cfg.first_k_dense], params["layers"])
            h = attention(h, p, cfg)
            x = _norm(h, p["post_attn_norm"]["scale"], cfg.rms_norm_eps)
            if dense:
                m = p["mlp"]
                h = h + _swiglu(x, m["w_gate"]["kernel"], m["w_up"]["kernel"], m["w_down"]["kernel"])
            else:
                ex = tuple(params["experts"][n][i - cfg.first_k_dense] for n in ("w_gate", "w_up", "w_down"))
                h = h + moe(x, p["mlp"], ex, held, cfg)
        h = _norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return np.asarray(h @ jnp.asarray(params["lm_head"], jnp.float32))

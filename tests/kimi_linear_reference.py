"""The plain reference of the gated delta-rule, sparse-expert decoder
(``models/delta_moe.py``) for tier 1: the forward pass of ONE sequence in
``jax.numpy`` and float32, every product at the highest matmul precision, the
recurrence a token at a time, no chunk form, no cache, no batching, the
experts a loop over those held. It shares nothing with the program but the
parameter tree it reads (``kda_layers`` / ``mla_layers`` stacked by kind,
``lead_<i>``, ``layers`` and ``experts`` stacked over the sparse layers).
``benchmark/references/kimi_linear.py`` is its twin at the served widths.

For ``x`` the residual stream and ``RMS(h; g) = h / sqrt(mean(h^2) + eps) * g``:

1. every layer: ``h = x + Mixer(RMS(x; input_norm))``, ``y = h + FFN(RMS(h;
   post_attn_norm))``;
2. a linear layer (``kda_layers``): ``[q | k | v] = n W_qkv``, each channel
   through a causal convolution of 4 taps (zeros in front of the first token,
   no bias) and a SiLU; per head ``q = q / |q| * d^-1/2``, ``k = k / |k|``;
   ``g = -exp(A_log_h) softplus(W_fb (W_fa n) + dt_bias)`` a channel, ``beta =
   sigmoid(W_b n)`` a head; ``S' = exp(g) * S`` (rows), ``S = S' + beta k (v -
   S'^T k)^T``, ``o = S^T q`` from ``S = 0``; ``W_o [RMS(o; o_norm) *
   sigmoid(W_gb (W_ga n))]``;
3. a full layer: ``q = n W_q`` (heads of ``nope + rope``), ``[c | r] = n
   W_dkv``, ``c`` RMS-normed; ``k_h = [W_uk,h c ; r]``, ``v_h = W_uv,h c``;
   causal softmax at scale ``(nope + rope)^-1/2``; nothing is rotated;
4. the FFN: a SwiGLU in the leading dense layers; else ``s = sigmoid(n W_g)``,
   the top k of ``s + b`` chosen (an argmax a choice), ``w = s`` at the chosen
   over their sum, times the scaling factor; ``sum_{e chosen and held} w_e
   E_e(n) + E_shared(n)``;
5. ``RMS(x; final_norm)``, then the logits against the untied head.

``faults`` compute it wrongly on purpose, for the tests that show the
comparison can fail: ``no_decay`` (alpha = 1), ``scalar_decay`` (a head's mean
log decay on every channel), ``beta_one``, ``no_l2norm``, ``taps_reversed``,
``rotated`` (the 64-wide slices rotated by position), ``bias_in_weights``.
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FAULTS = ("no_decay", "scalar_decay", "beta_one", "no_l2norm", "taps_reversed", "rotated", "bias_in_weights")


def _mm(x, w):
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32), precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def linear_attention(n, p, cfg, fault="", state_log=None):
    """A linear layer's mixer on the normed stream ``n [S, D]``."""
    S = n.shape[0]
    H, hd = cfg.kda_num_heads, cfg.kda_head_dim
    w = p["conv_w"].astype(jnp.float32)
    w = w[::-1] if fault == "taps_reversed" else w
    K = w.shape[0]
    run = jnp.concatenate([jnp.zeros((K - 1, w.shape[1]), jnp.float32), _mm(n, p["wqkv"]["kernel"])], axis=0)
    mixed = jax.nn.silu(sum(w[j][None] * run[j:j + S] for j in range(K)))
    q, k, v = (a.reshape(S, H, hd) for a in jnp.split(mixed, 3, axis=-1))
    if fault != "no_l2norm":
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * hd ** -0.5
    lift = _mm(_mm(n, p["f_a"]["kernel"]), p["f_b"]["kernel"]).reshape(S, H, hd)
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[None, :, None] * jax.nn.softplus(lift + p["dt_bias"][None])
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    if fault == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm(n, p["b_proj"]["kernel"]))
    if fault == "beta_one":
        beta = jnp.ones_like(beta)

    def step(state, x):  # state [H, hd (key), hd (value)]
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k, precision=HI))
        state = state + k[..., None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q, precision=HI)

    state, o = jax.lax.scan(step, jnp.zeros((H, hd, hd), jnp.float32), (q, k, v, g, beta))
    if state_log is not None:
        state_log.append(state)
    gate = jax.nn.sigmoid(_mm(_mm(n, p["g_a"]["kernel"]), p["g_b"]["kernel"])).reshape(S, H, hd)
    return _mm((_rms(o, p["o_norm"], cfg.rms_norm_eps) * gate).reshape(S, H * hd), p["wo"]["kernel"])


def _rotate(x, theta):
    """Rotation by halves of ``x [S, ..., R]`` at positions 0..S-1."""
    R = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    phase = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    phase = phase.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (R // 2,))
    a, b = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([a * jnp.cos(phase) - b * jnp.sin(phase), b * jnp.cos(phase) + a * jnp.sin(phase)], -1)


def latent_attention(n, p, cfg, fault=""):
    S = n.shape[0]
    H, C, dn, R, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = _mm(n, p["wq"]["kernel"]).reshape(S, H, dn + R)
    latent = _mm(n, p["wkv_a"]["kernel"])
    c, r = _rms(latent[:, :C], p["kv_norm"]["scale"], cfg.rms_norm_eps), latent[:, C:]
    q_r = q[..., dn:]
    if fault == "rotated" or not cfg.mla_use_nope:
        q_r, r = _rotate(q_r, cfg.rope_theta), _rotate(r, cfg.rope_theta)
    kv = _mm(c, p["wkv_b"]["kernel"]).reshape(S, H, dn + dv)
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn], precision=HI)
              + jnp.einsum("qhd,kd->hqk", q_r, r, precision=HI)) * (dn + R) ** -0.5
    at = jnp.arange(S)
    w = jax.nn.softmax(jnp.where(at[None, :, None] >= at[None, None, :], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, kv[..., dn:], precision=HI)
    return _mm(o.reshape(S, H * dv), p["wo"]["kernel"])


def route(n, mlp, cfg, fault=""):
    """``[S, E]`` weights (zero where not chosen) by the published rule."""
    s = jax.nn.sigmoid(_mm(n, mlp["router"]["kernel"]))
    choice = s + mlp["router_bias"].astype(jnp.float32)[None]
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(cfg.num_experts_per_token):
        i = jnp.argmax(jnp.where(chosen, -jnp.inf, choice), axis=-1)
        chosen = chosen | jax.nn.one_hot(i, s.shape[-1], dtype=bool)
    w = jnp.where(chosen, choice if fault == "bias_in_weights" else s, 0.0)
    if cfg.moe_renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor


def sparse_ffn(n, mlp, stacks, at, cfg, fault=""):
    w = route(n, mlp, cfg, fault)
    y = jnp.zeros_like(n)
    for e in range(stacks[0].shape[1]):  # the experts held here
        y = y + w[:, cfg.first_held + e, None] * _swiglu(n, *(s[at, e] for s in stacks))
    if cfg.num_shared_experts:
        sh = mlp["shared"]
        y = y + _swiglu(n, sh["w_gate"]["kernel"], sh["w_up"]["kernel"], sh["w_down"]["kernel"])
    return y


def forward(params, cfg, ids, fault="", state_log=None):
    """Logits ``[S, V]`` float32 of the token ids ``ids [S]``. ``state_log``
    (a list) is given every linear layer's last state, in order."""
    if fault and fault not in FAULTS:
        raise ValueError(f"fault={fault!r}: one of {FAULTS}")
    x = params["embedding"][ids].astype(jnp.float32)
    stacks = tuple(params["experts"][name] for name in ("w_gate", "w_up", "w_down")) if "experts" in params else ()
    ki = mi = 0
    for i in range(cfg.num_hidden_layers):
        lead = i < cfg.first_k_dense_replace
        layer = params[f"lead_{i}"] if lead else jax.tree_util.tree_map(
            lambda a: a[i - cfg.first_k_dense_replace], params["layers"])
        n = _rms(x, layer["input_norm"]["scale"], cfg.rms_norm_eps)
        if cfg.is_full(i):
            x = x + latent_attention(n, jax.tree_util.tree_map(lambda a: a[mi], params["mla_layers"]), cfg, fault)
            mi += 1
        else:
            x = x + linear_attention(n, jax.tree_util.tree_map(lambda a: a[ki], params["kda_layers"]), cfg,
                                     fault, state_log)
            ki += 1
        n = _rms(x, layer["post_attn_norm"]["scale"], cfg.rms_norm_eps)
        if lead:
            m = layer["mlp"]
            x = x + _swiglu(n, m["w_gate"]["kernel"], m["w_up"]["kernel"], m["w_down"]["kernel"])
        else:
            x = x + sparse_ffn(n, layer["mlp"], stacks, i - cfg.first_k_dense_replace, cfg, fault)
    return _mm(_rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps), params["lm_head"])

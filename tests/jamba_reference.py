"""The plain reference of the hybrid state-space decoder, where tier 1 can
import it (``benchmark/references/jamba.py`` is the benchmark's own copy;
``benchmark/tests/test_jamba_family.py`` holds the two to each other).

One function, ``forward``: the whole sequence at once in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, no cache, no
kernels, no batching, the recurrence a ``lax.scan``. For ``x`` the residual
stream and ``RMS(h; g) = h / sqrt(mean(h^2) + eps) * g``:

1. layer ``i`` is an attention layer if ``i % attn_layer_period ==
   attn_layer_offset``, else a state layer. Both: ``x += mixer(RMS(x;
   g_in))``, then ``x += (silu(h W_gate) * h W_up) W_down``, ``h = RMS(x; g_ff)``;
2. attention: ``q = h W_q`` (heads of ``hd``), ``k = h W_k``, ``v = h W_v``
   (the KV heads), NO rotation and no position term; causal softmax at scale
   ``hd^-1/2``; ``o W_o``;
3. state space: ``[u, z] = h W_in``; ``u_t <- silu(b_c + sum_j w_c[j] *
   u_{t-3+j})`` (zeros before the first token); ``[delta, B, C] = u W_x``,
   each RMS-normed with its own scale; ``dt = softplus(delta W_dt + b_dt)``;
   ``A = -exp(A_log)``; ``s_t = exp(dt_t A) * s_{t-1} + (dt_t * u_t) B_t``
   (``s_{-1} = 0``); ``y_t = s_t . C_t + D * u_t``; ``(y * silu(z)) W_out``;
4. ``RMS(x; g_final)`` and the logits against the head (the embedding
   transposed when tied).

It takes nothing from the program but the parameter tree (flat names, leaves
stacked by layer kind; ``ssm_A_log`` is ``[layers, d_state, d_inner]``, the
published leaf transposed, and ``ssm_conv_w`` ``[layers, d_conv, d_inner]``).

The faults the tests must see fail, each a keyword: ``inner_norms=False``,
``softplus=False`` (the time step a plain ``relu``), ``a_exp=False`` (``A =
-A_log``), ``attn_window=n`` (an attention layer sees its last ``n``
positions), ``drop_state_at=t`` / ``drop_conv_at=t`` (the state, or the
convolution's history, zero in front of position ``t``: a hand-over from
prefill to decode that loses it), ``state_dtype`` (the state rounded to it
after every position).
"""

import jax
import jax.numpy as jnp
import numpy as np


def _f(w):
    return jnp.asarray(w, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(g)


def state_mixer(h, p, cfg, *, inner_norms=True, softplus=True, a_exp=True, drop_state_at=None,
                drop_conv_at=None, state_dtype=None):
    """``h [S, D]`` normed -> the state-space mixer's output ``[S, D]``."""
    S = h.shape[0]
    Di, N, R, K = cfg.mamba_expand * cfg.hidden_size, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    xz = h @ p["in_proj"]
    u, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), jnp.float32), u], axis=0)
    t = np.arange(S)
    acc = _f(p["conv_b"])[None]
    for j in range(K):
        tap = padded[j:j + S]  # the input at t - (K - 1) + j
        if drop_conv_at is not None:  # inputs in front of the hand-over are lost to outputs behind it
            lost = (t >= drop_conv_at) & (t - (K - 1) + j < drop_conv_at)
            tap = jnp.where(jnp.asarray(lost)[:, None], 0.0, tap)
        acc = acc + _f(p["conv_w"])[j][None] * tap
    u = jax.nn.silu(acc)
    dbc = u @ p["x_proj"]
    delta, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if inner_norms:
        delta = _rms(delta, p["dt_norm"], cfg.rms_norm_eps)
        B, C = _rms(B, p["b_norm"], cfg.rms_norm_eps), _rms(C, p["c_norm"], cfg.rms_norm_eps)
    raw = delta @ p["dt_proj"] + _f(p["dt_bias"])[None]
    dt = jax.nn.softplus(raw) if softplus else jax.nn.relu(raw)
    A = -jnp.exp(_f(p["A_log"])) if a_exp else -_f(p["A_log"])  # [N, Di]
    keep = jnp.asarray(t != (-1 if drop_state_at is None else drop_state_at), jnp.float32)

    def step(s, xs):
        dt_t, u_t, b_t, c_t, keep_t = xs
        s = jnp.exp(dt_t[None, :] * A) * (s * keep_t) + (dt_t * u_t)[None, :] * b_t[:, None]
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, Di), jnp.float32), (dt, u, B, C, keep))
    y = y + _f(p["D"])[None] * u
    return (y * jax.nn.silu(z)) @ p["out_proj"]


def attention_mixer(h, p, cfg, *, attn_window=None):
    S = h.shape[0]
    H, K = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = cfg.hidden_size // H
    q = (h @ p["wq"]).reshape(S, K, H // K, hd)
    k, v = (h @ p["wk"]).reshape(S, K, hd), (h @ p["wv"]).reshape(S, K, hd)
    t = np.arange(S)
    mask = t[None, :] <= t[:, None]
    if attn_window is not None:
        mask &= t[None, :] > t[:, None] - attn_window
    s = jnp.einsum("qkgd,tkd->kgqt", q, k) * hd ** -0.5
    a = jax.nn.softmax(jnp.where(jnp.asarray(mask)[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("kgqt,tkd->qkgd", a, v).reshape(S, H * hd) @ p["wo"]


def forward(params, cfg, ids, *, attn_window=None, **faults):
    """``[S, vocab]`` float32 logits of the sequence ``ids``."""
    group = lambda prefix, i: {k[len(prefix):]: _f(v[i]) for k, v in params.items()  # noqa: E731
                               if k.startswith(prefix)}
    with jax.default_matmul_precision("highest"):
        x = _f(params["embedding"])[jnp.asarray(ids, jnp.int32)]
        mi = ai = 0
        for i in range(cfg.num_hidden_layers):
            lp = group("layers_", i)
            h = _rms(x, lp["input_norm"], cfg.rms_norm_eps)
            if i % cfg.attn_layer_period == cfg.attn_layer_offset:
                x, ai = x + attention_mixer(h, group("attn_", ai), cfg, attn_window=attn_window), ai + 1
            else:
                x, mi = x + state_mixer(h, group("ssm_", mi), cfg, **faults), mi + 1
            h = _rms(x, lp["ff_norm"], cfg.rms_norm_eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        h = _rms(x, params["final_norm"], cfg.rms_norm_eps)
        head = _f(params["embedding"]).T if cfg.tie_word_embeddings else _f(params["lm_head"])
        return h @ head

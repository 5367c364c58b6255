"""The plain reference of the block-window, pooled-summary decoder, where tier
1 can import it (``benchmark/references/evabyte.py`` is the benchmark's own
copy; ``benchmark/tests/test_evabyte_family.py`` holds the two to each other).

One function, ``forward``: the whole sequence at once in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, no cache, no
kernels, no batching. For ``x`` the residual stream (float32), a layer is

1. ``h = x / sqrt(mean(x^2) + eps) * (1 + g)``;
2. ``q, k, v = h W_q, h W_k, h W_v`` in heads, q and k rotated by position
   (halves paired);
3. for every complete chunk ``c`` of ``chunk_size`` positions of head ``n``:
   ``k~_c = sum_j softmax_j(k_j . mu_n) k_j``, ``v~_c = sum_j softmax_j(k_j .
   phi_n) v_j`` (rotated keys, unscaled logits);
4. position ``t`` attends, under one softmax at scale ``hd^-1/2``, to the
   positions ``j <= t`` of its own window (``j // W == t // W``) and to the
   summaries of the chunks of every EARLIER window;
5. ``x += o W_o``; ``x += (silu(h' W_gate) * h' W_up) W_down`` with ``h'`` by 1;

and after the last layer 1 once more and ``logits = h W_head``, ``[S,
num_pred_heads, vocab]`` (head-major columns). It takes nothing from the
program but the parameter tree (flat names, the layers' leaves stacked).

The faults the tests and the benchmark's controls must see fail, each a
keyword: ``summaries=False`` (window-only attention), ``swap_mu_phi``,
``mean_pool`` (the plain mean of a chunk), ``own_window_summaries`` (a query
also sees the summaries of its own window's complete chunks).
"""

import jax
import jax.numpy as jnp
import numpy as np


def _f(w):
    return jnp.asarray(w, jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f(g))


def _rotate(x, positions, theta):
    """``x [S, H, hd]`` by halves at ``positions [S]``."""
    hd = x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    phase = np.asarray(positions, np.float64)[:, None] * inv[None]
    cos, sin = _f(np.cos(phase))[:, None], _f(np.sin(phase))[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def forward(params, cfg, ids, *, summaries=True, swap_mu_phi=False, mean_pool=False,
            own_window_summaries=False):
    """``[S, num_pred_heads, vocab]`` float32 logits of the sequence ``ids``."""
    W, C = cfg.window_size, cfg.chunk_size
    H, hd, L = cfg.num_attention_heads, cfg.hidden_size // cfg.num_attention_heads, cfg.num_hidden_layers
    S = len(ids)
    t = np.arange(S)
    n_chunks = S // C  # complete ones
    exact = (t[None] <= t[:, None]) & (t[None] // W == t[:, None] // W)  # [S, S]
    chunk_window = (np.arange(n_chunks) * C) // W
    if own_window_summaries:  # the fault: complete chunks of the query's window too
        pooled = (chunk_window[None] <= t[:, None] // W) & ((np.arange(n_chunks)[None] + 1) * C - 1 <= t[:, None])
    else:
        pooled = chunk_window[None] < t[:, None] // W  # [S, n_chunks]
    if not summaries:
        pooled = np.zeros_like(pooled)
    mask = jnp.asarray(np.concatenate([exact, pooled], axis=1))
    with jax.default_matmul_precision("highest"):
        x = _f(params["embedding"])[np.asarray(ids)]
        for l in range(L):
            p = {k[len("layers_"):]: _f(v[l]) for k, v in params.items() if k.startswith("layers_")}
            h = _norm(x, p["input_norm"], cfg.rms_norm_eps)
            q = _rotate((h @ p["wq"]).reshape(S, H, hd), t, cfg.rope_theta)
            k = _rotate((h @ p["wk"]).reshape(S, H, hd), t, cfg.rope_theta)
            v = (h @ p["wv"]).reshape(S, H, hd)
            mu, phi = (p["phi"], p["mu"]) if swap_mu_phi else (p["mu"], p["phi"])
            kc = k[:n_chunks * C].reshape(n_chunks, C, H, hd)
            vc = v[:n_chunks * C].reshape(n_chunks, C, H, hd)
            if mean_pool:
                wk = wv = jnp.full((n_chunks, C, H), 1.0 / C)
            else:
                wk = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, mu), axis=1)
                wv = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, phi), axis=1)
            sk = jnp.einsum("cjh,cjhd->chd", wk, kc)
            sv = jnp.einsum("cjh,cjhd->chd", wv, vc)
            keys, vals = jnp.concatenate([k, sk], axis=0), jnp.concatenate([v, sv], axis=0)
            s = jnp.einsum("qhd,thd->hqt", q, keys) * hd**-0.5
            a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hqt,thd->qhd", a, vals).reshape(S, H * hd)
            x = x + o @ p["wo"]
            h = _norm(x, p["post_attn_norm"], cfg.rms_norm_eps)
            x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        h = _norm(x, params["final_norm"], cfg.rms_norm_eps)
        return (h @ _f(params["lm_head"])).reshape(S, cfg.num_pred_heads, cfg.vocab_size)

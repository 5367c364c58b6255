"""The plain reference of the windowed-attention sparse-expert decoder, where
tier 1 can import it (``benchmark/references/laguna.py`` is the benchmark's own
copy; ``benchmark/tests/test_laguna_family.py`` holds the two to each other).

One function, ``forward``: the whole sequence at once in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, every layer in the
published order (norm, q / k / v, the layer kind's rotation, masked softmax
attention of every head under one mask, the per-head gate, the output
projection, norm, the dense SwiGLU or the routed experts with the shared
one), no cache, no kernels, no batching, a Python loop over the held experts. It
takes nothing from the program but the parameter tree (``lead_<i>``,
``periods/l<j>`` stacked a period, ``experts`` stacked a sparse layer) and a
list of the published expert indices that are held (``None``: what the
configuration's ``ep_size`` / ``ep_rank`` say).

Departures from the publisher's code, each noted in the configuration file's
``assumed``: (1) the router scores by sigmoid and a bias moves the choice
only; (2) the gate is ``softplus`` of a linear map of the normed input to one
scalar a head, applied to the head's attention output in front of ``W_o``;
(3) no q / k normalisation; (4) rotation pairs dimension ``i`` with ``i +
rot/2`` (by halves) where the publisher may interleave: a permutation of
``W_q``'s and ``W_k``'s columns.

``forward(..., sliding_as_full=True)`` and ``gate=False`` are the two faults
the tests must see fail: a sliding layer attending to every earlier token,
and the gate left out.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f(w):
    return jnp.asarray(w, jnp.float32)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(scale)


def inv_freq(rope, head_dim: int) -> np.ndarray:
    """``[rot / 2]`` inverse frequencies over the ``rot = head_dim *
    partial_rotary_factor`` rotated dimensions; under ``yarn`` ``theta_i``
    below the dimension that turns ``beta_fast`` times in the original
    context, ``theta_i / factor`` above the one that turns ``beta_slow``
    times, a linear blend by dimension index between."""
    dim, theta = int(head_dim * rope.partial_rotary_factor), rope.rope_theta
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.rope_type != "yarn":
        return inv

    def dim_turning(turns):
        return dim * math.log(rope.original_max_position_embeddings / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_turning(rope.beta_fast)), 0)
    high = min(math.ceil(dim_turning(rope.beta_slow)), dim - 1)
    blend = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv * (1 - blend) + inv / rope.factor * blend


def _rope(x, inv, amp: float):
    """``x [S, hd]`` at positions 0..S-1: the first ``2 * len(inv)``
    dimensions rotated by halves, cos and sin times ``amp``, the rest as they are."""
    rot = 2 * len(inv)
    phase = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(phase) * amp, jnp.sin(phase) * amp
    a, b = x[:, :rot // 2], x[:, rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[:, rot:]], -1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def route(x, w_g, bias, cfg):
    """``[S, E]`` routing weights, zero where an expert is not chosen: sigmoid
    scores, the ``num_experts_per_tok`` largest of ``s + bias`` (ties to the
    lower index), ``s`` at the chosen normalised over them, times the scaling."""
    s = np.asarray(jax.nn.sigmoid(x @ _f(w_g)), np.float64)
    choice = s + np.asarray(bias, np.float64)[None, :]
    weights = np.zeros_like(s)
    for t in range(s.shape[0]):
        chosen = np.argsort(-choice[t], kind="stable")[:cfg.num_experts_per_tok]
        w = s[t, chosen]
        if cfg.norm_topk_prob:
            w = w / (w.sum() + 1e-20)
        weights[t, chosen] = w * cfg.moe_routed_scaling_factor
    return weights


def attention(h, p, cfg, kind: str, heads: int, *, sliding_as_full=False, gate=True):
    a, hd, K = p["attn"], cfg.head_dim, cfg.num_kv_heads
    S = h.shape[0]
    x = _norm(h, p["input_norm"]["scale"], cfg.rms_norm_eps)
    q = (x @ _f(a["wq"]["kernel"])).reshape(S, heads, hd)
    k = (x @ _f(a["wk"]["kernel"])).reshape(S, K, hd)
    v = (x @ _f(a["wv"]["kernel"])).reshape(S, K, hd)
    rope = cfg.rope_of(kind)
    inv = inv_freq(rope, hd)
    amp = rope.attention_factor if rope.rope_type == "yarn" else 1.0
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = j <= i
    if kind == "sliding_attention" and not sliding_as_full:
        live = live & (i - j < cfg.sliding_window)
    g = jax.nn.softplus(x @ _f(a["wg"]["kernel"]))  # [S, heads]
    kv_of = np.arange(heads) * K // heads  # query head n reads KV head floor(n K / heads)
    qr = jnp.stack([_rope(q[:, n], inv, amp) for n in range(heads)], 1)  # [S, heads, hd]
    kr = jnp.stack([_rope(k[:, n], inv, amp) for n in range(K)], 1)[:, kv_of]
    scores = jnp.where(live[None], jnp.einsum("snd,tnd->nst", qr, kr) / math.sqrt(hd), -jnp.inf)
    out = jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v[:, kv_of])  # [S, heads, hd]
    if gate:
        out = out * g[:, :, None]
    return h + out.reshape(S, heads * hd) @ _f(a["wo"]["kernel"])


def moe(x, mlp, experts, held, cfg):
    """``sum_{e in held, chosen} w_e E_e(x) + E_shared(x)``; ``experts`` are the
    three ``[len(held), ...]`` stacks of one layer, in ``held``'s order."""
    w = route(x, mlp["router"]["kernel"], mlp["router_bias"], cfg)
    y = jnp.zeros_like(x)
    for n, e in enumerate(held):
        y = y + jnp.asarray(w[:, e:e + 1], jnp.float32) * _swiglu(x, experts[0][n], experts[1][n], experts[2][n])
    sh = mlp["shared"]
    return y + _swiglu(x, sh["w_gate"]["kernel"], sh["w_up"]["kernel"], sh["w_down"]["kernel"])


def layer_params(params, cfg, i: int):
    """Layer ``i``'s own tree out of the program's layout."""
    if i < cfg.num_lead:
        return params[f"lead_{i}"]
    period, at = divmod(i - cfg.num_lead, cfg.period)
    return jax.tree.map(lambda a: a[period], params["periods"][f"l{at}"])


def forward(params, cfg, tokens, held=None, *, sliding_as_full=False, gate=True) -> np.ndarray:
    """Logits ``[S, V]`` of every position of ``tokens``."""
    if held is None:
        held = list(range(cfg.first_held, cfg.first_held + cfg.experts_held))
    with jax.default_matmul_precision("highest"):
        h = _f(params["embedding"])[jnp.asarray(tokens)]
        for i in range(cfg.num_layers):
            p = layer_params(params, cfg, i)
            h = attention(h, p, cfg, cfg.layer_types[i], cfg.num_attention_heads_per_layer[i],
                          sliding_as_full=sliding_as_full, gate=gate)
            x = _norm(h, p["post_attn_norm"]["scale"], cfg.rms_norm_eps)
            if cfg.mlp_layer_types[i] == "dense":
                m = p["mlp"]
                h = h + _swiglu(x, m["w_gate"]["kernel"], m["w_up"]["kernel"], m["w_down"]["kernel"])
            else:
                ex = tuple(params["experts"][n][i - cfg.num_lead] for n in ("w_gate", "w_up", "w_down"))
                h = h + moe(x, p["mlp"], ex, held, cfg)
        h = _norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return np.asarray(h @ _f(params["lm_head"]))

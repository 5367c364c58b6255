"""The plain reference of the gated short-convolution, sparse-expert decoder,
where tier 1 can import it (``benchmark/references/lfm2_moe.py`` is the
benchmark's own copy; ``benchmark/tests/test_lfm2_moe_family.py`` holds the
two to each other).

One function, ``forward``: the whole sequence at once in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, no cache, no
kernels, no batching, the experts a loop over every expert. For ``x`` the
residual stream and ``RMS(h; g) = h / sqrt(mean(h^2) + eps) * g``:

1. every layer: ``h = x + Op(RMS(x; operator_norm))``, ``y = h + FFN(RMS(h;
   ffn_norm))``;
2. a ``conv`` layer: ``[B, C, u] = n W_in`` (three vectors of the hidden size,
   in that order); ``z = B * u``; ``c_t = sum_j w_j * z_{t - (L - 1) + j}``
   over the ``L`` taps (zeros before the first token; no bias, no
   activation); ``Op = (C * c) W_out``;
3. a ``full_attention`` layer: ``q = n W_q`` (heads of ``hd``), ``k = n W_k``,
   ``v = n W_v`` (the KV heads); ``q`` and ``k`` RMS-normed over the head
   (one ``[hd]`` scale for every head), THEN rotated by halves over the whole
   head at ``rope_theta``; causal softmax at scale ``hd^-1/2``; ``o W_o``;
4. the FFN of the first ``num_dense_layers`` layers: ``(silu(n W_1) * n W_3)
   W_2``; of every later one: ``s = sigmoid(n W_g)``, the top
   ``num_experts_per_tok`` of ``s + b`` chosen (ties to the lower index), the
   weights ``s`` at the chosen over ``(their sum + 1e-6)``, times the scaling;
   ``sum_i w_i E_i(n)`` over the experts ``held`` (all of them by default);
5. ``RMS(x; embedding_norm)`` and the logits against the head (the embedding
   transposed when tied).

It takes nothing from the program but the parameter tree (``lead_<i>``,
``periods/l<i>`` stacked over the trips, ``tail_<i>``, ``experts`` stacked
``[sparse layers, held, ...]``).

The faults the tests must see fail, each a keyword: ``drop_conv_at=t`` (the
kept inputs zero in front of position ``t``: a hand-over from prefill to
decode that loses them), ``pads=n`` (``n`` pad tokens run through the conv
layers in front of the sequence, unmasked), ``taps_reversed``, ``qk_norm=False``,
``bias_in_weights`` (weights from ``s + b``), ``normed=False`` (the chosen
scores not normalised), ``conv_silu`` (an activation behind the taps),
``norm_after_rope`` (q and k normed behind the rotation), ``fp8="experts"`` /
``fp8="all"`` (both operands of the experts' matmuls, or of every matmul but
the router's, rounded to ``float8_e4m3fn``: the precision under bf16).
"""

import jax
import jax.numpy as jnp
import numpy as np


def _f(w):
    return jnp.asarray(w, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(g)


def _rope(x, theta, first=0):
    """``x [S, heads, hd]`` rotated by halves; position 0 is index ``first``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    phase = jnp.asarray(np.maximum(np.arange(x.shape[0]) - first, 0)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(phase)[:, None, :], jnp.sin(phase)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _round(x):
    """A row at a time to ``float8_e4m3fn`` and back, one scale a row."""
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    return (x * (448.0 / top)).astype(jnp.float8_e4m3fn).astype(jnp.float32) * (top / 448.0)


def _mm(x, w, low=False):
    """``x @ w``; ``low`` rounds both operands first: the input a token, the
    weight an output channel."""
    return _round(x) @ _round(_f(w).T).T if low else x @ _f(w)


def _swiglu(x, gate, up, down, low=False):
    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


def conv_operator(n, p, cfg, *, drop_conv_at=None, first=0, taps_reversed=False, conv_silu=False, low=False):
    """``n [S, D]`` normed -> the gated short convolution's output ``[S, D]``;
    positions in front of ``first`` are pads whose gated input is zero."""
    S, D = n.shape
    L = cfg.conv_L_cache
    bcu = _mm(n, p["in_proj"]["kernel"], low)
    gate_in, gate_out, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
    t = np.arange(S)
    z = jnp.where(jnp.asarray(t >= first)[:, None], gate_in * u, 0.0)
    padded = jnp.concatenate([jnp.zeros((L - 1, D), jnp.float32), z], axis=0)
    w = _f(p["conv_w"])[::-1] if taps_reversed else _f(p["conv_w"])
    acc = jnp.zeros((S, D), jnp.float32)
    for j in range(L):
        tap = padded[j:j + S]  # the input at t - (L - 1) + j
        if drop_conv_at is not None:  # inputs in front of the hand-over are lost to outputs behind it
            lost = (t >= drop_conv_at) & (t - (L - 1) + j < drop_conv_at)
            tap = jnp.where(jnp.asarray(lost)[:, None], 0.0, tap)
        acc = acc + w[j][None] * tap
    if conv_silu:
        acc = jax.nn.silu(acc)
    return _mm(gate_out * acc, p["out_proj"]["kernel"], low)


def attention_operator(n, p, cfg, *, first=0, qk_norm=True, norm_after_rope=False, low=False):
    S = n.shape[0]
    H, K = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = cfg.hidden_size // H
    q = _mm(n, p["wq"]["kernel"], low).reshape(S, H, hd)
    k = _mm(n, p["wk"]["kernel"], low).reshape(S, K, hd)
    v = _mm(n, p["wv"]["kernel"], low).reshape(S, K, hd)
    norm = lambda x, name: _rms(x, p[name]["scale"], cfg.norm_eps)  # noqa: E731
    if qk_norm and not norm_after_rope:
        q, k = norm(q, "q_norm"), norm(k, "k_norm")
    q, k = _rope(q, cfg.rope_theta, first), _rope(k, cfg.rope_theta, first)  # positions count from the first real token
    if qk_norm and norm_after_rope:
        q, k = norm(q, "q_norm"), norm(k, "k_norm")
    t = np.arange(S)
    mask = (t[None, :] <= t[:, None]) & ((t[None, :] >= first) | (t[None, :] == t[:, None]))
    s = jnp.einsum("qkgd,tkd->kgqt", q.reshape(S, K, H // K, hd), k) * hd ** -0.5
    a = jax.nn.softmax(jnp.where(jnp.asarray(mask)[None, None], s, -jnp.inf), axis=-1)
    return _mm(jnp.einsum("kgqt,tkd->qkgd", a, v).reshape(S, H * hd), p["wo"]["kernel"], low)


def route(n, mlp, cfg, *, bias_in_weights=False, normed=True, eps=1e-6):
    """``[S, E]`` routing weights, zero where an expert is not chosen (an
    argmax a choice: ties to the lower index)."""
    s = jax.nn.sigmoid(n @ _f(mlp["router"]["kernel"]))
    choice = s + _f(mlp["router_bias"])[None, :]
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(cfg.num_experts_per_tok):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, choice), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, s.shape[-1], dtype=bool)
    w = jnp.where(chosen, choice if bias_in_weights else s, 0.0)
    if cfg.norm_topk_prob and normed:
        w = w / (w.sum(-1, keepdims=True) + eps)
    return w * cfg.routed_scaling_factor


def moe(n, mlp, experts, held, cfg, low=False, **faults):
    """``sum_{e in held, chosen} w_e E_e(n)``; ``experts`` are the three
    ``[len(held), ...]`` stacks of one layer, in ``held``'s order."""
    w = route(n, mlp, cfg, **faults)
    y = jnp.zeros_like(n)
    for i, e in enumerate(held):
        y = y + w[:, e:e + 1] * _swiglu(n, experts[0][i], experts[1][i], experts[2][i], low)
    return y


def layer_params(params, cfg, i: int):
    """Layer ``i``'s own tree out of the program's layout."""
    if i < cfg.num_lead:
        return params[f"lead_{i}"]
    if i >= cfg.num_layers - cfg.num_tail:
        return params[f"tail_{i - (cfg.num_layers - cfg.num_tail)}"]
    trip, at = divmod(i - cfg.num_lead, cfg.period)
    return jax.tree.map(lambda a: a[trip], params["periods"][f"l{at}"])


def forward(params, cfg, ids, held=None, *, pads=0, drop_conv_at=None, taps_reversed=False, conv_silu=False,
            qk_norm=True, norm_after_rope=False, fp8="", **routing):
    """``[S, vocab]`` float32 logits of the sequence ``ids`` (with ``pads``,
    of the ``pads`` pad tokens and then the sequence)."""
    held = range(cfg.first_held, cfg.first_held + cfg.experts_held) if held is None else held
    low = fp8 == "all"
    with jax.default_matmul_precision("highest"):
        x = _f(params["embedding"])[jnp.concatenate([jnp.zeros((pads,), jnp.int32), jnp.asarray(ids, jnp.int32)])]
        for i, kind in enumerate(cfg.layer_types):
            p = layer_params(params, cfg, i)
            n = _rms(x, p["operator_norm"]["scale"], cfg.norm_eps)
            if kind == "conv":
                x = x + conv_operator(n, p["shortconv"], cfg, drop_conv_at=drop_conv_at, taps_reversed=taps_reversed,
                                      conv_silu=conv_silu, low=low)
            else:
                x = x + attention_operator(n, p["attn"], cfg, first=pads, qk_norm=qk_norm,
                                           norm_after_rope=norm_after_rope, low=low)
            n = _rms(x, p["ffn_norm"]["scale"], cfg.norm_eps)
            if i < cfg.num_lead:
                m = p["mlp"]
                x = x + _swiglu(n, m["w_gate"]["kernel"], m["w_up"]["kernel"], m["w_down"]["kernel"], low)
            else:
                experts = tuple(params["experts"][name][i - cfg.num_lead] for name in ("w_gate", "w_up", "w_down"))
                local = [e - cfg.first_held for e in held]
                x = x + moe(n, p["mlp"], tuple(w[jnp.asarray(local)] for w in experts), held, cfg,
                            low=bool(fp8), **routing)
        h = _rms(x, params["embedding_norm"]["scale"], cfg.norm_eps)
        head = _f(params["embedding"]).T if cfg.tie_word_embeddings else _f(params["lm_head"])
        return _mm(h, head, low)[pads:]

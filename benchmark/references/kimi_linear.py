"""The plain reference of ``model_type: "kimi_linear"``: the gated delta-rule,
sparse-expert decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, the
recurrence a TOKEN at a time (no chunk form), no cache, no kernels, no
batching, one sequence and one layer's weights at a time, the experts a LOOP
over those held (each upcast when it is used, each over every token, weighted
by the router's weight or zero), so that it fits beside the 8.6 GB the
service holds. The share is the configuration's: the router scores all 256
experts, weights are normalised over all chosen, only experts ``ep_rank *
held .. + held`` are summed, and what the absent ones would add is left out,
as in the program.

Published block (h 2304), for ``x`` the residual stream in float32 and
``RMS(h; g) = h / sqrt(mean(h^2) + 1e-5) * g``:

1. every layer: ``h = x + Mixer(RMS(x; input_norm))``, ``y = h + FFN(RMS(h;
   post_attn_norm))``;
2. a linear layer (``linear_attn_config.kda_layers``, 1-indexed): ``[q | k |
   v] = n W_qkv`` (three of 32 x 128), each channel through a causal
   convolution of 4 taps (zeros in front of the first token, no bias) and a
   SiLU; per head ``q = q / |q| * 128^-1/2``, ``k = k / |k|``; ``g =
   -exp(A_log_h) softplus(W_fb (W_fa n) + dt_bias)`` a channel, ``beta =
   sigmoid(W_b n)`` a head; from ``S = 0``: ``S' = exp(g) * S`` (a key channel
   a row), ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q``; ``W_o [RMS(o;
   o_norm) * sigmoid(W_gb (W_ga n))]``;
3. a full layer: ``q = n W_q`` (32 heads of 128 + 64), ``[c | r] = n W_dkv``
   (512 + 64), ``c`` RMS-normed; ``k_h = [W_uk,h c ; r]``, ``v_h = W_uv,h c``;
   causal softmax at scale ``192^-1/2``; NOTHING is rotated (``mla_use_nope``);
4. layer 0: ``FFN = (silu(n W_1) * n W_3) W_2`` (9216 wide); every later
   layer: ``s = sigmoid(n W_g)`` over 256 outputs, the 8 largest of ``s + b``
   chosen (an argmax a choice: ties to the lower index; one group, so no group
   limit), ``w = s`` at the chosen over ``(their sum + 1e-20)`` times 2.446,
   ``FFN = sum_{e chosen and held} w_e E_e(n) + E_shared(n)``;
5. ``RMS(x; final_norm)``, then the logits against the served (untied) head.

The tree is the program's (``models/delta_moe.py``: ``kda_layers`` and
``mla_layers`` stacked by kind, ``lead_<i>``, ``layers`` stacked over the
sparse layers, ``experts`` ``[sparse layers, held, ...]``).

CONTROLS, for the tolerances (``score(control=...)``;
``tests/controls_kimi_linear.py`` reads them on the chip over every distinct
request the cell itself finished): ``no_decay`` (alpha = 1), ``scalar_decay``
(a head's mean log decay on every channel), ``beta_one``, ``no_l2norm``,
``bf16_state`` (the state rounded to bf16 behind every token), ``taps_reversed``,
``rotated`` (the 64-wide slices rotated by position at ``rope_theta``),
``bias_in_weights`` (the weights from ``s + b``), ``clamped_inverse`` (the
recurrence in chunks of 64 with ``k_j / exp(G_j)`` formed alone and clamped at
``exp(30)``: the form ``ops/delta_rule.py`` must not take), ``commit_short``
(the state misses every delivered token's correction: a verify loop whose
every ``commit`` is told one position fewer than it kept) and ``fp8_matmuls``
(the WHOLE reference one precision down: both operands of every matmul but
the router's rounded to ``float8_e4m3fn``, the next floating-point format
under the bf16 the configuration states).
"""

from __future__ import annotations

import functools

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths (PR 49, PERF.md section 6; my chip runs of
# ``tests/controls_kimi_linear.py --audits 12``: 8 distinct (prompt, answer)
# pairs of the cell's own, 3355 to 3463 prompt tokens, at ``ep_rank`` 0 and at
# the file's 12, and the four audits of every plain run of the cell). Sound: the
# exact path's logit of a delivered token is 0.044 to 0.083 from the
# reference's, the reference's half gap 0.013 to 0.038. With the reference
# computed under a control, against the same exact path (logit error; half
# gap; requests a limit below refuses):
#   no_decay          4.457 to 5.834   2.568 to 2.965   8 of 8 over each limit
#   taps_reversed     3.357 to 4.781   1.614 to 2.311   8 of 8 over each
#   commit_short      2.892 to 3.973   1.633 to 2.299   8 of 8 over each
#   scalar_decay      2.485 to 3.265   1.289 to 1.952   8 of 8 over each
#   beta_one          2.017 to 2.842   1.152 to 1.451   8 of 8 over each
#   clamped_inverse   0.402 to 0.664   0.237 to 0.543   8 of 8 over each
#   fp8_matmuls       0.381 to 0.489   0.196 to 0.287   8 of 8 over each
#   rotated           0.374 to 0.567   0.186 to 0.311   8 of 8 over each
#   no_l2norm         no number: a key longer than sqrt(2 / beta) makes the correction DIVERGE (NaN), which
#                     run.py's ``<=`` refuses (the walk's ``>`` prints "0 of 8" beside it)
#   bf16_state        0.064 to 0.085   0.017 to 0.049   0 of 8: moves a logit by 0.039 to 0.061, INSIDE the sound band
#   bias_in_weights   0.052 to 0.084   0.013 to 0.042   0 of 8: moves 0.013 to 0.030, inside it
# ``LOGIT_TOL`` lies between the two readings the limit is owed to: 2.1 times
# over the largest sound one (0.083) and 2.1 times under the smallest of a
# control that fails (``rotated`` 0.374; the reference one precision down,
# ``fp8_matmuls``, 0.381). ``HALF_GAP_TOL`` is 2.1 times the largest sound half
# gap (0.0375) and 2.3 times under the smallest of ``rotated`` (0.186;
# ``fp8_matmuls`` 0.196). Two controls are NOT refused and nothing in this cell
# guards what they break. The state kept in bf16 rounds 2.1 MB a row-layer
# behind every token, but this draw's memories are short (alpha's median is
# 0.93: fourteen tokens) and the program itself computes q, k, v, the decay
# and every projection in bf16, so the state's rounding adds 0.04 to 0.06 to a
# logit where the program's own distance is 0.05 to 0.08: a limit between
# them would refuse sound runs. A weight taken from score plus bias (std 0.1
# against scores near 0.9) moves the held experts' term by less than one
# expert swapped at the top-8's edge does, as in the other sparse families'
# cells. Tier 1 holds both in float32, where neither hides: the state's type
# and shape, the chunk form against a float64 recurrence, and the routing rule
# bit for bit (tests/test_delta_rule.py, tests/test_kimi_linear.py).
HALF_GAP_TOL = 0.08  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.175  # the exact path's logit of a delivered token against the reference's

# the faults the limits above are held against (tests/controls_kimi_linear.py)
CONTROLS = ("no_decay", "scalar_decay", "beta_one", "no_l2norm", "bf16_state", "taps_reversed", "rotated",
            "bias_in_weights", "clamped_inverse", "commit_short", "fp8_matmuls")
ATTN_BLOCK = 512  # queries a full layer scores at once: [32, 512, S] float32
PAD_TO = 256  # a sequence is padded on the right to a multiple (causal: a pad changes nothing before it)
CLAMP_CHUNK, CLAMP_AT = 64, 30.0  # ``clamped_inverse``: the chunk, and the largest log of 1 / exp(G) it forms


def _mm(x, w, low: bool = False):
    """``x @ w`` in float32 at the highest precision; ``low`` (``fp8_matmuls``)
    rounds BOTH operands first: the input a token, the weight an output channel."""
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if low:
        x, w = _round(x), _round(w.T).T
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _round(x):
    """Round a row at a time to ``float8_e4m3fn`` and back, one scale a row
    (symmetric): 3 bits of mantissa, largest 448."""
    import jax.numpy as jnp

    top = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    return (x * (448.0 / top)).astype(jnp.float8_e4m3fn).astype(jnp.float32) * (top / 448.0)


def _rms(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _swiglu(x, gate, up, down, low: bool = False):
    import jax

    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


def _recurrence(q, k, v, g, beta, control: str):
    """``o [S, H, dv]`` of the delta rule a token at a time from a zero state
    (``q, k, g [S, H, dk]``, ``v [S, H, dv]``, ``beta [S, H]``): elementwise
    float32 products, no matmul unit."""
    import jax
    import jax.numpy as jnp

    def step(state, x):  # state [H, dk, dv]
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.sum(state * k[..., None], axis=1))
        state = state + k[..., None] * u[:, None, :]
        if control == "bf16_state":  # (a convert there and back is dropped as excess precision)
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * q[..., None], axis=1)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, g, beta))[1]


def _recurrence_clamped(q, k, v, g, beta):
    """The FAULT of ``clamped_inverse``: chunks of ``CLAMP_CHUNK`` with the
    decay split into ``exp(G_i)`` and a clamped ``1 / exp(G_j)``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    S, H, dk = q.shape
    C = CLAMP_CHUNK
    at = jnp.arange(C)

    def chunk(state, x):
        q, k, v, g, beta = x  # [C, H, ...]
        G = jnp.cumsum(g, axis=0)
        up, down = jnp.exp(G), jnp.exp(jnp.minimum(-G, CLAMP_AT))
        kk = jnp.einsum("ihc,jhc->hij", k * up, k * down, precision=hi)
        qk = jnp.einsum("ihc,jhc->hij", q * up, k * down, precision=hi)
        system = jnp.where(at[:, None] > at[None, :], beta.T[:, :, None] * kk, 0.0) + jnp.eye(C)
        rhs = beta.T[..., None] * (jnp.swapaxes(v, 0, 1) - jnp.einsum("ihc,hcv->hiv", k * up, state, precision=hi))
        u = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("ihc,hcv->hiv", q * up, state, precision=hi) + jnp.einsum(
            "hij,hjv->hiv", jnp.where(at[:, None] >= at[None, :], qk, 0.0), u, precision=hi)
        carried = k * jnp.exp(G[-1:] - G)
        state = state * up[-1][..., None] + jnp.einsum("jhc,hjv->hcv", carried, u, precision=hi)
        return state, jnp.swapaxes(o, 0, 1)

    xs = tuple(a.reshape((S // C, C) + a.shape[1:]) for a in (q, k, v, g, beta))
    o = jax.lax.scan(chunk, jnp.zeros((H, dk, v.shape[2]), jnp.float32), xs)[1]
    return o.reshape((S,) + o.shape[2:])


@functools.lru_cache(maxsize=None)
def _linear_fn(control: str, heads: int, head_dim: int, eps: float):
    """One linear layer's mixer half for ``x [S, D]``: ``(x + Mixer, the
    normed stream the FFN reads, the 5th / 50th / 95th percentile of alpha
    over the real positions)``. ``handover`` is the first position a decode
    step fed; positions from ``total`` on are the right pad."""
    import jax
    import jax.numpy as jnp

    H, hd = heads, head_dim
    low = control == "fp8_matmuls"

    def layer(x, layer_norms, p, handover, total):
        S = x.shape[0]
        n = _rms(x, layer_norms["input_norm"]["scale"], eps)
        w = p["conv_w"].astype(jnp.float32)
        w = w[::-1] if control == "taps_reversed" else w
        K = w.shape[0]
        run = jnp.concatenate([jnp.zeros((K - 1, w.shape[1]), jnp.float32), _mm(n, p["wqkv"]["kernel"], low)], axis=0)
        acc = jnp.zeros((S, w.shape[1]), jnp.float32)
        for j in range(K):  # the input at t - (K - 1) + j
            acc = acc + w[j][None] * jax.lax.dynamic_slice_in_dim(run, j, S, axis=0)
        q, k, v = (a.reshape(S, H, hd) for a in jnp.split(jax.nn.silu(acc), 3, axis=-1))
        if control != "no_l2norm":
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        q = q * hd ** -0.5
        lift = _mm(_mm(n, p["f_a"]["kernel"], low), p["f_b"]["kernel"], low).reshape(S, H, hd)
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[None, :, None] * jax.nn.softplus(lift + p["dt_bias"][None])
        t = jnp.arange(S)
        alpha = jnp.nanpercentile(jnp.where((t < total)[:, None, None], jnp.exp(g), jnp.nan).reshape(-1),
                                  jnp.asarray([5.0, 50.0, 95.0])) if control == "" else jnp.zeros(3)
        if control == "no_decay":
            g = jnp.zeros_like(g)
        if control == "scalar_decay":
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(_mm(n, p["b_proj"]["kernel"], low))
        if control == "beta_one":
            beta = jnp.ones_like(beta)
        if control == "commit_short":  # a delivered token's correction never reaches the state
            fed = (t >= handover)[:, None]
            g, beta = jnp.where(fed[..., None], 0.0, g), jnp.where(fed, 0.0, beta)
        if control == "clamped_inverse":
            o = _recurrence_clamped(q, k, v, g, beta)
        else:
            o = _recurrence(q, k, v, g, beta, control)
        gate = jax.nn.sigmoid(_mm(_mm(n, p["g_a"]["kernel"], low), p["g_b"]["kernel"], low)).reshape(S, H, hd)
        x = x + _mm((_rms(o, p["o_norm"], eps) * gate).reshape(S, H * hd), p["wo"]["kernel"], low)
        return x, _rms(x, layer_norms["post_attn_norm"]["scale"], eps), alpha

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _full_fn(control: str, heads: int, latent: int, nope: int, rope: int, value: int, theta: float, eps: float,
             rotate: bool):
    """One full layer's mixer half for ``x [S, D]``, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    H, C, dn, R, dv = heads, latent, nope, rope, value
    low = control == "fp8_matmuls"

    def turn(x):  # by halves at positions 0..S-1
        inv = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
        phase = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
        phase = phase.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (R // 2,))
        a, b = x[..., :R // 2], x[..., R // 2:]
        return jnp.concatenate([a * jnp.cos(phase) - b * jnp.sin(phase), b * jnp.cos(phase) + a * jnp.sin(phase)], -1)

    def layer(x, layer_norms, p, handover, total):
        S = x.shape[0]
        n = _rms(x, layer_norms["input_norm"]["scale"], eps)
        q = _mm(n, p["wq"]["kernel"], low).reshape(S, H, dn + R)
        both = _mm(n, p["wkv_a"]["kernel"], low)
        c, r = _rms(both[:, :C], p["kv_norm"]["scale"], eps), both[:, C:]
        q_n, q_r = q[..., :dn], q[..., dn:]
        if rotate or control == "rotated":
            q_r, r = turn(q_r), turn(r)
        kv = _mm(c, p["wkv_b"]["kernel"], low).reshape(S, H, dn + dv)
        at = jnp.arange(S)
        outs = []
        for lo in range(0, S, ATTN_BLOCK):
            rows = at[lo:lo + ATTN_BLOCK]
            s = (jnp.einsum("qhd,khd->hqk", q_n[lo:lo + ATTN_BLOCK], kv[..., :dn], precision=hi)
                 + jnp.einsum("qhd,kd->hqk", q_r[lo:lo + ATTN_BLOCK], r, precision=hi)) * (dn + R) ** -0.5
            w = jax.nn.softmax(jnp.where((at[None, :] <= rows[:, None])[None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", w, kv[..., dn:], precision=hi).reshape(-1, H * dv))
        x = x + _mm(jnp.concatenate(outs, axis=0), p["wo"]["kernel"], low)
        return x, _rms(x, layer_norms["post_attn_norm"]["scale"], eps), jnp.zeros(3)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _route_fn(control: str, top_k: int, scaling: float, normalize: bool):
    """``[S, E]`` weights (zero where not chosen) by the published rule,
    written with an argmax loop (ties to the lower index), not ``top_k``."""
    import jax
    import jax.numpy as jnp

    def route(n, w_g, bias):
        s = jax.nn.sigmoid(_mm(n, w_g))
        choice = s + bias.astype(jnp.float32)[None, :]
        chosen = jnp.zeros(s.shape, bool)
        for _ in range(top_k):
            i = jnp.argmax(jnp.where(chosen, -jnp.inf, choice), axis=-1)
            chosen = chosen | jax.nn.one_hot(i, s.shape[-1], dtype=bool)
        w = jnp.where(chosen, choice if control == "bias_in_weights" else s, 0.0)
        if normalize:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * scaling, chosen

    return jax.jit(route)


@functools.lru_cache(maxsize=None)
def _expert_fn(low: bool):
    import jax

    return jax.jit(lambda n, w, y, gate, up, down: y + w[:, None] * _swiglu(n, gate, up, down, low))


@functools.lru_cache(maxsize=None)
def _dense_fn(low: bool):
    import jax

    return jax.jit(lambda n, m: _swiglu(n, m["w_gate"]["kernel"], m["w_up"]["kernel"], m["w_down"]["kernel"], low))


def moe_layer(n, mlp, stacks, at: int, cfg, control: str = "", chosen_log=None):
    """``sum_{e chosen and held} w_e E_e(n) + E_shared(n)`` for ``n [S, D]``:
    ``mlp`` is one layer's ``router`` / ``router_bias`` / ``shared``;
    ``stacks`` the served ``(w_gate, w_up, w_down)`` ``[sparse layers, held,
    ...]``, read at layer ``at`` an expert at a time. ``chosen_log`` (a list)
    is given the ``[S, E]`` mask of who was chosen, over ALL the experts."""
    import jax.numpy as jnp

    w, chosen = _route_fn(control, int(cfg["num_experts_per_token"]), float(cfg["routed_scaling_factor"]),
                          bool(cfg.get("moe_renormalize", True)))(n, mlp["router"]["kernel"], mlp["router_bias"])
    if chosen_log is not None:
        chosen_log.append(chosen)
    held = stacks[0].shape[1]
    first = int(cfg.get("ep_rank", 0)) * held
    low = control == "fp8_matmuls"
    y = jnp.zeros_like(n)
    for e in range(held):  # a loop over the experts held here, each over every token
        y = _expert_fn(low)(n, w[:, first + e], y, *(stack[at, e] for stack in stacks))
    if int(cfg.get("num_shared_experts", 0)):
        y = y + _dense_fn(low)(n, mlp["shared"])
    return y


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, low: bool):
    import jax
    import jax.numpy as jnp

    def head(h, g, lm_head, chosen):
        logits = _mm(_rms(h, g, eps), lm_head, low)
        return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
                jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])

    return jax.jit(head)


def score(params: dict, cfg: dict, sequences, device, *, control: str = "", route_log=None,
          alpha_log=None) -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``DeltaMoEModel`` tree; each
    layer (and each expert) is brought to ``device`` when it is used.
    ``control`` computes the reference under one of ``CONTROLS``.
    ``route_log`` (a list) is given, for every sequence and sparse layer, how
    often each of ALL the experts was chosen by the tokens the program
    PREFILLS (the prompt) and by those it DECODES (every delivered token but
    the last); ``alpha_log`` (a list) every linear layer's 5th / 50th / 95th
    percentile of ``alpha = exp(g)`` over a sequence's positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "kda_layers" not in params or "mla_layers" not in params or "lm_head" not in params:
        raise ValueError("the reference reads the DeltaMoEModel parameter layout")

    def put(x):
        return jax.device_put(x, device)

    eps = float(cfg["rms_norm_eps"])
    la = cfg["linear_attn_config"]
    full = set(int(i) - 1 for i in la["full_attn_layers"])
    lead, depth = int(cfg["first_k_dense_replace"]), int(cfg["num_hidden_layers"])
    rows = []
    for prompt, emitted in sequences:
        ids = [int(t) for t in prompt] + [int(t) for t in emitted]
        rows.append((ids + [0] * (-len(ids) % PAD_TO), len(prompt), len(ids)))
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(ids, jnp.int32))].astype(jnp.float32) for ids, *_ in rows]
    del embedding
    linear = _linear_fn(control, int(la["num_heads"]), int(la["head_dim"]), eps)
    latent = _full_fn(control, int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
                      int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
                      float(cfg.get("rope_theta", 10000.0)), eps, not bool(cfg.get("mla_use_nope", True)))
    stacks = tuple(params["experts"][name] for name in ("w_gate", "w_up", "w_down")) if depth > lead else ()
    ki = mi = 0
    for i in range(depth):
        layer = jax.tree_util.tree_map(put, params[f"lead_{i}"]) if i < lead else jax.tree_util.tree_map(
            lambda a: put(a[i - lead]), params["layers"])
        if i in full:
            mixer, p = latent, jax.tree_util.tree_map(lambda a: put(a[mi]), params["mla_layers"])
            mi += 1
        else:
            mixer, p = linear, jax.tree_util.tree_map(lambda a: put(a[ki]), params["kda_layers"])
            ki += 1
        norms = {k: layer[k] for k in ("input_norm", "post_attn_norm")}
        out = []
        for n_seq, (h, (_, handover, total)) in enumerate(zip(hs, rows)):
            h, n, alpha = mixer(h, norms, p, jnp.int32(handover), jnp.int32(total))
            if alpha_log is not None and i not in full and not control:
                alpha_log.append({"sequence": n_seq, "layer": i, "alpha_p5_p50_p95": [float(a) for a in alpha]})
            if i < lead:
                out.append(h + _dense_fn(control == "fp8_matmuls")(n, layer["mlp"]))
                continue
            chosen = [] if route_log is not None else None
            out.append(h + moe_layer(n, layer["mlp"], stacks, i - lead, cfg, control, chosen))
            if chosen:
                mask = np.asarray(chosen[0])
                route_log.append({"sequence": n_seq, "layer": i,
                                  "prefill_tokens": handover, "prefill": mask[:handover].sum(0),
                                  "decode_tokens": total - 1 - handover, "decode": mask[handover:total - 1].sum(0)})
        hs = out
    head = _head_fn(eps, control == "fp8_matmuls")
    g, lm_head = put(params["final_norm"]["scale"]), put(params["lm_head"])
    result = []
    for h, (_, _, n), (_, emitted) in zip(hs, rows, sequences):
        w = len(emitted)
        lo = n - w - 1  # the position whose logits predict emitted[0]
        argmax, top, chosen = head(h[lo:lo + w], g, lm_head, put(jnp.asarray([int(x) for x in emitted], jnp.int32)))
        result.append({"argmax": np.asarray(argmax).astype(np.int64),
                       "max_logit": np.asarray(top).astype(np.float64),
                       "chosen_logit": np.asarray(chosen).astype(np.float64)})
    return result

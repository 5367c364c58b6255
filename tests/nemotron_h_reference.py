"""The plain reference of ``model_type: "nemotron_h"``: the Mamba-2 /
attention / latent-expert decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, the
Mamba-2 recurrence a POSITION at a time (no chunk form: this is the chunk
form's oracle), no cache, no kernels, no batching, one sequence and one
layer's weights at a time, the experts a LOOP over those held (each upcast
when it is used, each over every token, weighted by the router's weight or
zero), so that it fits beside the 9.3 GB the service holds. The share is the
configuration's: the router scores all the experts, weights are normalised
over all chosen, only experts ``ep_rank * held .. + held`` are summed, and
what the absent ones would add is left out, as in the program.

Published block (h 4096), for ``x`` the residual stream in float32 and
``RMS(h; g) = h / sqrt(mean(h^2) + eps) * g``: layer ``i`` of kind ``k =
hybrid_override_pattern[i]`` is ``x <- x + F_k(RMS(x; norm_i))``; ONE norm and
one residual a layer, no layer pairs a mixer with a feed-forward part.

- ``M`` (Mamba-2): ``[z | xBC | dt] = n W_in`` (8192 | 10240 | 128); every
  channel of ``xBC`` through a causal convolution of 4 taps (zeros in front of
  the first token) plus a bias, then a SiLU; ``xBC = x [128 heads, 64] | B [8
  groups, 128] | C [8, 128]``, head ``h`` reads group ``h // 16``; ``D_h =
  softplus(dt_h + dt_bias_h)``, ``a_h = D_h * (-exp(A_log_h))``; from ``H =
  0``: ``H_h <- exp(a_h) H_h + D_h x_h (x) B_g``, ``y_h = H_h C_g + D_skip,h
  x_h``; then the gate FIRST, ``y <- y * silu(z)``, the norm SECOND and by
  GROUP, ``y <- y * rsqrt(mean over each group of 1024 channels of y^2 + eps)
  * w``; ``y W_out``.
- ``*`` (attention): 32 query heads of 128 over 2 key/value heads, causal
  softmax at scale ``128^-1/2``; NOTHING is rotated.
- ``E`` (latent experts): ``s = sigmoid(n W_g)`` over 512 outputs, the 22
  largest of ``s + b`` chosen (an argmax a choice: ties to the lower index;
  one group, so no group limit), ``w = s`` at the chosen over (their sum +
  1e-20) times 5; ``l = n W_down`` (4096 -> 1024), ``E_e(l) = relu(l W1_e)^2
  W2_e``, ``F = (sum_{e chosen and held} w_e E_e(l)) W_up + relu(n S1)^2 S2``.
- ``RMS(x; final_norm)``, then the logits against the served (untied) head.

The tree is the program's (``models/ssd_moe.py``: flat names, a kind's leaves
stacked over its layers: ``mamba_*``, ``attn_*``, ``moe_*``, ``experts_*``
``[expert layers, held, ...]``, ``norms [layers, D]``).

CONTROLS, for the tolerances (``score(control=...)``;
``tests/controls_nemotron_h.py`` reads them on the chip over every distinct
request the cell itself finished): ``bf16_state`` (the state rounded to bf16
behind every position), ``ungrouped_norm`` (one RMS over all 8192 channels),
``norm_before_gate``, ``one_group_bc`` (every head reads group 0's ``B`` and
``C``), ``relu_not_squared`` (routed and shared experts), ``scaling_one``
(``routed_scaling_factor`` 1), ``weight_from_biased_score`` (the weights from
``s + b``) and ``fp8_matmuls`` (the WHOLE reference one precision down: both
operands of every matmul but the router's rounded to ``float8_e4m3fn``, the
next floating-point format under the bf16 the configuration states).
"""

from __future__ import annotations

import functools

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths (PR 55, PERF.md section 6; my chip runs of
# ``tests/controls_nemotron_h.py --audits 8``: 5 distinct (prompt, answer)
# pairs of the cell's own, 3133 to 3754 prompt tokens, at ``ep_rank`` 3, and
# the four audits of each of eight plain runs of the cell: 37 sound readings).
# Sound: the exact path's logit of a delivered token is 0.051 to 0.123 from the
# reference's, the reference's half gap 0.004 to 0.066. With the reference
# computed under a control, against the same exact path (logit error; half
# gap; requests a limit below refuses):
#   relu_not_squared          1.409 to 2.009   0.833 to 1.127   5 of 5 over each limit
#   norm_before_gate          1.056 to 1.346   0.618 to 0.773   5 of 5 over each
#   ungrouped_norm            0.920 to 1.046   0.532 to 0.699   5 of 5 over each
#   one_group_bc              0.548 to 0.895   0.327 to 0.502   5 of 5 over each
#   scaling_one               0.263 to 0.324   0.126 to 0.185   5 of 5 over each
#   fp8_matmuls               0.250 to 0.317   0.095 to 0.156   5 of 5 over the logit limit, 4 of 5 over the half gap's
#   bf16_state                0.080 to 0.119   0.026 to 0.042   0 of 5: moves a logit by 0.082 to 0.115, INSIDE the sound band
#   weight_from_biased_score  0.059 to 0.086   0.008 to 0.054   0 of 5: moves 0.046 to 0.068, inside it
# ``LOGIT_TOL`` lies between the two readings it is owed to: 1.42 times over the
# largest sound one (0.123) and 1.43 times under the smallest of the reference
# one precision down (``fp8_matmuls`` 0.250; ``scaling_one`` 0.263).
# ``HALF_GAP_TOL`` is 1.5 times the largest sound half gap (0.066) and 1.26
# times under the smallest of ``scaling_one`` (0.126): a half gap can reach a
# run's logit error where the reference's two best logits nearly tie, so the
# limit keeps more room over the sound readings than ``fp8_matmuls``' smallest
# half gap (0.095) would leave it; that control is the logit limit's. Two
# controls are NOT refused and nothing in this cell guards what they break. The
# state kept in bf16 rounds 4.2 MB a row-layer behind every token, but this
# draw's memories are short (``exp(a)``'s median is 0.93: fourteen tokens) and
# the program itself computes x, B, C, the time step and every projection in
# bf16, so the state's rounding adds 0.08 to 0.12 to a logit where the
# program's own distance is 0.05 to 0.12: a limit between them would refuse
# sound runs. A weight taken from score plus bias (std 0.1 against scores near
# 0.9) moves this chip's 5.5 experts' term by less than one expert swapped at
# the top-22's edge does, as in the other sparse families' cells. Tier 1 holds
# both in float32, where neither hides (tests/test_nemotron_h.py
# ``test_a_control_fails_the_tolerance``, tests/test_ssd.py).
HALF_GAP_TOL = 0.10  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.175  # the exact path's logit of a delivered token against the reference's

# the faults the limits above are held against (tests/controls_nemotron_h.py)
CONTROLS = ("bf16_state", "fp8_matmuls", "ungrouped_norm", "norm_before_gate", "one_group_bc",
            "relu_not_squared", "scaling_one", "weight_from_biased_score")
KINDS = "M*E"
ATTN_BLOCK = 512  # queries an attention layer scores at once: [32, 512, S] float32
PAD_TO = 256  # a sequence is padded on the right to a multiple (causal: a pad changes nothing before it)


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


def _relu2(x, up, down, low: bool, squared: bool = True):
    import jax
    import jax.numpy as jnp

    h = jax.nn.relu(_mm(x, up, low))
    return _mm(jnp.square(h) if squared else h, down, low)


@functools.lru_cache(maxsize=None)
def _mamba_fn(control: str, heads: int, head_dim: int, groups: int, state: int, eps: float):
    """One Mamba-2 layer for ``x [S, D]``: ``(x + mixer, the 5th / 50th / 95th
    percentile of exp(a) over the real positions)``; positions from ``total``
    on are the right pad."""
    import jax
    import jax.numpy as jnp

    H, P, G, N = heads, head_dim, groups, state
    Di = H * P
    low = control == "fp8_matmuls"

    def layer(x, norm, p, total):
        S = x.shape[0]
        n = _rms(x, norm, eps)
        zxd = _mm(n, p["in_proj"], low)
        z, xbc, dt = zxd[:, :Di], zxd[:, Di:Di + Di + 2 * G * N], zxd[:, Di + Di + 2 * G * N:]
        w = p["conv_w"].astype(jnp.float32)
        K = w.shape[0]
        run = jnp.concatenate([jnp.zeros((K - 1, w.shape[1]), jnp.float32), xbc], axis=0)
        acc = jnp.broadcast_to(p["conv_b"].astype(jnp.float32)[None], (S, w.shape[1]))
        for j in range(K):  # the input at t - (K - 1) + j
            acc = acc + w[j][None] * jax.lax.dynamic_slice_in_dim(run, j, S, axis=0)
        xbc = jax.nn.silu(acc)
        xs = xbc[:, :Di].reshape(S, H, P)
        Bm, Cm = xbc[:, Di:Di + G * N].reshape(S, G, N), xbc[:, Di + G * N:].reshape(S, G, N)
        if control == "one_group_bc":
            Bm, Cm = (jnp.broadcast_to(a[:, :1], a.shape) for a in (Bm, Cm))
        Bh, Ch = jnp.repeat(Bm, H // G, axis=1), jnp.repeat(Cm, H // G, axis=1)  # head h reads group h // (H / G)
        step = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32)[None])  # [S, H]
        a = step * -jnp.exp(p["A_log"].astype(jnp.float32))[None]
        t = jnp.arange(S)
        alpha = jnp.nanpercentile(jnp.where((t < total)[:, None], jnp.exp(a), jnp.nan).reshape(-1),
                                  jnp.asarray([5.0, 50.0, 95.0])) if control == "" else jnp.zeros(3)

        def position(h, at):  # h [H, P, N]
            x_t, b_t, c_t, d_t, a_t = at
            h = jnp.exp(a_t)[:, None, None] * h + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            if control == "bf16_state":  # (a convert there and back is dropped as excess precision)
                h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
            return h, jnp.sum(h * c_t[:, None, :], axis=-1)

        y = jax.lax.scan(position, jnp.zeros((H, P, N), jnp.float32), (xs, Bh, Ch, step, a))[1]
        y = (y + p["D"].astype(jnp.float32)[None, :, None] * xs).reshape(S, Di)
        gate, scale = jax.nn.silu(z), p["norm"].astype(jnp.float32)
        width = Di if control == "ungrouped_norm" else Di // G

        def group_norm(v):
            v = v.reshape(S, Di // width, width)
            return (v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)).reshape(S, Di) * scale

        y = group_norm(y) * gate if control == "norm_before_gate" else group_norm(y * gate)
        return x + _mm(y, p["out_proj"], low), alpha

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _attention_fn(control: str, heads: int, kv_heads: int, head_dim: int, eps: float):
    """One attention layer for ``x [S, D]``, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    H, K, hd = heads, kv_heads, head_dim
    low = control == "fp8_matmuls"

    def layer(x, norm, p):
        S = x.shape[0]
        n = _rms(x, norm, eps)
        q = _mm(n, p["wq"], low).reshape(S, K, H // K, hd)  # query head h reads KV head h // (H / K)
        k, v = _mm(n, p["wk"], low).reshape(S, K, hd), _mm(n, p["wv"], low).reshape(S, K, hd)
        at = jnp.arange(S)
        outs = []
        for lo in range(0, S, ATTN_BLOCK):
            rows = at[lo:lo + ATTN_BLOCK]
            s = jnp.einsum("qkgd,tkd->kgqt", q[lo:lo + ATTN_BLOCK], k, precision=hi) * hd ** -0.5
            w = jax.nn.softmax(jnp.where((at[None, :] <= rows[:, None])[None, None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("kgqt,tkd->qkgd", w, v, precision=hi).reshape(-1, H * hd))
        return x + _mm(jnp.concatenate(outs, axis=0), p["wo"], low)

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

        def pick(chosen, _):
            i = jnp.argmax(jnp.where(chosen, -jnp.inf, choice), axis=-1)
            return chosen | jax.nn.one_hot(i, s.shape[-1], dtype=bool), None

        chosen = jax.lax.scan(pick, jnp.zeros(s.shape, bool), None, length=top_k)[0]
        w = jnp.where(chosen, choice if control == "weight_from_biased_score" else s, 0.0)
        if normalize:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * (1.0 if control == "scaling_one" else scaling), chosen

    return jax.jit(route)


@functools.lru_cache(maxsize=None)
def _expert_fn(low: bool, squared: bool):
    import jax

    return jax.jit(lambda l, w, y, up, down: y + w[:, None] * _relu2(l, up, down, low, squared))


@functools.lru_cache(maxsize=None)
def _latent_fn(low: bool, squared: bool, eps: float):
    """``(normed stream, its latent)`` in front of the experts, and behind
    them ``x + r W_up + shared(n)``."""
    import jax

    def down(x, norm, p):
        n = _rms(x, norm, eps)
        return n, _mm(n, p["latent_down"], low)

    def up(x, n, r, p, shared: bool):
        y = _mm(r, p["latent_up"], low)
        return x + (y + _relu2(n, p["shared_up"], p["shared_down"], low, squared) if shared else y)

    return jax.jit(down), jax.jit(up, static_argnames="shared")


def moe_layer(x, norm, p, stacks, at: int, cfg, put=lambda a: a, control: str = "", chosen_log=None):
    """``x + F_E(RMS(x))`` for ``x [S, D]``: ``p`` is one expert layer's leaves
    on the stream (``router``, ``router_bias``, ``latent_down``,
    ``latent_up``, ``shared_up``, ``shared_down``); ``stacks`` the served
    ``(w_up, w_down)`` ``[expert layers, held, ...]``, read at layer ``at`` an
    expert at a time. ``chosen_log`` (a list) is given the ``[S, E]`` mask of
    who was chosen, over ALL the experts."""
    import jax.numpy as jnp

    low, squared = control == "fp8_matmuls", control != "relu_not_squared"
    down, up = _latent_fn(low, squared, float(cfg["layer_norm_epsilon"]))
    n, latent = down(x, norm, p)
    w, chosen = _route_fn(control, int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]),
                          bool(cfg.get("norm_topk_prob", True)))(n, p["router"], p["router_bias"])
    if chosen_log is not None:
        chosen_log.append(chosen)
    held = stacks[0].shape[1]
    first = int(cfg.get("ep_rank", 0)) * held
    r = jnp.zeros_like(latent)
    for e in range(held):  # a loop over the experts held here, each over every token
        r = _expert_fn(low, squared)(latent, w[:, first + e], r, *(put(stack[at, e]) for stack in stacks))
    return up(x, n, r, p, shared=bool(int(cfg.get("n_shared_experts", 1))))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, low: bool):
    import jax

    return jax.jit(lambda h, g, w: _mm(_rms(h, g, eps), w, low))


def _stack(params: dict, cfg: dict, hs: list, totals: list, put, control: str, route_log=None,
           alpha_log=None, handovers=None) -> list:
    """All layers over each ``h [S, D]`` of ``hs``, a layer at a time over the
    sequences; ``totals``: a sequence's real length. Returns the streams
    behind the last layer."""
    import jax.numpy as jnp
    import numpy as np

    eps = float(cfg["layer_norm_epsilon"])
    pattern = str(cfg["hybrid_override_pattern"])

    def leaves(prefix, i):
        return {n[len(prefix):]: put(params[n][i]) for n in params if n.startswith(prefix)}

    mamba = _mamba_fn(control, int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]), int(cfg["n_groups"]),
                      int(cfg["ssm_state_size"]), eps)
    attention = _attention_fn(control, int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                              int(cfg["head_dim"]), eps)
    stacks = (params["experts_w_up"], params["experts_w_down"])
    seen = {k: 0 for k in KINDS}
    for i, kind in enumerate(pattern):
        norm, at = put(params["norms"][i]), seen[kind]
        seen[kind] += 1
        if kind == "M":
            p = leaves("mamba_", at)
            out = [mamba(h, norm, p, jnp.int32(total)) for h, total in zip(hs, totals)]
            hs = [o[0] for o in out]
            if alpha_log is not None and not control:
                alpha_log.extend({"sequence": s, "layer": i, "alpha_p5_p50_p95": [float(a) for a in o[1]]}
                                 for s, o in enumerate(out))
        elif kind == "*":
            p = leaves("attn_", at)
            hs = [attention(h, norm, p) for h in hs]
        else:
            p, out = leaves("moe_", at), []
            for s, (h, total) in enumerate(zip(hs, totals)):
                chosen = [] if route_log is not None else None
                out.append(moe_layer(h, norm, p, stacks, at, cfg, put, control, chosen))
                if chosen:
                    mask, hand = np.asarray(chosen[0]), handovers[s]
                    route_log.append({"sequence": s, "layer": i,
                                      "prefill_tokens": hand, "prefill": mask[:hand].sum(0),
                                      "decode_tokens": total - 1 - hand, "decode": mask[hand:total - 1].sum(0)})
            hs = out
    return hs


def forward(params: dict, cfg: dict, ids, *, control: str = ""):
    """Logits ``[len(ids), V]`` of one sequence, every position (tier 1).
    ``cfg``: the published keys (``hybrid_override_pattern``, the Mamba-2
    sizes, the heads, the router's rule, ``layer_norm_epsilon``, ``ep_rank``)."""
    import jax.numpy as jnp

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    h = params["embedding"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    (h,) = _stack(params, cfg, [h], [len(ids)], lambda x: x, control)
    return _head_fn(float(cfg["layer_norm_epsilon"]), control == "fp8_matmuls")(
        h, params["final_norm"], params["lm_head"])


def score(params: dict, cfg: dict, sequences, device, *, control: str = "", route_log=None,
          alpha_log=None) -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``SSDMoEModel`` tree; each
    layer (and each expert) is brought to ``device`` when it is used.
    ``control`` computes the reference under one of ``CONTROLS``.
    ``route_log`` (a list) is given, for every sequence and expert layer, how
    often each of ALL the experts was chosen by the tokens the program
    PREFILLS (the prompt) and by those it DECODES (every delivered token but
    the last); ``alpha_log`` (a list) every Mamba-2 layer's 5th / 50th / 95th
    percentile of ``exp(a)`` over a sequence's positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "mamba_in_proj" not in params or "experts_w_up" not in params or "lm_head" not in params:
        raise ValueError("the reference reads the SSDMoEModel parameter layout")

    def put(x):
        return jax.device_put(x, device)

    rows = []
    for prompt, emitted in sequences:
        ids = [int(t) for t in prompt] + [int(t) for t in emitted]
        rows.append((ids + [0] * (-len(ids) % PAD_TO), len(prompt), len(ids)))
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(ids, jnp.int32))].astype(jnp.float32) for ids, *_ in rows]
    del embedding
    hs = _stack(params, cfg, hs, [n for *_, n in rows], put, control, route_log, alpha_log,
                [hand for _, hand, _ in rows])
    head = _head_fn(float(cfg["layer_norm_epsilon"]), control == "fp8_matmuls")
    g, lm_head = put(params["final_norm"]), put(params["lm_head"])
    result = []
    for h, (_, _, n), (_, emitted) in zip(hs, rows, sequences):
        width = len(emitted)
        lo = n - width - 1  # the position whose logits predict emitted[0]
        logits = head(h[lo:lo + width], g, lm_head)
        chosen = jnp.take_along_axis(logits, put(jnp.asarray([int(x) for x in emitted], jnp.int32))[:, None], axis=-1)
        result.append({"argmax": np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int64),
                       "max_logit": np.asarray(jnp.max(logits, axis=-1)).astype(np.float64),
                       "chosen_logit": np.asarray(chosen[:, 0]).astype(np.float64)})
    return result

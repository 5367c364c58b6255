"""The plain reference of ``model_type: "jamba"``: the hybrid state-space
decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, no cache,
no kernels, no batching, one sequence and one layer's weights at a time, the
recurrence a plain ``lax.scan`` over the positions with the state ``[16,
5120]`` in float32.

Published block (h 2560, 28 layers), for ``x`` the residual stream in float32
and ``RMS(h; g) = h / sqrt(mean(h^2) + eps) * g``:

1. layer ``i`` is an attention layer if ``i % 14 == 7``, else a Mamba layer.
   Both: ``x += mixer(RMS(x; g_in))``, then ``x += (silu(h W_gate) * h W_up)
   W_down`` with ``h = RMS(x; g_ff)``;
2. attention: ``q = h W_q`` (20 heads of 128), ``k = h W_k``, ``v = h W_v``
   (1 head of 128), NO rotation and no position term of any kind; causal
   softmax at scale ``128^-1/2``; ``o W_o``;
3. Mamba: ``[u, z] = h W_in``; ``u_t <- silu(b_c + sum_j w_c[j] u_{t-3+j})``
   (zeros before the first token); ``[delta, B, C] = u W_x``, each RMS-normed
   with its own scale; ``dt = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``;
   ``s_t = exp(dt_t A) s_{t-1} + (dt_t u_t) B_t`` (``s_{-1} = 0``); ``y_t = s_t
   . C_t + D u_t``; ``(y silu(z)) W_out``;
4. ``RMS(x; g_final)``, then the logits against the served (untied) head.

The tree is the program's (``models/hybrid_ssm.py``: flat names, leaves
stacked by layer kind; ``ssm_A_log [layers, d_state, d_inner]`` is the
published leaf transposed, ``ssm_conv_w [layers, d_conv, d_inner]`` too).

CONTROLS, for the tolerances (``score(control=...)``;
``tests/controls_jamba.py`` reads them on the chip over every distinct request
the cell itself finished): ``no_state_handover`` (the state zero at the first
decode step: a prefill that does not hand it on), ``no_conv_handover`` (the
convolution's history zero there), ``pads_unmasked`` (the bucket's left pads
run through the Mamba layers unmasked: a row's state polluted),
``no_inner_norms`` (delta, B and C not normed), ``state_bf16`` (the state
rounded to bf16 after every position), ``no_softplus`` (the time step a plain
``relu``; ``A``'s sign dropped instead overflows float32 within a few hundred
positions and gives no reading), ``attn_window_512`` (an attention layer sees
its last 512 positions) and ``fp8_matmuls`` (the WHOLE reference one precision
down: both operands of every matmul rounded to ``float8_e4m3fn``, the next
floating-point format under the bf16 the configuration states).
"""

from __future__ import annotations

import functools
import math

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths (PR 40, PERF.md section 6; my chip runs of
# ``tests/controls_jamba.py --audits 24``: the 8 to 9 distinct (prompt, answer)
# pairs a run of the cell holds, of at most 12 prompts; the seed moves their
# order, never what is asked; run once before the limits below were written
# and once more AT them, and the counts on the right are the second run's own
# ``fails``, or the first run's readings held to the limits). The exact path's
# logit of a delivered token is 0.128 to 0.237 from the reference's (the
# largest sound reading in those two runs, in two prompts served alone through
# the verify step, 0.174 and 0.177, and in the four audits of each of eight
# other runs), the reference's half gap 0.043 to 0.097: a bf16 residual stream
# through 28 layers reads three times what the other bf16 families do. With
# the reference computed under a control, against the same exact path (logit
# error; half gap):
#   pads_unmasked      0.222 to 0.371   0.101 to 0.194   6 of 8 over HALF_GAP_TOL (1 over LOGIT_TOL)
#   state_bf16         0.387 to 0.538   0.177 to 0.328   8 of 8 over each limit
#   attn_window_512    0.754 to 1.136   0.437 to 0.668   9 of 9 over each
#   fp8_matmuls        1.503 to 1.982   0.772 to 1.087   9 of 9 over each
#   no_state_handover  3.037 to 3.925   1.612 to 2.102   9 of 9 over each
#   no_conv_handover   3.183 to 5.111   1.416 to 2.789   9 of 9 over each
#   no_inner_norms     4.236 to 5.389   2.193 to 2.965   9 of 9 over each
#   no_softplus        5.849 to 7.182   2.968 to 3.523   9 of 9 over each
# Each limit is one and a half times the largest sound reading (0.237, 0.09 at
# the time; 0.097 since), under the smallest reading of every control but the
# nearest: ``fp8_matmuls``, the precision below the one the configuration
# states, is refused by both limits with four times of room. ``pads_unmasked``
# is the control the limits do NOT refuse on every request: what the bucket's
# ~1200 pads leave in a state has decayed by the end of a 2900-token prompt
# (the slowest channel forgets over ~1000 positions), so the fault moves an
# answer's logits by 0.20 to 0.27, the size of the program's own bf16 distance;
# it is refused in every run of four requests (6 of 8 requests), not in every
# request. The tier-1 test that holds a padded row's state to the row alone,
# bit for bit, is what guards it.
HALF_GAP_TOL = 0.135  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.36  # the exact path's logit of a delivered token against the reference's

# the faults the limits above are held against (tests/controls_jamba.py)
CONTROLS = ("no_state_handover", "no_conv_handover", "pads_unmasked", "no_inner_norms", "state_bf16",
            "no_softplus", "attn_window_512", "fp8_matmuls")
ATTN_BLOCK = 1024  # queries an attention layer scores at once: [20, 1024, S] float32
PAD_TO = 256  # a sequence is padded on the right to a multiple (causal: a pad changes nothing before it)


def _mm(x, w, low: bool = False):
    """``x @ w`` in float32 at the highest precision; ``low`` (the fp8
    control) rounds BOTH operands first: the input a token, the weight an
    output channel."""
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


def _ffn(x, p, eps, low):
    import jax

    h = _rms(x, p["ff_norm"], eps)
    return x + _mm(jax.nn.silu(_mm(h, p["w_gate"], low)) * _mm(h, p["w_up"], low), p["w_down"], low)


@functools.lru_cache(maxsize=None)
def _state_layer_fn(control: str, rank: int, eps: float):
    """One Mamba layer for ``x [S, D]``; ``handover`` is the first position
    a decode step fed (where the two hand-over controls drop what they drop)."""
    import jax
    import jax.numpy as jnp

    low = control == "fp8_matmuls"

    def layer(x, p, handover):
        S = x.shape[0]
        K, Di = p["conv_w"].shape
        N = p["A_log"].shape[0]
        h = _rms(x, p["input_norm"], eps)
        xz = _mm(h, p["in_proj"], low)
        u, z = xz[:, :Di], xz[:, Di:]
        t = jnp.arange(S)
        padded = jnp.concatenate([jnp.zeros((K - 1, Di), jnp.float32), u], axis=0)
        acc = p["conv_b"].astype(jnp.float32)[None]
        for j in range(K):
            tap = jax.lax.dynamic_slice_in_dim(padded, j, S, axis=0)  # the input at t - (K - 1) + j
            if control == "no_conv_handover":  # inputs in front of the hand-over are lost to outputs behind it
                tap = jnp.where(((t >= handover) & (t - (K - 1) + j < handover))[:, None], 0.0, tap)
            acc = acc + p["conv_w"][j].astype(jnp.float32)[None] * tap
        u = jax.nn.silu(acc)
        dbc = _mm(u, p["x_proj"], low)
        delta, B, C = dbc[:, :rank], dbc[:, rank:rank + N], dbc[:, rank + N:]
        if control != "no_inner_norms":
            delta, B, C = _rms(delta, p["dt_norm"], eps), _rms(B, p["b_norm"], eps), _rms(C, p["c_norm"], eps)
        raw = _mm(delta, p["dt_proj"], low) + p["dt_bias"].astype(jnp.float32)[None]
        dt = jax.nn.relu(raw) if control == "no_softplus" else jax.nn.softplus(raw)
        A = -jnp.exp(p["A_log"].astype(jnp.float32))  # [N, Di]
        keep = jnp.where((t == handover) & (control == "no_state_handover"), 0.0, 1.0)

        def step(s, xs):
            dt_t, u_t, b_t, c_t, keep_t = xs
            s = jnp.exp(dt_t[None, :] * A) * (s * keep_t) + (dt_t * u_t)[None, :] * b_t[:, None]
            if control == "state_bf16":  # an explicit rounding: the compiler drops a convert there and back
                s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
            return s, jnp.sum(s * c_t[:, None], axis=0)

        _, y = jax.lax.scan(step, jnp.zeros((N, Di), jnp.float32), (dt, u, B, C, keep))
        y = (y + p["D"].astype(jnp.float32)[None] * u) * jax.nn.silu(z)
        return _ffn(x + _mm(y, p["out_proj"], low), p, eps, low)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _attention_layer_fn(control: str, heads: int, kv_heads: int, eps: float):
    """One attention layer for ``x [S, D]``, a block of queries at a time;
    keys in front of ``first`` (the pads of ``pads_unmasked``) are masked."""
    import jax
    import jax.numpy as jnp

    low = control == "fp8_matmuls"
    hi = jax.lax.Precision.HIGHEST
    window = 512 if control == "attn_window_512" else None

    def layer(x, p, first):
        S = x.shape[0]
        hd = p["wq"].shape[1] // heads
        h = _rms(x, p["input_norm"], eps)
        q = _mm(h, p["wq"], low).reshape(S, kv_heads, heads // kv_heads, hd)
        k = _mm(h, p["wk"], low).reshape(S, kv_heads, hd)
        v = _mm(h, p["wv"], low).reshape(S, kv_heads, hd)
        at = jnp.arange(S)
        outs = []
        for lo in range(0, S, ATTN_BLOCK):
            rows = at[lo:lo + ATTN_BLOCK]
            ok = (at[None, :] <= rows[:, None]) & ((at[None, :] >= first) | (at[None, :] == rows[:, None]))
            if window is not None:
                ok &= at[None, :] > rows[:, None] - window
            s = jnp.einsum("qkgd,tkd->kgqt", q[lo:lo + ATTN_BLOCK], k, precision=hi) / math.sqrt(hd)
            a = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("kgqt,tkd->qkgd", a, v, precision=hi).reshape(-1, heads * hd))
        return _ffn(x + _mm(jnp.concatenate(outs, axis=0), p["wo"], low), p, eps, low)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, low: bool):
    import jax
    import jax.numpy as jnp

    def head(h, g, lm_head, chosen):
        logits = _mm(_rms(h, g, eps), lm_head, low)
        return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
                jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])

    return jax.jit(head)


def score(params: dict, cfg: dict, sequences, device, *, control: str = "") -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``HybridSSMModel`` tree; each
    layer's leaves are brought to ``device`` when they are used. ``control``
    computes the reference under one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "ssm_A_log" not in params or "lm_head" not in params:
        raise ValueError("the reference reads the HybridSSMModel parameter layout with an untied head")

    def put(x):
        return jax.device_put(x, device)

    eps = float(cfg["rms_norm_eps"])
    period, offset = int(cfg["attn_layer_period"]), int(cfg["attn_layer_offset"])
    buckets = sorted(cfg.get("serving", {}).get("engine", {}).get("prompt_buckets", ()))
    rows = []
    for prompt, emitted in sequences:
        ids = [int(t) for t in prompt] + [int(t) for t in emitted]
        pads = 0
        if control == "pads_unmasked":  # the bucket's left pads, run through the state layers as if real
            pads = next((b for b in buckets if b >= len(prompt)), len(prompt)) - len(prompt)
        ids = [0] * pads + ids
        rows.append((ids + [0] * (-len(ids) % PAD_TO), pads, pads + len(prompt), len(ids)))
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(ids, jnp.int32))].astype(jnp.float32) for ids, *_ in rows]
    del embedding
    state_layer = _state_layer_fn(control, int(cfg["mamba_dt_rank"]), eps)
    attention_layer = _attention_layer_fn(
        control, int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), eps)

    def leaves(prefix, i):
        return {n[len(prefix):]: put(params[n][i]) for n in params if n.startswith(prefix)}

    mi = ai = 0
    for i in range(int(cfg["num_hidden_layers"])):
        if i % period == offset:
            p, ai = {**leaves("layers_", i), **leaves("attn_", ai)}, ai + 1
            hs = [attention_layer(h, p, jnp.int32(pads)) for h, (_, pads, _, _) in zip(hs, rows)]
        else:
            p, mi = {**leaves("layers_", i), **leaves("ssm_", mi)}, mi + 1
            hs = [state_layer(h, p, jnp.int32(handover)) for h, (_, _, handover, _) in zip(hs, rows)]
    head = _head_fn(eps, control == "fp8_matmuls")
    g, lm_head = put(params["final_norm"]), put(params["lm_head"])
    result = []
    for h, (_, _, _, n), (_, emitted) in zip(hs, rows, sequences):
        w = len(emitted)
        lo = n - w - 1  # the position whose logits predict emitted[0]
        argmax, top, chosen = head(h[lo:lo + w], g, lm_head, put(jnp.asarray([int(x) for x in emitted], jnp.int32)))
        result.append({"argmax": np.asarray(argmax).astype(np.int64),
                       "max_logit": np.asarray(top).astype(np.float64),
                       "chosen_logit": np.asarray(chosen).astype(np.float64)})
    return result

"""The plain reference of ``model_type: "evabyte"``: the block-window,
pooled-summary decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, no cache,
no kernels, no batching, one layer's weights at a time, attention a WINDOW at a
time (a loop over the sequence's windows: the scores of one are ``[heads,
2048, 2048 + summaries]``; those of a whole prompt would not fit).

Published block (h 4096, 32 heads of 128, at the served widths), for ``x`` the
residual stream in float32:

1. ``h = x / sqrt(mean(x^2) + eps) * (1 + g)``;
2. ``q, k, v = h W_q, h W_k, h W_v`` in heads, q and k rotated by position
   (theta 1e5, halves paired);
3. for every complete chunk ``c`` of 16 positions of head ``n``: ``k~_c = sum_j
   softmax_j(k_j . mu_n) k_j``, ``v~_c = sum_j softmax_j(k_j . phi_n) v_j``
   (rotated keys, unscaled logits);
4. position ``t`` attends, under one softmax at scale ``128^-1/2``, to the
   positions ``j <= t`` of its own window (``j // 2048 == t // 2048``) and to
   the summaries of the chunks of every EARLIER window;
5. ``x += o W_o``; ``x += (silu(h' W_gate) * h' W_up) W_down`` with ``h'`` by 1;

after the last layer 1 once more and ``logits = h W_head``, ``[8, 320]``
head-major; the next byte is read from head 0. The configuration file's
``assumed`` says which of these the published keys do not fix (what ``mu`` and
``phi`` pool and from what; the head's column order; pairing by halves).

CONTROLS, for the tolerances (``score(control=...)``;
``tests/controls_evabyte.py`` reads them on the chip over every distinct
request the cell itself finished): ``no_summaries`` (window-only attention),
``swap_mu_phi`` (the pooling vectors exchanged), ``mean_pool`` (the plain
mean of a chunk for both summaries), ``own_window_summaries`` (a query also
sees the summaries of its own window's complete chunks) and ``fp8_matmuls``
(the WHOLE reference one precision down: both operands of every matmul
rounded to ``float8_e4m3fn``, the next floating-point format under the bf16
the configuration states). Each is refused on every request of a run: the
first by ``LOGIT_TOL`` alone, the others by both limits (the readings are below).
"""

from __future__ import annotations

import functools
import math

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths (PR 38, PERF.md section 6; my chip runs of
# ``tests/controls_evabyte.py --cell evabyte-pp4.closed8 --audits 48``: all 48
# requests of a run, which are 5 distinct (prompt, answer) pairs; the seed
# moves their order, never what is asked; run once before the limits below
# were written and once more AT them, with the same readings, and the counts
# on the right are the second run's own ``fails``). The exact path's logit of a
# delivered token is 0.00607 to 0.00792 from the reference's (the largest
# sound reading, in this run and in the four audits of each of 15 others: a
# float32 stream and float32 logits leave the program a sixth of the bf16
# distance the other families read), the reference's half gap 0.0 in every
# audit (``fails`` 0 of 5; a sixth prompt served alone so that its answer runs
# over a window's end, through the verify step: 0.0062 and 0.0); and with the
# reference computed under a control, against the same
# exact path (logit error; half gap):
#   own_window_summaries  0.0278 to 0.0321   0.0               5 of 5 over LOGIT_TOL
#   fp8_matmuls           0.1440 to 0.1837   0.0180 to 0.0713   5 of 5 over each limit
#   mean_pool             0.3350 to 0.3620   0.0650 to 0.1224   5 of 5 over each
#   swap_mu_phi           0.6531 to 0.7590   0.2440 to 0.3132   5 of 5 over each
#   no_summaries          0.6566 to 0.9239   0.0664 to 0.5646   5 of 5 over each
# LOGIT_TOL lies between the largest sound reading (0.00792) and fp8_matmuls'
# smallest (0.1440), with 1.9 times of room above the first: it is the
# geometric mean of 0.00792 and the smallest reading of the NEAREST control,
# own_window_summaries (0.0278: a query that also sees the summaries of its
# own window's complete chunks moves a logit by a fifth of what fp8 does,
# because those bytes are already there exactly), so that every control of
# ``CONTROLS`` is refused. A sound half gap cannot pass the logit error of the
# two tokens it parts (0.008); HALF_GAP_TOL lies between that and fp8_matmuls'
# smallest half gap (0.0180).
HALF_GAP_TOL = 0.01  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.015  # the exact path's logit of a delivered token against the reference's

# the faults the limits above are held against (tests/controls_evabyte.py)
CONTROLS = ("no_summaries", "swap_mu_phi", "mean_pool", "own_window_summaries", "fp8_matmuls")
LOW = {"fp8_matmuls": "fp8"}  # control -> what every matmul is rounded to


def _mm(x, w, low: str = ""):
    """``x @ w`` in float32 at the highest precision; ``low`` (a control:
    "fp8") rounds BOTH operands first: the input a token, the weight an
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


def _norm(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta: float):
    """``x [S, heads, hd]`` at positions 0..S-1, rotated by halves."""
    import jax.numpy as jnp
    import numpy as np

    s, hd = x.shape[0], x.shape[-1]
    inv = (1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)).astype(np.float32)
    phase = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(phase)[:, None, :], jnp.sin(phase)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(control: str, heads: int, window: int, chunk: int, theta: float, eps: float):
    """One layer for ``x [S, D]`` (``S`` whole windows), every window in turn."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mm = functools.partial(_mm, low=LOW.get(control, ""))
    hi = jax.lax.Precision.HIGHEST
    per = window // chunk

    def layer(x, p):
        s = x.shape[0]
        hd = p["wq"].shape[1] // heads
        h = _norm(x, p["input_norm"], eps)
        q = _rope(mm(h, p["wq"]).reshape(s, heads, hd), theta)
        k = _rope(mm(h, p["wk"]).reshape(s, heads, hd), theta)
        v = mm(h, p["wv"]).reshape(s, heads, hd)
        mu, phi = p["mu"].astype(jnp.float32), p["phi"].astype(jnp.float32)
        if control == "swap_mu_phi":
            mu, phi = phi, mu
        kc, vc = k.reshape(s // chunk, chunk, heads, hd), v.reshape(s // chunk, chunk, heads, hd)
        if control == "mean_pool":
            wk = wv = jnp.full((s // chunk, chunk, heads), 1.0 / chunk, jnp.float32)
        else:
            wk = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, mu, precision=hi), axis=1)
            wv = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, phi, precision=hi), axis=1)
        sk = jnp.einsum("cjh,cjhd->chd", wk, kc, precision=hi)  # [S / chunk, heads, hd]
        sv = jnp.einsum("cjh,cjhd->chd", wv, vc, precision=hi)
        at = np.arange(window)
        causal = at[None, :] <= at[:, None]  # [query, key] of one window
        outs = []
        for w in range(s // window):
            rows = slice(w * window, (w + 1) * window)
            seen = 0 if control == "no_summaries" else per * w  # summaries of the earlier windows
            keys, vals, ok = [k[rows], sk[:seen]], [v[rows], sv[:seen]], [causal, np.ones((window, seen), bool)]
            if control == "own_window_summaries":  # the fault: this window's complete chunks too
                own = slice(per * w, per * (w + 1))
                keys.append(sk[own]), vals.append(sv[own])
                ok.append((np.arange(per)[None, :] + 1) * chunk - 1 <= at[:, None])
            keys, vals = jnp.concatenate(keys, axis=0), jnp.concatenate(vals, axis=0)
            scores = jnp.einsum("qhd,thd->hqt", q[rows], keys, precision=hi) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(jnp.asarray(np.concatenate(ok, axis=1))[None], scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqt,thd->qhd", probs, vals, precision=hi))
        x = x + mm(jnp.concatenate(outs, axis=0).reshape(s, heads * hd), p["wo"])
        h = _norm(x, p["post_attn_norm"], eps)
        return x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]), p["w_down"])

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, n_heads: int, low: str = ""):
    import jax
    import jax.numpy as jnp

    def head(h, g, lm_head, chosen):
        # all the next-byte heads, head-major columns; the next byte is head 0's
        logits = _mm(_norm(h, g, eps), lm_head, low).reshape(h.shape[0], n_heads, -1)[:, 0]
        return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
                jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])

    return jax.jit(head)


def score(params: dict, cfg: dict, sequences, device, *, control: str = "") -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``BlockWindowModel`` tree (flat
    names, the layers' leaves stacked); each layer is brought to ``device``
    when it is used. ``control`` computes the reference under one of
    ``CONTROLS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "layers_mu" not in params:
        raise ValueError("the reference reads the BlockWindowModel parameter layout")

    def put(x):
        return jax.device_put(x, device)

    window, chunk = int(cfg["window_size"]), int(cfg["chunk_size"])
    tokens = [[int(t) for t in p] + [int(t) for t in e] for p, e in sequences]
    embedding = put(params["embedding"])
    # padded on the right to whole windows (causal: a pad changes nothing
    # before it; the chunks it completes are seen by later windows only)
    hs = [embedding[put(jnp.asarray(t + [0] * (-len(t) % window), jnp.int32))].astype(jnp.float32)
          for t in tokens]
    del embedding
    eps = float(cfg["rms_norm_eps"])
    layer = _layer_fn(control, int(cfg["num_attention_heads"]), window, chunk, float(cfg["rope_theta"]), eps)
    names = sorted(n for n in params if n.startswith("layers_"))
    for i in range(int(cfg["num_hidden_layers"])):
        p = {n[len("layers_"):]: put(params[n][i]) for n in names}
        hs = [layer(h, p) for h in hs]
    head = _head_fn(eps, int(cfg["num_pred_heads"]), LOW.get(control, ""))
    g, lm_head = put(params["final_norm"]), put(params["lm_head"])
    result = []
    for h, t, (_, emitted) in zip(hs, tokens, sequences):
        w = len(emitted)
        lo = len(t) - w - 1  # the position whose logits predict emitted[0]
        argmax, top, chosen = head(h[lo:lo + w], g, lm_head, put(jnp.asarray([int(x) for x in emitted], jnp.int32)))
        result.append({"argmax": np.asarray(argmax).astype(np.int64),
                       "max_logit": np.asarray(top).astype(np.float64),
                       "chosen_logit": np.asarray(chosen).astype(np.float64)})
    return result

"""The plain reference of ``model_type: "lfm2_moe"``: the gated
short-convolution, sparse-expert decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, no cache,
no kernels, no batching, one sequence and one layer's weights at a time, the
experts a LOOP over all of them (each upcast when it is used, each over every
token, weighted by the router's weight or zero), so that it fits beside the
10.8 GB the service holds.

Published block (h 2048), for ``x`` the residual stream in float32 and
``RMS(h; g) = h / sqrt(mean(h^2) + 1e-5) * g``:

1. every layer: ``h = x + Op(RMS(x; operator_norm))``, ``y = h + FFN(RMS(h;
   ffn_norm))``;
2. a ``conv`` layer: ``[B, C, u] = n W_in`` (three vectors of 2048, in that
   order); ``z = B * u``; ``c_t = sum_j w_j * z_{t-2+j}`` over three taps
   (zeros before the first token; no bias, no activation); ``(C * c) W_out``;
3. a ``full_attention`` layer: ``q = n W_q`` (32 heads of 64), ``k, v = n W_k,
   n W_v`` (8 heads of 64); ``q`` and ``k`` RMS-normed over the 64 of a head
   (one scale for every head), THEN rotated by halves at theta 1e6; causal
   softmax at scale 1/8; ``o W_o``;
4. layers 0 and 1: ``FFN = (silu(n W_1) * n W_3) W_2`` (11776 wide); every
   later layer: ``s = sigmoid(n W_g)`` over 64 outputs, the 4 largest of ``s +
   b`` chosen (an argmax a choice: ties to the lower index), ``w = s`` at the
   chosen over ``(their sum + 1e-6)``, ``FFN = sum_i w_i E_i(n)``;
5. ``RMS(x; embedding_norm)``, then the logits against the served (untied) head.

The tree is the program's (``models/conv_moe.py``: ``lead_<i>``, ``periods/l<i>``
stacked over the trips, ``tail_<i>``, ``experts`` ``[sparse layers, 64, ...]``).

CONTROLS, for the tolerances (``score(control=...)``;
``tests/controls_lfm2_moe.py`` reads them on the chip over every distinct
request the cell itself finished): ``no_conv_handover`` (the two kept inputs
zero at the first decode step: a prefill that does not hand them on),
``pads_unmasked`` (the bucket's left pads run through the conv layers
unmasked), ``taps_reversed`` (the taps applied newest first), ``no_qk_norm``,
``bias_in_weights`` (the weights from ``s + b``), ``unnormed_topk`` (the chosen
scores not normalised), ``fp8_experts`` (both operands of the experts' matmuls
rounded to ``float8_e4m3fn``) and ``fp8_matmuls`` (the WHOLE reference one
precision down: every matmul but the router's, the next floating-point format
under the bf16 the configuration states).
"""

from __future__ import annotations

import functools
import math

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths (PR 45, PERF.md section 6; my chip run of
# ``tests/controls_lfm2_moe.py --audits 12``: 9 distinct (prompt, answer) pairs
# of the cell's own, 2888 to 3482 prompt tokens, and the four audits of every
# plain run of the cell). Sound: the exact path's logit of a delivered token is
# 0.037 to 0.097 from the reference's, the reference's half gap 0.005 to 0.022.
# The sound band's own source is an expert that bf16 swaps for its neighbour at
# the top-4's edge (the chosen weights sum to ONE: a swap moves the stream by a
# quarter of an expert's output), which is why two faults of the sparse block
# read INSIDE it. With the reference computed under a control, against the same
# exact path (logit error; half gap; requests a limit below refuses):
#   taps_reversed      3.834 to 5.144   2.004 to 2.910   9 of 9 over each limit
#   no_conv_handover   1.489 to 3.147   0.986 to 1.885   9 of 9 over each
#   unnormed_topk      0.394 to 0.777   0.222 to 0.377   9 of 9 over each
#   fp8_matmuls        0.353 to 0.497   0.159 to 0.294   9 of 9 over each
#   no_qk_norm         0.156 to 0.242   0.066 to 0.153   9 of 9 over HALF_GAP_TOL (5 over LOGIT_TOL)
#   fp8_experts        0.041 to 0.095   0.005 to 0.025   0 of 9: moves a logit by 0.020 to 0.055, INSIDE the sound band
#   bias_in_weights    0.034 to 0.094   0.006 to 0.021   0 of 9: moves 0.016 to 0.056, inside it
#   pads_unmasked      0.039 to 0.096   0.006 to 0.021   0 of 9: moves 0.007 to 0.060, inside it
# ``LOGIT_TOL`` lies between the two readings the limit is owed to: 1.9 times
# over the largest sound one (0.097) and 1.9 times under the smallest of the
# reference one precision down (``fp8_matmuls`` 0.353). ``HALF_GAP_TOL`` is 1.9
# times the largest sound half gap (0.0215) and 1.6 times under the smallest of
# ``no_qk_norm`` (0.066; ``fp8_matmuls`` 0.159). Three controls are NOT refused
# and nothing in this cell guards what they break: the experts' precision and a
# weight taken from score plus bias (std 0.05 against scores near 0.9: a
# twentieth of each weight) move the experts' term by less than one swapped
# expert does, as PR 44 found with every expert held; and a convolution of
# three taps forgets the bucket's ~1200 pads two positions behind them, 2900
# positions in front of the first delivered token. Tier 1 holds each to the
# reference in float32, where no expert swaps: the routing rule, the shares'
# sum and a padded row's kept inputs bit for bit (tests/test_lfm2_moe.py).
HALF_GAP_TOL = 0.04  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.185  # the exact path's logit of a delivered token against the reference's

# the faults the limits above are held against (tests/controls_lfm2_moe.py)
CONTROLS = ("no_conv_handover", "pads_unmasked", "taps_reversed", "no_qk_norm", "bias_in_weights",
            "unnormed_topk", "fp8_experts", "fp8_matmuls")
ATTN_BLOCK = 512  # queries an attention layer scores at once: [32, 512, S] float32
PAD_TO = 256  # a sequence is padded on the right to a multiple (causal: a pad changes nothing before it)
CONV = "conv"


def _mm(x, w, low: bool = False):
    """``x @ w`` in float32 at the highest precision; ``low`` (the fp8
    controls) rounds BOTH operands first: the input a token, the weight an
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


def _swiglu(x, gate, up, down, low: bool = False):
    import jax

    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


@functools.lru_cache(maxsize=None)
def _conv_fn(control: str, eps: float):
    """One conv layer's operator half for ``x [S, D]``: ``(x + Op, the normed
    stream the FFN reads)``. ``first`` is the first real position (pads in
    front of it are masked, but under ``pads_unmasked``); ``handover`` the
    first position a decode step fed."""
    import jax
    import jax.numpy as jnp

    low = control == "fp8_matmuls"

    def layer(x, p, first, handover):
        S, D = x.shape
        n = _rms(x, p["operator_norm"]["scale"], eps)
        bcu = _mm(n, p["shortconv"]["in_proj"]["kernel"], low)
        gate_in, gate_out, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
        t = jnp.arange(S)
        z = gate_in * u
        if control != "pads_unmasked":
            z = jnp.where((t >= first)[:, None], z, 0.0)
        w = p["shortconv"]["conv_w"].astype(jnp.float32)
        w = w[::-1] if control == "taps_reversed" else w
        L = w.shape[0]
        padded = jnp.concatenate([jnp.zeros((L - 1, D), jnp.float32), z], axis=0)
        acc = jnp.zeros((S, D), jnp.float32)
        for j in range(L):
            tap = jax.lax.dynamic_slice_in_dim(padded, j, S, axis=0)  # the input at t - (L - 1) + j
            if control == "no_conv_handover":  # inputs in front of the hand-over are lost to outputs behind it
                tap = jnp.where(((t >= handover) & (t - (L - 1) + j < handover))[:, None], 0.0, tap)
            acc = acc + w[j][None] * tap
        x = x + _mm(gate_out * acc, p["shortconv"]["out_proj"]["kernel"], low)
        return x, _rms(x, p["ffn_norm"]["scale"], eps)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _attention_fn(control: str, heads: int, kv_heads: int, theta: float, eps: float):
    """One attention layer's operator half for ``x [S, D]``, a block of
    queries at a time; keys in front of ``first`` (the bucket's pads under
    ``pads_unmasked``) are masked and positions count from it."""
    import jax
    import jax.numpy as jnp

    low = control == "fp8_matmuls"
    hi = jax.lax.Precision.HIGHEST

    def rope(x, first):
        hd = x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        phase = jnp.maximum(jnp.arange(x.shape[0]) - first, 0).astype(jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(phase)[:, None, :], jnp.sin(phase)[:, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    def layer(x, p, first, handover):
        S = x.shape[0]
        a = p["attn"]
        hd = a["wq"]["kernel"].shape[1] // heads
        n = _rms(x, p["operator_norm"]["scale"], eps)
        q = _mm(n, a["wq"]["kernel"], low).reshape(S, heads, hd)
        k = _mm(n, a["wk"]["kernel"], low).reshape(S, kv_heads, hd)
        v = _mm(n, a["wv"]["kernel"], low).reshape(S, kv_heads, hd)
        if control != "no_qk_norm":
            q, k = _rms(q, a["q_norm"]["scale"], eps), _rms(k, a["k_norm"]["scale"], eps)
        q = rope(q, first).reshape(S, kv_heads, heads // kv_heads, hd)
        k = rope(k, first)
        at = jnp.arange(S)
        outs = []
        for lo in range(0, S, ATTN_BLOCK):
            rows = at[lo:lo + ATTN_BLOCK]
            ok = (at[None, :] <= rows[:, None]) & ((at[None, :] >= first) | (at[None, :] == rows[:, None]))
            s = jnp.einsum("qkgd,tkd->kgqt", q[lo:lo + ATTN_BLOCK], k, precision=hi) / math.sqrt(hd)
            w = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("kgqt,tkd->qkgd", w, v, precision=hi).reshape(-1, heads * hd))
        x = x + _mm(jnp.concatenate(outs, axis=0), a["wo"]["kernel"], low)
        return x, _rms(x, p["ffn_norm"]["scale"], eps)

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
        if normalize and control != "unnormed_topk":
            w = w / (w.sum(-1, keepdims=True) + 1e-6)
        return w * scaling

    return jax.jit(route)


@functools.lru_cache(maxsize=None)
def _expert_fn(low: bool):
    import jax

    return jax.jit(lambda n, w, y, gate, up, down: y + w[:, None] * _swiglu(n, gate, up, down, low))


def moe_layer(n, mlp, stacks, at: int, cfg, control: str = "", chosen_log=None):
    """``sum_{e chosen} w_e E_e(n)`` for ``n [S, D]``: ``mlp`` is one layer's
    ``router`` / ``router_bias``; ``stacks`` the served ``(w_gate, w_up,
    w_down)`` ``[sparse layers, E, ...]``, read at layer ``at`` an expert at a
    time (a layer's 64 are 0.6 GB: never sliced whole). ``chosen_log`` (a
    list) is given the ``[S, E]`` mask of who was chosen."""
    import jax.numpy as jnp

    w = _route_fn(control, int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]),
                  bool(cfg.get("norm_topk_prob", True)))(n, mlp["router"]["kernel"], mlp["router_bias"])
    if chosen_log is not None:
        chosen_log.append(w > 0)
    expert = _expert_fn(control in ("fp8_experts", "fp8_matmuls"))
    y = jnp.zeros_like(n)
    for e in range(stacks[0].shape[1]):  # a loop over the experts, each over every token
        y = expert(n, w[:, e], y, *(stack[at, e] for stack in stacks))
    return y


@functools.lru_cache(maxsize=None)
def _dense_fn(low: bool):
    import jax

    return jax.jit(lambda n, m: _swiglu(n, m["w_gate"]["kernel"], m["w_up"]["kernel"], m["w_down"]["kernel"], low))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, low: bool):
    import jax
    import jax.numpy as jnp

    def head(h, g, lm_head, chosen):
        logits = _mm(_rms(h, g, eps), lm_head, low)
        return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
                jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])

    return jax.jit(head)


def layout(cfg: dict):
    """``(dense layers in front of the loop, layers a period, layers behind
    the loop)`` of the program's tree, from the published lists: the shortest
    pattern the layers behind the dense ones repeat, its last copy cut."""
    kinds, lead = list(cfg["layer_types"]), int(cfg["num_dense_layers"])
    rest = kinds[lead:]
    period = next((p for p in range(1, len(rest) + 1) if all(rest[i] == rest[i % p] for i in range(len(rest)))), 1)
    return lead, period, len(rest) % period


def score(params: dict, cfg: dict, sequences, device, *, control: str = "", route_log=None) -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``ConvMoEModel`` tree; each
    layer (and each expert) is brought to ``device`` when it is used.
    ``control`` computes the reference under one of ``CONTROLS``.
    ``route_log`` (a list) is given, for every sequence and sparse layer, how
    often each expert was chosen by the tokens the program PREFILLS (the
    prompt) and by those it DECODES (every delivered token but the last)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "experts" not in params or "lm_head" not in params or "embedding_norm" not in params:
        raise ValueError("the reference reads the ConvMoEModel parameter layout with an untied head")

    def put(x):
        return jax.device_put(x, device)

    eps = float(cfg["norm_eps"])
    theta = float(cfg.get("rope_parameters", {}).get("rope_theta", 1000000))
    buckets = sorted(cfg.get("serving", {}).get("engine", {}).get("prompt_buckets", ()))
    rows = []
    for prompt, emitted in sequences:
        ids = [int(t) for t in prompt] + [int(t) for t in emitted]
        pads = 0
        if control == "pads_unmasked":  # the bucket's left pads, run through the conv layers as if real
            pads = next((b for b in buckets if b >= len(prompt)), len(prompt)) - len(prompt)
        ids = [0] * pads + ids
        rows.append((ids + [0] * (-len(ids) % PAD_TO), pads, pads + len(prompt), len(ids)))
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(ids, jnp.int32))].astype(jnp.float32) for ids, *_ in rows]
    del embedding
    lead, period, tail = layout(cfg)
    depth = int(cfg["num_hidden_layers"])
    low = control == "fp8_matmuls"
    conv = _conv_fn(control, eps)
    attention = _attention_fn(control, int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), theta, eps)
    stacks = tuple(params["experts"][name] for name in ("w_gate", "w_up", "w_down"))
    for i, kind in enumerate(cfg["layer_types"]):
        if i < lead:
            layer = jax.tree_util.tree_map(put, params[f"lead_{i}"])
        elif i >= depth - tail:
            layer = jax.tree_util.tree_map(put, params[f"tail_{i - (depth - tail)}"])
        else:
            trip, at = divmod(i - lead, period)
            layer = jax.tree_util.tree_map(lambda a: put(a[trip]), params["periods"][f"l{at}"])
        operator = conv if kind == CONV else attention
        out = []
        for n_seq, (h, (_, pads, handover, total)) in enumerate(zip(hs, rows)):
            h, n = operator(h, layer, jnp.int32(pads), jnp.int32(handover))
            if i < lead:
                out.append(h + _dense_fn(low)(n, layer["mlp"]))
                continue
            chosen = [] if route_log is not None else None
            out.append(h + moe_layer(n, layer["mlp"], stacks, i - lead, cfg, control, chosen))
            if chosen:
                mask = np.asarray(chosen[0])
                route_log.append({"sequence": n_seq, "layer": i,
                                  "prefill_tokens": handover - pads, "prefill": mask[pads:handover].sum(0),
                                  "decode_tokens": total - 1 - handover, "decode": mask[handover:total - 1].sum(0)})
        hs = out
    head = _head_fn(eps, low)
    g, lm_head = put(params["embedding_norm"]["scale"]), put(params["lm_head"])
    result = []
    for h, (_, _, _, n), (_, emitted) in zip(hs, rows, sequences):
        w = len(emitted)
        lo = n - w - 1  # the position whose logits predict emitted[0]
        argmax, top, chosen = head(h[lo:lo + w], g, lm_head, put(jnp.asarray([int(x) for x in emitted], jnp.int32)))
        result.append({"argmax": np.asarray(argmax).astype(np.int64),
                       "max_logit": np.asarray(top).astype(np.float64),
                       "chosen_logit": np.asarray(chosen).astype(np.float64)})
    return result

"""The plain reference of ``model_type: "laguna"``: the windowed-attention,
sparse-expert decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, attention
a head at a time under its mask (causal, and on a sliding layer ``i - j <
sliding_window``), no cache, no kernels, no batching, one layer's weights at a
time, and the experts as a LOOP over the held experts (each over every token,
weighted by the router's weight or zero). The share is the configuration's:
the router scores all published experts, weights are normalised over all
selected, only experts ``ep_rank * held .. + held`` are summed, and what the
absent ones would add is left out, as in the program.

Published block (h 3072, 8 KV heads of 128, H = 48 query heads on a full layer
and 72 on a sliding one, at the served widths): ``x = RMSNorm(h)``; ``q = x
W_q`` -> ``[S, H, 128]``, ``k, v = x W_k, x W_v`` -> ``[S, 8, 128]``; rotation
by the layer's type (full: the first 64 dimensions of every head by YaRN's
frequencies over 64 dimensions, cos and sin times ``attention_factor``, the
other 64 untouched; sliding: all 128 at theta 1e4), by halves; ``o_h =
softmax(q_h k_{h*8/H}^T / sqrt(128) + mask) v``; ``g = softplus(x W_g)`` one
scalar a head, ``o_h <- g_h o_h``; ``h <- h + concat(o) W_o``. ``x2 =
RMSNorm(h)``; the dense layer: ``h <- h + SwiGLU(x2)``; a sparse one: ``s =
sigmoid(x2 W_r)``, the 10 largest of ``s + b`` chosen, ``w = 2.5 s_e /
sum_chosen s``, ``h <- h + sum_e w_e SwiGLU_e(x2) + SwiGLU_shared(x2)``.
The configuration file's ``assumed`` says which of these the published keys
do not fix (sigmoid scores with a selection-only bias; the gate's softplus
and its place; no q/k normalisation; pairing by halves).

CONTROLS, for the tolerances (``score(control=...)``; ``tests/controls_laguna.py
--cell`` reads them on the chip over every distinct request the cell itself
finished): ``sliding_as_full`` (every sliding layer attends to every earlier
token), ``no_gate`` (the per-head gate left out) and ``fp8_matmuls`` (the
WHOLE reference one precision down: both operands of every matmul but the
router's rounded to ``float8_e4m3fn``, the next floating-point format under
the bf16 the configuration states). Each FAILS ``LOGIT_TOL`` and
``HALF_GAP_TOL`` on every request of a run (the readings are below).
"""

from __future__ import annotations

import functools
import math

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths (PR 33, PERF.md section 6; my chip run of
# ``tests/controls_laguna.py --cell laguna-s-ep16.closed8 --ep-rank 10``: all
# 160 requests of a run, which are 28 distinct (prompt, answer) pairs). The
# exact path's logit of a delivered token is 0.0239 to 0.0504 from the
# reference's (the largest sound reading), the reference's half gap 0.0 to
# 0.0288; and with the reference computed under a control, against the same
# exact path (logit error; half gap):
#   fp8_matmuls       0.2494 to 0.3919   0.0985 to 0.2224   28 of 28 over each limit
#   sliding_as_full   1.6919 to 2.8864   1.0269 to 1.6144   28 of 28
#   no_gate           1.9733 to 2.9112   1.0953 to 1.8787   28 of 28
# Each limit is the geometric mean of the largest sound reading and the
# smallest of a control's (0.0504 and 0.2494; 0.0288 and 0.0985). These
# readings are at the family's own attention gains (families/laguna.py QK_GAIN
# 1.2, VO_GAIN 1.0). At lib/serve.py's 0.25 for every projection the first run
# (ep_rank 0, 13 distinct requests) read sound 0.0297 to 0.0753 and the
# controls INSIDE it: sliding_as_full 0.0396 to 0.0623, no_gate 0.0386 to
# 0.0656, fp8_matmuls 0.0767 to 0.1243: scores of spread 0.06 make every
# softmax a plain mean, and no limit parts a sliding layer from a full one.
HALF_GAP_TOL = 0.053  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.112  # the exact path's logit of a delivered token against the reference's
PAD_TO = 512  # sequences are padded on the right (causal: the pad changes nothing before it)
QUERY_BLOCK = 512  # queries of one head scored at a time
SLIDING = "sliding_attention"


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


def _rms_norm(x, scale, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def inv_freq(rope: dict, head_dim: int):
    """``[rot / 2]`` inverse frequencies over the ``rot = head_dim *
    partial_rotary_factor`` rotated dimensions (numpy, float64 then float32)."""
    import numpy as np

    dim, theta = int(head_dim * rope.get("partial_rotary_factor", 1)), float(rope["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") != "yarn":
        return inv.astype(np.float32)
    orig = rope["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim_of(rope["beta_fast"])), 0), min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / rope["factor"] * ramp + inv * (1.0 - ramp)).astype(np.float32)


def _rope(x, inv, amp: float):
    """``x [S, heads, hd]`` at positions 0..S-1: the first ``2 * len(inv)``
    dimensions of every head rotated by halves, the rest as they are."""
    import jax.numpy as jnp

    s, rot = x.shape[0], 2 * len(inv)
    phase = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(phase)[:, None, :] * amp, jnp.sin(phase)[:, None, :] * amp
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _frozen(rope: dict) -> tuple:
    return tuple(sorted(rope.items()))


@functools.lru_cache(maxsize=None)
def _attention_fn(low: str, heads: int, kv_heads: int, hd: int, eps: float, rope: tuple, window: int,
                  gate: bool):
    """One layer's attention half for ``h [S, D]``: ``(h + attention, the
    normed stream the FFN reads)``. ``window`` 0: every earlier token."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(_mm, low=low)
    rope = dict(rope)
    inv = inv_freq(rope, hd)
    amp = float(rope.get("attention_factor", 1.0)) if rope.get("rope_type") == "yarn" else 1.0
    hi = jax.lax.Precision.HIGHEST

    def attention(h, p):
        s = h.shape[0]
        x = _rms_norm(h, p["input_norm"]["scale"], eps)
        a = p["attn"]
        q = _rope(mm(x, a["wq"]["kernel"]).reshape(s, heads, hd), inv, amp)
        k = _rope(mm(x, a["wk"]["kernel"]).reshape(s, kv_heads, hd), inv, amp)
        v = mm(x, a["wv"]["kernel"]).reshape(s, kv_heads, hd)
        g = jax.nn.softplus(mm(x, a["wg"]["kernel"]))  # [S, heads]
        pos = jnp.arange(s)
        qb = min(QUERY_BLOCK, s)
        kv_of = jnp.arange(heads) * kv_heads // heads

        def one_head(args):
            qh, n = args  # [S, hd], the KV head it reads
            kh, vh = k[:, n], v[:, n]

            def block(i):
                rows = jax.lax.dynamic_slice_in_dim(qh, i * qb, qb, 0)
                scores = jnp.einsum("sd,td->st", rows, kh, precision=hi) / math.sqrt(hd)
                at = (i * qb + jnp.arange(qb))[:, None]
                ok = pos[None, :] <= at
                if window:
                    ok = ok & (at - pos[None, :] < window)
                probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
                return jnp.einsum("st,td->sd", probs, vh, precision=hi)

            return jax.lax.map(block, jnp.arange(s // qb)).reshape(s, hd)

        out = jax.lax.map(one_head, (q.transpose(1, 0, 2), kv_of)).transpose(1, 0, 2)  # [S, heads, hd]
        if gate:
            out = out * g[:, :, None]
        h = h + mm(out.reshape(s, heads * hd), a["wo"]["kernel"])
        return h, _rms_norm(h, p["post_attn_norm"]["scale"], eps)

    return jax.jit(attention)


def _swiglu(x, gate, up, down, low: str = ""):
    import jax

    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


@functools.lru_cache(maxsize=None)
def _route_fn(top_k: int, scaling: float, normalize: bool):
    """``[S, E]`` weights (zero where not chosen) by the published rule,
    written with an argmax loop (ties to the lower index), not ``top_k``."""
    import jax
    import jax.numpy as jnp

    def route(x, w_g, bias):
        s = jax.nn.sigmoid(_mm(x, w_g))
        choice = s + bias.astype(jnp.float32)[None, :]
        chosen = jnp.zeros(s.shape, bool)
        for _ in range(top_k):
            i = jnp.argmax(jnp.where(chosen, -jnp.inf, choice), axis=-1)
            chosen = chosen | jax.nn.one_hot(i, s.shape[-1], dtype=bool)
        w = jnp.where(chosen, s, 0.0)
        if normalize:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * scaling

    return jax.jit(route)


def moe_layer(x, mlp, experts, held, cfg, control: str = "", chosen_log=None):
    """``sum_{e held and chosen} w_e E_e(x) + E_shared(x)`` for ``x [S, D]``:
    ``mlp`` is one layer's ``router``/``router_bias``/``shared``; ``experts``
    its ``(w_gate, w_up, w_down)`` ``[held, ...]``; ``held`` the published
    indices of the experts they are. ``chosen_log`` (a list) is given the
    ``[S, E]`` mask of who was chosen."""
    import jax
    import jax.numpy as jnp

    w = _route_fn(int(cfg["num_experts_per_tok"]), float(cfg["moe_routed_scaling_factor"]),
                  bool(cfg.get("norm_topk_prob", True)))(x, mlp["router"]["kernel"], mlp["router_bias"])
    if chosen_log is not None:
        chosen_log.append(w > 0)
    w_held = w[:, jnp.asarray(list(held))]  # [S, held]
    expert = jax.jit(functools.partial(_swiglu, low=LOW.get(control, "")))
    y = jnp.zeros_like(x)
    for j in range(len(held)):  # a loop over the held experts, each over every token
        y = y + w_held[:, j:j + 1] * expert(x, experts[0][j], experts[1][j], experts[2][j])
    sh = mlp["shared"]
    return y + expert(x, sh["w_gate"]["kernel"], sh["w_up"]["kernel"], sh["w_down"]["kernel"])


def dense_mlp(x, mlp, control: str = ""):
    import jax

    return jax.jit(functools.partial(_swiglu, low=LOW.get(control, "")))(
        x, mlp["w_gate"]["kernel"], mlp["w_up"]["kernel"], mlp["w_down"]["kernel"])


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, low: str = ""):
    import jax
    import jax.numpy as jnp

    def head(h, final_scale, lm_head, chosen):
        logits = _mm(_rms_norm(h, final_scale, eps), lm_head, low)
        return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
                jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])

    return jax.jit(head)


def held_experts(cfg: dict) -> range:
    held = int(cfg["num_experts"]) // int(cfg.get("ep_size", 1))
    first = int(cfg.get("ep_rank", 0)) * held
    return range(first, first + held)


def layout(cfg: dict):
    """``(leading layers outside the loop, layers a period)`` of the
    program's tree, from the published lists: the leading run of dense
    layers, then periods that end with their full layer."""
    kinds, ffn = list(cfg["layer_types"]), list(cfg["mlp_layer_types"])
    lead = next((i for i, t in enumerate(ffn) if t != "dense"), len(ffn))
    rest = kinds[lead:]
    return lead, (rest.index("full_attention") + 1 if "full_attention" in rest else 1)


# the faults the limits above are held against (tests/controls_laguna.py)
CONTROLS = ("sliding_as_full", "no_gate", "fp8_matmuls")
LOW = {"fp8_matmuls": "fp8"}  # control -> what every matmul but the router's is rounded to


def score(params: dict, cfg: dict, sequences, device, *, control: str = "", route_log=None) -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``WindowedMoEModel`` tree; each
    layer (and each expert) is brought to ``device`` when it is used.

    ``control`` computes the reference under one of ``CONTROLS``.
    ``route_log`` (a list) is given, for every sequence and sparse layer, how
    often each of the published experts was chosen by the tokens the program
    PREFILLS (the prompt) and by those it DECODES (every delivered token but
    the last, which is never fed back)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "experts" not in params or "periods" not in params:
        raise ValueError("the reference reads the WindowedMoEModel parameter layout")

    def put(x):
        return jax.device_put(x, device)

    tokens = [[int(t) for t in p] + [int(t) for t in e] for p, e in sequences]
    padded = -(-max(len(t) for t in tokens) // PAD_TO) * PAD_TO
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(t + [0] * (padded - len(t)), jnp.int32))].astype(jnp.float32)
          for t in tokens]
    del embedding
    lead, period = layout(cfg)
    held = held_experts(cfg)
    low, eps = LOW.get(control, ""), float(cfg["rms_norm_eps"])
    for i in range(int(cfg["num_hidden_layers"])):
        kind = cfg["layer_types"][i]
        sparse = cfg["mlp_layer_types"][i] == "sparse"
        if i < lead:
            layer = jax.tree_util.tree_map(put, params[f"lead_{i}"])
        else:
            trip, at = divmod(i - lead, period)
            layer = jax.tree_util.tree_map(lambda a: put(a[trip]), params["periods"][f"l{at}"])
        if sparse:
            experts = tuple(put(params["experts"][n][i - lead]) for n in ("w_gate", "w_up", "w_down"))
        window = int(cfg["sliding_window"]) if kind == SLIDING and control != "sliding_as_full" else 0
        attention = _attention_fn(low, int(cfg["num_attention_heads_per_layer"][i]),
                                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), eps,
                                  _frozen(cfg["rope_parameters"][kind]), window, control != "no_gate")
        out = []
        for n, h in enumerate(hs):
            h, x = attention(h, layer)
            if not sparse:
                out.append(h + dense_mlp(x, layer["mlp"], control))
                continue
            chosen = [] if route_log is not None else None
            out.append(h + moe_layer(x, layer["mlp"], experts, held, cfg, control, chosen))
            if chosen:
                fed, total = len(sequences[n][0]), len(tokens[n])
                mask = np.asarray(chosen[0])
                route_log.append({"sequence": n, "layer": i,
                                  "prefill_tokens": fed, "prefill": mask[:fed].sum(0),
                                  "decode_tokens": total - 1 - fed, "decode": mask[fed:total - 1].sum(0)})
        hs = out
    head = _head_fn(eps, low)
    final_scale, lm_head = put(params["final_norm"]["scale"]), put(params["lm_head"])
    result = []
    for h, t, (_, emitted) in zip(hs, tokens, sequences):
        w = len(emitted)
        lo = len(t) - w - 1  # the slot whose logits predict emitted[0]
        argmax, top, chosen = head(h[lo:lo + w], final_scale, lm_head,
                                   put(jnp.asarray([int(x) for x in emitted], jnp.int32)))
        result.append({"argmax": np.asarray(argmax).astype(np.int64),
                       "max_logit": np.asarray(top).astype(np.float64),
                       "chosen_logit": np.asarray(chosen).astype(np.float64)})
    return result

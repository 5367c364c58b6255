"""The plain reference of ``model_type: "longcat_flash"``: the shortcut-connected
latent-attention decoder written out in float32.

Nothing of the program is in it but the weights it serves, which are data:
float32 activations, every product at the highest matmul precision, the
EXPANDED (non-absorbed) attention under a causal mask (per-head ``k = [k_nope
| k_rope]`` and ``v`` rebuilt from the latents), no cache, no batching, and
the experts as a LOOP over the held experts (each over every token, weighted
by the router's weight or zero). A layer, as published
(``LongcatFlashDecoderLayer``; ``x`` the residual stream, ``D`` its width)::

    for i in (0, 1):
        x   = x + MLA_i(RMSNorm_in_i(x))
        u_i = RMSNorm_post_i(x)
        if i == 0:  m = MoE(u_0)            # branches off here ...
        x   = x + FFN_i(u_i)                # SwiGLU
    x = x + m                               # ... joins after the second FFN

``MLA``: ``c_q = RMSNorm(x W_DQ)``; ``q = (c_q W_UQ) (D / q_rank)^1/2`` in
heads of ``nope | rope``; ``[c | r] = x W_DKV``; ``c_kv = RMSNorm(c) (D /
kv_rank)^1/2``; ``k_rope = RoPE(r)`` (one for all heads, not scaled);
``[k_nope | v] = c_kv W_UKV``; ``o = softmax(q k^T (nope + rope)^-1/2 +
causal) v``; plain RoPE (no scaling). ``MoE(u)``: ``p = softmax(u W_g)`` in
float32 over routed + zero outputs; choice = the ``moe_topk`` largest of ``p
+ b``; weight ``routed_scaling_factor * p`` at the chosen, NOT renormalised;
``m = sum_{chosen e routed} w_e E_e(u) + (sum_{chosen e zero} w_e) u``.

Departures from the publisher's code: RoPE pairs dimension ``i`` with ``i +
R/2`` (by halves, as the program; the publisher's interleaved pairing is a
permutation of ``W_UQ``'s and ``W_DKV``'s rope columns, which are random
here); the SHARE is the configuration's: the router scores all outputs, only
experts ``ep_rank * held .. + held`` are summed, the zero experts' term is
whole, and what absent experts would add is left out, as in the program.

Memory: the served weights stay where they are (bf16, 10.35 GB at the served
size); a layer's matrix, or ONE expert's, is sliced out of the stacked tree
and brought to float32 inside the jitted step that multiplies by it, and
attention scores one head's 512 queries at a time.

CONTROLS, for the tolerances (``score(control=...)``;
``tests/controls_longcat_flash.py --cell`` reads them on the chip over every
distinct request the cell itself finished): the WHOLE reference one precision
down, both operands of every matmul but the router's rounded to fp8
(``fp8_matmuls``: ``float8_e4m3fn``, the next floating-point format under the
bf16 the configuration states) or to int8 (``int8_matmuls``); fp8 on the
attention sublayers' and the dense FFNs' matmuls alone (``fp8_dense_path``);
the zero experts' identity term left out (``drop_zero_experts``); every
layer's second sublayer left out (``drop_second_sublayer``); sublayer 1's
keys and values rebuilt from sublayer 0's latent, as a wrong cache plane
gives them (``shared_plane``); the branch joined after the FIRST sublayer's
FFN, an ordinary expert layer followed by a dense one (``early_join``).
"""

from __future__ import annotations

import functools

# what the served model may differ from this reference by, in logits. Chip
# readings at the served widths and weights (families/longcat_flash.py
# DENSE_PATH_GAIN 0.55) at ep_rank 7 (PR 31 after its review, PERF.md section 6;
# my chip runs: every distinct request one run finished, 15, and four audits
# of each of six more runs). A run now shows 15 to 18 distinct (prompt, answer)
# pairs over 9 or 10 prompt lengths where the first draw showed 10: one prompt
# is not always answered alike (not placed: PERF.md section 7), so the
# readings are a sample, not a closed set. The exact
# path's logit of a delivered token is 0.0234 to 0.0718 from the reference's
# (the largest sound reading; 0.0066 to 0.0082 in the mean over a request's
# tokens), its half gap at most 0.0138, and with the reference computed under a
# control, against the same exact path:
#   fp8_dense_path        0.2060 to 0.3405   every request over LOGIT_TOL: fp8
#                         on the attention sublayers' and the dense FFNs'
#                         matmuls ALONE (experts, router and head in float32)
#   fp8_matmuls           0.2247 to 0.3588   every request over
#   drop_zero_experts     1.5141 to 2.0028   every request over
#   drop_second_sublayer  1.5614 to 2.1855   every request over: MLA_1 and FFN_1
#                         of every layer left out
#   shared_plane          1.9373 to 3.0454   every request over: sublayer 1's keys
#                         and values rebuilt from sublayer 0's latent (what a
#                         wrong cache plane index gives)
#   int8_matmuls          0.0855 to 0.1360   13 of 15 over: token-and-channel-
#                         scaled int8 is twice bf16's own distance, no more
#   early_join            0.0528 to 0.1015   1 of 15 over: it moves the reference
#                         by 0.05 to 0.11 (0.001 to 0.0017 when the dense path was
#                         drawn at lib/serve.py's 0.25): the zero experts' term is
#                         parallel to the stream and the held experts are 1/32 of
#                         the routed term, so WHERE the branch joins shows only
#                         through them; tier 1's float32 test holds the shortcut's
#                         timing (tests/test_longcat_flash.py)
# At the first draw (the dense path at 0.25, an attention or a dense FFN 1% of
# the residual) sound runs read 0.0142 to 0.0255 and fp8_matmuls 0.0651 to
# 0.1010, but a fault in a dense-path sublayer could not have passed 0.04.
# LOGIT_TOL is the geometric mean of the largest sound reading and the smallest
# of the controls that must fail (0.0718 and 0.2060: 0.1216): 1.67 times of
# room above the one, 1.72 below the other; the per-token MEAN parts them by 7
# times but is lib/stats.py's to take (PERF.md section 7). HALF_GAP_TOL is the
# same distance, halved: 4.3 times the largest sound half gap, under the fp8
# controls' smallest (0.0669).
HALF_GAP_TOL = 0.06  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.12  # the exact path's logit of a delivered token against the reference's
PAD_TO = 512  # sequences are padded on the right (causal: the pad changes nothing before it)
QUERY_BLOCK = 512  # queries of one head scored at a time

CONTROLS = ("fp8_matmuls", "drop_zero_experts", "fp8_dense_path", "drop_second_sublayer", "shared_plane",
            "early_join", "int8_matmuls")
# control -> what its matmuls are rounded to: everywhere but the router, or
# (fp8_dense_path) in the attention sublayers and the dense FFNs alone
LOW = {"int8_matmuls": "int8", "fp8_matmuls": "fp8", "fp8_dense_path": "fp8"}


def _round(x, low: str):
    """Round a row at a time to ``low`` and back, one scale a row (symmetric):
    "int8" (127 steps either side) or "fp8" (``float8_e4m3fn``: 3 bits of
    mantissa, largest 448)."""
    import jax.numpy as jnp

    top = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    if low == "fp8":
        return (x * (448.0 / top)).astype(jnp.float8_e4m3fn).astype(jnp.float32) * (top / 448.0)
    return jnp.round(x * (127.0 / top)) * (top / 127.0)


def _mm(x, w, low: str = ""):
    """``x @ w`` in float32 at the highest precision; ``low`` (a control)
    rounds BOTH operands first: the input a token, the weight an output
    channel."""
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if low:
        x, w = _round(x, low), _round(w.T, low).T
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta: float):
    """``x [S, heads, R]`` at positions 0..S-1, rotated by halves."""
    import jax.numpy as jnp
    import numpy as np

    s, dim = x.shape[0], x.shape[-1]
    inv = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
    phase = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(phase)[:, None, :], jnp.sin(phase)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _at(tree, *index):
    """One layer's (or one expert's) leaves of a stacked tree, sliced where
    they are used: inside a jitted step, so no copy of the slice is kept."""
    import jax

    def take(a):
        for i in index:
            a = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        return a

    return jax.tree_util.tree_map(take, tree)


def _shape(cfg: dict, low: str = "") -> tuple:
    return (low, int(cfg["hidden_size"]), int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]),
            int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]), float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            bool(cfg.get("mla_scale_q_lora", False)), bool(cfg.get("mla_scale_kv_lora", False)))


@functools.lru_cache(maxsize=None)
def _attention_fn(shape: tuple, sub: int):
    """``(h, layers, i, latent=None) -> (h + MLA_sub(norm(h)), post-attention
    norm of it, the sublayer's latent [S, C + R])`` for layer ``i`` of the
    stacked ``layers``. ``latent`` (the control ``shared_plane``) is another
    sublayer's, which keys and values are then rebuilt from."""
    import jax
    import jax.numpy as jnp

    low, D, H, q_rank, C, dn, R, dv, eps, theta, scale_q, scale_kv = shape
    mm = functools.partial(_mm, low=low)
    hi = jax.lax.Precision.HIGHEST

    def attention(h, layers, i, latent=None):
        s = h.shape[0]
        a = _at(layers[f"attn_{sub}"], i)
        x = _rms_norm(h, _at(layers[f"input_norm_{sub}"]["scale"], i), eps)
        q = mm(_rms_norm(mm(x, a["wq_a"]["kernel"]), a["q_norm"]["scale"], eps), a["wq_b"]["kernel"])
        if scale_q:
            q = q * (D / q_rank) ** 0.5
        q = q.reshape(s, H, dn + R)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
        own = mm(x, a["wkv_a"]["kernel"])
        latent = own if latent is None else latent
        c_kv = _rms_norm(latent[:, :C], a["kv_norm"]["scale"], eps)
        if scale_kv:
            c_kv = c_kv * (D / C) ** 0.5
        k_rope = _rope(latent[:, None, C:], theta)  # [S, 1, R], one for all heads, not scaled
        kv = mm(c_kv, a["wkv_b"]["kernel"]).reshape(s, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (s, H, R))], axis=-1)
        v = kv[..., dn:]
        pos = jnp.arange(s)
        qb = min(QUERY_BLOCK, s)

        def one_head(args):
            qh, kh, vh = args  # [S, dn + R], [S, dn + R], [S, dv]

            def block(b):
                rows = jax.lax.dynamic_slice_in_dim(qh, b * qb, qb, 0)
                scores = jnp.einsum("sd,td->st", rows, kh, precision=hi) * (dn + R) ** -0.5
                ok = pos[None, :] <= (b * qb + jnp.arange(qb))[:, None]
                probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
                return jnp.einsum("st,td->sd", probs, vh, precision=hi)

            return jax.lax.map(block, jnp.arange(s // qb)).reshape(s, dv)

        out = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        h = h + mm(out.transpose(1, 0, 2).reshape(s, H * dv), a["wo"]["kernel"])
        return h, _rms_norm(h, _at(layers[f"post_attn_norm_{sub}"]["scale"], i), eps), own

    return jax.jit(attention)


def _swiglu(x, gate, up, down, low: str = ""):
    import jax

    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


@functools.lru_cache(maxsize=None)
def _dense_fn(sub: int, low: str):
    """``(u, layers, i) -> FFN_sub(u)`` of layer ``i``, one matrix upcast at a time."""
    import jax

    def dense(u, layers, i):
        f = _at(layers[f"ffn_{sub}"], i)
        return _swiglu(u, f["w_gate"]["kernel"], f["w_up"]["kernel"], f["w_down"]["kernel"], low)

    return jax.jit(dense)


@functools.lru_cache(maxsize=None)
def _expert_fn(low: str):
    """``(u, experts, i, j) -> E(u)`` for held expert ``j`` of layer ``i``."""
    import jax

    def expert(u, experts, i, j):
        e = _at(experts, i, j)
        return _swiglu(u, e["w_gate"], e["w_up"], e["w_down"], low)

    return jax.jit(expert)


@functools.lru_cache(maxsize=None)
def _route_fn(top_k: int, scaling: float, normalize: bool):
    """``[S, E]`` weights (zero where not chosen) by the published rule,
    written with argmax loops (ties to the lower index), not ``top_k``."""
    import jax
    import jax.numpy as jnp

    def route(u, layers, i):
        mlp = _at(layers["mlp"], i)
        p = jax.nn.softmax(_mm(u, mlp["router"]["kernel"]), axis=-1)  # float32, over routed + zero
        choice = p + mlp["router_bias"].astype(jnp.float32)[None, :]
        chosen = jnp.zeros(choice.shape, bool)
        for _ in range(top_k):
            best = jnp.argmax(jnp.where(chosen, -jnp.inf, choice), axis=-1)
            chosen = chosen | jax.nn.one_hot(best, choice.shape[-1], dtype=bool)
        w = jnp.where(chosen, p, 0.0)
        if normalize:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * scaling

    return jax.jit(route)


def moe_layer(u, layers, experts, i, held, cfg, control: str = "", chosen_log=None):
    """``sum_{e held and chosen} w_e E_e(u) + (sum_{e zero and chosen} w_e) u``
    for ``u [S, D]`` at layer ``i``: ``experts`` the stacked ``[layers, held,
    ...]`` weights, ``held`` the published indices of the experts they are.
    ``chosen_log`` (a list) is given the ``[S, E]`` mask of who was chosen."""
    import jax.numpy as jnp

    n_routed = int(cfg["n_routed_experts"])
    w = _route_fn(int(cfg["moe_topk"]), float(cfg["routed_scaling_factor"]),
                  bool(cfg.get("norm_topk_prob", False)))(u, layers, i)
    if chosen_log is not None:
        chosen_log.append(w > 0)
    y = jnp.zeros_like(u)
    if control != "drop_zero_experts":
        y = w[:, n_routed:].sum(-1, keepdims=True) * u  # identity experts: nothing to multiply by
    expert = _expert_fn("" if control == "fp8_dense_path" else LOW.get(control, ""))
    for j, e in enumerate(held):  # a loop over the held experts, each over every token
        y = y + w[:, e:e + 1] * expert(u, experts, i, j)
    return y


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
    held = int(cfg["n_routed_experts"]) // int(cfg.get("ep_size", 1))
    first = int(cfg.get("ep_rank", 0)) * held
    return range(first, first + held)


def score(params: dict, cfg: dict, sequences, device, *, control: str = "", route_log=None) -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``: for each, arrays of ``len(emitted)``: ``argmax``,
    ``max_logit``, ``chosen_logit`` (the shape of the program's
    ``score_exact``). ``params`` is the served ``LatentMoEModel`` tree.

    ``control`` computes the reference under one of ``CONTROLS``.
    ``route_log`` (a list) is given, for every sequence and layer, how often
    each of the router's outputs (routed, then zero) was chosen by the tokens
    the program PREFILLS (the prompt) and by those it DECODES (every delivered
    token but the last, which is never fed back)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control={control!r}: one of {CONTROLS}")
    if "experts" not in params or "attn_1" not in params["layers"]:
        raise ValueError("the reference reads the shortcut-connected LatentMoEModel parameter layout")

    def put(x):
        return jax.device_put(x, device)

    low = LOW.get(control, "")
    tokens = [[int(t) for t in p] + [int(t) for t in e] for p, e in sequences]
    padded = -(-max(len(t) for t in tokens) // PAD_TO) * PAD_TO
    layers, experts = put(params["layers"]), put(params["experts"])
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(t + [0] * (padded - len(t)), jnp.int32))].astype(jnp.float32)
          for t in tokens]
    del embedding
    attention = [_attention_fn(_shape(cfg, low), sub) for sub in (0, 1)]
    dense = [_dense_fn(sub, low) for sub in (0, 1)]
    held = held_experts(cfg)
    subs = (0,) if control == "drop_second_sublayer" else (0, 1)  # dropped: x skips MLA_1 and FFN_1
    for i in range(int(cfg["num_layers"])):
        out = []
        for n, h in enumerate(hs):
            latent = None
            for sub in subs:
                h, u, latent = attention[sub](h, layers, i, latent if control == "shared_plane" else None)
                if sub == 0:
                    chosen = [] if route_log is not None else None
                    m = moe_layer(u, layers, experts, i, held, cfg, control, chosen)
                h = h + dense[sub](u, layers, i)
                if sub == 0 and control == "early_join":
                    h = h + m
            if control != "early_join":
                h = h + m
            out.append(h)
            if chosen:
                fed, total = len(sequences[n][0]), len(tokens[n])
                mask = np.asarray(chosen[0])
                route_log.append({"sequence": n, "layer": i, "zero_experts": int(cfg["zero_expert_num"]),
                                  "prefill_tokens": fed, "prefill": mask[:fed].sum(0),
                                  "decode_tokens": total - 1 - fed, "decode": mask[fed:total - 1].sum(0)})
        hs = out
    head = _head_fn(float(cfg["rms_norm_eps"]), "" if control == "fp8_dense_path" else low)
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

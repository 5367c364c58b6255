"""The plain reference: a dense Mistral-style decoder written out in float32.

``correct`` may not rest on the program's own exact path alone: that path
runs the program's kernels, its activation dtype and its KV dtype, so a change
that drops precision or mathematics in the model code moves both sides and
passes. This is the same block (RMSNorm, grouped-query attention with RoPE by
halves, SwiGLU, an untied head) with nothing of the program in it but the
weights it serves, which are data: float32 activations, float32 KV, every
product at the highest matmul precision, plain softmax attention under a
causal mask, one layer's weights at a time on one device (an int8 kernel is
``kernel_q * qscale``, which is what the served model IS). One teacher-forced
pass over ``prompt + delivered`` gives, for each delivered token, the
reference's greedy choice, its logit and the delivered token's logit: the
shape of the program's ``score_exact`` result, so the two can be set side by
side.

This is the reference of ``model_type: "mistral"``: ``lib/serve.py
load_reference`` finds a family's by that name and holds it to ``score``,
``HALF_GAP_TOL`` and ``LOGIT_TOL``.
"""

from __future__ import annotations

import functools
import math

# what the served model may differ from this reference by, in logits (PR 23,
# chip, 24 sequences of the int8-weight, int8-KV configuration: half gaps up
# to 0.009, logit errors 0.029 to 0.041; PERF.md section 6). About twice and
# five times what bf16 activations and int8 KV cost today, so that a further
# loss of precision of that size fails.
HALF_GAP_TOL = 0.05  # half the gap between the reference's choice and a delivered token
LOGIT_TOL = 0.10  # the exact path's logit of a delivered token against the reference's
PAD_TO = 512  # sequences are padded on the right (causal: the pad changes nothing before it)


def _dense(x, group):
    import jax
    import jax.numpy as jnp

    if "kernel_q" in group:
        w = group["kernel_q"].astype(jnp.float32) * group["qscale"].astype(jnp.float32)[None, :]
    else:
        w = group["kernel"].astype(jnp.float32)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta: float):
    """``x [S, heads, hd]`` at positions 0..S-1, rotated by halves (dimension
    ``i`` pairs with ``i + hd/2``, the published layout)."""
    import jax.numpy as jnp

    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    phase = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(phase)[:, None, :], jnp.sin(phase)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(heads: int, kv_heads: int, head_dim: int, eps: float, theta: float):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def layer(h, p):
        s = h.shape[0]
        x = _rms_norm(h, p["input_norm"]["scale"], eps)
        a = p["attn"]
        q = _rope(_dense(x, a["wq"]).reshape(s, heads, head_dim), theta)
        k = _rope(_dense(x, a["wk"]).reshape(s, kv_heads, head_dim), theta)
        v = _dense(x, a["wv"]).reshape(s, kv_heads, head_dim)
        causal = jnp.tril(jnp.ones((s, s), bool))

        def one_kv_head(args):  # the query heads that share one KV head
            qg, kh, vh = args  # [group, S, hd], [S, hd], [S, hd]
            scores = jnp.einsum("gsd,td->gst", qg, kh, precision=hi) / math.sqrt(head_dim)
            probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gst,td->gsd", probs, vh, precision=hi)

        qg = q.reshape(s, kv_heads, heads // kv_heads, head_dim).transpose(1, 2, 0, 3)
        out = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        out = out.transpose(2, 0, 1, 3).reshape(s, heads * head_dim)
        h = h + _dense(out, a["wo"])
        x = _rms_norm(h, p["post_attn_norm"]["scale"], eps)
        m = p["mlp"]
        return h + _dense(jax.nn.silu(_dense(x, m["w_gate"])) * _dense(x, m["w_up"]), m["w_down"])

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax
    import jax.numpy as jnp

    def head(h, final_scale, head_params, chosen):
        x = _rms_norm(h, final_scale, eps)
        if "lm_head_q" in head_params:
            w = head_params["lm_head_q"].astype(jnp.float32) * \
                head_params["lm_head_scale"].astype(jnp.float32)[None, :]
        else:
            w = head_params["lm_head"].astype(jnp.float32)
        logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
        return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
                jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])

    return jax.jit(head)


def score(params: dict, cfg: dict, sequences, device) -> list:
    """Teacher-forced reference scores of each ``(prompt_ids, emitted)`` of
    ``sequences``.

    ``params`` is the served tree in the program's canonical layout (stacked
    ``layers``, unfused projections; bf16 kernels or ``kernel_q``/``qscale``),
    wherever it lives: each layer is brought to ``device`` once and every
    sequence passes through it there. ``cfg`` is the configuration file
    (published key names). Returns, for each sequence, arrays of
    ``len(emitted)``: ``argmax``, ``max_logit``, ``chosen_logit``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    layers = params["layers"]
    if "wq" not in layers["attn"] or "w_gate" not in layers["mlp"]:
        raise ValueError("the reference reads the canonical (unfused) parameter layout")

    def put(x):
        return jax.device_put(x, device)

    tokens = [[int(t) for t in p] + [int(t) for t in e] for p, e in sequences]
    padded = -(-max(len(t) for t in tokens) // PAD_TO) * PAD_TO
    embedding = put(params["embedding"])
    hs = [embedding[put(jnp.asarray(t + [0] * (padded - len(t)), jnp.int32))].astype(jnp.float32)
          for t in tokens]
    del embedding
    layer = _layer_fn(int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                      int(cfg["head_dim"]), float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]))
    for i in range(int(cfg["num_hidden_layers"])):
        weights = jax.tree_util.tree_map(lambda a: put(a[i]), layers)
        hs = [layer(h, weights) for h in hs]
    head = _head_fn(float(cfg["rms_norm_eps"]))
    final_scale = put(params["final_norm"]["scale"])
    head_params = {k: put(v) for k, v in params.items() if k.startswith("lm_head")}
    out = []
    for h, t, (_, emitted) in zip(hs, tokens, sequences):
        w = len(emitted)
        lo = len(t) - w - 1  # the slot whose logits predict emitted[0]
        argmax, top, chosen = head(h[lo:lo + w], final_scale, head_params,
                                   put(jnp.asarray([int(x) for x in emitted], jnp.int32)))
        out.append({"argmax": np.asarray(argmax).astype(np.int64),
                    "max_logit": np.asarray(top).astype(np.float64),
                    "chosen_logit": np.asarray(chosen).astype(np.float64)})
    return out

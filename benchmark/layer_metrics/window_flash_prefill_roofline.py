"""The windowed flash prefill kernel against its roofline: the least time the
chip could take for its calls (operations over peak bf16 FLOP/s, or bytes over
HBM bandwidth, whichever is longer) over the time they took in the trace. The
trace names the kernel ``flash_attention_window`` and gives its result type
``[rows x heads, bucket, head_dim]``.

Operations are those of the LIVE band of the prompts' REAL tokens: query ``i``
of a prompt (counted from its first real token) sees ``min(i + 1, W)`` keys,
so a prompt of ``n`` tokens has ``W (W + 1) / 2 + (n - W) W`` live pairs (``n
(n + 1) / 2`` under ``W``), each 2 x head_dim multiply-adds for QK^T and as
many for PV, every query head of a sliding layer; padding to the bucket and
the dead part of an edge block add none. Bytes: q and o of every query head, k
and v of every KV head, at the bucket's length."""

import re

SLIDING = "sliding_attention"


def live_pairs(n_tokens: float, window: int) -> float:
    """Query-key pairs a causal window of ``window`` keeps over ``n_tokens``."""
    if n_tokens <= window:
        return n_tokens * (n_tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (n_tokens - window) * float(window)


def flops(n_tokens: float, window: int, heads: int, head_dim: int) -> float:
    return 4.0 * heads * head_dim * live_pairs(n_tokens, window)


def bytes_moved(bucket: int, heads: int, kv_heads: int, head_dim: int, itemsize: int = 2) -> float:
    return float((2 * heads + 2 * kv_heads) * bucket * head_dim * itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or "sliding_window" not in cfg:
        return None
    per_layer = dict(zip(cfg.get("layer_types", ()), cfg.get("num_attention_heads_per_layer", ())))
    if SLIDING not in per_layer:
        return None
    heads, kv, hd = int(per_layer[SLIDING]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    window, peaks = int(cfg["sliding_window"]), ctx["peaks"]
    prompts = ctx["prompt_tokens"]
    row_flops = sum(flops(n, window, heads, hd) for n in prompts) / len(prompts)
    least = seconds = 0.0
    for key, (calls, sec) in tr["kernels"].items():
        m = re.match(r"^flash_attention_window \w+\[(\d+),(\d+),(\d+)\]$", key)
        if not m or int(m.group(1)) % heads or int(m.group(3)) != hd:
            continue
        rows, bucket = int(m.group(1)) // heads, int(m.group(2))
        least += calls * rows * max(row_flops / peaks["bf16_flops_per_s"],
                                    bytes_moved(bucket, heads, kv, hd) / peaks["hbm_bytes_per_s"])
        seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0

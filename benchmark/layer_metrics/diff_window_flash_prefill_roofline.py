"""The window differential-attention layers' flash prefill against its
roofline: the least time the chip could take for the calls over the time they
took in the trace (``window_flash_prefill_roofline``'s arithmetic; that reader
needs ``layer_types`` and a published ``head_dim`` of the kernel's width, which
this configuration has not, so the cell has this reader instead). The trace
names the kernel ``flash_attention_window`` and gives its result type ``[rows
x query heads, bucket, 2 hd]``.

Operations are the WORK's over the LIVE band of the prompts' REAL tokens
(``window_flash_prefill_roofline.live_pairs``): a pair and query head ``2 hd``
multiply-adds for its score (half of what the zero-padded head multiplies) and
``2 x 2 hd`` for its value pair. Bytes: q and o of every query head at ``2
hd``, k and v of every pair head, at the bucket's length. It cannot pass 100%."""

import re


def live_pairs(n_tokens: float, window: int) -> float:
    """Query-key pairs a causal window of ``window`` keeps over ``n_tokens``."""
    if n_tokens <= window:
        return n_tokens * (n_tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (n_tokens - window) * float(window)


def flops(n_tokens: float, window: int, heads: int, head_dim: int) -> float:
    return heads * (2.0 * head_dim + 4.0 * head_dim) * live_pairs(n_tokens, window)


def bytes_moved(bucket: int, heads: int, pair_heads: int, pair_dim: int, itemsize: int = 2) -> float:
    return float((2 * heads + 2 * pair_heads) * bucket * pair_dim * itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or cfg.get("model_type") != "phi4flash":
        return None
    heads, pairs = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]) // 2
    hd, window, peaks = int(cfg["hidden_size"]) // heads, int(cfg["sliding_window"]), ctx["peaks"]
    prompts = ctx["prompt_tokens"]
    row_flops = sum(flops(n, window, heads, hd) for n in prompts) / len(prompts)
    least = seconds = 0.0
    for key, (calls, sec) in tr["kernels"].items():
        m = re.match(r"^flash_attention_window \w+\[(\d+),(\d+),(\d+)\]$", key)
        if not m or int(m.group(1)) % heads or int(m.group(3)) != 2 * hd:
            continue
        rows, bucket = int(m.group(1)) // heads, int(m.group(2))
        least += calls * rows * max(row_flops / peaks["bf16_flops_per_s"],
                                    bytes_moved(bucket, heads, pairs, 2 * hd) / peaks["hbm_bytes_per_s"])
        seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0

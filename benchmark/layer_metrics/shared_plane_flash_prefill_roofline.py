"""The full differential-attention layer's flash prefill against its roofline:
the least time the chip could take for the call (operations over peak bf16
FLOP/s, or bytes over HBM bandwidth, whichever is longer) over the kernel's
mean time in the trace. The trace names the kernel ``flash_attention`` and
gives its result type ``[query heads, bucket, 2 hd]``: the layer goes through
the grouped-query kernel with a pair of ``hd``-wide key heads as one key head
of ``2 hd`` and every query head zero-padded to it (``models/cross_decoder.py``).

Operations are the WORK's, whatever implements it: causal pairs of the
prompts' REAL tokens (``n (n + 1) / 2``), and a pair and query head ``2 hd``
multiply-adds for its score (``q1 k1`` or ``q2 k2``: half of what the padded
head multiplies) and ``2 x 2 hd`` for its ``2 hd``-wide value pair. Bytes: q
and o of every query head at ``2 hd``, k and v of every pair head, at the
bucket's length. It cannot pass 100%: the kernel multiplies more than is
counted here and moves no less."""

import re


def flops(n_tokens: float, heads: int, head_dim: int) -> float:
    """``heads`` padded query heads of a layer whose published head is ``head_dim`` wide."""
    return heads * (2.0 * head_dim + 4.0 * head_dim) * n_tokens * (n_tokens + 1) / 2.0


def bytes_moved(bucket: int, heads: int, pair_heads: int, pair_dim: int, itemsize: int = 2) -> float:
    return float((2 * heads + 2 * pair_heads) * bucket * pair_dim * itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or cfg.get("model_type") != "phi4flash":
        return None
    heads, pairs = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]) // 2
    hd = int(cfg["hidden_size"]) // heads
    calls = seconds = 0.0
    bucket = 0
    for key, (n, sec) in tr["kernels"].items():
        m = re.match(r"^flash_attention \w+\[(\d+),(\d+),(\d+)\]$", key)
        if m and int(m.group(1)) == heads and int(m.group(3)) == 2 * hd:  # one row's padded heads
            calls, seconds, bucket = calls + n, seconds + sec, int(m.group(2))
    if not calls:
        return None
    n_tokens = sum(ctx["prompt_tokens"]) / len(ctx["prompt_tokens"])
    peaks = ctx["peaks"]
    least = max(flops(n_tokens, heads, hd) / peaks["bf16_flops_per_s"],
                bytes_moved(bucket, heads, pairs, 2 * hd) / peaks["hbm_bytes_per_s"])
    return least / (seconds / calls) * 100.0

"""The latent-attention prefill kernel against its roofline: the least time
the chip could take for one call (operations over peak bf16 FLOP/s, or bytes
over HBM bandwidth, whichever is longer) over the kernel's mean time in the
trace. One call is one prompt row: 128 heads of causal attention whose key
width (nope + rope) differs from its value width. Operations are those over
the prompts' REAL tokens; bytes are q, k, v and o at the bucket's length."""

import re


def flops(n_tokens: float, heads: int, qk_dim: int, v_dim: int) -> float:
    """QK^T is 2*qk_dim and PV 2*v_dim operations for each of n(n+1)/2
    query-key pairs and each head; padding to the bucket adds none."""
    return 2.0 * heads * (qk_dim + v_dim) * n_tokens * (n_tokens + 1) / 2.0


def bytes_moved(bucket: int, heads: int, qk_dim: int, v_dim: int, itemsize: int = 2) -> float:
    """q and k at the key width, v and o at the value width, every head."""
    return float(heads * bucket * (2 * qk_dim + 2 * v_dim) * itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or "qk_nope_head_dim" not in cfg:
        return None
    heads, dv = int(cfg["num_attention_heads"]), int(cfg["v_head_dim"])
    dq = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    calls = seconds = 0.0
    bucket = 0
    for key, (n, sec) in tr["kernels"].items():
        m = re.match(r"^mla_flash_attention \w+\[(\d+),(\d+),(\d+)\]$", key)
        if m and int(m.group(1)) == heads and int(m.group(3)) == dv:  # one row, every head
            calls, seconds, bucket = calls + n, seconds + sec, int(m.group(2))
    if not calls:
        return None
    n_tokens = sum(ctx["prompt_tokens"]) / len(ctx["prompt_tokens"])
    peaks = ctx["peaks"]
    least = max(flops(n_tokens, heads, dq, dv) / peaks["bf16_flops_per_s"],
                bytes_moved(bucket, heads, dq, dv) / peaks["hbm_bytes_per_s"])
    return least / (seconds / calls) * 100.0

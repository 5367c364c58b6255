"""The block-window prefill kernel against its roofline: the least time the
chip could take for its calls (operations over peak bf16 FLOP/s, or bytes over
HBM bandwidth, whichever is longer) over the time they took in the trace. The
trace names the kernel ``window_summary_flash_attention`` and gives its result
type ``[heads, bucket, head_dim]``: one call is one layer of ONE prompt row.

Operations are those of the prompt's REAL positions, whatever computes them:
position ``t`` sees the ``t % W + 1`` keys of its own window up to itself and
the ``(W / C) * (t // W)`` summaries of the windows before it, each 2 x
head_dim multiply-adds for QK^T and as many for PV, every head; padding to the
bucket and the dead half of a diagonal block add none. Bytes: q and o at the
bucket's length, the row's k and v once (a window's strip is read for that
window's queries only), and for window ``w`` the ``(W / C) * w`` summaries of
the windows before it, keys and values, once a window."""

import re

KERNEL = "window_summary_flash_attention"


def keys_seen(n_tokens: int, window: int, chunk: int) -> float:
    """Keys and summaries the ``n_tokens`` positions of a prompt attend to, summed."""
    per = window // chunk
    whole, rest = divmod(int(n_tokens), window)
    exact = whole * window * (window + 1) / 2.0 + rest * (rest + 1) / 2.0
    pooled = per * window * whole * (whole - 1) / 2.0 + per * whole * rest
    return exact + pooled


def flops(n_tokens: int, window: int, chunk: int, heads: int, head_dim: int) -> float:
    return 4.0 * heads * head_dim * keys_seen(n_tokens, window, chunk)


def bytes_moved(bucket: int, window: int, chunk: int, heads: int, head_dim: int, itemsize: int = 2) -> float:
    windows = -(-bucket // window)
    pooled = (window // chunk) * windows * (windows - 1) / 2.0  # summaries read, over the windows
    return float((4 * bucket + 2 * pooled) * heads * head_dim * itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or "window_size" not in cfg or "chunk_size" not in cfg:
        return None
    heads = int(cfg["num_attention_heads"])
    hd = int(cfg["hidden_size"]) // heads
    window, chunk, peaks = int(cfg["window_size"]), int(cfg["chunk_size"]), ctx["peaks"]
    prompts = ctx["prompt_tokens"]
    row_flops = sum(flops(n, window, chunk, heads, hd) for n in prompts) / len(prompts)
    least = seconds = 0.0
    for key, (calls, sec) in tr["kernels"].items():
        m = re.match(r"^" + KERNEL + r" \w+\[(\d+),(\d+),(\d+)\]$", key)
        if not m or int(m.group(1)) != heads or int(m.group(3)) != hd:
            continue
        bucket = int(m.group(2))
        least += calls * max(row_flops / peaks["bf16_flops_per_s"],
                             bytes_moved(bucket, window, chunk, heads, hd) / peaks["hbm_bytes_per_s"])
        seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0

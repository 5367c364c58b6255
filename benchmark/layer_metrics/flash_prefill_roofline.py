"""The decoder's flash prefill kernel against its roofline: the least time
the chip could take for the call (operations over peak bf16 FLOP/s, or bytes
over HBM bandwidth, whichever is longer) over the kernel's mean time in the
trace. Operations are those of causal attention over the prompts' REAL tokens
(padding to the bucket adds none); bound by compute at these lengths. The
trace names the kernel ``flash_attention`` and gives its result type, which
tells the decoder's call from the encoder's."""

import re


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens"):
        return None
    tp = int(cfg["serving"]["tp"])
    heads, kv = cfg["num_attention_heads"] // tp, cfg["num_key_value_heads"] // tp
    hd = cfg["head_dim"]
    calls = seconds = 0.0
    bucket = 0
    for key, (n, sec) in tr["kernels"].items():
        m = re.match(r"^flash_attention \w+\[(\d+),(\d+),(\d+)\]$", key)
        if m and int(m.group(1)) == heads and int(m.group(3)) == hd:  # one row, this chip's heads
            calls, seconds, bucket = calls + n, seconds + sec, int(m.group(2))
    if not calls:
        return None
    n_tokens = sum(ctx["prompt_tokens"]) / len(ctx["prompt_tokens"])
    st, peaks = ctx["stats"], ctx["peaks"]
    least = max(st.flash_attention_flops(n_tokens, heads, hd) / peaks["bf16_flops_per_s"],
                st.flash_attention_bytes(bucket, heads, kv, hd) / peaks["hbm_bytes_per_s"])
    return least / (seconds / calls) * 100.0

"""The absorbed decode kernel over the latent cache against its roofline: the
least time for one call (bytes over HBM bandwidth, or operations over peak,
whichever is longer) over its mean time in the trace. One call is one layer
of one decode step for the whole batch: every row's LIVE cache slots (its
prompt's real tokens plus, on average, half the answer) of ``kv_lora_rank +
qk_rope_head_dim`` values are read once; q and o are ``heads x (rank [+
rope])`` a row. Operations: scores over rank + rope and the weighted sum over
rank, 2 each a head and slot."""

import re


def bytes_moved(rows: int, live_slots: float, heads: int, rank: int, rope: int, itemsize: int = 2) -> float:
    return rows * (live_slots * (rank + rope) + heads * (2 * rank + rope)) * float(itemsize)


def flops(rows: int, live_slots: float, heads: int, rank: int, rope: int) -> float:
    return 2.0 * rows * heads * live_slots * (2 * rank + rope)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or "kv_lora_rank" not in cfg:
        return None
    heads, rank, rope = (int(cfg[k]) for k in ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim"))
    live = sum(ctx["prompt_tokens"]) / len(ctx["prompt_tokens"]) + ctx["new_tokens"] / 2.0
    peaks = ctx["peaks"]
    least = seconds = 0.0
    for key, (n, sec) in tr["kernels"].items():
        m = re.match(r"^mla_decode_attention \w+\[(\d+),(\d+),(\d+)\]$", key)
        if m and int(m.group(2)) == heads and int(m.group(3)) == rank:
            rows = int(m.group(1))
            least += n * max(bytes_moved(rows, live, heads, rank, rope) / peaks["hbm_bytes_per_s"],
                             flops(rows, live, heads, rank, rope) / peaks["bf16_flops_per_s"])
            seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0

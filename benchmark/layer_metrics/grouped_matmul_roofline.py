"""The grouped expert matmul against its roofline: over every call in the
trace, the least time the chip could take (operations over peak bf16 FLOP/s,
or bytes over HBM bandwidth, whichever is longer) over the time it took.

A call multiplies the assignment rows of one pass by the held experts they
chose (gate, up: 7168 -> 2048; down: 2048 -> 7168 at the served widths; the
trace gives ``[row buffer, n]``, ``k`` is the other width). What a call had to
do is counted by the program, on the device, over the window
(``tpu_rag_engine_moe_*``): the rows it was given (assignments computed over
layer calls; the buffer is larger) and, in decode, the held experts actually
HIT a layer-step, never all held: an expert no token chose is not read. In a
prefill every held expert is hit. Bytes: the hit experts' ``k x n`` weights
once, the rows in and out once."""

import re

STAT = "tpu_rag_engine_moe_{}"
DECODE_ROWS = 1024  # a row buffer under this is a decode step's (8 rows x top-8 at most)


def flops(rows: float, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def bytes_moved(rows: float, experts_hit: float, k: int, n: int, itemsize: int = 2) -> float:
    return (experts_hit * k * n + rows * (k + n)) * float(itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or "moe_intermediate_size" not in cfg:
        return None
    d = lambda name: ctx["stats"].delta(ctx["before"], ctx["after"], STAT.format(name))  # noqa: E731
    steps, prefills = d("decode_layer_steps"), d("prefill_layer_calls")
    if not steps or not prefills:
        return None
    per_call = {  # mode -> (rows, experts hit) of an average call
        "decode": (d("decode_assignments_computed") / steps, d("decode_experts_hit") / steps),
        "prefill": (d("prefill_assignments_computed") / prefills,
                    float(int(cfg["n_routed_experts"]) // int(cfg.get("ep_size", 1)))),
    }
    widths = {int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])}
    peaks = ctx["peaks"]
    least = seconds = 0.0
    for key, (calls, sec) in tr["kernels"].items():
        m = re.match(r"^grouped_matmul \w+\[(\d+),(\d+)\]$", key)
        if not m or int(m.group(2)) not in widths:
            continue
        n = int(m.group(2))
        (k,) = widths - {n}
        rows, hit = per_call["decode" if int(m.group(1)) < DECODE_ROWS else "prefill"]
        least += calls * max(flops(rows, k, n) / peaks["bf16_flops_per_s"],
                             bytes_moved(rows, hit, k, n) / peaks["hbm_bytes_per_s"])
        seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0

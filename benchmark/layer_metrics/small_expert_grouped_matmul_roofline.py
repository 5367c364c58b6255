"""The grouped expert matmul against its roofline at the "many small experts"
shape (3072 <-> 1024 at the served widths, ten choices a token): over every
call in the trace, the least time the chip could take (operations over peak
bf16 FLOP/s, or bytes over HBM bandwidth, whichever is longer) over the time it
took. The arithmetic is ``grouped_matmul_roofline.py``'s own (``flops``,
``bytes_moved``, imported from that file); this reader differs in the
published names it reads (``num_experts``, held by ``ep_size``) and in never
giving a call more rows than the row buffer the trace shows it had (a layer
call an imbalance cuts into two passes is two kernel calls of at most a buffer
each). What a call had to do is counted by the program on the device over the
window (``tpu_rag_engine_moe_*``), as there. None where the configuration has
no ``num_experts`` or the trace holds no such call."""

import importlib.util
import os
import re

_spec = importlib.util.spec_from_file_location(
    "benchmark_per_layer_grouped_matmul_roofline",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "grouped_matmul_roofline.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
flops, bytes_moved, STAT, DECODE_ROWS = _base.flops, _base.bytes_moved, _base.STAT, _base.DECODE_ROWS


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or "num_experts" not in cfg or "moe_intermediate_size" not in cfg:
        return None
    d = lambda name: ctx["stats"].delta(ctx["before"], ctx["after"], STAT.format(name))  # noqa: E731
    steps, prefills = d("decode_layer_steps"), d("prefill_layer_calls")
    if not steps or not prefills:
        return None
    per_call = {  # mode -> (rows, experts hit) of an average layer call
        "decode": (d("decode_assignments_computed") / steps, d("decode_experts_hit") / steps),
        "prefill": (d("prefill_assignments_computed") / prefills,
                    float(int(cfg["num_experts"]) // int(cfg.get("ep_size", 1)))),
    }
    widths = {int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])}
    peaks = ctx["peaks"]
    least = seconds = 0.0
    for key, (calls, sec) in tr["kernels"].items():
        m = re.match(r"^grouped_matmul \w+\[(\d+),(\d+)\]$", key)
        if not m or int(m.group(2)) not in widths:
            continue
        buffer, n = int(m.group(1)), int(m.group(2))
        (k,) = widths - {n}
        rows, hit = per_call["decode" if buffer < DECODE_ROWS else "prefill"]
        rows = min(rows, float(buffer))
        least += calls * max(flops(rows, k, n) / peaks["bf16_flops_per_s"],
                             bytes_moved(rows, hit, k, n) / peaks["hbm_bytes_per_s"])
        seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0

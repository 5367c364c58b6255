"""The grouped expert matmul against its roofline at the LATENT experts' shape
(``moe_latent_size`` <-> ``moe_intermediate_size``: 1024 <-> 2688 at the
served widths, 22 choices a token, TWO products an expert: no gate): over
every such call in the trace, the least time the chip could take (operations
over peak bf16 FLOP/s, or bytes over HBM bandwidth, whichever is longer) over
the time it took.

A call multiplies the assignment rows of one pass by the held experts they
chose; the trace gives ``[row buffer, n]``, ``k`` is the other width. What a
call had to do is counted by the program, on the device, over the window
(``tpu_rag_engine_moe_*``): the rows it was given (assignments computed over
layer calls, and never more than the row buffer the trace shows the call had:
a layer call an imbalance cuts into two passes is two kernel calls of at most
a buffer each) and, in decode, the held experts actually HIT a layer-step,
never all held: an expert no token chose is not read. In a prefill every held
expert is hit. Bytes: the hit experts' ``k x n`` weights once, the rows in
and out once.

The bound it cannot pass: a call is never given more rows than it had, nor
more experts than are held, and each at the least its work costs; the time is
the kernel's own. None where the configuration has no ``moe_latent_size``
(``grouped_matmul_roofline.py`` and ``small_expert_grouped_matmul_roofline.py``
read the experts that work at the stream's width) or the trace holds no such
call."""

import re

STAT = "tpu_rag_engine_moe_{}"
DECODE_ROWS = 1024  # a row buffer under this is a decode step's (8 rows x top-22 at most)


def flops(rows: float, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def bytes_moved(rows: float, experts_hit: float, k: int, n: int, itemsize: int = 2) -> float:
    return (experts_hit * k * n + rows * (k + n)) * float(itemsize)


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not cfg.get("moe_latent_size") or ctx["peaks"] is None:
        return None
    d = lambda name: ctx["stats"].delta(ctx["before"], ctx["after"], STAT.format(name))  # noqa: E731
    steps, prefills = d("decode_layer_steps"), d("prefill_layer_calls")
    if not steps or not prefills:
        return None
    held = float(int(cfg["n_routed_experts"]) // int(cfg.get("ep_size", 1)))
    per_call = {  # mode -> (rows, experts hit) of an average layer call
        "decode": (d("decode_assignments_computed") / steps, min(d("decode_experts_hit") / steps, held)),
        "prefill": (d("prefill_assignments_computed") / prefills, held),
    }
    widths = {int(cfg["moe_latent_size"]), int(cfg["moe_intermediate_size"])}
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

"""The selective-scan kernel against the chip's published peaks: the least time
the chip could take for its calls over the time they took in the trace. The
trace names the kernel ``selective_scan``; its first result is ``y [rows,
positions, d_inner / 128, 128]``: one call is one state layer of a prefill's
rows.

``peaks.json`` has a peak for the MXU and for HBM and none for the vector or
the transcendental unit, which are what bound this kernel: per position,
channel and state one ``exp``, five multiplies and two adds, nothing on the
MXU. So the floor here is the work's BYTES at ``hbm_bytes_per_s``, and the
share reads LOW BY CONSTRUCTION (a kernel at the vector unit's own limit would
read well under 100%): it says how far the scan is from being free, not how
far from its own bound. It cannot pass 100%: no implementation moves fewer
bytes. The ``selective_scan`` information line gives the state updates a
second beside it, the number a vector-unit peak would be held against.

Bytes, whatever implements the scan (a gate or a skip fused in or not moves
the time, not the count): for every row and position ``u`` and ``delta`` in
and ``y`` out (``d_inner`` each, in the compute type), ``B_t`` and ``C_t``
(``d_state`` each, float32); ``A`` and ``D`` once a call; the state out
(``d_state x d_inner`` float32 a row)."""

import json
import re

KERNEL = "selective_scan"


def bytes_moved(rows: int, positions: int, d_inner: int, d_state: int, itemsize: int = 2) -> float:
    per_position = 3 * d_inner * itemsize + 2 * d_state * 4
    once = (d_inner * d_state + d_inner) * 4
    return float(rows * (positions * per_position + d_inner * d_state * 4) + once)


def state_updates(rows: int, positions: int, d_inner: int, d_state: int) -> float:
    """``exp``s (and as many state updates of five multiplies and two adds)."""
    return float(rows) * positions * d_inner * d_state


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or "mamba_d_state" not in cfg:
        return None
    d_inner = int(cfg["mamba_expand"]) * int(cfg["hidden_size"])
    d_state, peaks = int(cfg["mamba_d_state"]), ctx["peaks"]
    least = seconds = updates = 0.0
    for key, (calls, sec) in tr["kernels"].items():
        m = re.match(r"^" + KERNEL + r" \(?\w+\[(\d+),(\d+),(\d+),(\d+)\]", key)
        if not m or int(m.group(3)) * int(m.group(4)) < d_inner:
            continue
        rows, positions = int(m.group(1)), int(m.group(2))
        least += calls * bytes_moved(rows, positions, d_inner, d_state) / peaks["hbm_bytes_per_s"]
        updates += calls * state_updates(rows, positions, d_inner, d_state)
        seconds += sec
    if not seconds:
        return None
    print(json.dumps({"event": "selective_scan", "seconds": seconds,
                      "state_updates_per_s": updates / seconds,
                      "bytes_per_s": least * peaks["hbm_bytes_per_s"] / seconds}), flush=True)
    return least / seconds * 100.0

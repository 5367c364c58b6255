"""How far the window's slowest dispatch lay above its median, in
milliseconds, from the responses' own ``timings``: requests are grouped by
``dispatch_seq``; a dispatch's time is its riders' largest ``queue_wait_ms``
+ ``launch_ms`` + ``device_ms`` + its riders' largest ``deliver_ms``; over
the dispatches of the modal ``dispatch_rows``, the largest less the median.
The ``slowest_dispatch`` information line names that dispatch and each
stage's excess over that stage's median: which stage a slow round was slow
in. None where no response names a dispatch (a program from before PR 51)."""

import json
from collections import Counter

STAGES = ("queue_wait_ms", "launch_ms", "device_ms", "deliver_ms")


def dispatches(requests) -> dict:
    """``{seq: {"rows": n, <stage>: ms}}``, each stage its riders' largest."""
    out = {}
    for r in requests:
        t = r["timings"] if r["status"] == 200 else {}
        if "dispatch_seq" not in t:
            continue
        d = out.setdefault(int(t["dispatch_seq"]), {"rows": int(t["dispatch_rows"])})
        for s in STAGES:
            d[s] = max(d.get(s, 0.0), float(t.get(s, 0.0)))
    return out


def read(ctx):
    found = dispatches(ctx["requests"])
    if not found:
        return None
    rows = Counter(d["rows"] for d in found.values()).most_common(1)[0][0]
    modal = {seq: d for seq, d in found.items() if d["rows"] == rows}
    median = ctx["stats"].percentile
    total = {seq: sum(d[s] for s in STAGES) for seq, d in modal.items()}
    slowest = max(total, key=total.get)
    excess = total[slowest] - median(list(total.values()), 50)
    print(json.dumps({
        "event": "slowest_dispatch", "dispatch_seq": slowest, "rows": rows,
        "dispatches": len(found), "of_modal_rows": len(modal),
        "total_ms": round(total[slowest], 3), "excess_ms": round(excess, 3),
        "stage_excess_ms": {s: round(modal[slowest][s] - median([d[s] for d in modal.values()], 50), 3)
                            for s in STAGES},
    }), flush=True)
    return excess

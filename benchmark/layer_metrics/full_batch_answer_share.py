"""Of the answers dispatched to the device between the window's edges, the
share that rode a dispatch of as many rows as the cell has callers:
``rag_generate_dispatch_rows_total{path, rows}`` counts answers by the path
that launched their program and the rows it carried. 100 when every round of
a closed loop is one batch; lower by each round the admission race split
(one caller alone on the fused path, the rest batched behind it)."""

import re

FAMILY = "rag_generate_dispatch_rows_total"
_ROWS = re.compile(r'rows="(\d+)"')


def read(ctx):
    clients = int(ctx["traffic"].get("clients", 0))
    full = total = 0.0
    for key in ctx["after"]:
        m = _ROWS.search(key) if key.startswith(FAMILY + "{") else None
        if m:
            n = ctx["stats"].delta(ctx["before"], ctx["after"], key)
            total += n
            if int(m.group(1)) == clients:
                full += n
    if not clients or not total:
        return None
    return full / total * 100.0

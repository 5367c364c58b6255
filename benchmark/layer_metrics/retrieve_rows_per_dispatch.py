"""Retrievals per batched call of the retrieve coalescer over the window:
``rag_coalesce_dispatch_rows_total{stage="retrieve", rows}`` counts items by
the size of the batch they rode, so the items over the batches they imply
(each ``rows`` child's growth / rows). The callers of a closed loop when
every round's retrievals are one batch; a caller retrieved alone takes the
fused batch-1 path, so the round that will split shows here first. None where
nothing was retrieved, or on a program without the family."""

import re

FAMILY = 'rag_coalesce_dispatch_rows_total{'
_ROWS = re.compile(r'rows="(\d+)"')


def read(ctx):
    items = batches = 0.0
    for key in ctx["after"]:
        m = _ROWS.search(key) if key.startswith(FAMILY) and 'stage="retrieve"' in key else None
        if m:
            n = ctx["stats"].delta(ctx["before"], ctx["after"], key)
            items += n
            batches += n / int(m.group(1))
    return items / batches if batches else None

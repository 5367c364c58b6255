"""Set-up's totals, from the scrape taken at the window's opening. The process
started at zero, so the absolute values of the program's own build census
(``rag_compile_seconds_total{program, stage}``, ``rag_compile_events_total
{program, cache}``) and of ``rag_ingest_stage_seconds{stage}`` there ARE what
set-up spent: the readers of ``setup_*`` take no delta."""

from __future__ import annotations

import re

_LABEL = re.compile(r'(\w+)="([^"]*)"')


def total(scrape: dict, family: str, label: str, values) -> float | None:
    """The sum of ``family``'s series whose ``label`` is one of ``values``.
    None where no series of the family carries the label at all: a program
    from before the census, whose two counters are unlabeled sums."""
    labeled, out = False, 0.0
    for key, value in scrape.items():
        name, _, rest = key.partition("{")
        if name != family:
            continue
        labels = dict(_LABEL.findall(rest))
        if label in labels:
            labeled = True
            if labels[label] in values:
                out += value
    return out if labeled else None

"""Metric arithmetic: percentiles, due-time latency, /metrics deltas, the
comparison that decides ``correct``, the peaks table."""

from __future__ import annotations

import json
import math
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics
    (numpy's default). An empty list has none."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[hi] == xs[lo]:  # also keeps inf - inf out of it
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies_ms(requests, new_tokens: int) -> list:
    """Due-to-last-byte times of every request the window offered. One that
    failed, was shed, or did not carry its whole answer misses every limit:
    it counts as infinitely late, so a tail cannot improve by failing."""
    out = []
    for r in requests:
        ok = r["status"] == 200 and r["tokens"] == new_tokens
        out.append((r["end"] - r["due"]) * 1e3 if ok else math.inf)
    return out


def n_failed(requests, new_tokens: int) -> int:
    return sum(1 for r in requests
               if not (r["status"] == 200 and r["tokens"] == new_tokens))


_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_exposition(text: str) -> dict:
    """Prometheus text exposition -> {``name{labels}``: value}."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line.strip())
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                continue
    return out


def delta(before: dict, after: dict, key: str):
    """Counter growth over the window; None when the program has no such
    series (the reader then reports nothing)."""
    if key not in after:
        return None
    return after[key] - before.get(key, 0.0)


def judge_audit(score: dict, emitted) -> float:
    """Half the logit gap at the first position where the exact path's
    greedy choice differs from the delivered token (0.0 when none does):
    the least perturbation of the logits that explains the served stream.
    The arithmetic of ``obs/shadow.py ShadowAuditor._audit``, kept here so
    the verdict does not move with the program."""
    for t, tok in enumerate(emitted):
        if int(score["argmax"][t]) != int(tok):
            gap = float(score["max_logit"][t]) - float(score["chosen_logit"][t])
            return max(gap, 0.0) / 2.0
    return 0.0


def half_gap_max(score: dict) -> float:
    """Against the plain reference every delivered token is judged, not the
    first divergence alone (each position is teacher-forced on what was
    delivered): the largest half gap between the reference's greedy logit and
    the delivered token's. 0.0 when every delivered token is the reference's
    choice."""
    return max(max(float(m) - float(c), 0.0) / 2.0
               for m, c in zip(score["max_logit"], score["chosen_logit"]))


def logit_err_max(exact: dict, ref: dict) -> float:
    """How far the program's exact path is from the reference: the largest
    difference between their logits of the delivered tokens. A change that
    lowers precision in the model code moves the served stream and the exact
    path together, and shows here."""
    return max(abs(float(a) - float(b))
               for a, b in zip(exact["chosen_logit"], ref["chosen_logit"]))


def flash_attention_flops(n_tokens: float, heads: int, head_dim: int) -> float:
    """Operations causal self-attention needs over ``n_tokens`` real tokens:
    QK^T and PV are 2*hd multiply-adds for each of n(n+1)/2 query-key pairs
    and each head. Padding a prompt to its bucket adds none."""
    return 4.0 * heads * head_dim * n_tokens * (n_tokens + 1) / 2.0


def flash_attention_bytes(bucket: int, heads: int, kv_heads: int, head_dim: int,
                          itemsize: int = 2) -> float:
    """Bytes one call must move: q and o for every head, k and v for every
    KV head, at the bucket's length."""
    return float((2 * heads + 2 * kv_heads) * bucket * head_dim * itemsize)


def load_peaks(device_kind: str) -> dict:
    """Published peaks of the chip the run is on; an unknown kind raises."""
    with open(os.path.join(BENCH_DIR, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            "benchmark/peaks.json: add a row with its source, never a default")
    return table[device_kind]

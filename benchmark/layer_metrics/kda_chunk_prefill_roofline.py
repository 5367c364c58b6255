"""The chunked delta-rule prefill against the chip's published peaks: the least
time the chip could take for the recurrence of the slice's prefill rows over
the time its operations took in the trace (self time under
``prefill/.../attn/kda/delta``, whatever implements it: the XLA chunk form of
``ops/delta_rule.py`` today, a kernel under the same scope tomorrow).

Operations, per LIVE position and head (chunk ``C``, ``d`` key and value
channels): the intra-chunk products ``A`` (k.k) and ``B`` (q.k) with their
decay, ``C * d`` multiply-adds and ``(C + 1) / 2 * d`` exponentials; the
unit-triangular solve, ``(C - 1) * d`` multiply-adds; the state's matmuls
(``(exp(G) K) S``, ``(exp(G) Q) S``, ``K^T U``: ``d * d`` each) and ``B U``,
``(C + 1) / 2 * d``. Bytes: q, k, v in and o out in the compute type, the log
decay in float32, beta; the state in and out once a row-layer. The live
positions are the program's own count (``engine_kda_prefill_positions`` over
the window's answers, a row's share of it times the slice's rows).

``peaks.json`` has a peak for the MXU and for HBM and none for the vector or
the transcendental unit, which bound the intra-chunk products, and the
state's products are float32 (several bf16 passes each): so the share reads
LOW BY CONSTRUCTION. It says how far the recurrence is from the price of its
matmuls in bf16, not how far from its own bound; it cannot pass 100%."""

import json

from benchmark.lib import kda_scopes, phases

ADVANCED = "tpu_rag_engine_kda_prefill_positions"
CHUNK = 64


def flops(positions: float, heads: int, d: int, chunk: int = CHUNK) -> float:
    """Of ``positions`` row-layer positions (a multiply-add is two)."""
    intra = 2.0 * chunk * d + (chunk + 1) / 2.0 * d
    solve = 2.0 * (chunk - 1) * d
    state = 3 * 2.0 * d * d + 2.0 * (chunk + 1) / 2.0 * d
    return positions * heads * (intra + solve + state)


def bytes_moved(positions: float, row_layers: float, heads: int, d: int, itemsize: int = 2) -> float:
    per_position = heads * (4 * d * itemsize + d * 4 + 4)
    return positions * per_position + row_layers * 2 * heads * d * d * 4


def read(ctx):
    if kda_scopes.of(ctx) is None or "linear_attn_config" not in ctx["config"] or ctx["peaks"] is None:
        return None
    cfg = ctx["config"]
    seconds = kda_scopes.seconds(ctx, "prefill", "delta")
    rows = phases.of(ctx)["prefill_rows"]
    advanced = ctx["stats"].delta(ctx["before"], ctx["after"], ADVANCED)
    answers = sum(1 for r in ctx["requests"] if r["status"] == 200)
    if not seconds or not rows or not advanced or not answers:
        return None
    la = cfg["linear_attn_config"]
    heads, d, layers = int(la["num_heads"]), int(la["head_dim"]), len(la["kda_layers"])
    positions = advanced / answers * rows  # row-layer positions of the slice's rows
    peaks = ctx["peaks"]
    by_flops = flops(positions, heads, d) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(positions, rows * layers, heads, d) / peaks["hbm_bytes_per_s"]
    print(json.dumps({"event": "kda_chunk_prefill", "seconds": seconds, "prefill_rows": rows,
                      "row_layer_positions": positions, "least_by_flops_s": by_flops,
                      "least_by_bytes_s": by_bytes}), flush=True)
    return max(by_flops, by_bytes) / seconds * 100.0

"""The chunked Mamba-2 prefill against the chip's published peaks: the least
time the chip could take for the recurrence of the slice's prefill rows over
the time its operations took in the trace (self time under
``prefill/.../attn/ssd``, WHATEVER implements it: the XLA chunk form of
``ops/ssd.py`` today, a kernel under the same scope tomorrow; the yardstick
counts the work, not the implementation).

Operations, per LIVE position of a Mamba-2 layer (chunk ``Q``, ``H`` heads of
``P`` channels, ``G`` groups, state ``N``): ``C B^T`` ONCE A GROUP over the
causal half of the chunk, ``(Q + 1) / 2 * N`` multiply-adds a group; the
masked product with ``dt * X``, ``(Q + 1) / 2 * P`` a head; the two products
with the state (``C H0^T`` and ``X^T B`` into the next state), ``P * N`` each a
head. Bytes: ``x``, ``B``, ``C`` in and ``y`` out in the compute type, the
time step in float32; the state in and out once a row-layer. The live
positions are the program's own count (``engine_ssd_prefill_positions`` over
the window's answers, a row's share of it times the slice's rows): pads are
not work.

The bound it cannot pass: every product is counted at the bf16 peak though
the program makes them in float32 (several passes each), only the causal half
of a chunk is counted, and the decay masks' exponentials (``Q / 2`` a
position-head, on a unit ``peaks.json`` has no peak for) not at all; a live
position is never counted that the walk did not visit. So the share reads LOW
BY CONSTRUCTION and cannot pass 100%."""

import json

from benchmark.lib import path_scopes, phases

ADVANCED = "tpu_rag_engine_ssd_prefill_positions"


def flops(positions: float, heads: int, head_dim: int, groups: int, state: int, chunk: int) -> float:
    """Of ``positions`` row-layer positions (a multiply-add is two)."""
    half = (chunk + 1) / 2.0
    return positions * 2.0 * (groups * half * state + heads * (half * head_dim + 2 * head_dim * state))


def bytes_moved(positions: float, row_layers: float, heads: int, head_dim: int, groups: int, state: int,
                itemsize: int = 2) -> float:
    per_position = (2 * heads * head_dim + 2 * groups * state) * itemsize + heads * 4
    return positions * per_position + row_layers * 2 * heads * head_dim * state * 4


def read(ctx):
    cfg = ctx["config"]
    if path_scopes.of(ctx) is None or "ssm_state_size" not in cfg or ctx["peaks"] is None:
        return None
    seconds = path_scopes.seconds(ctx, "prefill", "attn/ssd")
    rows = phases.of(ctx)["prefill_rows"]
    advanced = ctx["stats"].delta(ctx["before"], ctx["after"], ADVANCED)
    answers = sum(1 for r in ctx["requests"] if r["status"] == 200)
    if not seconds or not rows or not advanced or not answers:
        return None
    heads, head_dim = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, state, chunk = int(cfg["n_groups"]), int(cfg["ssm_state_size"]), int(cfg["chunk_size"])
    layers = str(cfg["hybrid_override_pattern"]).count("M")
    positions = advanced / answers * rows  # row-layer positions of the slice's rows
    peaks = ctx["peaks"]
    by_flops = flops(positions, heads, head_dim, groups, state, chunk) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(positions, rows * layers, heads, head_dim, groups, state) / peaks["hbm_bytes_per_s"]
    print(json.dumps({"event": "ssd_chunk_prefill", "seconds": seconds, "prefill_rows": rows,
                      "row_layer_positions": positions, "least_by_flops_s": by_flops,
                      "least_by_bytes_s": by_bytes}), flush=True)
    return max(by_flops, by_bytes) / seconds * 100.0

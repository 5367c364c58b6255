"""Of the choices the routers made in the window (``moe_topk`` a token-layer),
the share that fell on zero-computation experts: the sum over prefill, decode
and chunk calls of ``tpu_rag_engine_moe_<mode>_assignments_zero`` over
``tpu_rag_engine_moe_tokens_routed`` x ``moe_topk`` (counted on the device in
the cache's counters and fetched with each answer, like
``moe_dropped_assignment_share``'s). A seeded router sends about a third there
(256 of 768 outputs); the published model's controller holds about 4 of 12.
With the served weights fixed it does not move; a move means the routing
changed. None where the program has no such counters (a family without zero
experts' counters, or a program from before them)."""

MODES = ("prefill", "decode", "chunk")
NAME = "tpu_rag_engine_moe_{}_assignments_zero"


def read(ctx):
    d, top_k = ctx["stats"].delta, ctx["config"].get("moe_topk")
    zero = [d(ctx["before"], ctx["after"], NAME.format(m)) for m in MODES]
    tokens = d(ctx["before"], ctx["after"], "tpu_rag_engine_moe_tokens_routed")
    if None in zero or not tokens or not top_k:
        return None
    return sum(zero) / (tokens * int(top_k)) * 100.0

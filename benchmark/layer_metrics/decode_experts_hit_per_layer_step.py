"""Experts a sparse layer's decode step streams: held experts with at least
one assignment, summed over the window's decode layer-steps, over those
layer-steps (``tpu_rag_engine_moe_decode_experts_hit`` /
``tpu_rag_engine_moe_decode_layer_steps``, counted on the device by the sort
that groups the assignments and fetched with each answer). At batch 1 it is
the model's ``num_experts_per_tok`` exactly (4.0: the step streams its
sparsity and nothing more); a batch of eight distinct rows hits about 26 of
64. None where the program has no such counters or the window no such step
(a verify loop's steps count under ``chunk``, which has no such counter)."""

NAME = "tpu_rag_engine_moe_decode_{}"


def read(ctx):
    d = ctx["stats"].delta
    hit = d(ctx["before"], ctx["after"], NAME.format("experts_hit"))
    steps = d(ctx["before"], ctx["after"], NAME.format("layer_steps"))
    if hit is None or not steps:
        return None
    return hit / steps

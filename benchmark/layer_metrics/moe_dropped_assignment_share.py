"""Of the assignments the routers sent to experts held on this chip in the
window, the share the grouped matmuls did NOT compute: 1 - computed /
routed-to-held over prefill, decode and chunk calls alike
(``tpu_rag_engine_moe_<mode>_assignments_{held,computed}``, counted on the
device and fetched with each answer: ``held`` by the gather from the
router's choices, ``computed`` by the down projection's grouped kernel
itself, the rows of every store it made). The expert layer has no capacity
limit, so this reads 0.0; anything else is a dropped token. None where the program
has no such counters (a dense family, or a program from before them)."""

MODES = ("prefill", "decode", "chunk")
NAME = "tpu_rag_engine_moe_{}_assignments_{}"


def read(ctx):
    d = ctx["stats"].delta
    held = [d(ctx["before"], ctx["after"], NAME.format(m, "held")) for m in MODES]
    computed = [d(ctx["before"], ctx["after"], NAME.format(m, "computed")) for m in MODES]
    if None in held or None in computed or not sum(held):
        return None
    return (1.0 - sum(computed) / sum(held)) * 100.0

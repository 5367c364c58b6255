"""Of the positions a decode step's fetched slots stand for, the share it
reads POOLED: 100 x ``chunk_size`` x ``engine_decode_summary_slots_fetched`` /
``engine_decode_slots_attended_positions`` over the window (the block-window
family's counters, counted on the device from the plan the decode kernel's own
copies follow and fetched with each answer; ``attended_positions`` is the ring
slots fetched + ``chunk_size`` x the summary slots fetched). Just after a
window closes a step reads nearly everything pooled (about 99 at nine closed
windows), at the window's end about 90. None where the program has no such
counters, or its decode steps take the XLA path."""

POOLED = "tpu_rag_engine_decode_summary_slots_fetched"
POSITIONS = "tpu_rag_engine_decode_slots_attended_positions"


def read(ctx):
    d = ctx["stats"].delta
    pooled = d(ctx["before"], ctx["after"], POOLED)
    positions = d(ctx["before"], ctx["after"], POSITIONS)
    if pooled is None or not positions or "chunk_size" not in ctx["config"]:
        return None
    return 100.0 * int(ctx["config"]["chunk_size"]) * pooled / positions

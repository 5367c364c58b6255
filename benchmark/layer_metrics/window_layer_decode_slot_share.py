"""Of the cache slots allocated to the SLIDING layers' planes for the rows of
the window's decode steps, the share their decode walks fetched: 100 x
``engine_decode_slots_streamed_window`` / ``engine_decode_slots_allocated_window``
(summed over the sliding layers; counted on the device from the plan the
kernel's own copies follow on the window each layer hands it, and fetched with
each answer). A sliding layer of window 512 over planes of 4352 slots in steps
of 256 fetches 3 steps of 17: about 18, where ``decode_streamed_slot_share``
(a FULL layer's) reads about 82 on the same rows. None where the program has
no such counters."""

STREAMED = "tpu_rag_engine_decode_slots_streamed_window"
ALLOCATED = "tpu_rag_engine_decode_slots_allocated_window"


def read(ctx):
    d = ctx["stats"].delta
    streamed = d(ctx["before"], ctx["after"], STREAMED)
    allocated = d(ctx["before"], ctx["after"], ALLOCATED)
    if streamed is None or not allocated:
        return None
    return 100.0 * streamed / allocated

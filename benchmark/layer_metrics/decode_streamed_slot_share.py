"""Of the cache slots allocated to the rows of the window's decode steps, the
share the decode-attention kernel fetched: 100 x
``engine_decode_slots_streamed`` / ``engine_decode_slots_allocated`` (rows x
the slots the kernel's walk fetches of one layer's cache, rows x the cache
length, a single-token step at a time; counted on the device from the plan the
kernel's own copies follow, and fetched with each answer). 100.0 is a kernel
that reads every row's whole allocation; a row whose window is 3.3k of 4352
slots reads about 80. None where the program has no such counters (a program
from before them, or one whose decode steps do not go through the kernel)."""

STREAMED = "tpu_rag_engine_decode_slots_streamed"
ALLOCATED = "tpu_rag_engine_decode_slots_allocated"


def read(ctx):
    d = ctx["stats"].delta
    streamed = d(ctx["before"], ctx["after"], STREAMED)
    allocated = d(ctx["before"], ctx["after"], ALLOCATED)
    if streamed is None or not allocated:
        return None
    return 100.0 * streamed / allocated

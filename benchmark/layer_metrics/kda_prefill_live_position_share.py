"""Of the row-layer positions a bucket-wide prefill would have advanced the
linear layers' recurrence by in the window, the share it did advance: 100 x
``engine_kda_prefill_positions`` / ``engine_kda_prefill_positions_bucketed``
(counted on the device, where the first live chunk is chosen, and fetched with
each answer). 100.0 is a program that advances the bucket. None where the
program has no such counters."""

ADVANCED = "tpu_rag_engine_kda_prefill_positions"
BUCKET = "tpu_rag_engine_kda_prefill_positions_bucketed"


def read(ctx):
    d = ctx["stats"].delta
    advanced, bucket = d(ctx["before"], ctx["after"], ADVANCED), d(ctx["before"], ctx["after"], BUCKET)
    if advanced is None or not bucket:
        return None
    return 100.0 * advanced / bucket

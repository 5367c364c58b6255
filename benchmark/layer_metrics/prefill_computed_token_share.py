"""Of the token rows of the prompts' padded batches in the window, the share
the layers' matmuls ran on: 100 x ``engine_prefill_tokens_computed`` /
``engine_prefill_tokens_bucketed`` (rows x the live suffix, rows x the bucket,
a prefill call at a time; counted on the device where the suffix is chosen,
and fetched with each answer). 100.0 is a program that computes the bucket;
87.5 one that skips an eighth of it. None where the program has no such
counters (a family that does not count them, or a program from before them)."""

COMPUTED = "tpu_rag_engine_prefill_tokens_computed"
BUCKET = "tpu_rag_engine_prefill_tokens_bucketed"


def read(ctx):
    d = ctx["stats"].delta
    computed = d(ctx["before"], ctx["after"], COMPUTED)
    bucket = d(ctx["before"], ctx["after"], BUCKET)
    if computed is None or not bucket:
        return None
    return 100.0 * computed / bucket

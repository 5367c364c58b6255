"""The batch scheduler's coalescing window a dispatch, in milliseconds: from
the instant its first request is in hand to the last aboard, the ``gather``
stage of ``rag_generate_dispatch_stage_seconds{path="batched"}`` (sum over
count, as deltas over the window). Under the scheduler's window when every
caller of a round arrives together; near the window when a round was split and
the scheduler waited for a caller who was served elsewhere. None where the
window held no batched dispatch, or on a program without the family."""

from benchmark.lib import host_stages


def read(ctx):
    got = host_stages.stage_delta(ctx, "gather", path="batched")
    if got is None or not got[1]:
        return None
    return got[0] / got[1] * 1e3

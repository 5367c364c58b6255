"""Mean wait of a request in the generate coalescer over the window:
``rag_coalesce_wait_seconds{stage="generate"}`` sum over count, as deltas."""

SUM = 'rag_coalesce_wait_seconds_sum{stage="generate"}'
COUNT = 'rag_coalesce_wait_seconds_count{stage="generate"}'


def read(ctx):
    d = ctx["stats"].delta
    total, n = d(ctx["before"], ctx["after"], SUM), d(ctx["before"], ctx["after"], COUNT)
    if total is None or not n:
        return None
    return total / n * 1e3

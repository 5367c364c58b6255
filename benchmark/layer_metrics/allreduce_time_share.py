"""Share of device 0's operation time spent in all-reduce events (the
tensor-parallel collectives), from the trace."""


def read(ctx):
    return None if ctx["trace"] is None else ctx["trace"]["all_reduce_share"] * 100.0

"""Of the row-positions of the window's fresh prompt calls, the share the
cross-decoder half ran on: 100 x ``engine_cross_positions_computed`` /
``engine_cross_positions_fed`` (rows x 1 where the prefill stops half way, rows
x the bucket where it does not, over rows x the bucket; a fresh multi-token
call at a time, counted on the device and fetched with each answer). About
100 / bucket on a program that runs the half at the last position only; 100.0
on one that runs the whole stack. None where the program has no such counters."""

COMPUTED = "tpu_rag_engine_cross_positions_computed"
FED = "tpu_rag_engine_cross_positions_fed"


def read(ctx):
    d = ctx["stats"].delta
    computed = d(ctx["before"], ctx["after"], COMPUTED)
    fed = d(ctx["before"], ctx["after"], FED)
    if computed is None or not fed:
        return None
    return 100.0 * computed / fed

"""Share of device 0's leaf operation time that no phase scope names: the
guard that programs arrive scoped. Near 100 means the executables carry no
scopes at all: a program from before the scopes, or a persistent compile
cache that still holds one (its key does not cover op metadata)."""

from benchmark.lib import phases


def read(ctx):
    ph = phases.of(ctx)
    if ph is None or ph["unscoped_share"] is None:
        return None
    return ph["unscoped_share"] * 100.0

"""Share of device 0's operation time spent inside Mosaic custom calls (the
Pallas kernels: flash prefill, decode attention, kNN), from the trace."""


def read(ctx):
    return None if ctx["trace"] is None else ctx["trace"]["mosaic_share"] * 100.0

"""Host time in series with a device program, a dispatch, in milliseconds:
the ``launch`` stage (host preparation up to the enqueue) plus the ``deliver``
stage (the trim, the stats and goodput folds, the riders' release) of
``rag_generate_dispatch_stage_seconds``, every path, over the dispatches the
window made (the ``device`` stage's count), as deltas. None where the window
made no dispatch, or on a program without the family."""

from benchmark.lib import host_stages


def read(ctx):
    parts = [host_stages.stage_delta(ctx, s) for s in ("launch", "deliver", "device")]
    if None in parts or not parts[2][1]:
        return None
    return (parts[0][0] + parts[1][0]) / parts[2][1] * 1e3

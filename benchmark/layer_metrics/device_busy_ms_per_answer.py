"""Device time an answer costs: the traced window's busy share (union of
device-operation intervals over its length, averaged over the chips) times
the window's mean time between completed answers."""


def read(ctx):
    tr = ctx["trace"]
    t0, t1 = ctx["window"]
    done = sum(1 for r in ctx["requests"] if r["status"] == 200 and r["end"] <= t1)
    if tr is None or not done or not tr["window_s"]:
        return None
    return tr["busy_s"] / tr["window_s"] * (t1 - t0) / done * 1e3

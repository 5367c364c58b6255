"""Generated tokens per second completed: the tokens of every request the
window offered that got its whole answer, over the time from the window's
opening to the last of them (the window and its drain, or less where a closed
loop is through its plans sooner). All the work and all the time. Not
"completed inside the window over the window": callers that re-arrive together
complete together, and a batch landing just before or just after the window's
edge moved that reading by one batch in fifteen (PR 23: 155.9 and 164.7
tokens/s from two runs whose every latency agreed). In a closed loop this is
the callers over the mean latency: a round that is not coalesced into one
batch costs a round, which the median latency cannot see and this does."""


def read(ctx):
    t0, _ = ctx["window"]
    done = [r for r in ctx["requests"] if r["status"] == 200]
    if not done:
        return None
    return sum(r["tokens"] for r in done) / (max(r["end"] for r in done) - t0)

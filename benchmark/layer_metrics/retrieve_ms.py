"""Median of the responses' ``timings.embed_retrieve_ms``: the host clock
around the fused embed+kNN call, coalescer wait included."""


def read(ctx):
    xs = [r["timings"]["embed_retrieve_ms"] for r in ctx["requests"]
          if r["status"] == 200 and "embed_retrieve_ms" in r["timings"]]
    return ctx["stats"].percentile(xs, 50) if xs else None

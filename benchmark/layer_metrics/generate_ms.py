"""Median of the responses' ``timings.generate_ms``: the host clock around
prompt assembly, prefill, the decode loop and detokenization (and, on the
batched path, the wait for the coalesced batch)."""


def read(ctx):
    xs = [r["timings"]["generate_ms"] for r in ctx["requests"]
          if r["status"] == 200 and "generate_ms" in r["timings"]]
    return ctx["stats"].percentile(xs, 50) if xs else None

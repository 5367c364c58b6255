"""90th percentile of the same due-to-last-byte time, over all requests the
window offered. p90 and not p95: a window holds 25 to 60 requests at these
sizes, and a p95 would be the second or third largest value."""


def read(ctx):
    lat = ctx["stats"].latencies_ms(ctx["requests"], ctx["new_tokens"])
    return ctx["stats"].percentile(lat, 90) if lat else None

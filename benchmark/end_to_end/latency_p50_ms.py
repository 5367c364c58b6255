"""Median time of a /generate request over the socket, from the instant it
was due to the last byte of a 200 carrying its whole answer. All requests the
window offered; one that failed counts as never answered."""


def read(ctx):
    lat = ctx["stats"].latencies_ms(ctx["requests"], ctx["new_tokens"])
    return ctx["stats"].percentile(lat, 50) if lat else None

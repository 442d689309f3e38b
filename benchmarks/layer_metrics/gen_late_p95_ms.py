"""Load generator: how late requests due in the window were sent (send
time minus due time), 95th percentile, ms. A starved generator must not be
read as a fast server."""


def read(ctx):
    return ctx["summary"]["gen_late_p95_ms"]

"""Served path, client side: due time to first SSE token of the requests
due in the window, 95th percentile, ms (see ``first_token_p50_ms``)."""


def read(ctx):
    return ctx["summary"]["ttft_p95_ms"]

"""Served path, client side: due time to first SSE token of the requests
due in the window, median, ms. Not end to end because its spread admits no
bound at 138 requests a window (PERF.md, Finding 2); recorded so that a
first-token regression at least shows in the ledger."""


def read(ctx):
    return ctx["summary"]["ttft_p50_ms"]

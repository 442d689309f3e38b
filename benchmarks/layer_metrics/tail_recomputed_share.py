"""Radix tree: of the prompt tokens the tree's rows would have served to
the requests admitted in the window, the share that lay between the tail a
request resumed at and its match and was computed again, percent (counters
``/debug/state`` ``tail``: delta ``recomputed_tokens`` / delta
``kv_matched_tokens``). 0: every request resumed AT its match."""


def read(ctx):
    a, b = (ctx[k]["state"].get("tail") for k in ("before", "after"))
    if a is None or b is None:
        return None
    matched = b["kv_matched_tokens"] - a["kv_matched_tokens"]
    if matched <= 0:
        return None
    return 100.0 * (b["recomputed_tokens"] - a["recomputed_tokens"]) / matched

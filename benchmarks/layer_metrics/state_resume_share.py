"""Radix tree: of the prompt tokens the tree's rows would have served to
the requests admitted in the window, the share the requests really resumed
at — the deepest snapshot at or before — percent (counters ``/debug/state``
``state``: delta ``resumed_tokens`` / delta ``kv_matched_tokens``). 100:
every hit on rows was a hit on a state too; the rest was computed again
(``recomputed_tokens``). A program without state slots: nothing to read."""


def read(ctx):
    a, b = (ctx[k]["state"].get("state") for k in ("before", "after"))
    if a is None or b is None:
        return None
    matched = b["kv_matched_tokens"] - a["kv_matched_tokens"]
    if matched <= 0:
        return None
    return 100.0 * (b["resumed_tokens"] - a["resumed_tokens"]) / matched

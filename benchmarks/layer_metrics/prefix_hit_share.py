"""Radix tree: share of the prompt tokens admitted in the window that were
served from the tree (delta ``cached_tokens`` / delta ``prompt_tokens`` of
``/stats``), percent."""


def read(ctx):
    a, b = ctx["before"]["stats"], ctx["after"]["stats"]
    prompt = b["prompt_tokens"] - a["prompt_tokens"]
    if prompt <= 0:
        return None
    return 100.0 * (b["cached_tokens"] - a["cached_tokens"]) / prompt

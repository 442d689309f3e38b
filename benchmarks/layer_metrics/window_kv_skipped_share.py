"""KV pool: of the context tokens the window's decode launches held on
sliding layers, the share the decode kernel did not have to read, percent
(counters ``/debug/state`` ``attn``: delta ``window_tokens_skipped`` /
(skipped + ``window_tokens_read``), summed over rows and sliding layers).
These tokens still hold their slots: the pool keeps one slot per token for
every layer, so this is also the share of window-layer K/V a per-layer-
group cache could free. A program without the counters: nothing."""


def read(ctx):
    a0, a1 = (ctx[k]["state"].get("attn") for k in ("before", "after"))
    if a0 is None or a1 is None:
        return None
    skipped = a1["window_tokens_skipped"] - a0["window_tokens_skipped"]
    read_ = a1["window_tokens_read"] - a0["window_tokens_read"]
    if skipped + read_ <= 0:
        return None
    return 100.0 * skipped / (skipped + read_)

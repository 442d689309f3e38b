"""Model step: wall milliseconds of the window's prefill waves per 1 000
prompt tokens they had to compute. Counter ``/debug/state``
``engine.prefill`` (delta ``wall_s`` / delta ``tokens_new``): per admission
wave, host seconds from before the prefill's dispatch to after the
first-token sync. No decode launch is in flight then, so it is the wave's
device time plus its dispatch and its sync tail — an upper bound of the
device time — and it counts every wave of the window (140-290) where a 3 s
capture holds 3-8. A program without the counter (the parent of PR 35):
nothing."""


def delta(ctx):
    """Delta of ``engine.prefill`` over the window, or None."""
    a, b = (ctx[k]["state"]["engine"].get("prefill") for k in ("before", "after"))
    if a is None or b is None:
        return None
    return {k: b[k] - a[k] for k in ("waves", "rows", "tokens_new",
                                     "tokens_launched", "wall_s")}


def read(ctx):
    d = delta(ctx)
    if d is None or d["tokens_new"] <= 0:
        return None
    return 1e3 * d["wall_s"] / (d["tokens_new"] / 1e3)

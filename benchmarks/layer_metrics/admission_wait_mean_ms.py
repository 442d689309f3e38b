"""HTTP + admission: mean wait of the requests admitted in the window
between their last (re)queueing — submission, SLO dispatch or preemption
requeue, whichever came last — and the instant the engine secured them a
batch row, ms. Delta ``sum_s`` / delta ``count`` of ``/debug/state``
``engine.admission_wait`` over the window. The client's first-token clock
starts at the due time, before the server's submission stamp, so this is
below the window's mean first-token time."""


def delta(ctx):
    """``(count, sum_s, counts, le_ms)`` of the window, or None where the
    program has no such counter."""
    a = ctx["before"]["state"]["engine"].get("admission_wait")
    b = ctx["after"]["state"]["engine"].get("admission_wait")
    if a is None or b is None:
        return None
    return (b["count"] - a["count"], b["sum_s"] - a["sum_s"],
            [y - x for x, y in zip(a["counts"], b["counts"])], b["le_ms"])


def read(ctx):
    d = delta(ctx)
    return None if d is None or d[0] <= 0 else 1e3 * d[1] / d[0]

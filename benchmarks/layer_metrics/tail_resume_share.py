"""Radix tree: of the requests admitted in the window whose rows the tree
held, the share that resumed at a window tail — the deepest resume point
at or before their match — percent (counters ``/debug/state`` ``tail``:
delta ``resumes`` / delta ``matches``). 100: every hit on rows found a
tail to resume from; the rest computed their whole prompt again although
the full layers' rows were there (their tails had been evicted). A program
without tail-only resume points: nothing to read."""


def read(ctx):
    a, b = (ctx[k]["state"].get("tail") for k in ("before", "after"))
    if a is None or b is None:
        return None
    matches = b["matches"] - a["matches"]
    if matches <= 0:
        return None
    return 100.0 * (b["resumes"] - a["resumes"]) / matches

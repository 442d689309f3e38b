"""KV pool: the most slots that requests held at once in the window,
percent of the pool (``/debug/state`` polled once a second: ``num_slots`` -
``free_slots`` - the tree's ``evictable_tokens``). Evictable cache is left
out: the tree keeps every finished prefix until the pool needs the room, so
``free_slots`` alone reads 0 from the first minute on and says nothing."""


def read(ctx):
    t0, t1 = ctx["window"]
    inside = [(free, n, ev) for t, free, n, ev in ctx["polls"] if t0 <= t <= t1]
    if not inside:
        return None
    return 100.0 * max((n - free - ev) / n for free, n, ev in inside)

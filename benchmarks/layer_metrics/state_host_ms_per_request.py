"""Radix tree: host milliseconds the engine thread spent on state slots
per finished request of the window — taking a request's slots and
resolving where it resumes at admission (``rm.state.restore``, inside
``rm.tree.match``) and handing snapshots to the tree at publish
(``rm.state.snapshot``, inside ``rm.tree.publish``): (delta seconds of the
two spans in ``/debug/state`` ``engine.spans``) / delta ``finished``."""

SPANS = ("rm.state.restore", "rm.state.snapshot")


def read(ctx):
    a, b = (ctx[k]["state"]["engine"].get("spans") or {} for k in ("before", "after"))
    finished = (ctx["after"]["state"]["engine"]["finished"]
                - ctx["before"]["state"]["engine"]["finished"])
    if finished <= 0 or not any(name in b for name in SPANS):
        return None
    spent = sum(b[n]["s"] - a.get(n, {"s": 0.0})["s"] for n in SPANS if n in b)
    return 1e3 * spent / finished

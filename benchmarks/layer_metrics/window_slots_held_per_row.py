"""KV pool: the most slots of the window group any row of any decode
launch held (``/debug/state`` ``window.held_peak_row``, counted when a
launch is built: after the row took the page its next token needs, before
it gives back what left the window). Bound by construction: the window +
2 pages = 544."""


def read(ctx):
    w = ctx["after"]["state"].get("window")
    return None if w is None else w["held_peak_row"]

"""Model step: seconds of XLA compilation (persistent-cache look-ups
included) that ended in the window, as the server's own
``jax.monitoring`` listener counted them. Counter ``/debug/state``
``process.compiles``: delta ``s``; its ``last`` names each program and the
span it compiled under. ``compiles_in_window`` counts the same events
from the server's stderr. A program without the counter: nothing."""


def read(ctx):
    a, b = (ctx[k]["state"].get("process") for k in ("before", "after"))
    if a is None or b is None:
        return None
    return b["compiles"]["s"] - a["compiles"]["s"]

"""Scheduler: milliseconds of the window the server process spent in
garbage collections (every generation; a collection holds the GIL, so the
engine thread and every stream wait). Counter ``/debug/state``
``process.gc`` (one ``gc.callbacks`` entry): delta of the generations'
``s``, summed. A program without the counter: nothing."""


def read(ctx):
    a, b = (ctx[k]["state"].get("process") for k in ("before", "after"))
    if a is None or b is None:
        return None
    return 1e3 * (sum(g["s"] for g in b["gc"].values())
                  - sum(g["s"] for g in a["gc"].values()))

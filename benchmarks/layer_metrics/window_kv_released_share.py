"""KV pool: of the slots the window's launches wrote in the window group,
the share that live rows gave back because the tokens had left their
window, percent (``/debug/state`` ``window``: delta ``pages_released`` x
page / delta ``slots_written``). The rest is what rows still hold, what
their release handed to the tree as a snapshot's tail, or freed at once."""


def read(ctx):
    a, b = (ctx[k]["state"].get("window") for k in ("before", "after"))
    if a is None or b is None:
        return None
    written = b["slots_written"] - a["slots_written"]
    if written <= 0:
        return None
    page = ctx["after"]["state"]["pool"]["page_size"]
    return 100.0 * (b["pages_released"] - a["pages_released"]) * page / written

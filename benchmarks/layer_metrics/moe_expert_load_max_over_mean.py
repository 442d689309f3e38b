"""Expert layer: how uneven the routing of a decode step is — rows of the
fullest expert over the mean rows of an expert, summed over the window's
layer-launches (counters ``/debug/state`` ``moe.decode``: delta
``max_load`` / (delta ``assignments`` / ``num_experts``)). 1.0 is a
perfectly even router; the fullest expert's row tile is what the grouped
product's longest visit works on."""


def read(ctx):
    moe0, moe1 = (ctx[k]["state"].get("moe") for k in ("before", "after"))
    if moe0 is None or moe1 is None:
        return None
    d = {k: moe1["decode"][k] - moe0["decode"][k] for k in ("max_load", "assignments")}
    if d["assignments"] <= 0:
        return None
    return d["max_load"] / (d["assignments"] / ctx["config"]["num_experts"])

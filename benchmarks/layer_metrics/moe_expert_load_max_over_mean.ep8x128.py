"""Expert layer, on the cell of the configuration with Mamba-2 blocks: how
uneven the routing of a decode step is — rows of the fullest HELD expert
over the mean rows of a held expert, summed over the window's
layer-launches (counters ``/debug/state`` ``moe.decode``: delta
``max_load`` / (delta ``assignments`` / ``n_routed_experts``, the 16
held)). ``moe_expert_load_max_over_mean``'s arithmetic under this source's
key for the experts."""
from benchmarks.harness import ssd_trace


def read(ctx):
    moe0, moe1 = (ctx[k]["state"].get("moe") for k in ("before", "after"))
    if not ssd_trace.is_cell(ctx) or moe0 is None or moe1 is None:
        return None
    d = {k: moe1["decode"][k] - moe0["decode"][k] for k in ("max_load", "assignments")}
    if d["assignments"] <= 0:
        return None
    return d["max_load"] / (d["assignments"] / ctx["config"]["n_routed_experts"])

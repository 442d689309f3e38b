"""moe_expert_load_max_over_mean's reader on the cell of the configuration with KDA
linear-attention layers (this configuration's num_experts is the held count, 64): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_expert_load_max_over_mean").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

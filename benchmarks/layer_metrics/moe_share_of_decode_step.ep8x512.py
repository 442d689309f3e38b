"""moe_share_of_decode_step's reader on the cell of the configuration with KDA
linear-attention layers (six sparse layers of 64 held experts of 768): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_share_of_decode_step").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

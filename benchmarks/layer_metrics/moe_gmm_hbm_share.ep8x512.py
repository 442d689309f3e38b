"""moe_gmm_hbm_share's reader on the cell of the configuration with KDA
linear-attention layers (64 of 512 experts of 768 held a layer, one routing group: 64 rows x top-8 of 512 in 4 of 8 groups touch ~40 of the 64): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_gmm_hbm_share").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

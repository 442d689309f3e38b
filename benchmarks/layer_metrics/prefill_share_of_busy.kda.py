"""prefill_share_of_busy.docs's reader on the cell of the configuration with KDA
linear-attention layers (every prefill is 64-272 new tokens from a restored state): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "prefill_share_of_busy.docs").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

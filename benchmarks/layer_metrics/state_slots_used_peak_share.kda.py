"""state_slots_used_peak_share's reader on the cell of the configuration with KDA
linear-attention layers (321 slots of 12.4 MiB, counted by bytes, run full by design): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "state_slots_used_peak_share").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

"""state_host_ms_per_request's reader on the cell of the configuration with KDA
linear-attention layers (the host's part of restoring, snapshotting and handing over 12.4 MiB snapshots): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "state_host_ms_per_request").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

"""prefill_share_of_busy.docs's reader on the cell of the configuration with selective
state-space layers (a snapshot here is a 3 MiB state slot AND the window
tail beside it; cached_tokens counts resumed positions): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import phi4flash_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "prefill_share_of_busy.docs").read


def read(ctx):
    if not phi4flash_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

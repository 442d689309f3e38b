"""state_resume_share's reader on the cell of the configuration with KDA
linear-attention layers (a snapshot here is a 12.4 MiB state slot beside 1 280 B a token of latent rows; cached_tokens counts resumed positions): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "state_resume_share").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

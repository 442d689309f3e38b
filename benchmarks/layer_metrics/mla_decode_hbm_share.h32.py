"""mla_decode_hbm_share's reader on the cell of the configuration with KDA
linear-attention layers (ONE latent layer at 32 heads of 128 + 64, values of 128, the 576-in-640 row: latent_tokens_read counts the latent layers only): the accepted
lists of the namesake are pinned to their cells, so this cell reports
under a name of its own."""
from benchmarks.harness import kda_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "mla_decode_hbm_share").read


def read(ctx):
    if not kda_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

"""Model step: the grouped expert products' share of a decode step over 38
sparse layers of 8 held experts — the reader of
``moe_share_of_decode_step``."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_share_of_decode_step").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"]:  # not this configuration's cell
        return None
    return _read(ctx)

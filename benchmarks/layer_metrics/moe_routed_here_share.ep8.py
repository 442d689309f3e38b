"""Expert layer: share of the router's assignments that went to one of the 8
experts this chip holds — the reader of ``moe_routed_here_share``; an even
router: 12.5."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_routed_here_share").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"]:  # not this configuration's cell
        return None
    return _read(ctx)

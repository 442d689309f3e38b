"""Expert layer: rows of the fullest held expert over the mean rows of a held
expert — the reader of ``moe_expert_load_max_over_mean`` (this
configuration's ``num_experts`` is the held count, 8)."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_expert_load_max_over_mean").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"]:  # not this configuration's cell
        return None
    return _read(ctx)

"""Radix tree, on agent sessions: the ratio and reader of ``prefix_hit_share``
(delta ``cached_tokens`` / delta ``prompt_tokens``) — with
``cached_tokens`` counting the positions a request RESUMED at, so a hit on
rows without a state counts for nothing."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "prefix_hit_share").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"]:  # not this configuration's cell
        return None
    return _read(ctx)

"""Radix tree, on three long contexts: the same ratio and reader as
``prefix_hit_share`` (delta ``cached_tokens`` / delta ``prompt_tokens``,
percent), expected near 99.6 — the tree serves all of a prompt but the
question, rows and index keys alike (one allocator: a hit brings both)."""
from benchmarks.harness.opsbytes_dsa import expert_share
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "prefix_hit_share").read


def read(ctx):
    if expert_share(ctx["config"]) <= 1:  # not this configuration's cell
        return None
    return _read(ctx)

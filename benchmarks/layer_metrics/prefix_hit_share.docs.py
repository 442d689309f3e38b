"""Radix tree, on a hot set of long documents: the same ratio as
``prefix_hit_share`` (delta ``cached_tokens`` / delta ``prompt_tokens``,
percent), expected near 99 — the tree serves all of a prompt but the
question. There it moves ``tokens_per_s``: the same reader."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "prefix_hit_share").read

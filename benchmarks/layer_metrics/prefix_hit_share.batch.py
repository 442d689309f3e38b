"""Radix tree, on unshared traffic: the same ratio as ``prefix_hit_share``
(delta ``cached_tokens`` / delta ``prompt_tokens``, percent), expected about
0 — the cell bypasses the tree, and this shows that it does. There it moves
``tokens_per_s``: the same reader."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "prefix_hit_share").read

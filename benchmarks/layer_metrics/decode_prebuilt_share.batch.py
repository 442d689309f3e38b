"""Scheduler, in the closed-loop cells: the same ratio as
``decode_prebuilt_share`` (launches built behind the launch before them /
all decode launches of the window, percent). There it moves
``tokens_per_s``: the same reader."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "decode_prebuilt_share").read

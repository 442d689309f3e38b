"""``trace_lower_s_in_window`` in the closed-loop cells, where it moves ``tokens_per_s``
(they report no token-gap tail): the same reader."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "trace_lower_s_in_window").read

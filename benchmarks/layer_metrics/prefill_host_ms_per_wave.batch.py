"""``prefill_host_ms_per_wave`` in the closed-loop cells, where it moves ``tokens_per_s``
(they report no token-gap tail): the same reader."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "prefill_host_ms_per_wave").read

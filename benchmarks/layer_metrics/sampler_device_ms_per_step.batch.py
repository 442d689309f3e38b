"""``sampler_device_ms_per_step`` in the closed-loop batch cell, where it
moves ``tokens_per_s`` (that cell reports no token-gap tail): the same
reader."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "sampler_device_ms_per_step").read

"""Model step: the prefill programs' share of the device's busy time in the
capture — the reader of ``prefill_share_of_busy.docs``: every prefill here
is a 64-528-token tail from a restored state."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "prefill_share_of_busy.docs").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"]:  # not this configuration's cell
        return None
    return _read(ctx)

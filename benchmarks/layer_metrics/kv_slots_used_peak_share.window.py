"""KV pool, by group: the most slots of the WINDOW group ever counted on
at once (live rows' windows + the tails of the tree's snapshots, a shared
page once), percent of the group (``/debug/state`` ``kv.groups.window``).
At 100 the engine evicts snapshots (state + tail), superseded ones
first."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "kv_slots_used_peak_share.full").read


def read(ctx):
    return _read(ctx, "window")

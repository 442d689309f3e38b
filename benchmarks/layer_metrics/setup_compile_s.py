"""Model step: seconds of the set-up the COMPILER took: the backend-compile
intervals that were no persistent-cache hit (a miss's look-up and its write
to the cache included), from the process's start to the window's opening.
Counters ``/debug/state`` ``process.compiles.s`` less
``process.compiles.cache_load_s`` in the snapshot taken as the window
opens: the value, not a delta (``benchmarks/SETUP_ACCOUNT.md``). A program
without the counters: nothing."""
from benchmarks.harness.registry import load_module

_compiles = load_module("layer_metrics", "setup_trace_s").compiles_at_window_start


def read(ctx):
    c = _compiles(ctx)
    load_s = None if c is None else c.get("cache_load_s")
    return None if load_s is None else c["s"] - load_s

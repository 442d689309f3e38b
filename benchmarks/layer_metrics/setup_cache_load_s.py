"""Model step: seconds of the set-up spent LOADING programs from the
persistent compilation cache: the backend-compile intervals that were cache
hits (look-up, read, deserialise, load onto the device), from the process's
start to the window's opening. A warm checkout pays this and little
``setup_compile_s``; a cold one the reverse. Counter ``/debug/state``
``process.compiles.cache_load_s`` in the snapshot taken as the window opens:
the value, not a delta (``benchmarks/SETUP_ACCOUNT.md``). A program without
the counter: nothing."""
from benchmarks.harness.registry import load_module

_compiles = load_module("layer_metrics", "setup_trace_s").compiles_at_window_start


def read(ctx):
    c = _compiles(ctx)
    return None if c is None else c.get("cache_load_s")

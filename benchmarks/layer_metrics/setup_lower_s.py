"""Model step: seconds of the set-up that went to LOWERING the traced
programs to MLIR modules (a Pallas kernel's Mosaic lowering and the traces
a lowering makes are inside it), from the process's start to the window's
opening. Counter ``/debug/state`` ``process.compiles.lower_s`` in the
snapshot taken as the window opens: the value, not a delta
(``setup_trace_s``; ``benchmarks/SETUP_ACCOUNT.md``). A program without the
counter: nothing."""
from benchmarks.harness.registry import load_module

_compiles = load_module("layer_metrics", "setup_trace_s").compiles_at_window_start


def read(ctx):
    c = _compiles(ctx)
    return None if c is None else c.get("lower_s")

"""Model step: the share of the measured window the engine thread spent
in prefill waves (dispatch to first-token sync; every stream waits
meanwhile), percent. Counter ``/debug/state`` ``engine.prefill``:
100 x delta ``wall_s`` / seconds of ``ctx["window"]``. A program without
the counter: nothing."""
from benchmarks.harness.registry import load_module

delta = load_module("layer_metrics", "prefill_wall_ms_per_ktok").delta


def read(ctx):
    d = delta(ctx)
    t0, t1 = ctx["window"]
    if d is None or t1 <= t0:
        return None
    return 100.0 * d["wall_s"] / (t1 - t0)

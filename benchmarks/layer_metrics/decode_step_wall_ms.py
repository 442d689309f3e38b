"""Scheduler: the window's own decode step, wall milliseconds — the
engine thread's seconds inside ``rm.engine.step`` less its prefill waves,
per decode step. Counters ``/debug/state`` ``engine.spans`` and
``engine.prefill``: (delta ``spans["rm.engine.step"].s`` - delta
``prefill.wall_s``) / delta ``decode_steps``. To hold against
``decode_step_device_ms`` + ``step_host_exposed_ms`` of the capture, which
is taken 15-21 s after the window. A program without the counters:
nothing."""
from benchmarks.harness.registry import load_module

delta = load_module("layer_metrics", "prefill_wall_ms_per_ktok").delta


def read(ctx):
    a, b = (ctx[k]["state"]["engine"] for k in ("before", "after"))
    d = delta(ctx)
    if d is None or "spans" not in a or "spans" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    in_step = b["spans"]["rm.engine.step"]["s"] - a["spans"]["rm.engine.step"]["s"]
    return 1e3 * (in_step - d["wall_s"]) / steps

"""Scheduler: of the tokens the window's prefill launches were shaped for
(power-of-two lengths, rows x chunk x chunks of a group), the share that
was padding, percent. Counter ``/debug/state`` ``engine.prefill``:
100 x (1 - delta ``tokens_new`` / delta ``tokens_launched``). A program
without the counter: nothing."""
from benchmarks.harness.registry import load_module

delta = load_module("layer_metrics", "prefill_wall_ms_per_ktok").delta


def read(ctx):
    d = delta(ctx)
    if d is None or d["tokens_launched"] <= 0:
        return None
    return 100.0 * (1.0 - d["tokens_new"] / d["tokens_launched"])

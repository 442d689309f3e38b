"""Scheduler: host milliseconds the engine thread spends inside the
prefill launch spans, per admission wave of the window — building the
wave's inputs, uploading them and dispatching the model program, BEFORE
the first-token sync (``rm.first_token.sync`` is not in it). Counters
``/debug/state`` ``engine.spans`` and ``engine.prefill``: 1e3 x (delta
``spans["rm.prefill.dense"].s`` + delta ``spans["rm.prefill.group"].s``) /
delta ``prefill.waves``. No decode launch is in flight during a wave, so
the device idles for as long as this takes. Which launch the waves took is
``engine.prefill.by_path``, read by hand. A program without the counters
(the parent of PR 35): nothing."""
from benchmarks.harness.registry import load_module

delta = load_module("layer_metrics", "prefill_wall_ms_per_ktok").delta

SPANS = ("rm.prefill.dense", "rm.prefill.group")


def read(ctx):
    a, b = (ctx[k]["state"]["engine"].get("spans") for k in ("before", "after"))
    d = delta(ctx)
    if d is None or a is None or b is None or d["waves"] <= 0:
        return None
    if not all(s in a and s in b for s in SPANS):
        return None
    return 1e3 * sum(b[s]["s"] - a[s]["s"] for s in SPANS) / d["waves"]

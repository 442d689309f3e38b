"""Scheduler: device-idle milliseconds per decode step that fall inside
``rm.engine.step`` — host work of the step that the device waits out
(admission, the row loop and uploads, dispatch, the sampler's launch, the
token sync, the per-row consume loop). From the capture's host spans
(``harness/hostspans.py``): idle time given to a span nested in
``rm.engine.step`` / ``rm.decode.dispatch`` spans of the capture."""
from benchmarks.harness import hostspans


def read(ctx):
    got = hostspans.of_cell(ctx)
    return None if got is None else 1e3 * got["in_step_s"] / got["steps"]

"""HTTP + admission: device-idle milliseconds per decode step while the
engine thread is outside ``rm.engine.step`` — the runner's lock hand-over
between steps (``time.sleep(0)``, re-taking the lock and the GIL from the
stream handlers: ``outside_step.http_emit`` / ``outside_step.other``) and
``rm.runner.pre_step``. From the capture's host spans
(``harness/hostspans.py``)."""
from benchmarks.harness import hostspans


def read(ctx):
    got = hostspans.of_cell(ctx)
    return None if got is None else 1e3 * got["outside_step_s"] / got["steps"]

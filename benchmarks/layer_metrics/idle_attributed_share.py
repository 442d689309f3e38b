"""Device: share of the capture's device-idle time that the host spans
name (a ``rm.*`` span of the engine thread or ``outside_step.*``), percent:
the tracing's own coverage. The rest lies before the engine thread's first
span of the capture or after its last (``harness/hostspans.py``)."""
from benchmarks.harness import hostspans


def read(ctx):
    got = hostspans.of_cell(ctx)
    if got is None or not got["idle_s"]:
        return None
    return 100.0 * (1.0 - got["unattributed_s"] / got["idle_s"])

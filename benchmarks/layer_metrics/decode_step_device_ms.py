"""Model step: device time of one ``jit_decode_step`` execution, ms (sum of
the module's device intervals in the trace / their count, first device
plane)."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    n, s = modules_matching(ctx["trace"], "jit_decode_step")
    return 1e3 * s / n if n else None

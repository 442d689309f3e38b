"""Model step: the state-space side's share of a decode step's device
time, percent: device seconds under ``rm_ssm`` (9 Mamba operators: two
projections, convolution, x_proj / dt_proj, the one-step recurrence on the
state read from and written to the row's slots, the gate) and ``rm_gmu`` (7
Gated Memory Units) / device seconds of the ``jit_decode_step``
executions (``harness/phi4flash_trace.py``: by the scopes, or by the
5 120-, 160- and 192-wide tensors only these operators make or read)."""
from benchmarks.harness import phi4flash_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    ssm = phi4flash_trace.of_run(ctx, "ssm")
    _, step_s = modules_matching(ctx["trace"] or {}, "jit_decode_step")
    if ssm is None or not step_s:
        return None
    return 100.0 * ssm["seconds"] / step_s

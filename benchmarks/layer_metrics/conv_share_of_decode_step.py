"""Model step: the gated short convolutions' share of a decode step's
device time, percent: device seconds of the ops under the operator's scope
(``rm_shortconv``: the two projections, the gates, the taps, the state's
hand-back; ``harness/lfm2_trace.py``) / device seconds of the
``jit_decode_step`` executions, 30 conv layers a step. The read of the 32
rows' states before the layers and the write after them are two ops
OUTSIDE the scope (and outside this share): 2 x 7.5 MB a step."""
from benchmarks.harness import lfm2_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    conv = lfm2_trace.of_run(ctx)
    _, step_s = modules_matching(ctx["trace"] or {}, "jit_decode_step")
    if conv is None or not step_s:
        return None
    return 100.0 * conv["seconds"] / step_s

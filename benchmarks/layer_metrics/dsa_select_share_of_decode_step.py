"""Model step: the selection's share of a decode step's device time,
percent: device seconds of the selection's named pieces
(``harness/dsa_trace.py``: inside the ``jit_decode_step`` executions, the
bisection loop over a row's index scores, the sort of its 128-score
segments and the compaction's gather — or, for a short row, the sort that
``lax.top_k`` lowers to — wherever a row holds more scores than
``index_topk``; the router's top-k sorts 256 scores and is not among
them; the selection's masks and running counts are unnamed fusions outside
it) / device seconds of those executions. Two selections a step here (two
full layers); the shared layers run none."""
from benchmarks.harness import dsa_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    xla = dsa_trace.of_run(ctx)
    _, step_s = modules_matching(ctx["trace"] or {}, "jit_decode_step")
    if xla is None or not xla["selects"] or not step_s:
        return None
    return 100.0 * xla["select_s"] / step_s

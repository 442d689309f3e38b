"""Model step, on the cell of the configuration with Mamba-1 layers beside
two multi-query attention layers: the attention layers' paged kernel's share
of a decode step's device time, percent: device seconds of the
``radixmesh_decode_fused_mh`` events (two launches a step: layers 7 and 21,
each writing the token's row and attending the row's pages) / device seconds
of the ``jit_decode_step`` executions in the capture. At 64 rows x 55 k of
context the two layers read as many bytes as two thirds of the weights."""
from benchmarks.harness import jamba_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    kernel = jamba_trace.decode_kernel(ctx)
    if not kernel or not kernel["seconds"]:
        return None
    _, step_s = modules_matching(ctx["trace"], "jit_decode_step")
    if not step_s:
        return None
    return 100.0 * kernel["seconds"] / step_s

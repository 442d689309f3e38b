"""Model step: the attention over the SHARED rows' share of a decode
step's device time, percent: device seconds of the fused decode kernel's
launches on the full layer's group — layer 17 and the 7 cross-attention
layers, all reading ONE layer's K/V (scope ``rm_shared_kv_attn``, or the
kernel's events that alias the one-layer pool: ``harness/phi4flash_trace.py``) — / device seconds of the
``jit_decode_step`` executions."""
from benchmarks.harness import phi4flash_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    attn = phi4flash_trace.of_run(ctx, "shared_kv_attn")
    _, step_s = modules_matching(ctx["trace"] or {}, "jit_decode_step")
    if attn is None or not step_s:
        return None
    return 100.0 * attn["seconds"] / step_s

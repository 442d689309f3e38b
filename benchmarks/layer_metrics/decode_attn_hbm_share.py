"""Kernels: the decode-attention kernel's share of the chip's HBM
bandwidth, percent. Bound: memory.

Bytes the kernel HAD to read per decode step: for every token that
arrived in the window after a request's first, that request's context
length at that token times the configuration's K/V bytes per token (one
chip's share under tensor parallelism), summed, divided by the decode
steps of the window — exact while speculation is off. Time per step:
device time of the ``radixmesh_decode_*`` kernel events in the trace /
the ``jit_decode_step`` executions it holds."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    planes = trace.get("planes") or {}
    if not planes or peaks is None:
        return None
    plane = next(iter(planes.values()))
    kernel_s = sum(k["seconds"] for name, k in plane["kernels"].items()
                   if name.startswith("radixmesh_decode"))
    steps_traced, _ = modules_matching(trace, "jit_decode_step")
    t0, t1 = ctx["window"]
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not kernel_s or not steps_traced or steps <= 0:
        return None
    context_tokens = 0
    for r in ctx["records"]:
        for i, t in enumerate(r.token_t):
            if i and t0 <= t < t1:
                context_tokens += r.n_prompt + i
    per_chip = ctx["kv_bytes_per_token"] / ctx["config"]["serving"]["tp"]
    bytes_per_step = context_tokens * per_chip / steps
    return 100.0 * bytes_per_step / (kernel_s / steps_traced) / peaks["hbm_bytes_per_s"]

"""Kernels: the decode step's grouped expert products' share of the chip's
HBM bandwidth, percent. Bound: memory.

Bytes the products HAD to read per decode step: the experts that were
given at least one row, summed over the layers (counter ``/debug/state``
``moe.decode.experts_hit``, delta over the window) x one expert's three
matrices (``opsbytes_moe.expert_bytes``) / the window's decode steps.
Needed bytes only: an expert fetched for two row tiles counts once, the
gathered rows and the products' outputs not at all. Time per step: device
time of the ``radixmesh_moe_gmm_decode`` kernel events in the trace / the
``jit_decode_step`` executions it holds. A program without the counter or
the kernel (the parent of the PR that added them): nothing to read."""
from benchmarks.harness.opsbytes_moe import expert_bytes
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    planes = (trace or {}).get("planes") or {}
    moe0, moe1 = (ctx[k]["state"].get("moe") for k in ("before", "after"))
    if not planes or peaks is None or moe0 is None or moe1 is None:
        return None
    kernel = next(iter(planes.values()))["kernels"].get("radixmesh_moe_gmm_decode")
    steps_traced, _ = modules_matching(trace, "jit_decode_step")
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not kernel or not steps_traced or steps <= 0:
        return None
    hit = moe1["decode"]["experts_hit"] - moe0["decode"]["experts_hit"]
    bytes_per_step = hit * expert_bytes(ctx["config"]) / steps
    return (100.0 * bytes_per_step / (kernel["seconds"] / steps_traced)
            / peaks["hbm_bytes_per_s"])

"""Model step: the grouped expert products' share of a decode step's
device time, percent: device seconds of the ``radixmesh_moe_gmm_decode``
kernel events / device seconds of the ``jit_decode_step`` modules, first
device plane. Routing, the counting sort, the row gather and the weighted
combine are XLA fusions that carry no name of ours on the trace's op line,
so they are OUTSIDE this share (``jax.named_scope("rm_moe_route" |
"rm_moe_combine")`` marks them for a reader of the profile)."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    planes = (ctx["trace"] or {}).get("planes") or {}
    if not planes:
        return None
    kernel = next(iter(planes.values()))["kernels"].get("radixmesh_moe_gmm_decode")
    _, step_s = modules_matching(ctx["trace"], "jit_decode_step")
    if not kernel or not step_s:
        return None
    return 100.0 * kernel["seconds"] / step_s

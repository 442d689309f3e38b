"""Model step: the KDA state kernels' share of a decode step's device time,
percent: device seconds of ``radixmesh_kda_decode`` (6 launches a step:
every live row's 2 MiB matrix a layer read, updated and written back in
place) and ``radixmesh_kda_boundary`` (the boundary copies of the rows that
completed a page) / device seconds of the ``jit_decode_step`` executions,
first device plane. The six projections, the convolution, the gates and
the output norm of a KDA layer are XLA fusions OUTSIDE this share
(``jax.named_scope("radixmesh_kda_conv")`` marks the convolution for a
reader of the profile)."""
from benchmarks.harness import kda_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    step = kda_trace.kernel(ctx, "radixmesh_kda_decode")
    copies = kda_trace.kernel(ctx, "radixmesh_kda_boundary")
    if step is None or not step["count"]:
        return None
    _, step_s = modules_matching(ctx["trace"], "jit_decode_step")
    if not step_s:
        return None
    return 100.0 * (step["seconds"] + copies["seconds"]) / step_s

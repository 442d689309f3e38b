"""Model step: the Mamba-2 state kernels' share of a decode step's device
time, percent: device seconds of ``radixmesh_ssd_decode`` (10 launches a
step: every live row's 2 MiB matrix a block read, updated and written back
in place) and ``radixmesh_kda_boundary`` (the boundary copies of the rows
that completed a page: the kernel both kinds of matrix state share) /
device seconds of the ``jit_decode_step`` executions, first device plane.
The projections, the convolution, the gate and the norm of a Mamba-2 block
are XLA fusions OUTSIDE this share (``jax.named_scope("rm_ssd")`` marks
them for a reader of the profile)."""
from benchmarks.harness import ssd_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    step = ssd_trace.kernel(ctx, "radixmesh_ssd_decode")
    copies = ssd_trace.kernel(ctx, "radixmesh_kda_boundary")
    if step is None or not step["count"]:
        return None
    _, step_s = modules_matching(ctx["trace"], "jit_decode_step")
    if not step_s:
        return None
    return 100.0 * (step["seconds"] + copies["seconds"]) / step_s

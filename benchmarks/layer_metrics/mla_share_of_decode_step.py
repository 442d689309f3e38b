"""Model step: the latent decode-attention kernel's share of a decode
step's device time, percent: device seconds of the ``radixmesh_mla_decode``
kernel events / device seconds of the ``jit_decode_step`` modules, first
device plane. The absorb and un-absorb products are XLA fusions OUTSIDE
this share (``jax.named_scope("rm_mla_absorb" | "rm_mla_unabsorb")`` marks
them for a reader of the profile)."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    planes = (ctx["trace"] or {}).get("planes") or {}
    if not planes:
        return None
    kernel = next(iter(planes.values()))["kernels"].get("radixmesh_mla_decode")
    _, step_s = modules_matching(ctx["trace"], "jit_decode_step")
    if not kernel or not step_s:
        return None
    return 100.0 * kernel["seconds"] / step_s

"""Kernels: ``radixmesh_ssd_decode``'s share of the chip's HBM bandwidth,
percent; bound: MEMORY (a launch reads and writes 2 x 2 MiB a row against a
few vector operations an element). Bytes a launch HAD to move
(``opsbytes_ssd.ssd_decode_kernel_bytes``): each LIVE row's matrix read and
written back and the step's vectors — live rows a step: delta
``ssd.decode_rows`` / delta ``decode_steps``; the launch also moves the
idle rows' scratch slot, which is waste and not counted. Time a launch: the
kernel's device seconds / its events in the capture (10 a step)."""
from benchmarks.harness import ssd_trace
from benchmarks.harness.opsbytes_ssd import ssd_decode_kernel_bytes


def read(ctx):
    k, c, peaks = (ssd_trace.kernel(ctx, "radixmesh_ssd_decode"),
                   ssd_trace.counters(ctx), ctx["peaks"])
    if k is None or c is None or peaks is None or not k["count"]:
        return None
    if c["decode_steps"] <= 0:
        return None
    rows = c["decode_rows"] / c["decode_steps"]
    return (100.0 * ssd_decode_kernel_bytes(ctx["config"], rows)
            / (k["seconds"] / k["count"]) / peaks["hbm_bytes_per_s"])

"""Kernels: ``radixmesh_ssd_chunk``'s share of the chip's bf16 MXU peak in
the capture's prefills, percent. Expected LOW: the operations counted are
those of the chunked (SSD) form at a block of 128 (``opsbytes_ssd.
ssd_chunk_flops_per_token``, fixed whatever the kernel does), ~3.4 MFLOP a
token and block, while the kernel takes float32 operands at full precision
(six passes of the MXU) and is bound by its steps' overhead, not the MXU.
FLOPs a launch HAD to do: the REAL new tokens of a prefill launch of the
window (delta ``ssd.chunk_tokens`` / delta ``engine.prefill.launches``:
padding is waste and not counted) x FLOPs a token and block x the Mamba-2
blocks; time a launch: the kernel's device seconds / the prefill programs
in the capture (``jit_prefill*`` executions). A capture that holds no
prefill at all reads 0 — the kernel used none of the MXU there — not
nothing: a traced line of this cell always carries the metric."""
from benchmarks.harness import ssd_trace
from benchmarks.harness.opsbytes_ssd import ssd_chunk_flops_per_token
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    k, c, peaks = (ssd_trace.kernel(ctx, "radixmesh_ssd_chunk"),
                   ssd_trace.counters(ctx), ctx["peaks"])
    if k is None or c is None or peaks is None or c["prefill_launches"] <= 0:
        return None
    launches, _ = modules_matching(ctx["trace"], "jit_prefill")
    if not k["count"] or not launches:
        return 0.0
    tokens = c["chunk_tokens"] / c["prefill_launches"]
    need = (tokens * ssd_chunk_flops_per_token(ctx["config"])
            * ctx["config"]["serving"]["mamba2_blocks"])
    return 100.0 * need / (k["seconds"] / launches) / peaks["bf16_flops_per_s"]

"""Kernels: ``radixmesh_kda_chunk``'s share of the chip's bf16 MXU peak in
the capture's prefills, percent. Expected LOW, and the bound is NOT the
MXU: the operations counted are those of the CHUNKED form of the delta
rule at a block of 64 (``opsbytes_kda.kda_chunk_flops_per_token``, fixed
whatever the kernel does), while the kernel on the chip walks the
positions on the vector unit (``ops/kda.py``): the share says how far
that walk is from what the chunked form on the MXU could cost. FLOPs a
launch HAD to do: the REAL new tokens of a prefill launch of the window
(delta ``kda.chunk_tokens`` / delta ``engine.prefill.launches``: padding
is waste and not counted) x FLOPs a token and layer x the KDA layers; time
a launch: the kernel's device seconds / the prefill programs in the
capture (``jit_prefill*`` executions: a program holds one call a KDA layer
and 16 rows, so the kernel's own events do not count launches). A capture
that holds no prefill at all reads 0 — the kernel used none of the MXU
there — not nothing: a traced line of this cell always carries the
metric."""
from benchmarks.harness import kda_trace
from benchmarks.harness.opsbytes_kda import kda_chunk_flops_per_token
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    k, c, peaks = (kda_trace.kernel(ctx, "radixmesh_kda_chunk"),
                   kda_trace.counters(ctx), ctx["peaks"])
    if k is None or c is None or peaks is None or c["prefill_launches"] <= 0:
        return None
    launches, _ = modules_matching(ctx["trace"], "jit_prefill")
    if not k["count"] or not launches:
        return 0.0
    tokens = c["chunk_tokens"] / c["prefill_launches"]
    need = (tokens * kda_chunk_flops_per_token(ctx["config"])
            * ctx["config"]["serving"]["kda_layers"])
    return 100.0 * need / (k["seconds"] / launches) / peaks["bf16_flops_per_s"]

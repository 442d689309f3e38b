"""Kernels: ``radixmesh_ssm_scan``'s share of the chip's HBM bandwidth in
the capture's prefills, percent. Expected LOW: the kernel is bound by the
vector unit and ``exp`` (``ops/ssm.py``: 16 x 5120 exponentials and ~6
vector operations on them a position, serial in the position, against ~60
KB of traffic), not by memory; a share near 100 would mean the walk had
become free. Bytes a launch HAD to move: the REAL new tokens of a prefill
wave of the window (delta ``tokens_new`` / delta ``waves``: padding is
waste and not counted) x ``opsbytes_phi4flash.ssm_scan_bytes_per_token``;
time a launch: the kernel's device seconds / its events in the capture
(9 a chunk program: one a Mamba layer). A capture that holds no prefill at
all (3 s of 64 rows decoding 512-1 024-token replies can: PR 41, one run of
eight) reads 0 — the kernel used none of the bandwidth there — not
nothing: a traced line of this cell always carries the metric."""
from benchmarks.harness import phi4flash_trace
from benchmarks.harness.opsbytes_phi4flash import ssm_scan_bytes_per_token


def read(ctx):
    planes = (ctx.get("trace") or {}).get("planes") or {}
    peaks = ctx["peaks"]
    if not phi4flash_trace.is_cell(ctx) or not planes or peaks is None:
        return None
    kernel = next(iter(planes.values()))["kernels"].get("radixmesh_ssm_scan")
    a, b = (ctx[k]["state"]["engine"]["prefill"] for k in ("before", "after"))
    waves = b["waves"] - a["waves"]
    if waves <= 0:
        return None
    if not kernel or not kernel["count"]:
        return 0.0
    tokens = (b["tokens_new"] - a["tokens_new"]) / waves
    need = tokens * ssm_scan_bytes_per_token(ctx["config"])
    return (100.0 * need / (kernel["seconds"] / kernel["count"])
            / peaks["hbm_bytes_per_s"])

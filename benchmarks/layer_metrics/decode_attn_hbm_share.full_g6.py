"""Kernels: the FULL layers' decode attention's share of the chip's HBM
bandwidth in a decode step, percent; bound: memory (48 query heads over 8
kv heads: six queries share a key, 6 FLOPs a byte). Bytes the launches HAD
to read a step: for every token that arrived in the window after a
request's first, ``context`` tokens x 4 096 B x the full layers
(``opsbytes_laguna.full_kv_bytes_needed``), over the window's decode steps.
Time a step: device seconds of ``radixmesh_decode_fused_mh``'s launches on
the pool in ``jit_decode_step`` (``harness/laguna_trace.py``: part
``full_attn``) / the executions the capture holds."""
from benchmarks.harness import laguna_trace
from benchmarks.harness.opsbytes_laguna import full_kv_bytes_needed

PART, NEEDED = "full_attn", full_kv_bytes_needed


def read(ctx, part=PART, needed=NEEDED):
    if not laguna_trace.is_cell(ctx):
        return None
    attn, peaks = laguna_trace.of_run(ctx, part), ctx["peaks"]
    steps = laguna_trace.decode_steps(ctx)
    if attn is None or peaks is None or steps <= 0:
        return None
    per_step = sum(needed(ctx["config"], c)
                   for c in laguna_trace.decoded_contexts(ctx)) / steps
    return (100.0 * per_step / (attn["seconds"] / attn["executions"])
            / peaks["hbm_bytes_per_s"])

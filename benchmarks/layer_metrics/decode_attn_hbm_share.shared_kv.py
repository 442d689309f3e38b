"""Kernels: the shared-K/V attention's share of the chip's HBM bandwidth
in a decode step, percent; bound: memory. Bytes the launches HAD to read a
step: for every token that arrived in the window after a request's first,
``context`` tokens x 5 120 B (the full layer's K and V) x 8 reading layers
(``opsbytes_phi4flash.shared_kv_bytes_needed``: stored once, read by layer
17 and every cross-attention layer), over the window's decode steps. Time
a step: device seconds of those launches in ``jit_decode_step``
(``harness/phi4flash_trace.py``: part ``shared_kv_attn``) /
the executions the capture holds."""
from benchmarks.harness import phi4flash_trace
from benchmarks.harness.opsbytes_phi4flash import shared_kv_bytes_needed

PART, NEEDED = "shared_kv_attn", shared_kv_bytes_needed


def read(ctx, part=PART, needed=NEEDED):
    attn, peaks = phi4flash_trace.of_run(ctx, part), ctx["peaks"]
    a, b = ctx["before"]["state"]["engine"], ctx["after"]["state"]["engine"]
    steps = b["decode_steps"] - a["decode_steps"]
    if attn is None or peaks is None or steps <= 0:
        return None
    per_step = sum(needed(ctx["config"], c)
                   for c in phi4flash_trace.decoded_contexts(ctx)) / steps
    return (100.0 * per_step / (attn["seconds"] / attn["executions"])
            / peaks["hbm_bytes_per_s"])

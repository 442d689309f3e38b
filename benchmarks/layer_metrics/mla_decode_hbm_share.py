"""Kernels: the latent decode-attention kernel's share of the chip's HBM
bandwidth, percent. Bound: memory.

Bytes the kernel HAD to read per decode step: the latent rows the window's
decode launches attended (counter ``/debug/state`` ``attn.latent_tokens_read``:
context x layers, summed over rows, delta over the window) x one row's
bytes in one layer (``opsbytes_mla.row_bytes``: 1 152 B, the row's needed
elements, not the 1 280 B it is stored in) / the window's decode steps.
Time per step: device time of the ``radixmesh_mla_decode`` kernel events in
the trace / the ``jit_decode_step`` executions it holds. A program without
the counter or the kernel (the parent of the PR that added them): nothing
to read."""
from benchmarks.harness.opsbytes_mla import row_bytes
from benchmarks.harness.xplane import modules_matching


def per_step(ctx):
    """``(latent rows read per decode step, kernel seconds per step)`` or
    None: shared with ``mla_decode_mxu_share``."""
    trace = ctx["trace"]
    planes = (trace or {}).get("planes") or {}
    a0, a1 = (ctx[k]["state"].get("attn") or {} for k in ("before", "after"))
    if not planes or "latent_tokens_read" not in a0 or "latent_tokens_read" not in a1:
        return None
    kernel = next(iter(planes.values()))["kernels"].get("radixmesh_mla_decode")
    steps_traced, _ = modules_matching(trace, "jit_decode_step")
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not kernel or not steps_traced or steps <= 0:
        return None
    rows = a1["latent_tokens_read"] - a0["latent_tokens_read"]
    return rows / steps, kernel["seconds"] / steps_traced


def read(ctx):
    got = per_step(ctx)
    if got is None or ctx["peaks"] is None:
        return None
    rows, seconds = got
    return (100.0 * rows * row_bytes(ctx["config"]) / seconds
            / ctx["peaks"]["hbm_bytes_per_s"])

"""Kernels: the chunk-attention kernel's share of the chip's bf16 peak in
a prefill launch, at 48 and 64 query heads of 128 over 8 kv heads, percent;
bound: compute (64-512 new tokens against 2 k-13 k cached ones: 4 x 128
FLOPs a visible pair and head against 4 096 B a key once a query block).
FLOPs the launches HAD to make: for every request of the load admitted in
the window, its new tokens (the prompt less the page-rounded history it
re-sent, which the tree served) against what was visible to each, in the
full layers all of it and in the sliding layers the last 512
(``opsbytes_laguna.chunk_attn_flops_needed``), over the window's launches
that wrote rows (``/debug/state`` ``kv.write.launches``: every prefill of
this cell is a chunk launch). Time a launch: device seconds of
``radixmesh_chunk_attn`` / the ``jit_prefill_chunk_paged`` executions the
capture holds. Padding of the launched shape (rows to a power of two, a
tail to its chunk) is waste: it can only understate."""
from benchmarks.harness import laguna_trace
from benchmarks.harness.opsbytes_laguna import chunk_attn_flops_needed
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    planes = (trace or {}).get("planes") or {}
    if not laguna_trace.is_cell(ctx) or not planes or peaks is None:
        return None
    kernel = next(iter(planes.values()))["kernels"].get("radixmesh_chunk_attn")
    launches_traced, _ = modules_matching(trace, "jit_prefill_chunk_paged")
    w0, w1 = (ctx[k]["state"].get("kv", {}).get("write") for k in ("before", "after"))
    if not kernel or not launches_traced or not w0 or not w1:
        return None
    launches = w1["launches"] - w0["launches"]
    t0, t1 = ctx["window"]
    page = ctx["after"]["state"]["pool"]["page_size"]
    flops = 0
    for r in ctx["records"]:
        if r.tag != "load" or not r.ok or not r.token_t or not t0 <= r.token_t[0] < t1:
            continue
        prior = min(r.resent_tokens // page * page, (r.n_prompt - 1) // page * page)
        flops += chunk_attn_flops_needed(ctx["config"], prior, r.n_prompt - prior)
    if launches <= 0 or not flops:
        return None
    return (100.0 * (flops / launches) / (kernel["seconds"] / launches_traced)
            / peaks["bf16_flops_per_s"])

"""Kernels, on the cell of the configuration with Mamba-2 blocks: the decode
attention's share of the chip's HBM bandwidth in a decode step, percent;
bound: memory (32 query heads on 2 KV heads: sixteen queries share a key,
1 024 B a token and block: the thinnest row the pool has held). Bytes the
launches HAD to read a step: for every token that arrived in the window
after a request's first, ``context`` tokens x 3 072 B (``opsbytes_ssd.
kv_bytes_per_token``: the three attention blocks), over the window's decode
steps. Time a step: device seconds of the ``radixmesh_decode_*`` events /
the ``jit_decode_step`` executions the capture holds."""
from benchmarks.harness import ssd_trace
from benchmarks.harness.opsbytes_ssd import kv_bytes_per_token
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    planes = (ctx.get("trace") or {}).get("planes") or {}
    if not ssd_trace.is_cell(ctx) or not planes or ctx["peaks"] is None:
        return None
    kernel_s = sum(k["seconds"] for name, k in next(iter(planes.values()))[
        "kernels"].items() if name.startswith("radixmesh_decode"))
    steps_traced, _ = modules_matching(ctx["trace"], "jit_decode_step")
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not kernel_s or not steps_traced or steps <= 0:
        return None
    t0, t1 = ctx["window"]
    tokens = sum(r.n_prompt + i for r in ctx["records"]
                 for i, t in enumerate(r.token_t) if i and t0 <= t < t1)
    per_step = tokens * kv_bytes_per_token(ctx["config"]) / steps
    return (100.0 * per_step / (kernel_s / steps_traced)
            / ctx["peaks"]["hbm_bytes_per_s"])

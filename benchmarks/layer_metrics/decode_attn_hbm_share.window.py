"""Kernels: ``decode_attn_hbm_share`` for a configuration with window
layers — the decode-attention kernel's share of the chip's HBM bandwidth,
percent; bound: memory. The same method (client ledger x needed bytes /
the window's decode steps, over the ``radixmesh_decode_*`` kernel time per
traced ``jit_decode_step``), with the bytes a window layer needs: for
every token that arrived in the window after a request's first, K/V of
``context`` tokens on each full layer and of ``min(context,
sliding_window)`` on each sliding layer (``opsbytes_moe.kv_bytes_needed``).
The dense reader's ``kv_bytes_per_token x context`` would count pages the
kernel rightly skips and could pass 100%."""
from benchmarks.harness.opsbytes_moe import kv_bytes_needed
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    trace, peaks, config = ctx["trace"], ctx["peaks"], ctx["config"]
    planes = (trace or {}).get("planes") or {}
    if not planes or peaks is None or "layer_types" not in config:
        return None
    plane = next(iter(planes.values()))
    kernel_s = sum(k["seconds"] for name, k in plane["kernels"].items()
                   if name.startswith("radixmesh_decode"))
    steps_traced, _ = modules_matching(trace, "jit_decode_step")
    t0, t1 = ctx["window"]
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not kernel_s or not steps_traced or steps <= 0:
        return None
    needed = sum(kv_bytes_needed(config, r.n_prompt + i)
                 for r in ctx["records"] for i, t in enumerate(r.token_t)
                 if i and t0 <= t < t1)
    per_step = needed / config["serving"]["tp"] / steps
    return 100.0 * per_step / (kernel_s / steps_traced) / peaks["hbm_bytes_per_s"]

"""Kernels, on the cell of the configuration with Mamba-2 blocks: the decode
step's grouped expert products' share of the chip's HBM bandwidth, percent;
bound: memory. ``moe_gmm_hbm_share``'s arithmetic with an UN-GATED expert's
TWO matrices at the PUBLISHED width 1856 (``opsbytes_ssd.expert_params``;
the program stores 1920: the zero columns it streams are waste and not
counted): experts given at least one row (delta ``moe.decode.experts_hit``)
x an expert's bytes / the window's decode steps, over the device time of
``radixmesh_moe_gmm_decode`` a ``jit_decode_step`` execution."""
from benchmarks.harness import ssd_trace
from benchmarks.harness.opsbytes_ssd import expert_params
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    kernel, peaks = ssd_trace.kernel(ctx, "radixmesh_moe_gmm_decode"), ctx["peaks"]
    moe0, moe1 = (ctx[k]["state"].get("moe") for k in ("before", "after"))
    if kernel is None or not kernel["count"] or peaks is None or moe0 is None or moe1 is None:
        return None
    steps_traced, _ = modules_matching(ctx["trace"], "jit_decode_step")
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not steps_traced or steps <= 0:
        return None
    hit = moe1["decode"]["experts_hit"] - moe0["decode"]["experts_hit"]
    per_step = (hit * expert_params(ctx["config"])
                * ctx["config"]["serving"]["weight_bytes_per_param"] / steps)
    return (100.0 * per_step / (kernel["seconds"] / steps_traced)
            / peaks["hbm_bytes_per_s"])

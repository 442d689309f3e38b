"""Kernels: the grouped expert products' share of the HBM bandwidth with 8 of
64 experts held a layer — the reader of ``moe_gmm_hbm_share`` (bytes of the
held experts that were hit / device time of ``radixmesh_moe_gmm_decode`` a
step): 32 rows x top-4 of 64 touch ~7 of the 8."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_gmm_hbm_share").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"]:  # not this configuration's cell
        return None
    return _read(ctx)

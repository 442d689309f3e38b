"""Kernels: the MXU twin of ``mla_sparse_decode_hbm_share``: the same rows
selected per decode step x the FLOPs of absorbed attention a selected row
(``opsbytes_dsa.sparse_attn_flops_per_row``: every head scores the whole
row and mixes its latent part) / the same gather-and-attend time / the
chip's bf16 peak, percent. 0.5 x the HBM share by arithmetic at these
widths (121 FLOP a byte against a ridge of 240)."""
from benchmarks.harness.opsbytes_dsa import sparse_attn_flops_per_row
from benchmarks.harness.registry import load_module

_per_step = load_module("layer_metrics", "mla_sparse_decode_hbm_share").per_step


def read(ctx):
    got = _per_step(ctx)
    if got is None or ctx["peaks"] is None:
        return None
    rows, seconds = got
    return (100.0 * rows * sparse_attn_flops_per_row(ctx["config"]) / seconds
            / ctx["peaks"]["bf16_flops_per_s"])

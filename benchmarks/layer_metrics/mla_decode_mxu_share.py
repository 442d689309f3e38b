"""Kernels: the latent decode-attention kernel's share of the chip's bf16
peak, percent: the other side of its ridge. The same rows and the same
time as ``mla_decode_hbm_share``, x the FLOPs absorbed attention needs per
cached row and layer (``opsbytes_mla.decode_attn_flops_per_token_layer``:
43 520 = 2 x 20 heads x (576 + 512)) / 197 TFLOP/s. By arithmetic 0.157 x
the HBM share; against the ~31 TFLOP/s that 20 query rows of a 128-row MXU
pass can give, it is the same share as the HBM one."""
from benchmarks.harness.opsbytes_mla import decode_attn_flops_per_token_layer
from benchmarks.harness.registry import load_module

per_step = load_module("layer_metrics", "mla_decode_hbm_share").per_step


def read(ctx):
    got = per_step(ctx)
    if got is None or ctx["peaks"] is None:
        return None
    rows, seconds = got
    return (100.0 * rows * decode_attn_flops_per_token_layer(ctx["config"])
            / seconds / ctx["peaks"]["bf16_flops_per_s"])

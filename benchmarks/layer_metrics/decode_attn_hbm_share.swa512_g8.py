"""Kernels: ``decode_attn_hbm_share.full_g6``'s reader for the launches on
the WINDOW group (part ``window_attn``; 64 query heads over 8 kv heads):
``min(context, 512)`` tokens x 4 096 B x the sliding layers a decoded token
(``opsbytes_laguna.window_kv_bytes_needed``; the block's edge the kernel
reads beside the window is waste and not counted)."""
from benchmarks.harness.opsbytes_laguna import window_kv_bytes_needed
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "decode_attn_hbm_share.full_g6").read


def read(ctx):
    return _read(ctx, "window_attn", window_kv_bytes_needed)

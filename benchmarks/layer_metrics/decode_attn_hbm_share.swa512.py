"""Kernels: ``decode_attn_hbm_share.shared_kv``'s reader for the launches
on the WINDOW group (part ``window_attn``): ``min(context, 512)``
tokens x 40 960 B (8 sliding layers' K and V) a decoded token
(``opsbytes_phi4flash.window_kv_bytes_needed``; the block's edge the kernel
reads beside the window is waste and not counted)."""
from benchmarks.harness.opsbytes_phi4flash import window_kv_bytes_needed
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "decode_attn_hbm_share.shared_kv").read


def read(ctx):
    return _read(ctx, "window_attn", window_kv_bytes_needed)

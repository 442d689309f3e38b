"""``decode_attn_hbm_share`` for heads of 64 laid two to a 128-lane pool
row: the same reader (context tokens of the window's decode steps x the
K/V bytes a token / device time of ``radixmesh_decode_*`` a step / the
chip's HBM bandwidth) with THIS configuration's bytes a token
(``opsbytes_lfm2.kv_bytes_per_token``: the 10 attention layers alone, 8 kv
heads of 64, 20 480 B — ``harness/opsbytes.py`` counts every layer and
would say 81 920). No padding is counted because none is stored: a row of
128 lanes holds two heads."""
from benchmarks.harness.opsbytes_lfm2 import kv_bytes_per_token
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "decode_attn_hbm_share").read


def read(ctx):
    if "conv_L_cache" not in ctx["config"] or not (ctx["trace"] or {}).get("planes"):
        return None
    return _read({**ctx, "kv_bytes_per_token": kv_bytes_per_token(ctx["config"])})

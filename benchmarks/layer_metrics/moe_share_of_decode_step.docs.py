"""``moe_share_of_decode_step`` in the documents cell: the same reader
(device seconds of ``radixmesh_moe_gmm_decode`` / those of
``jit_decode_step``)."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "moe_share_of_decode_step").read

"""``moe_gmm_hbm_share`` in the documents cell: the same arithmetic (bytes
of the experts that were hit / device time of ``radixmesh_moe_gmm_decode``
a step / the chip's HBM bandwidth) and the same reader;
``opsbytes_moe.expert_bytes`` reads keys this configuration has under the
same names. The shared expert is no assignment and not in these bytes."""
from benchmarks.harness.registry import load_module

read = load_module("layer_metrics", "moe_gmm_hbm_share").read

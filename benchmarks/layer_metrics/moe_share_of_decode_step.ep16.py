"""``moe_share_of_decode_step`` under an expert share: the same reader
(``radixmesh_moe_gmm_decode`` / ``jit_decode_step``). With 16 of 256
experts held and 32 rows a step the products see 1/16 of the assignments:
expected small beside the attention and the dense projections."""
from benchmarks.harness.opsbytes_dsa import expert_share
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_share_of_decode_step").read


def read(ctx):
    if expert_share(ctx["config"]) <= 1:  # not this configuration's cell
        return None
    return _read(ctx)

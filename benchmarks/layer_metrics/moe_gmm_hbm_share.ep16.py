"""``moe_gmm_hbm_share`` under an expert share: the same arithmetic and
reader (bytes of the experts that were hit / device time of
``radixmesh_moe_gmm_decode`` a step / the chip's HBM bandwidth):
``moe.decode.experts_hit`` counts HELD experts only from PR 33 on, and
``opsbytes_moe.expert_bytes`` reads keys this configuration has under the
same names. The shared expert is no assignment and not in these bytes."""
from benchmarks.harness.opsbytes_dsa import expert_share
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_gmm_hbm_share").read


def read(ctx):
    if expert_share(ctx["config"]) <= 1:  # not this configuration's cell
        return None
    return _read(ctx)

"""moe_gmm_hbm_share's reader on the cell of the configuration with 256
small experts a layer (3 x 2048 x 512: 6.3 MB each; ``opsbytes_moe.expert_bytes``
reads the same keys as ``opsbytes_laguna.expert_bytes``): 64 rows x top-8
over 256 experts give ~2 rows an expert and ~221 of 256 hit a layer, the
many-small-experts end of ``radixmesh_moe_gmm_decode``. The accepted lists
of the namesake are pinned to their cells, so this cell reports under a
name of its own."""
from benchmarks.harness import laguna_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_gmm_hbm_share").read


def read(ctx):
    if not laguna_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

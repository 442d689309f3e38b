"""moe_expert_load_max_over_mean's reader on the cell of the configuration with 256 small experts a
layer and a shared one (four sparse layers of the cut; the shared expert
and the dense first layer are XLA products outside the kernel): the
accepted lists of the namesake are pinned to their cells, so this cell
reports under a name of its own."""
from benchmarks.harness import laguna_trace
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_expert_load_max_over_mean").read


def read(ctx):
    if not laguna_trace.is_cell(ctx):  # not this configuration's cell
        return None
    return _read(ctx)

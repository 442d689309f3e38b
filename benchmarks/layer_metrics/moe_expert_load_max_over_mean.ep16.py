"""``moe_expert_load_max_over_mean`` under an expert share: the same
arithmetic and reader (delta ``max_load`` / (delta ``assignments`` /
experts)), over the experts HELD here: ``assignments`` counts them alone
from PR 33 on, and this configuration's ``n_routed_experts`` is the held
count (the reader knows the key ``num_experts``)."""
from benchmarks.harness.opsbytes_dsa import expert_share
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_expert_load_max_over_mean").read


def read(ctx):
    config = ctx["config"]
    if expert_share(config) <= 1:  # not this configuration's cell
        return None
    return _read({**ctx, "config": {
        **config, "num_experts": config["n_routed_experts"]}})

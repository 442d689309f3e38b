"""``moe_expert_load_max_over_mean`` in the documents cell: the same
arithmetic and reader (delta ``max_load`` / (delta ``assignments`` /
experts), counters ``/debug/state`` ``moe.decode``), given the number of
routed experts under the key that reader knows (it reads ``num_experts``;
this configuration's source says ``n_routed_experts``)."""
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "moe_expert_load_max_over_mean").read


def read(ctx):
    config = ctx["config"]
    return _read({**ctx, "config": {
        **config, "num_experts": config["n_routed_experts"]}})

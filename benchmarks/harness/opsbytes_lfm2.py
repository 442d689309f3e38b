"""Operations and bytes a step NEEDS of a configuration whose layers are
gated short convolutions and grouped-query attention (``layer_types``:
"conv" | "full_attention"), dense FFNs first and sparse experts after,
under an expert share — from its widths. Needed, not executed: a cache
row counts at its head width (two heads of 64 fill a 128-lane row: no
padding to leave out), a state at its ``conv_L_cache - 1`` positions,
each read once; an expert is streamed once however many rows chose it.
Waste can only understate a share computed from these, never push it past
100%.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level and the ``serving`` group.
"""
from __future__ import annotations


def head_dim(config: dict) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


def conv_layers(config: dict) -> int:
    return sum(t == "conv" for t in config["layer_types"])


def attention_layers(config: dict) -> int:
    return sum(t != "conv" for t in config["layer_types"])


def sparse_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def kv_bytes_per_token(config: dict) -> int:
    """K and V of one token over the layers that own rows — the attention
    layers alone — and all kv heads."""
    return (2 * attention_layers(config) * config["num_key_value_heads"]
            * head_dim(config) * config["serving"]["kv_bytes_per_element"])


def state_bytes(config: dict) -> int:
    """One snapshot: every conv layer's ``conv_L_cache - 1`` positions x
    ``hidden_size`` channels."""
    return (conv_layers(config) * (config["conv_L_cache"] - 1)
            * config["hidden_size"] * config["serving"]["state_bytes_per_element"])


def conv_params(config: dict) -> int:
    """One conv operator: in_proj [H, 3H], the depthwise kernel [H, taps],
    out_proj [H, H]."""
    h = config["hidden_size"]
    return h * 3 * h + h * config["conv_L_cache"] + h * h


def attention_params(config: dict) -> int:
    """One attention operator: q and o [H, heads x D], k and v [H, kv
    heads x D] (the per-head norms' 2 x D weights are noise)."""
    h, d = config["hidden_size"], head_dim(config)
    return (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def parameters(config: dict) -> int:
    """Matmul parameters this chip holds (norms left out): every operator,
    the dense FFNs, each sparse layer's router (its published width) and
    HELD experts (``num_experts`` here is the share), the tied embedding
    once."""
    h = config["hidden_size"]
    return (
        conv_layers(config) * conv_params(config)
        + attention_layers(config) * attention_params(config)
        + config["num_dense_layers"] * 3 * h * config["intermediate_size"]
        + sparse_layers(config) * (
            h * config["serving"]["router_experts"]
            + config["num_experts"] * expert_params(config))
        + h * config["vocab_size"]
    )


def conv_decode_bytes_per_step(config: dict, rows: float) -> float:
    """What the conv operators of ONE decode step have to move: every conv
    layer's three matrices once, and each of ``rows`` live rows' state read
    and written back."""
    s = config["serving"]
    return (conv_layers(config) * conv_params(config) * s["weight_bytes_per_param"]
            + 2 * rows * state_bytes(config))


def conv_flops_per_token(config: dict) -> int:
    """FLOPs of the conv operators for one token, all conv layers: the
    two projections, the gates and the taps."""
    h = config["hidden_size"]
    return conv_layers(config) * (
        2 * (h * 3 * h + h * h) + h * (2 * config["conv_L_cache"] + 2))

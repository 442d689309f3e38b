"""Operations and bytes a step NEEDS of a configuration whose attention
layers are of two shapes (``layer_types``: "full_attention" |
"sliding_attention", ``num_attention_heads_per_layer`` query heads over
``num_key_value_heads``), with a head-wise output gate, a dense first
layer and sparse layers of many small experts beside a shared one — from
its widths. Needed, not executed: a full layer's rows count at every
context token, a window layer's only inside its window, an expert's
weights once however many rows chose it, a chunk's attention only where a
key is visible to a query. Waste (a page read and masked, an expert
fetched for two row tiles, a query block's padding) can only understate a
share computed from these, never push it past 100%.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level and the ``serving`` group.
"""
from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def _count(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def heads_of(config: dict, kind: str) -> int:
    """Query heads of a layer of ``kind`` (every layer of a kind has the
    same number)."""
    heads = {h for t, h in zip(config["layer_types"],
                               config["num_attention_heads_per_layer"]) if t == kind}
    if len(heads) != 1:
        raise ValueError(f"layers of {kind} have {sorted(heads)} query heads")
    return heads.pop()


def layer_kv_bytes(config: dict) -> int:
    """K and V of one token in ONE attention layer, all kv heads."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * config["serving"]["kv_bytes_per_element"])


def full_kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the pool (kept for every token)."""
    return _count(config, FULL) * layer_kv_bytes(config)


def window_kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the window group (kept while it is among a
    row's last ``sliding_window`` tokens)."""
    return _count(config, SLIDING) * layer_kv_bytes(config)


def full_kv_bytes_needed(config: dict, context: int) -> int:
    """Bytes one decoded token's attention has to read in the full layers."""
    return context * full_kv_bytes_per_token(config)


def window_kv_bytes_needed(config: dict, context: int) -> int:
    """... in the sliding layers: ``min(context, sliding_window)`` tokens."""
    return min(context, config["sliding_window"]) * window_kv_bytes_per_token(config)


def attention_params(config: dict, kind: str) -> int:
    """One attention operator of ``kind``: W_q, W_k, W_v, W_o and the
    head-wise gate's [hidden, heads]."""
    h, d = config["hidden_size"], config["head_dim"]
    q, kv = heads_of(config, kind) * d, config["num_key_value_heads"] * d
    gate = h * heads_of(config, kind) if config.get("gating") else 0
    return h * (q + 2 * kv) + q * h + gate


def gate_flops_per_token(config: dict, kind: str) -> int:
    """The gate of one layer for one token: the [hidden, heads] product
    and one multiply an output element."""
    heads = heads_of(config, kind)
    return 2 * config["hidden_size"] * heads + heads * config["head_dim"]


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_bytes(config: dict) -> int:
    """One routed expert's three matrices at the stored width."""
    return expert_params(config) * config["serving"]["weight_bytes_per_param"]


def shared_expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["shared_expert_intermediate_size"]


def sparse_layers(config: dict) -> int:
    return sum(t == "sparse" for t in config["mlp_layer_types"])


def parameters(config: dict) -> int:
    """Matmul-scale parameters of the model as the file cuts it (norms and
    the router's bias left out): both attention kinds with their gates,
    the dense FFNs, the routers, every routed and shared expert, the
    embedding and the untied head."""
    h = config["hidden_size"]
    dense = len(config["mlp_layer_types"]) - sparse_layers(config)
    return (
        sum(attention_params(config, t) for t in config["layer_types"])
        + dense * 3 * h * config["intermediate_size"]
        + sparse_layers(config) * (
            h * config["num_experts"]
            + config["num_experts"] * expert_params(config)
            + shared_expert_params(config))
        + 2 * h * config["vocab_size"]
    )


def decode_other_weight_bytes(config: dict) -> int:
    """Weights a decode step streams whatever is routed where: everything
    but the routed experts and the embedding's rows (the head is read
    whole)."""
    routed = sparse_layers(config) * config["num_experts"] * expert_params(config)
    embed = config["hidden_size"] * config["vocab_size"]
    return ((parameters(config) - routed - embed)
            * config["serving"]["weight_bytes_per_param"])


def expected_experts_hit(config: dict, rows: float) -> float:
    """Experts of one layer given at least one of ``rows`` x top-k
    assignments by an even router: ``E (1 - (1 - 1/E)^(rows k))``."""
    e = config["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (rows * config["num_experts_per_tok"]))


def visible_pairs(prior: int, new: int, window: int = 0) -> int:
    """(query, key) pairs of ``new`` queries at positions ``prior ..
    prior + new - 1`` under the causal mask: query ``i`` sees keys ``0 ..
    i``, or, with a window, the last ``window`` of them."""
    total = 0
    for i in range(prior, prior + new):
        total += min(i + 1, window) if window else i + 1
    return total


def chunk_attn_flops_needed(config: dict, prior: int, new: int) -> int:
    """FLOPs of a request's prefill attention over ``new`` tokens behind
    ``prior`` cached ones, every layer: 4 x head_dim (scores and values) a
    visible (query, key) pair and query head."""
    d, w = config["head_dim"], config["sliding_window"]
    full = _count(config, FULL) * heads_of(config, FULL) * visible_pairs(prior, new)
    slide = _count(config, SLIDING) * heads_of(config, SLIDING) * visible_pairs(
        prior, new, w)
    return 4 * d * (full + slide)

"""Operations and bytes a step NEEDS of a configuration with latent
attention (MLA), a leading dense layer and a shared expert beside the
routed ones, from its widths (``harness/opsbytes.py`` is K/V-per-head and
dense-only; its ``kv_bytes_per_token`` is wrong for such a configuration).
Needed, not executed: the cache row counts at its ``kv_lora_rank +
qk_rope_head_dim`` elements (the device stores and reads it padded to whole
128-lane tiles: waste), a row is read once for scores and values alike.
Waste can only understate a share computed from these, never push it past
100%.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level and the ``serving`` group.
"""
from __future__ import annotations


def row_elements(config: dict) -> int:
    """Elements of the one cache row a token leaves in a layer: the
    normalised latent and the rotated rotary key."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def row_bytes(config: dict) -> int:
    """Bytes of one token's cache row in ONE layer."""
    return row_elements(config) * config["serving"]["kv_bytes_per_element"]


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of one token's cache rows over all layers."""
    return config["num_hidden_layers"] * row_bytes(config)


def decode_attn_flops_per_token_layer(config: dict) -> int:
    """FLOPs of absorbed decode attention per cached token and layer:
    every head scores the whole row and mixes its latent part
    (2 x heads x (row + kv_lora_rank))."""
    return 2 * config["num_attention_heads"] * (
        row_elements(config) + config["kv_lora_rank"])


def attention_params(config: dict) -> int:
    """Matmul parameters of one layer's attention: q_a, q_b, kv_a, kv_b, o."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (h * config["q_lora_rank"]
            + config["q_lora_rank"] * heads * qk
            + h * row_elements(config)
            + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"]
                                                + config["v_head_dim"])
            + heads * config["v_head_dim"] * h)


def param_counts(config: dict) -> dict:
    """Matmul parameters (norms and the router's bias are noise at these
    widths and left out): a dense layer, a sparse layer, the embedding and
    the head, and the whole configuration as it is run."""
    h = config["hidden_size"]
    expert = 3 * h * config["moe_intermediate_size"]
    attn = attention_params(config)
    dense = attn + 3 * h * config["intermediate_size"]
    sparse = (attn + h * config["n_routed_experts"]
              + (config["n_routed_experts"] + config["n_shared_experts"]) * expert)
    n_dense = config["first_k_dense_replace"]
    n_sparse = config["num_hidden_layers"] - n_dense
    embed = h * config["vocab_size"]
    return {
        "attention": attn, "expert": expert, "dense_layer": dense,
        "sparse_layer": sparse, "embed": embed, "lm_head": embed,
        "total": n_dense * dense + n_sparse * sparse + 2 * embed,
    }


def weight_bytes(config: dict) -> int:
    """Bytes of the weights the chip holds."""
    return param_counts(config)["total"] * config["serving"]["weight_bytes_per_param"]

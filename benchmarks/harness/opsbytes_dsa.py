"""Operations and bytes a decode step NEEDS of a configuration with learned
sparse attention (an indexer, top-k selection inside paged latent
attention) and an expert share, from its widths. Needed, not executed: an
index key counts at its ``index_head_dim`` elements, a selected row at its
``kv_lora_rank + qk_rope_head_dim`` elements (it is stored and gathered
padded to whole 128-lane tiles: waste), each read once; the selection
itself moves scores the program made, not cache bytes. Waste can only
understate a share computed from these, never push it past 100%.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level and the ``serving`` group. ``dsa_trace`` finds the
two XLA pieces of the sparse path (the selection's named ops, the gather of
the selected rows) in a capture's op line by the shapes these widths give.
"""
from __future__ import annotations

from benchmarks.harness.opsbytes_mla import row_bytes, row_elements


def index_key_bytes(config: dict) -> int:
    """Bytes of ONE token's index key in ONE full layer."""
    return config["index_head_dim"] * config["serving"]["index_key_bytes_per_element"]


def full_layers(config: dict) -> int:
    """Layers that hold an indexer (score and select)."""
    return sum(t == "full" for t in config["indexer_types"])


def index_flops_per_key(config: dict) -> int:
    """FLOPs of scoring one cached token for one query in one full layer:
    every index head's dot product, then relu and the weighted sum."""
    return 2 * config["index_n_heads"] * config["index_head_dim"] + 3 * config[
        "index_n_heads"]


def selected_row_bytes(config: dict) -> int:
    """Bytes of one selected cache row in one layer (needed elements)."""
    return row_bytes(config)


def sparse_attn_flops_per_row(config: dict) -> int:
    """FLOPs of absorbed attention per SELECTED row and layer: every head
    scores the whole row and mixes its latent part."""
    return 2 * config["num_attention_heads"] * (
        row_elements(config) + config["kv_lora_rank"])


def cache_bytes_per_token(config: dict) -> int:
    """Bytes of one token in the two pool arrays, needed elements: a row a
    layer and an index key a full layer."""
    return (config["num_hidden_layers"] * row_bytes(config)
            + full_layers(config) * index_key_bytes(config))


def stored_row_width(config: dict) -> int:
    """Elements a cache row is stored in: whole 128-lane tiles."""
    return -(-row_elements(config) // 128) * 128


def dense_rows_bytes(config: dict, context: int) -> int:
    """What dense latent attention would have to read for one query at
    ``context`` tokens, all layers — what the selection replaces."""
    return config["num_hidden_layers"] * context * row_bytes(config)


def sparse_step_bytes(config: dict, context: int) -> int:
    """What the sparse path has to read for one query at ``context``
    tokens: every index key in the full layers, ``min(context, index_topk)``
    rows in every layer."""
    rows = min(context, config["index_topk"])
    return (full_layers(config) * context * index_key_bytes(config)
            + config["num_hidden_layers"] * rows * row_bytes(config))


def held_expert_bytes(config: dict) -> int:
    """Expert weights this chip holds, all sparse layers: ``n_routed_experts``
    here is the share (the router's width is ``serving.router_experts``)."""
    sparse = sum(t == "sparse" for t in config["mlp_layer_types"])
    return (sparse * config["n_routed_experts"] * 3 * config["hidden_size"]
            * config["moe_intermediate_size"]
            * config["serving"]["weight_bytes_per_param"])


def expert_share(config: dict) -> int:
    """Over how many chips each layer's experts are divided (1: every
    expert is held here, or there are none)."""
    return int(config.get("serving", {}).get("ep_size", 1))

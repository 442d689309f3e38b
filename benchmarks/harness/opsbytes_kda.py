"""Operations and bytes a step NEEDS of a configuration with KDA
linear-attention layers beside latent attention and an expert share
(``layer_types``: "kda" | "full_attention") — from its widths. Needed, not
executed: a row's state is read once and written once a layer and step, a
latent cache row counts at its ``kv_lora_rank + qk_rope_head_dim``
elements (``opsbytes_mla.row_bytes``), an expert is streamed once however
many rows chose it, a boundary copy is no part of a decode step's need.
Waste (an idle batch row's scratch slot, a row padded to a tile) can only
understate a share computed from these, never push it past 100%.

The prefill's operations are those of the CHUNKED form of the delta rule
at a block of 64 positions (``serving.chunk_block``), fixed there whatever
the kernel on the chip does (``ops/kda.py``: it walks the positions on the
vector unit, so its share of the MXU's peak says how far that walk is from
what the chunked form would cost).

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level, ``layer_types`` and the ``serving`` group.
"""
from __future__ import annotations


def _count(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def kda_channels(config: dict) -> int:
    """Channels of q, of k and of v of a KDA layer."""
    s = config["serving"]
    return s["kda_heads"] * s["kda_head_dim"]


def layer_state_bytes(config: dict) -> int:
    """What ONE KDA layer carries for one sequence: a ``[d, d]`` matrix a
    head and the ``taps - 1`` inputs of the three convolutions."""
    s = config["serving"]
    matrix = kda_channels(config) * s["kda_head_dim"] * s["state_bytes_per_element"]
    conv = ((config["short_conv_kernel_size"] - 1) * 3 * kda_channels(config)
            * s["conv_state_bytes_per_element"])
    return matrix + conv


def state_bytes(config: dict) -> int:
    """One snapshot: every KDA layer's state."""
    return _count(config, "kda") * layer_state_bytes(config)


def latent_bytes_per_token(config: dict) -> int:
    """What a token leaves in the cache: one row in every latent layer, as
    STORED (whole 128-lane tiles)."""
    width = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"]) // 128) * 128
    return (_count(config, "full_attention") * width
            * config["serving"]["kv_bytes_per_element"])


def kda_params(config: dict) -> int:
    """One KDA operator: W_q, W_k, W_v, W_alpha, W_g [H, C] and W_o [C, H],
    W_beta [H, heads], the depth-wise kernel [taps, 3C], dt_bias [C], A_log
    [heads] and the output norm's weight [C]."""
    h, c = config["hidden_size"], kda_channels(config)
    heads = config["serving"]["kda_heads"]
    return (6 * h * c + h * heads + config["short_conv_kernel_size"] * 3 * c
            + c + heads + c)


def latent_params(config: dict) -> int:
    """One latent operator without a query bottleneck: W_q, W_kva, W_kvb,
    W_o and the head-wise gate W_g [H, heads]."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (h * heads * qk
            + h * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"]
                                                + config["v_head_dim"])
            + heads * config["v_head_dim"] * h + h * heads)


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def sparse_ffn_params(config: dict) -> int:
    """A sparse layer's FFN as HELD: the router over all experts, the held
    experts, the shared expert."""
    return (config["hidden_size"] * config["serving"]["router_experts"]
            + config["num_experts"] * expert_params(config)
            + 3 * config["hidden_size"] * config["moe_shared_expert_intermediate_size"])


def parameters(config: dict) -> int:
    """Matmul-scale parameters of the configuration as it is run (the
    layers' two RMSNorm weights and the router's bias left out)."""
    types, kd = config["layer_types"], config["first_k_dense_replace"]
    total = 2 * config["hidden_size"] * config["vocab_size"]
    for l, kind in enumerate(types):
        total += kda_params(config) if kind == "kda" else latent_params(config)
        total += (3 * config["hidden_size"] * config["intermediate_size"]
                  if l < kd else sparse_ffn_params(config))
    return total


def kda_decode_kernel_bytes(config: dict, rows: float) -> float:
    """What ONE launch of ``radixmesh_kda_decode`` (one layer of one step)
    has to move: each of ``rows`` live rows' matrix read and written back,
    and the row's 64 KiB of per-channel inputs (a, k, a b k, q columns;
    b v in, o out)."""
    s = config["serving"]
    c, d = kda_channels(config), s["kda_head_dim"]
    matrix = c * d * s["state_bytes_per_element"]
    return rows * (2 * matrix + (4 * c + 2 * c) * 4)


def kda_decode_bytes_per_step(config: dict, rows: float) -> float:
    """What the KDA side of ONE decode step has to move: every KDA
    operator's weights once, and each live row's state (matrix and
    convolution inputs) read and written back in every KDA layer."""
    w = config["serving"]["weight_bytes_per_param"]
    return _count(config, "kda") * (
        kda_params(config) * w + 2 * rows * layer_state_bytes(config))


def kda_chunk_flops_per_token(config: dict) -> float:
    """FLOPs one position of ONE KDA layer needs in the chunked form at a
    block of ``C`` = ``serving.chunk_block`` positions, all heads: a head
    and block computes ``K K^T`` and ``Q K^T`` under the decay (2 x 2 C^2
    d), solves the unit-triangular system for ``U`` (C^2 d), applies ``Q``
    to it (2 C^2 d), and meets the carried state three times (``K S_0``,
    ``Q S_0``, the state's update: 3 x 2 C d^2) — a position's share is
    that over ``C``. The projections and the convolution are XLA's and not
    the kernel's."""
    s = config["serving"]
    c, d = s["chunk_block"], s["kda_head_dim"]
    per_head = 2 * 2 * c * d + c * d + 2 * c * d + 3 * 2 * d * d
    return float(s["kda_heads"] * per_head)

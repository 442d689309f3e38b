"""Operations and bytes a step NEEDS of a decoder-hybrid-decoder with
selective state-space layers (``layer_types``: "mamba" |
"sliding_attention" | "full_attention" | "gmu" | "cross_attention") —
from its widths. Needed, not executed: a cache row counts at its head
width (two heads of 64 fill a 128-lane row: no padding to leave out), the
full layer's rows once for every layer that READS them, a window layer's
only inside its window, a state at its published size, each read once.
Waste can only understate a share computed from these, never push it past
100%.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level, ``layer_types``, ``assumed_sizes`` (the Mamba
sizes the source has no key for) and the ``serving`` group.
"""
from __future__ import annotations


def _count(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def head_dim(config: dict) -> int:
    return config["assumed_sizes"]["head_dim"]


def layer_kv_bytes(config: dict) -> int:
    """K and V of one token in ONE attention layer, all kv heads."""
    return (2 * config["num_key_value_heads"] * head_dim(config)
            * config["serving"]["kv_bytes_per_element"])


def full_kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the full layer's group (kept for every
    token): the one full-attention layer's K and V."""
    return _count(config, "full_attention") * layer_kv_bytes(config)


def window_kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the window group (kept while it is among a
    row's last ``sliding_window`` tokens): every sliding layer's K and V."""
    return _count(config, "sliding_attention") * layer_kv_bytes(config)


def kv_readers(config: dict) -> int:
    """Layers that attend the full layer's rows: itself and every
    cross-attention layer."""
    return _count(config, "full_attention") + _count(config, "cross_attention")


def shared_kv_bytes_needed(config: dict, context: int) -> int:
    """Bytes one decoded token's attention over the SHARED rows has to
    read: ``context`` tokens of the full layer's K/V, once a reading
    layer."""
    return context * layer_kv_bytes(config) * kv_readers(config)


def window_kv_bytes_needed(config: dict, context: int) -> int:
    """... over the window group: ``min(context, sliding_window)`` tokens
    in every sliding layer."""
    return min(context, config["sliding_window"]) * window_kv_bytes_per_token(config)


def state_bytes(config: dict) -> int:
    """One snapshot: every Mamba layer's ``d_state x d_inner`` recurrent
    state and the ``d_conv - 1`` inputs of its convolution."""
    a, s = config["assumed_sizes"], config["serving"]
    per = a["d_inner"] * (a["d_state"] * s["state_bytes_per_element"]
                          + (a["d_conv"] - 1) * s["conv_state_bytes_per_element"])
    return _count(config, "mamba") * per


def mamba_params(config: dict) -> int:
    """One Mamba operator: in_x and in_z [H, di] each, the depthwise kernel and its
    bias, x_proj [di, R + 2N], dt_proj [R, di] and its bias, A_log [di, N],
    D, out_proj [di, H]."""
    h, a = config["hidden_size"], config["assumed_sizes"]
    di, n, r, k = a["d_inner"], a["d_state"], a["dt_rank"], a["d_conv"]
    return (h * 2 * di + di * k + di + di * (r + 2 * n) + r * di + di
            + di * n + di + di * h)


def attention_params(config: dict) -> int:
    """One self-attention operator: Wqkv and the output projection."""
    h, d = config["hidden_size"], head_dim(config)
    q, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    return h * (q + 2 * kv) + q * h


def cross_params(config: dict) -> int:
    h = config["hidden_size"]
    return 2 * h * config["num_attention_heads"] * head_dim(config)


def gmu_params(config: dict) -> int:
    return 2 * config["hidden_size"] * config["assumed_sizes"]["d_inner"]


def mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def parameters(config: dict) -> int:
    """Matmul-scale parameters of the whole model (norms, biases and the
    lambda vectors left out), the tied embedding once."""
    n_attn = _count(config, "sliding_attention") + _count(config, "full_attention")
    return (
        _count(config, "mamba") * mamba_params(config)
        + n_attn * attention_params(config)
        + _count(config, "cross_attention") * cross_params(config)
        + _count(config, "gmu") * gmu_params(config)
        + config["num_hidden_layers"] * mlp_params(config)
        + config["hidden_size"] * config["vocab_size"]
    )


def ssm_decode_bytes_per_step(config: dict, rows: float) -> float:
    """What the state-space side of ONE decode step has to move: every
    Mamba and GMU operator's weights once, and each of ``rows`` live rows'
    state read and written back."""
    w = config["serving"]["weight_bytes_per_param"]
    return ((_count(config, "mamba") * mamba_params(config)
             + _count(config, "gmu") * gmu_params(config)) * w
            + 2 * rows * state_bytes(config))


def ssm_scan_bytes_per_token(config: dict) -> int:
    """What ``radixmesh_ssm_scan`` has to move for one position of one
    Mamba layer: x and dt in, y out (float32, ``d_inner`` wide) and the two
    ``d_state`` columns B and C. The states at a chunk's ends are a few
    hundred KB a row and left out."""
    a = config["assumed_sizes"]
    return 4 * (3 * a["d_inner"] + 2 * a["d_state"])

"""Operations and bytes a step NEEDS of a configuration whose Mamba-1
layers are interleaved with plain multi-query attention layers
(``layer_types``: "mamba" | "full_attention") — from its widths. Needed,
not executed: a cache row counts at its head width, a state at its
published size, each read once. Waste can only understate a share computed
from these, never push it past 100%.

The state-space side's functions are ``opsbytes_phi4flash``'s, which read
the same keys (``layer_types``, ``assumed_sizes``, the ``serving`` group)
and count no GMU where ``layer_types`` names none: ``state_bytes``,
``mamba_params``, ``ssm_decode_bytes_per_step``,
``ssm_scan_bytes_per_token`` (``benchmarks/tests/test_jamba2_cell.py`` holds
them to this configuration's numbers). Here: what the attention layers and
the whole model need.
"""
from __future__ import annotations

from benchmarks.harness.opsbytes_phi4flash import _count, head_dim, mamba_params


def kv_bytes_per_token(config: dict) -> int:
    """K and V of one token over the layers that keep any: the
    full_attention layers' kv heads of ``head_dim``."""
    return (2 * _count(config, "full_attention") * config["num_key_value_heads"]
            * head_dim(config) * config["serving"]["kv_bytes_per_element"])


def attn_decode_bytes_per_step(config: dict, context_tokens: float) -> float:
    """What the attention layers of ONE decode step have to read: every
    live row's context (``context_tokens``: summed over the rows, this
    token included), K and V, once a layer that holds them."""
    return context_tokens * kv_bytes_per_token(config)


def attention_params(config: dict) -> int:
    """One attention operator: W_q, W_k, W_v and the output projection."""
    h, d = config["hidden_size"], head_dim(config)
    q, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    return h * (q + 2 * kv) + q * h


def mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def parameters(config: dict) -> int:
    """Matmul-scale parameters of the whole model (norms left out), the
    tied embedding once."""
    return (_count(config, "mamba") * mamba_params(config)
            + _count(config, "full_attention") * attention_params(config)
            + config["num_hidden_layers"] * mlp_params(config)
            + config["hidden_size"] * config["vocab_size"])


def weight_bytes_per_step(config: dict) -> int:
    """Bytes of weights one decode step streams: every matrix once, the
    tied embedding as the head."""
    return parameters(config) * config["serving"]["weight_bytes_per_param"]

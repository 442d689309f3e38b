"""Operations and bytes a configuration's step NEEDS, from its widths.

All inputs are keys of the configuration file: the public ``config.json``
names at its top level, plus its ``serving`` group (weight and KV bytes
per element, tensor-parallel degree). Needed, not executed: padding,
recomputation and copies do not count, so a share computed from these can
only be understated by waste, never pushed past 100% by it.
"""
from __future__ import annotations


def param_counts(m: dict) -> dict:
    """Matmul parameters per layer and of the LM head (norms and biases
    are noise at these widths and left out)."""
    h = m["hidden_size"]
    d = m.get("head_dim") or h // m["num_attention_heads"]
    q = m["num_attention_heads"] * d
    kv = m["num_key_value_heads"] * d
    attn = h * q + 2 * h * kv + q * h
    mlp = 3 * h * m["intermediate_size"]
    return {
        "per_layer": attn + mlp,
        "layers": m["num_hidden_layers"] * (attn + mlp),
        "lm_head": h * m["vocab_size"],
        "embed": h * m["vocab_size"],
    }


def weight_bytes_per_step(config: dict) -> float:
    """Bytes of weights one decode step must stream, per chip: every layer
    matrix and the LM head once (the embedding is a row lookup), at the
    stored width, divided over the tensor-parallel chips."""
    p = param_counts(config)
    s = config["serving"]
    return (p["layers"] + p["lm_head"]) * s["weight_bytes_per_param"] / s["tp"]


def kv_bytes_per_token(config: dict) -> float:
    """K and V of one token over all layers and kv heads, whole model
    (divide by tp for one chip's share)."""
    m = config
    d = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"] * d
            * config["serving"]["kv_bytes_per_element"])


def prefill_flops_per_token(config: dict, context: int = 0) -> float:
    """Forward FLOPs one prompt token needs: 2 per layer-matmul parameter
    (only a prompt's LAST position needs the LM head, so it is left out),
    plus causal attention against ``context`` earlier tokens (QK^T and PV:
    4 * heads * head_dim per context token per layer)."""
    m = config
    p = param_counts(m)
    d = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    attn = 4 * m["num_hidden_layers"] * m["num_attention_heads"] * d * context
    return 2.0 * p["layers"] + attn

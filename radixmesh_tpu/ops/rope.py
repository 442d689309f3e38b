"""Rotary position embeddings (RoPE) with Llama-3 frequency scaling.

Position-dependent but cache-friendly: K is stored in the paged KV pool
*already rotated* (rotation depends only on the token's absolute position,
which is immutable for a cached prefix — this is what makes radix prefix
reuse sound for RoPE models).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    theta: float = 500000.0,
    llama3_scaling: dict | tuple | None = None,
) -> jnp.ndarray:
    """Inverse frequencies [head_dim // 2], optionally with the Llama-3.x
    long-context NTK-by-parts rescale (factor/low_freq/high_freq/original
    context length). ``llama3_scaling`` may be a dict or a tuple of
    ``(key, value)`` pairs (the hashable form ModelConfig stores so it can
    be a jit-static argument)."""
    inv = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if isinstance(llama3_scaling, tuple):
        llama3_scaling = dict(llama3_scaling)
    if llama3_scaling:
        factor = llama3_scaling.get("factor", 8.0)
        low = llama3_scaling.get("low_freq_factor", 1.0)
        high = llama3_scaling.get("high_freq_factor", 4.0)
        orig = llama3_scaling.get("original_max_position_embeddings", 8192)
        wavelen = 2.0 * jnp.pi / inv
        low_bound = orig / low
        high_bound = orig / high
        smooth = (orig / wavelen - low) / (high - low)
        scaled = jnp.where(
            wavelen > low_bound,
            inv / factor,
            jnp.where(
                wavelen < high_bound,
                inv,
                (1.0 - smooth) * inv / factor + smooth * inv,
            ),
        )
        inv = scaled
    return inv


def yarn_frequencies(
    head_dim: int,
    theta: float,
    factor: float,
    original_max_position_embeddings: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    truncate: bool = True,
) -> np.ndarray:
    """YaRN inverse frequencies [head_dim // 2], float32, term for term
    what transformers' ``modeling_rope_utils._compute_yarn_parameters``
    computes: frequencies that turn more than ``beta_fast`` times over
    the original context keep their value, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, a linear ramp over the
    dimension index joins the two. Static: nothing depends on the served
    length. The ``attention_factor`` that multiplies cos/sin is the
    config's own number and is applied by ``apply_rope(scale=)``."""

    def correction_dim(rotations):
        return (
            head_dim
            * math.log(original_max_position_embeddings / (rotations * 2 * math.pi))
        ) / (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001  # transformers' guard against the singular ramp
    pos_freqs = np.float32(theta) ** (
        np.arange(0, head_dim, 2, dtype=np.float32) / np.float32(head_dim)
    )
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float32) - np.float32(low))
        / np.float32(high - low),
        0.0, 1.0,
    ).astype(np.float32)
    keep = np.float32(1.0) - ramp  # 1 where the frequency extrapolates
    inv = (1.0 / (np.float32(factor) * pos_freqs)) * (1.0 - keep) + (
        1.0 / pos_freqs
    ) * keep
    return inv.astype(np.float32)


@partial(jax.jit, static_argnames=())
def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Rotate ``x`` ([..., seq, heads, head_dim]) by absolute ``positions``
    ([..., seq]). Uses the interleaved-half convention (rotate_half), fp32
    internally. ``scale`` (a scalar) multiplies cos and sin: YaRN's
    ``attention_factor``."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, dim/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)

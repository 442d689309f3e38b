"""RMSNorm and LayerNorm.

The reference has no model/ops layer (SURVEY §1: "no model/ops layer");
this is part of the serving stack the north star requires
(BASELINE.json "north_star"). Computed in float32 regardless of input dtype
— bf16 accumulation visibly degrades perplexity — and left un-fused: XLA
fuses the normalize-scale chain into neighbouring ops better than a
hand-written kernel would here.
"""

from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5,
               bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """LayerNorm beside :func:`rms_norm` (same signature, plus the bias of
    a model that has one): mean and variance over the last axis in
    float32, ``(x - mean) / sqrt(var + eps) * weight + bias``."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jnp.reciprocal(jnp.sqrt(var + eps)) * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)

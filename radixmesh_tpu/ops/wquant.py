"""Weight-only int8 quantization (W8A16) for serving.

Decode is weight-streaming-bound: every step reads all dense weights from
HBM once, so storing them int8 halves the dominant traffic — and halves
resident weight bytes, which is what puts an 8B-class model (~16 GB bf16)
onto a single 16 GB v5e chip at all. The reference has no analogue (no
model executor at all); this extends the TPU-first serving stack the same
way int8 KV extends the pool. ``models.llama.init_params(weight_quant=
"int8")`` draws random weights directly in this form, layer by layer.

Scheme: symmetric per-OUTPUT-channel scales over each weight's
contraction axis — ``scale[o] = amax_i |w[i, o]| / 127`` — applied AFTER
the matmul (``y = (x @ w_int8.astype(bf16)) * s``), which is exact for
per-out-channel scaling and keeps the MXU operands plain bf16: compute
precision is unchanged, only storage/streaming shrinks. Embeddings
quantize per ROW (the vocab axis), which serves both the gather
(``embed[tok] * s[tok]``) and the tied LM head (``x @ embed.T * s``)
with one scale vector.

Layout contract: quantized leaves keep their NAME and shape (dtype
int8); each gains a sibling ``<name>_s`` float32 scale leaf in the same
pytree level. Every consumer (scan over layers, tp sharding, pp stage
slicing, checkpointing) therefore flows unchanged — the scale slices
ride the same leading axes as their weight.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "quantize_weight",
    "quantize_params",
    "LAYER_QUANT_WEIGHTS",
]

# The per-layer dense weights worth quantizing ([L, in, out] layout; the
# tiny norm vectors and biases stay bf16).
LAYER_QUANT_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

_EPS = 1e-8


def quantize_weight(w: jnp.ndarray, axis: int):
    """Symmetric int8 quantization of ``w`` along contraction ``axis``.

    Returns ``(q int8 like w, scale f32 like w minus axis)`` with
    ``w ≈ q * scale`` broadcast over ``axis``.
    """
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis)
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(
        jnp.round(wf / jnp.expand_dims(scale, axis)), -127, 127
    ).astype(jnp.int8)
    return q, scale


def quantize_params(params: dict) -> dict:
    """Return a new param pytree with the dense weights int8-quantized and
    ``<name>_s`` scale leaves added (see module docstring). Idempotent:
    already-int8 leaves pass through."""
    out = {k: v for k, v in params.items()}
    layers = dict(params["layers"])
    for name in LAYER_QUANT_WEIGHTS:
        w = layers.get(name)
        if w is None or w.dtype == jnp.int8:
            continue
        # [L, in, out]: contraction is the middle axis.
        q, s = quantize_weight(w, axis=1)
        layers[name] = q
        layers[name + "_s"] = s
    out["layers"] = layers
    if params["embed"].dtype != jnp.int8:
        # [V, H], per-row scales (serves gather AND the tied LM head).
        q, s = quantize_weight(params["embed"], axis=1)
        out["embed"] = q
        out["embed_s"] = s
    if "lm_head" in params and params["lm_head"].dtype != jnp.int8:
        # [H, V], contraction over H.
        q, s = quantize_weight(params["lm_head"], axis=0)
        out["lm_head"] = q
        out["lm_head_s"] = s
    return out

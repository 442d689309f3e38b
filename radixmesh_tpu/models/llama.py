"""Llama-3-family transformer, pure functional JAX (no flax/nnx).

The reference is cache-only — "There is no model, no attention kernel, no
scheduler" (SURVEY, verified over all 37 files) — so this module implements
the serving stack's model side that the north star requires
(``BASELINE.json``: Llama-3-8B on v5e, Qwen2-72B 32k on v5p). Design:

- **Params are a flat pytree** with per-layer tensors stacked on a leading
  layer axis, consumed by ``lax.scan`` — one traced layer body instead of
  ``n_layers`` copies, which keeps XLA compile time flat in depth and makes
  layer-sharded (pp) layouts a reshape away.
- **Two entry points**: ``prefill_forward`` (new tokens attend to an
  optional cached prefix — the radix-cache reuse path) and ``decode_step``
  (one token per sequence; writes K/V into the paged pool *inside* the scan
  and attends via the Pallas paged kernel on TPU). Everything under one
  ``jit`` per call; the KV pool array is donated so decode updates HBM in
  place.
- **Sharding-ready**: ``param_logical_axes`` names every axis logically
  ("embed", "q_heads", "kv_heads", "ffn", "vocab"); ``parallel/sharding.py``
  maps logical names to mesh axes (tp/dp/...) so the same model code runs
  single-chip or pjit-sharded.
- Qwen2 is the same architecture with QKV biases and its own dims
  (``models/qwen2.py``).
- **Layers of different kinds, one scanned body** (``models/mellum2.py``):
  a config with ``layer_types`` scans a per-layer table beside the weights
  — rotary inverse frequencies, the cos/sin factor, the attention window
  (0 = full) — so window and full layers, each with its own RoPE, share
  the traced body. A config with ``n_experts`` takes the sparse expert
  layer (``ops/moe.py``) where the others take ``_mlp``; that choice is
  static. Such a config's entry points return one more value, ``aux``:
  the expert ids every token chose (a scan output; fetched only when a
  request asks) and the ``moe`` counters, accumulated on the device.
  What does not serve such a config is refused by name
  (``unsupported_reason``), never served by a dense fallback.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from radixmesh_tpu.ops.attention import (
    attend_prefill,
    paged_chunk_attention,
    paged_decode_attention,
)
from radixmesh_tpu.ops.moe import STATS as MOE_STATS, moe_ffn
from radixmesh_tpu.ops.norm import rms_norm
from radixmesh_tpu.ops.rope import apply_rope, rope_frequencies, yarn_frequencies
from radixmesh_tpu.ops.wquant import quantize_weight

__all__ = [
    "ModelConfig",
    "init_params",
    "prefill_forward",
    "prefill_forward_sp",
    "prefill_chunk_paged",
    "decode_step",
    "param_logical_axes",
    "convert_hf_state_dict",
    "UnsupportedForModel",
    "unsupported_reason",
    "refuse_unsupported",
    "MOE_SITES",
    "new_moe_stats",
    "read_moe_stats",
]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 128256
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 14336
    rope_theta: float = 500000.0
    # Tuple of (key, value) pairs, not a dict: ModelConfig is a jit-static
    # argument and must hash.
    rope_scaling: tuple | None = None
    rms_eps: float = 1e-5
    qkv_bias: bool = False  # True for Qwen2
    tie_embeddings: bool = False
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # Layers of different kinds. ``layer_types`` names each layer
    # ("sliding_attention" | "full_attention"; None = every layer full,
    # one RoPE from ``rope_theta``/``rope_scaling``). A sliding layer's
    # query sees ``sliding_window`` keys, its own among them
    # (transformers' convention). ``rope_by_type`` holds the rotary
    # parameters of each layer type as ``((type, ((key, value), ...)),
    # ...)`` — tuples, because the config is a jit-static argument.
    layer_types: tuple | None = None
    sliding_window: int = 0
    rope_by_type: tuple | None = None
    # Sparse expert layer in every layer when ``n_experts`` > 0
    # (``intermediate`` is then used by no layer).
    n_experts: int = 0
    n_experts_per_tok: int = 0
    moe_intermediate: int = 0
    norm_topk_prob: bool = True

    def __post_init__(self):
        if self.layer_types is not None:
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.n_layers or not kinds <= {
                "sliding_attention", "full_attention"
            }:
                raise ValueError(
                    f"layer_types must name {self.n_layers} layers as "
                    f"sliding_attention or full_attention, got "
                    f"{len(self.layer_types)} of {sorted(kinds)}"
                )
            if "sliding_attention" in kinds and self.sliding_window < 2:
                raise ValueError("sliding layers need sliding_window >= 2")
            missing = kinds - {t for t, _ in self.rope_by_type or ()}
            if missing:
                raise ValueError(f"rope_by_type lacks {sorted(missing)}")
        if self.n_experts and not (
            0 < self.n_experts_per_tok <= self.n_experts
            and self.moe_intermediate > 0
        ):
            raise ValueError(
                "n_experts needs 0 < n_experts_per_tok <= n_experts and "
                "moe_intermediate > 0"
            )

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def has_window(self) -> bool:
        return bool(self.layer_types) and "sliding_attention" in self.layer_types

    @classmethod
    def llama3_8b(cls) -> "ModelConfig":
        """Meta-Llama-3-8B: NO rope scaling (the HF config's
        rope_scaling is null at this generation, same as the 70B;
        scaling arrives with 3.1) and the 8k window."""
        return cls(rope_scaling=None)

    @classmethod
    def llama3_70b(cls) -> "ModelConfig":
        """Meta-Llama-3-70B: NO rope scaling (the HF config's
        rope_scaling is null at this generation; scaling arrives with
        3.1) and the 8k window."""
        return cls(
            hidden=8192,
            n_layers=80,
            n_heads=64,
            n_kv_heads=8,
            head_dim=128,
            intermediate=28672,
            rope_scaling=None,
        )

    @classmethod
    def llama31_8b(cls) -> "ModelConfig":
        """Llama-3.1-8B: the 3.0-8B dims plus the 3.1 llama3-style rope
        scaling + 128k window the base 3.0-8B preset deliberately lacks
        (mirrors the 70B/3.1-70B split); serving length stays
        pool-bounded."""
        return cls.llama3_8b().replace(
            rope_scaling=(
                ("factor", 8.0),
                ("low_freq_factor", 1.0),
                ("high_freq_factor", 4.0),
                ("original_max_position_embeddings", 8192),
            ),
            max_seq_len=131072,
        )

    @classmethod
    def llama31_70b(cls) -> "ModelConfig":
        """Llama-3.1-70B: the 70B dims plus the 3.1 rope scaling + 128k
        window the base 3.0-70B preset deliberately lacks."""
        return cls.llama3_70b().replace(
            rope_scaling=(
                ("factor", 8.0),
                ("low_freq_factor", 1.0),
                ("high_freq_factor", 4.0),
                ("original_max_position_embeddings", 8192),
            ),
            max_seq_len=131072,
        )

    @classmethod
    def llama32_1b(cls) -> "ModelConfig":
        return cls(
            vocab_size=128256,
            hidden=2048,
            n_layers=16,
            n_heads=32,
            n_kv_heads=8,
            head_dim=64,
            intermediate=8192,
            tie_embeddings=True,
            rope_scaling=(
                ("factor", 32.0),
                ("low_freq_factor", 1.0),
                ("high_freq_factor", 4.0),
                ("original_max_position_embeddings", 8192),
            ),
            max_seq_len=131072,
        )

    @classmethod
    def llama32_3b(cls) -> "ModelConfig":
        return cls.llama32_1b().replace(
            hidden=3072, n_layers=28, n_heads=24, n_kv_heads=8,
            head_dim=128, intermediate=8192,
        )

    @classmethod
    def tiny(cls) -> "ModelConfig":
        """Test/bench config: same architecture, toy dims."""
        return cls(
            vocab_size=512,
            hidden=128,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            head_dim=32,
            intermediate=256,
            max_seq_len=512,
        )


def _draw_blocks(key, n: int, shape: tuple, fan_in: int, dtype, quant_axis):
    """``[n, *shape]`` weights (``normal / sqrt(fan_in)``) drawn ONE block
    at a time under ``lax.map``, so the only float32 slab alive is one
    block's — never the stacked tensor (Qwen2-7B's stacked ``w_gate`` is
    7.6 GB in f32; one layer of it is 272 MB). Returns ``(weights,
    scales)``: ``dtype`` weights and ``None``, or — with ``quant_axis``,
    the block's contraction axis — each block quantized to W8A16 as it is
    drawn, int8 weights and their f32 scales."""
    scale = 1.0 / np.sqrt(fan_in)

    def block(k):
        w = jax.random.normal(k, shape, dtype=jnp.float32) * scale
        if quant_axis is None:
            return w.astype(dtype)
        return quantize_weight(w, axis=quant_axis)

    out = jax.lax.map(block, jax.random.split(key, n))
    return (out, None) if quant_axis is None else out


def _vocab_blocks(vocab: int, hidden: int, limit: int) -> int:
    """Smallest block count dividing ``vocab`` whose ``[vocab/n, hidden]``
    block stays within ``limit`` elements (the largest per-layer slab), so
    the embedding and LM head never out-size a layer while being drawn."""
    n = 1
    while vocab % n or (vocab // n) * hidden > limit:
        n += 1
    return n


@partial(jax.jit, static_argnames=("cfg", "weight_quant", "mesh"))
def _init_params(key, cfg: ModelConfig, weight_quant, mesh) -> dict:
    # Contraction axis of a block to quantize along, or None for float.
    def axis(a):
        return a if weight_quant is not None else None

    keys = jax.random.split(key, 9)
    L, H, V = cfg.n_layers, cfg.hidden, cfg.vocab_size
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ffn_width = cfg.moe_intermediate if cfg.n_experts else cfg.intermediate
    ffn = {"w_gate": (H, ffn_width), "w_up": (H, ffn_width),
           "w_down": (ffn_width, H)}
    dims = {"wq": (H, qd), "wk": (H, kvd), "wv": (H, kvd), "wo": (qd, H)}
    dims |= {"w_router": (H, cfg.n_experts)} if cfg.n_experts else ffn
    layers = {
        "attn_norm": jnp.ones((L, H), dtype=cfg.dtype),
        "mlp_norm": jnp.ones((L, H), dtype=cfg.dtype),
    }
    for k, (name, (i_dim, o_dim)) in zip(keys, dims.items()):
        # [L, in, out]: contraction is each layer block's axis 0.
        layers[name], s = _draw_blocks(
            k, L, (i_dim, o_dim), i_dim, cfg.dtype, axis(0)
        )
        if s is not None:
            layers[name + "_s"] = s
    if cfg.n_experts:
        # [L, E, in, out], one expert's block at a time.
        E = cfg.n_experts
        for k, (name, (i_dim, o_dim)) in zip(
            jax.random.split(keys[6], 3), ffn.items()
        ):
            w, _ = _draw_blocks(k, L * E, (i_dim, o_dim), i_dim, cfg.dtype, None)
            layers[name] = w.reshape(L, E, i_dim, o_dim)
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, qd), dtype=cfg.dtype)
        layers["bk"] = jnp.zeros((L, kvd), dtype=cfg.dtype)
        layers["bv"] = jnp.zeros((L, kvd), dtype=cfg.dtype)
    params = {"final_norm": jnp.ones((H,), dtype=cfg.dtype), "layers": layers}
    n_blk = _vocab_blocks(V, H, H * max(ffn_width, qd))
    # [V, H] in row blocks; per-ROW scales serve the gather and the tied
    # LM head alike (ops/wquant.py).
    w, s = _draw_blocks(
        keys[7], n_blk, (V // n_blk, H), H, cfg.dtype, axis(1)
    )
    params["embed"] = w.reshape(V, H)
    if s is not None:
        params["embed_s"] = s.reshape(V)
    if not cfg.tie_embeddings:
        # [H, V] in column blocks (contraction over H, each block's axis
        # 0); the blocks interleave into [H, V] with one narrow-dtype copy.
        w, s = _draw_blocks(
            keys[8], n_blk, (H, V // n_blk), H, cfg.dtype, axis(0)
        )
        params["lm_head"] = w.transpose(1, 0, 2).reshape(H, V)
        if s is not None:
            params["lm_head_s"] = s.reshape(V)
    if mesh is not None:
        from radixmesh_tpu.parallel.sharding import param_sharding

        params = jax.lax.with_sharding_constraint(
            params, param_sharding(param_logical_axes(cfg, params), mesh)
        )
    return params


def init_params(
    cfg: ModelConfig,
    key: jax.Array,
    weight_quant: str | None = None,
    mesh=None,
) -> dict:
    """Random-init a model ON DEVICE, one layer at a time: no step of the
    program holds more than one layer's float32 slab, so a full-width
    model initializes in about the memory it will serve from.

    ``weight_quant="int8"`` draws the weights DIRECTLY in W8A16 form
    (``ops/wquant.py`` layout: int8 leaves + ``<name>_s`` scales) — the
    float pytree of an 8B-class model never exists, which is what lets
    ``serve --weight-quant int8`` start on one 16 GB chip. ``mesh`` births
    every leaf already tp-sharded (``parallel/sharding.py`` rules), so a
    model bigger than one chip is never gathered on device 0."""
    if weight_quant not in (None, "int8"):
        raise ValueError(f"unknown weight quantization {weight_quant!r}")
    refuse_unsupported(cfg, weight_quant=weight_quant, device_mesh=mesh)
    return _init_params(key, cfg, weight_quant, mesh)


def param_logical_axes(cfg: ModelConfig, params: dict | None = None) -> dict:
    """Logical axis names per parameter, mapped to mesh axes by
    ``parallel/sharding.py`` (tp shards "q_heads"/"kv_heads"/"ffn"/"vocab",
    everything else replicates). Pass ``params`` to also cover the
    ``<name>_s`` scale leaves of W8A16-quantized weights (``ops/wquant.py``
    — each scale shards like its weight's OUTPUT axis)."""
    axes = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        "layers": {
            "attn_norm": ("layer", "embed"),
            "mlp_norm": ("layer", "embed"),
            "wq": ("layer", "embed", "q_heads"),
            "wk": ("layer", "embed", "kv_heads"),
            "wv": ("layer", "embed", "kv_heads"),
            "wo": ("layer", "q_heads", "embed"),
            "w_gate": ("layer", "embed", "ffn"),
            "w_up": ("layer", "embed", "ffn"),
            "w_down": ("layer", "ffn", "embed"),
        },
    }
    if cfg.n_experts:
        # No mesh axis is mapped to "expert" (parallel/sharding.py): every
        # chip would hold every expert. Engine refuses a mesh for such a
        # config; an expert-parallel share is ROADMAP's.
        axes["layers"] |= {
            "w_router": ("layer", "embed", "expert"),
            "w_gate": ("layer", "expert", "embed", "ffn"),
            "w_up": ("layer", "expert", "embed", "ffn"),
            "w_down": ("layer", "expert", "ffn", "embed"),
        }
    if cfg.qkv_bias:
        axes["layers"]["bq"] = ("layer", "q_heads")
        axes["layers"]["bk"] = ("layer", "kv_heads")
        axes["layers"]["bv"] = ("layer", "kv_heads")
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if params is not None:
        # Each W8A16 scale shards like its weight's OUTPUT (last) axis —
        # derived from the weight's own entry so a layout change can't
        # drift the two apart.
        for name in list(axes["layers"]):
            if name + "_s" in params.get("layers", {}):
                axes["layers"][name + "_s"] = ("layer", axes["layers"][name][-1])
        if "embed_s" in params:
            axes["embed_s"] = ("vocab",)
        if "lm_head_s" in params:
            axes["lm_head_s"] = ("vocab",)
    return axes


# fp32 inputs on TPU are otherwise demoted to one-pass bf16 multiplies;
# HIGHEST makes fp32 honest and is a no-op for bf16 operands.
_PREC = jax.lax.Precision.HIGHEST


def _wmm(lp: dict, name: str, eq: str, x: jnp.ndarray, reshape=None,
         **einsum_kw):
    """Dense matmul honoring W8A16 storage (``ops/wquant.py``): int8
    weights feed the MXU as bf16 (only HBM *streaming* shrinks — compute
    precision is unchanged) and the per-out-channel scale applies to the
    output, which is exact for per-out-channel quantization."""
    w = lp[name]
    if w.dtype == jnp.int8:
        wm = w.astype(x.dtype)
        if reshape is not None:
            wm = wm.reshape(reshape)
        y = jnp.einsum(eq, x, wm, precision=_PREC, **einsum_kw)
        y = y * lp[name + "_s"]
        # The f32 scale would otherwise promote the whole activation
        # stream to f32 from the first quantized layer on — cast back
        # unless the caller asked for a widened output (the logits head).
        if "preferred_element_type" not in einsum_kw:
            y = y.astype(x.dtype)
        return y
    if reshape is not None:
        w = w.reshape(reshape)
    return jnp.einsum(eq, x, w, precision=_PREC, **einsum_kw)


def _embed_lookup(params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    """Embedding gather honoring W8A16 storage: int8 rows dequantize by
    their per-row scale right after the (int8-narrow) gather."""
    e = params["embed"]
    if e.dtype == jnp.int8:
        x = e[tokens].astype(jnp.float32) * params["embed_s"][tokens][..., None]
        return x.astype(params["final_norm"].dtype)
    return e[tokens]


def _qkv(lp: dict, x: jnp.ndarray, cfg: ModelConfig):
    """x: [B, S, H] → q [B,S,Hq,D], k/v [B,S,Hkv,D]."""
    q = _wmm(lp, "wq", "bsh,hd->bsd", x)
    k = _wmm(lp, "wk", "bsh,hd->bsd", x)
    v = _wmm(lp, "wv", "bsh,hd->bsd", x)
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    B, S = x.shape[:2]
    return (
        q.reshape(B, S, cfg.n_heads, cfg.head_dim),
        k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
        v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
    )


def _mlp(lp: dict, x: jnp.ndarray) -> jnp.ndarray:
    gate = jax.nn.silu(_wmm(lp, "w_gate", "bsh,hi->bsi", x))
    up = _wmm(lp, "w_up", "bsh,hi->bsi", x)
    return _wmm(lp, "w_down", "bsi,ih->bsh", gate * up)


class UnsupportedForModel(ValueError):
    """A serving option that this model's layers cannot run under."""


def unsupported_reason(cfg: ModelConfig, **options) -> str | None:
    """Why ``options`` cannot serve ``cfg``, or None. A config with
    experts or window layers runs on one chip in bf16 through
    ``prefill_forward`` / ``prefill_chunk_paged`` / ``decode_step`` and
    nothing else; every other launch path assumes the dense layer and is
    refused here by name, so that none of them serves such a model with a
    dense layer's arithmetic. ``options`` are ``Engine``'s keyword names
    (a falsy value is "not asked for")."""
    if not (cfg.n_experts or cfg.has_window):
        return None
    mesh = options.get("device_mesh")
    named = {
        "weight_quant": "int8 weights (--weight-quant int8)",
        "kv_quant": "an int8 KV pool (--kv-quant int8)",
        "spec_decode_tokens": "speculative decode (--spec-decode-tokens)",
        "prefill_inline_budget": "inline prefill waves (--prefill-inline-budget)",
        "host_cache_slots": "the host KV tier (--host-cache-slots)",
        "kv_tier_dir": "the disk KV tier (--kv-tier-dir)",
        "disaggregated": "disaggregated prefill/decode (engine/disagg.py)",
        "hf_checkpoint": "loading a HuggingFace checkpoint (--weights)",
    }
    asked = [text for key, text in named.items() if options.get(key)]
    if mesh is not None and any(n > 1 for n in dict(mesh.shape).values()):
        asked.append(f"a device mesh {dict(mesh.shape)} (tp / sp / pp)")
    if not asked:
        return None
    kinds = " and ".join(
        k for k, on in (("sparse experts", cfg.n_experts),
                        ("window attention", cfg.has_window)) if on
    )
    return (
        f"a model with {kinds} does not support " + "; ".join(asked)
        + ": it serves on one chip, bf16 weights and KV (ROADMAP D6 / R4)"
    )


def refuse_unsupported(cfg: ModelConfig, **options) -> None:
    reason = unsupported_reason(cfg, **options)
    if reason is not None:
        raise UnsupportedForModel(reason)


def _layer_tables(cfg: ModelConfig) -> dict | None:
    """Per-layer inputs scanned beside the weights of a model whose
    layers differ in kind: ``inv_freq`` [L, D/2] and ``rope_scale`` [L]
    (each layer type's RoPE: default, or YaRN with its attention factor on
    cos/sin) and ``window`` [L] (keys a query sees; 0 = all). None for a
    model of one layer kind, whose body closes over one ``inv_freq``."""
    if cfg.layer_types is None:
        return None
    by_type = {}
    for kind, pairs in cfg.rope_by_type:
        rp = dict(pairs)
        theta = float(rp.get("rope_theta", cfg.rope_theta))
        if rp.get("rope_type", "default") == "yarn":
            inv = yarn_frequencies(
                cfg.head_dim, theta, float(rp["factor"]),
                int(rp["original_max_position_embeddings"]),
                float(rp.get("beta_fast", 32.0)),
                float(rp.get("beta_slow", 1.0)),
                bool(rp.get("truncate", True)),
            )
            scale = float(rp["attention_factor"])
        elif rp.get("rope_type", "default") == "default":
            inv = rope_frequencies(cfg.head_dim, theta)
            scale = 1.0
        else:
            raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
        by_type[kind] = (inv, scale)
    return {
        "inv_freq": jnp.stack(
            [jnp.asarray(by_type[t][0]) for t in cfg.layer_types]
        ),
        "rope_scale": jnp.asarray(
            [by_type[t][1] for t in cfg.layer_types], jnp.float32
        ),
        "window": jnp.asarray(
            [cfg.sliding_window if t == "sliding_attention" else 0
             for t in cfg.layer_types], jnp.int32,
        ),
    }


def _rope_of(inv_freq, table: dict | None):
    """``(inv_freq, cos/sin factor, window)`` of one layer: its row of
    ``_layer_tables``, or the model's one RoPE (``inv_freq``, computed
    once outside the scan) and no window."""
    if table is None:
        return inv_freq, None, None
    return table["inv_freq"], table["rope_scale"], table["window"]


# ``moe`` counters live on the device as uint32 [site, counter, (lo, hi)]:
# 64 bits a counter (a day of serving overflows 32), no int64 on the chip.
MOE_SITES = ("decode", "prefill")


def new_moe_stats() -> jnp.ndarray:
    return jnp.zeros((len(MOE_SITES), len(MOE_STATS), 2), jnp.uint32)


def read_moe_stats(stats) -> dict:
    """The device counters as ``{site: {counter: int}}`` (a host read:
    waits for the step that wrote them)."""
    a = np.asarray(stats).astype(np.uint64)
    return {
        site: {
            name: int(a[i, j, 0] + (a[i, j, 1] << np.uint64(32)))
            for j, name in enumerate(MOE_STATS)
        }
        for i, site in enumerate(MOE_SITES)
    }


def _bump_moe_stats(stats, site: str, delta: jnp.ndarray) -> jnp.ndarray:
    """``stats[site] += delta`` ([n_counters] int32, non-negative) with
    the carry into the high word."""
    stats = new_moe_stats() if stats is None else stats
    i = MOE_SITES.index(site)
    lo = stats[i, :, 0] + delta.astype(jnp.uint32)
    hi = stats[i, :, 1] + (lo < stats[i, :, 0]).astype(jnp.uint32)
    return stats.at[i].set(jnp.stack([lo, hi], axis=-1))


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _scanned_layers(cfg: ModelConfig, layers: dict) -> dict:
    """What the layer scan slices per layer: every stacked leaf but the
    expert weights, which the expert kernel indexes itself (``_ffn``)."""
    if not cfg.n_experts:
        return layers
    return {k: v for k, v in layers.items() if k not in _EXPERT_WEIGHTS}


def _ffn(params: dict, lp: dict, l_idx, x: jnp.ndarray, cfg: ModelConfig,
         site: str, valid: jnp.ndarray | None = None, **kernel_kw):
    """Layer ``l_idx``'s feed-forward on ``x`` [B, S, H]: ``_mlp``, or the
    sparse expert layer for a config with experts (a static choice; it
    takes the STACKED expert weights and the layer's index, because a
    scan's per-layer slice of them handed to a kernel is a copy).
    Returns ``(y, aux)``; ``aux`` is None for the dense layer, else
    ``(expert ids [B, S, k], this launch's counter increments [4])``,
    the counters over the tokens ``valid`` [B, S] marks."""
    if not cfg.n_experts:
        return _mlp(lp, x), None
    B, S, H = x.shape
    stacked = params["layers"]
    y, ids, stats = moe_ffn(
        x.reshape(B * S, H), lp["w_router"], stacked["w_gate"],
        stacked["w_up"], stacked["w_down"], layer=l_idx,
        k=cfg.n_experts_per_tok, norm_topk=cfg.norm_topk_prob,
        site=site, valid=None if valid is None else valid.reshape(B * S),
        **kernel_kw,
    )
    return y.reshape(B, S, H), (ids.reshape(B, S, -1), stats)


def _moe_aux(cfg: ModelConfig, per_layer, moe_stats, site: str) -> tuple:
    """The trailing ``aux`` of an entry point: ``()`` for a dense model,
    else one dict — the scan's stacked expert ids [L, B, S, k] and the
    counters with this launch added."""
    if not cfg.n_experts:
        return ()
    ids, stats = per_layer
    return ({
        "expert_ids": ids,
        "moe_stats": _bump_moe_stats(moe_stats, site, jnp.sum(stats, axis=0)),
    },)


def _attn_out(lp: dict, attn: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The wo projection shared by every forward variant: attn is
    [B, S, Hq·D] (or already [B, S, Hq, D])."""
    B, S = attn.shape[:2]
    return _wmm(
        lp, "wo", "bsqd,qdh->bsh",
        attn.reshape(B, S, cfg.n_heads, cfg.head_dim),
        reshape=(cfg.n_heads, cfg.head_dim, cfg.hidden),
    )


def _logits(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        name, eq = "embed", "bsh,vh->bsv"
    else:
        name, eq = "lm_head", "bsh,hv->bsv"
    return _wmm(
        params, name, eq, x, preferred_element_type=jnp.float32
    )


@partial(jax.jit, static_argnames=("cfg",))
def prefill_forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S_new]
    positions: jnp.ndarray,  # [B, S_new] absolute positions
    cached_k: jnp.ndarray,  # [L, B, P_max, Hkv, D] rotated prefix K, RIGHT-aligned
    cached_v: jnp.ndarray,  # [L, B, P_max, Hkv, D]
    prefix_lengths: jnp.ndarray,  # [B] valid cached-prefix tokens (≤ P_max)
    new_lengths: jnp.ndarray | None = None,  # [B] real new tokens (counters)
    moe_stats: jnp.ndarray | None = None,  # the device's moe counters
):
    """Prefill new tokens against an optional cached prefix.

    Ragged prefixes are **right-aligned** in the ``P_max`` prefix region
    (row ``b`` occupies ``[P_max - prefix_lengths[b], P_max)``); the front
    padding is masked via ``kv_start``, so batched prefill with different
    hit lengths is exact. Pass ``P_max = 0`` arrays for no cache.

    Returns ``(logits [B,S,V], new_k [L,B,S,Hkv,D], new_v [...])`` — the
    caller writes new_k/new_v into the paged pool at the slots the radix
    tree allocated, which is how a served prompt becomes a reusable cached
    prefix (the contract the reference's commented-out scheduler hooks
    sketch, ``radix_cache.py:439-519``). A config with experts returns
    ``aux`` as a fourth value (module docstring); ``new_lengths`` keeps
    the bucket's padding out of its counters.
    """
    one_rope = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    x = _embed_lookup(params, tokens)
    p_max = cached_k.shape[2]
    s_new = tokens.shape[1]
    pad = p_max - prefix_lengths  # [B] front padding per row
    # Index-space position of query t (abs position p) inside the context
    # buffer [pad | prefix | new]: p + pad.
    attn_pos = positions + pad[:, None]
    kv_end = jnp.full_like(prefix_lengths, p_max + s_new)
    valid = None
    if new_lengths is not None:
        valid = jnp.arange(s_new)[None, :] < new_lengths[:, None]

    def layer(x, xs):
        l_idx, lp, ck, cv, table = xs
        inv_freq, rope_scale, window = _rope_of(one_rope, table)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(lp, h, cfg)
        q = apply_rope(q, positions, inv_freq, rope_scale)
        k = apply_rope(k, positions, inv_freq, rope_scale)
        k_ctx = jnp.concatenate([ck, k], axis=1)  # [B, P_max + S, Hkv, D]
        v_ctx = jnp.concatenate([cv, v], axis=1)
        attn = attend_prefill(
            q, k_ctx, v_ctx, attn_pos, kv_end, kv_start=pad, window=window
        )
        x = x + _attn_out(lp, attn, cfg)
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        y, aux = _ffn(params, lp, l_idx, h2, cfg, "prefill", valid)
        return x + y, (k, v, aux)

    x, (new_k, new_v, aux) = jax.lax.scan(
        layer, x,
        (jnp.arange(cfg.n_layers), _scanned_layers(cfg, params["layers"]),
         cached_k, cached_v, _layer_tables(cfg)),
    )
    return (_logits(params, cfg, x), new_k, new_v,
            *_moe_aux(cfg, aux, moe_stats, "prefill"))


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def prefill_forward_sp(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] — S divisible by the sp axis size
    positions: jnp.ndarray,  # [B, S]
    mesh,
    axis: str = "sp",
    logits_at: jnp.ndarray | None = None,  # [B] per-row positions, or None
):
    """Sequence-parallel prefill: activations sharded over the ``sp`` mesh
    axis along S, attention via ring attention (K/V blocks rotate over ICI
    with ``ppermute`` while each chip keeps its query shard — SURVEY §5's
    long-context requirement, serving-side). Everything outside attention
    partitions via GSPMD from the sharding constraint alone.

    Scaling regime: sp multiplies prefill FLOPs/HBM across chips (TTFT for
    long prompts); the CHUNKED path (``prefill_chunk_paged``) bounds
    memory on one chip. The engine composes them: sp-prefill the fresh
    span when a mesh with sp>1 is present, chunk otherwise.

    Returns ``(logits, new_k [L, B, S, Hkv, D], new_v)`` — sequence-
    sharded; callers scatter into the paged pool (GSPMD inserts the
    collectives). Logits are [B, S, V] — unless ``logits_at`` gives one
    position per row, in which case only those rows hit the LM head and
    logits are [B, 1, V]: a 32k-prompt serve must not materialize an
    S×vocab tensor it samples one row of.
    """
    from radixmesh_tpu.parallel.ring_attention import ring_self_attention

    refuse_unsupported(cfg, device_mesh=mesh)
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    from jax.sharding import NamedSharding, PartitionSpec

    seq_sharded = NamedSharding(mesh, PartitionSpec(None, axis))
    tokens = jax.lax.with_sharding_constraint(tokens, seq_sharded)
    x = _embed_lookup(params, tokens)

    def layer(x, xs):
        lp = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(lp, h, cfg)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        attn = ring_self_attention(q, k, v, mesh, axis=axis)
        x = x + _attn_out(lp, attn, cfg)
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(lp, h2)
        return x, (k, v)

    x, (new_k, new_v) = jax.lax.scan(layer, x, params["layers"])
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return _logits(params, cfg, x), new_k, new_v


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "page_size", "kv_block_pages", "mesh", "use_kernel",
        "interpret",
    ),
    donate_argnums=(4,),
    donate_argnames=("kv_scale",),
)
def prefill_chunk_paged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, C] one chunk of the prompt (tail-padded)
    positions: jnp.ndarray,  # [B, C] absolute positions
    kv_pool: jnp.ndarray,  # [2, L, Hkv, num_slots, D] (donated)
    slots: jnp.ndarray,  # [B, C] pool slot per chunk token (pad → scratch)
    page_table: jnp.ndarray,  # [B, max_pages] request pages, in order
    kv_lengths: jnp.ndarray,  # [B] context tokens valid after this chunk
    page_size: int = 16,
    kv_block_pages: int = 32,
    kv_scale: jnp.ndarray | None = None,  # [2, L, Hkv, num_slots] int8 pool
    mesh=None,
    use_kernel: bool | None = None,
    interpret: bool = False,
    logits_at: jnp.ndarray | None = None,  # [B] per-row chunk offsets, or None
    moe_stats: jnp.ndarray | None = None,  # the device's moe counters
):
    """One CHUNK of long-context prefill against the paged pool (SURVEY §5:
    the 32k Qwen2 gate must never materialize O(S²) scores — VERDICT
    round-1 gap #4). Prior context (cached prefix + earlier chunks)
    streams blockwise out of the pool pages READ-ONLY; the chunk's own
    K/V rides dense through the layer scan and is scattered into the pool
    ONCE after the scan. Keeping the pool out of the scan carry matters:
    a per-layer scatter + page read of the carry made XLA materialize a
    full pool copy every layer (the same bug the fused decode kernel
    fixes on its path). Peak memory is O(C · kv_block), independent of
    prompt length; the host loops chunks, so compile cost is one variant
    per (B, C, max_pages) bucket triple.

    Returns ``(logits [B, C, V], kv_pool)`` — plus the updated
    ``kv_scale`` when the pool is int8-quantized. ``logits_at`` gives one
    chunk offset per row: only those positions hit the LM head and logits
    are ``[B, 1, V]`` (``prefill_forward_sp``'s contract) — a prefill wave
    samples one position per row, and the default 8 × 512 wave's
    all-position f32 logits at a 152k vocabulary are 2.3 GiB. A config
    with experts returns ``aux`` as one more trailing value (module
    docstring); its counters leave out the chunk's padding.
    """
    refuse_unsupported(cfg, kv_quant=kv_scale is not None, device_mesh=mesh)
    one_rope = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    x = _embed_lookup(params, tokens)  # [B, C, H]
    num_slots = kv_pool.shape[3]
    pages_shape = (
        2, cfg.n_layers, cfg.n_kv_heads,
        num_slots // page_size, page_size, cfg.head_dim,
    )
    kv_pages = kv_pool.reshape(pages_shape)
    scale_pages = (
        None
        if kv_scale is None
        else kv_scale.reshape(
            2, cfg.n_layers, cfg.n_kv_heads, num_slots // page_size, page_size
        )
    )
    # Tokens in the pool BEFORE this chunk: chunk start per row. (Padded
    # rows may carry clamped positions; their outputs are discarded and
    # the masking below stays finite either way.)
    prior_lengths = jnp.minimum(positions[:, 0], kv_lengths)
    valid = positions < kv_lengths[:, None]  # real tokens, for the counters
    moe_kw = {} if use_kernel is None else {
        "use_kernel": use_kernel, "interpret": interpret,
    }

    def layer(x, xs):
        l_idx, lp, table = xs
        inv_freq, rope_scale, window = _rope_of(one_rope, table)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(lp, h, cfg)  # [B,C,*,D]
        q = apply_rope(q, positions, inv_freq, rope_scale)
        k = apply_rope(k, positions, inv_freq, rope_scale)
        if kv_scale is not None:
            # Quantize NOW and attend the dequantized copy, so the chunk
            # sees exactly what any later pool read will see (the fused
            # decode kernel keeps the same invariant) — otherwise logits
            # drift between a speculative verify pass and plain decode.
            from radixmesh_tpu.ops.quant import quantize_for_store

            k_int, v_int, k_sc, v_sc, k, v = quantize_for_store(k, v)
        attn = paged_chunk_attention(
            q,
            k,
            v,
            kv_pages,
            page_table,
            positions,
            prior_lengths,
            kv_lengths,
            l_idx,
            kv_block_pages=kv_block_pages,
            kv_scales=scale_pages,
            use_kernel=use_kernel,
            mesh=mesh,
            interpret=interpret,
            window=window,
        )
        x = x + _attn_out(lp, attn, cfg)
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        y, aux = _ffn(params, lp, l_idx, h2, cfg, "prefill", valid, **moe_kw)
        x = x + y
        if kv_scale is not None:
            return x, (k_int, v_int, k_sc, v_sc)
        return x, (k.astype(kv_pool.dtype), v.astype(kv_pool.dtype), aux)

    def pick(x):
        if logits_at is None:
            return x
        return jnp.take_along_axis(x, logits_at[:, None, None], axis=1)

    xs = (jnp.arange(cfg.n_layers), _scanned_layers(cfg, params["layers"]),
          _layer_tables(cfg))
    if kv_scale is not None:
        x, (new_k, new_v, new_ks, new_vs) = jax.lax.scan(layer, x, xs)
        # Already quantized in-layer (same ints attention saw); scatter the
        # int8 payloads + scales: scan stacks [L, B, C, Hkv(, D)] → the
        # pool target [:, :, :, slots[B, C]] expects [2, L, Hkv, B, C(, D)].
        new_kv = jnp.stack([new_k, new_v]).transpose(0, 1, 4, 2, 3, 5)
        new_s = jnp.stack([new_ks, new_vs]).transpose(0, 1, 4, 2, 3)
        kv_pool = kv_pool.at[:, :, :, slots].set(new_kv)
        kv_scale = kv_scale.at[:, :, :, slots].set(new_s)
        return _logits(params, cfg, pick(x)), kv_pool, kv_scale
    x, (new_k, new_v, aux) = jax.lax.scan(layer, x, xs)
    # One scatter for the whole chunk across all layers: scan stacks
    # [L, B, C, Hkv, D]; the pool indexed at [:, :, :, slots[B,C]] expects
    # [2, L, Hkv, B, C, D].
    new_kv = jnp.stack([new_k, new_v]).transpose(0, 1, 4, 2, 3, 5)
    kv_pool = kv_pool.at[:, :, :, slots].set(new_kv)
    return (_logits(params, cfg, pick(x)), kv_pool,
            *_moe_aux(cfg, aux, moe_stats, "prefill"))


@partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "mesh"),
    donate_argnums=(3,),
    donate_argnames=("kv_scale",),
)
def decode_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] current token per sequence
    kv_pool: jnp.ndarray,  # [2, L, Hkv, num_slots, D] (donated)
    slots: jnp.ndarray,  # [B] pool slot for this token's KV
    page_table: jnp.ndarray,  # [B, max_pages]
    lengths: jnp.ndarray,  # [B] context length incl. this token
    page_size: int = 16,
    mesh=None,
    kv_scale: jnp.ndarray | None = None,  # [2, L, Hkv, num_slots] int8 pool
    moe_stats: jnp.ndarray | None = None,  # the device's moe counters
):
    """One decode step for a continuous batch: writes this token's K/V into
    the paged pool inside the layer scan, attends over the radix-cache
    pages (Pallas kernel on TPU), returns ``(logits [B,V], kv_pool)``.

    ``page_size`` is a property of the pool/page-table pairing (static so
    the pages view is a pure reshape). ``mesh`` (static) enables the
    tensor-parallel kernel path: heads/pool sharded over the mesh's tp
    axis, the Pallas kernel shard_map'd per chip; all other ops partition
    via GSPMD from the params/pool shardings. A config with experts
    returns ``aux`` as a third value (module docstring); a row whose
    ``lengths`` is 1 is an idle batch row and stays out of its counters."""
    return _decode_core(
        params, cfg, tokens, kv_pool, slots, page_table, lengths, page_size,
        mesh, kv_scale, moe_stats,
    )


def _decode_core(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    kv_pool: jnp.ndarray,
    slots: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    page_size: int,
    mesh,
    kv_scale: jnp.ndarray | None = None,
    moe_stats: jnp.ndarray | None = None,
):
    refuse_unsupported(cfg, kv_quant=kv_scale is not None, device_mesh=mesh)
    one_rope = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    positions = lengths - 1  # [B]
    live = (lengths > 1)[:, None]  # idle rows ride with lengths == 1
    x = _embed_lookup(params, tokens)[:, None, :]  # [B, 1, H]
    B = tokens.shape[0]
    num_slots = kv_pool.shape[3]
    pages_shape = (
        2, cfg.n_layers, cfg.n_kv_heads,
        num_slots // page_size, page_size, cfg.head_dim,
    )

    scales_shape = (
        2, cfg.n_layers, cfg.n_kv_heads, num_slots // page_size, page_size,
    )

    def layer(carry, xs):
        x, kv_pool, kv_scale = carry
        l_idx, lp, table = xs
        inv_freq, rope_scale, window = _rope_of(one_rope, table)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(lp, h, cfg)  # [B,1,*,D]
        q = apply_rope(q, positions[:, None], inv_freq, rope_scale)
        k = apply_rope(k, positions[:, None], inv_freq, rope_scale)
        # Fused write+attend: the Pallas kernel writes this token's K/V row
        # into the (aliased) pool and attends over this layer's pages — the
        # pool buffer flows through the scan with zero copies. (A separate
        # XLA scatter + kernel read used to force a full pool copy per
        # layer: ~4 GB of HBM traffic per step at bench shapes.) For
        # quantized pools the raw row goes in (the kernel quantizes) and
        # the scale pool rides the carry the same zero-copy way.
        if kv_scale is not None:
            attn, kv_pages, scale_pages = paged_decode_attention(
                q[:, 0],
                k[:, 0],
                v[:, 0],
                kv_pool.reshape(pages_shape),
                slots,
                page_table,
                lengths,
                l_idx,
                mesh=mesh,
                kv_scales=kv_scale.reshape(scales_shape),
            )
            kv_scale = scale_pages.reshape(
                2, cfg.n_layers, cfg.n_kv_heads, num_slots
            )
        else:
            attn, kv_pages = paged_decode_attention(
                q[:, 0],
                k[:, 0].astype(kv_pool.dtype),
                v[:, 0].astype(kv_pool.dtype),
                kv_pool.reshape(pages_shape),
                slots,
                page_table,
                lengths,
                l_idx,
                mesh=mesh,
                window=window,
            )
        kv_pool = kv_pages.reshape(2, cfg.n_layers, cfg.n_kv_heads, num_slots,
                                   cfg.head_dim)
        x = x + _wmm(
            lp, "wo", "bqd,qdh->bh",
            attn.reshape(B, cfg.n_heads, cfg.head_dim),
            reshape=(cfg.n_heads, cfg.head_dim, cfg.hidden),
        )[:, None, :]
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        y, aux = _ffn(params, lp, l_idx, h2, cfg, "decode", live)
        return (x + y, kv_pool, kv_scale), aux

    (x, kv_pool, kv_scale), aux = jax.lax.scan(
        layer, (x, kv_pool, kv_scale),
        (jnp.arange(cfg.n_layers), _scanned_layers(cfg, params["layers"]),
         _layer_tables(cfg)),
    )
    logits = _logits(params, cfg, x)[:, 0]
    if kv_scale is not None:
        return logits, kv_pool, kv_scale
    return logits, kv_pool, *_moe_aux(cfg, aux, moe_stats, "decode")


# ---------------------------------------------------------------------------
# HF checkpoint conversion
# ---------------------------------------------------------------------------


def convert_hf_state_dict(cfg: ModelConfig, state: dict) -> dict:
    """Map a HuggingFace Llama/Qwen2 state dict (numpy arrays, HF names)
    into this module's stacked-layer param pytree.

    Accepts ``model.layers.{i}.self_attn.q_proj.weight`` etc. (HF stores
    ``[out, in]``; we store ``[in, out]`` so every projection is applied as
    ``x @ W``).
    """
    refuse_unsupported(cfg, hf_checkpoint=True)
    L = cfg.n_layers

    def get(name):
        return np.asarray(state[name])

    def proj(name_fmt):
        return jnp.stack(
            [
                jnp.asarray(get(name_fmt.format(i)).T, dtype=cfg.dtype)
                for i in range(L)
            ]
        )

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype=cfg.dtype),
        "final_norm": jnp.asarray(get("model.norm.weight"), dtype=cfg.dtype),
        "layers": {
            "attn_norm": jnp.stack(
                [
                    jnp.asarray(
                        get(f"model.layers.{i}.input_layernorm.weight"),
                        dtype=cfg.dtype,
                    )
                    for i in range(L)
                ]
            ),
            "mlp_norm": jnp.stack(
                [
                    jnp.asarray(
                        get(f"model.layers.{i}.post_attention_layernorm.weight"),
                        dtype=cfg.dtype,
                    )
                    for i in range(L)
                ]
            ),
            "wq": proj("model.layers.{}.self_attn.q_proj.weight"),
            "wk": proj("model.layers.{}.self_attn.k_proj.weight"),
            "wv": proj("model.layers.{}.self_attn.v_proj.weight"),
            "wo": proj("model.layers.{}.self_attn.o_proj.weight"),
            "w_gate": proj("model.layers.{}.mlp.gate_proj.weight"),
            "w_up": proj("model.layers.{}.mlp.up_proj.weight"),
            "w_down": proj("model.layers.{}.mlp.down_proj.weight"),
        },
    }
    if cfg.qkv_bias:
        for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj")):
            params["layers"][ours] = jnp.stack(
                [
                    jnp.asarray(
                        get(f"model.layers.{i}.self_attn.{theirs}.bias"),
                        dtype=cfg.dtype,
                    )
                    for i in range(L)
                ]
            )
    if cfg.tie_embeddings:
        pass
    elif "lm_head.weight" in state:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype=cfg.dtype)
    else:
        params["lm_head"] = params["embed"].T
    return params

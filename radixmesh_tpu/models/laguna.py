"""Laguna-XS.2 (poolside, ``model_type: "laguna"``, "33B-A3B"): 40 layers
in periods of (full, sliding, sliding, sliding) attention over 8 kv heads
of 128 — a FULL layer has 48 query heads, rotates HALF of each head (YaRN,
theta 500 000, factor 64 over 4 096, beta_fast 64, cos/sin times
1.4158883083359672), a SLIDING layer has 64 query heads, a window of 512
keys (the query's own among them) and rotates the whole head (default,
theta 10 000); every head's attention output passes a sigmoid gate
computed from the layer's normed input. Layer 0's FFN is a dense SwiGLU of
8 192; layers 1-39 route over 256 experts of width 512 (sigmoid scores, a
selection bias, the 8 best renormalised and times 2.5) beside one shared
expert of 512. RMSNorm eps 1e-6, no bias, an untied head over 100 352.
Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json

What a token leaves behind (``models/llama.py``): K and V of 8 heads of
128 in every FULL layer, for every token (``kv_pool_shape``: 4 096 B a
layer); the same in every SLIDING layer, wanted for the last 512 tokens of
a row only — a group of rows with counted pages of its own
(``window_pool_shape``), whose pages a live row gives back as they leave
its window. No layer carries a state: what the radix tree keeps for a
resume is the window TAIL alone (counts on the 32 pages before the cut).
The two kinds' ``wq`` / ``wo`` / ``wg`` differ in shape, so each kind's
attention matrices are a stack of its own (``params["full_attention"]``,
``params["sliding_attention"]``) and the layers run by their static kind
through ``_scan_mixed``; the layer body is ``models/llama.py``'s.

Assumed, because the config file cannot settle it:

- the gate (``gating: true``) is HEAD-WISE, a sigmoid, computed from the
  layer's normed input: without gate weights the published keys give
  33.44 B parameters, the card's "33.4B"; a head-wise ``W_g`` [2048, heads]
  adds 4.9 M (33.44 B), an element-wise one [2048, heads * 128] 629 M
  (34.07 B) — so the gate has no projection of the output's width;
- the router is DeepSeek-V3's (sigmoid scores, a per-expert selection
  bias, the chosen scores normalised, times ``moe_routed_scaling_factor``
  2.5; no expert groups): the config has the factor and neither
  ``scoring_func`` nor ``norm_topk_prob`` nor ``n_group``, and 2.5 on 8
  normalised weights is that family's pairing;
- no q/k normalisation and no multi-token-prediction head: the config
  names neither.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3

_ROPE_BY_TYPE = (
    ("full_attention", (
        ("rope_type", "yarn"), ("rope_theta", 500000.0), ("factor", 64.0),
        ("original_max_position_embeddings", 4096), ("beta_fast", 64.0),
        ("beta_slow", 1.0), ("attention_factor", 1.4158883083359672),
        ("partial_rotary_factor", 0.5),
    )),
    ("sliding_attention", (("rope_type", "default"), ("rope_theta", 10000.0))),
)


def laguna_xs2(n_layers: int = 40) -> ModelConfig:
    """The published model; ``n_layers`` keeps the first so many layers
    (the dense layer 0 and what follows it, in layer order)."""
    return ModelConfig(
        vocab_size=100352,
        hidden=2048,
        n_layers=n_layers,
        n_heads=64,
        n_kv_heads=8,
        head_dim=128,
        intermediate=8192,
        rope_theta=500000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        # The config declares 262144; served: 16384. A launch's page table
        # is scalar-prefetched whole by the paged kernels, and 64 rows x
        # 1024 pages are what the chip's scalar memory takes (PERF.md §7).
        # YaRN is static: the tables are the source's at any served length.
        max_seq_len=16384,
        layer_types=(_PERIOD * -(-n_layers // len(_PERIOD)))[:n_layers],
        sliding_window=512,
        rope_by_type=_ROPE_BY_TYPE,
        heads_by_type=(("full_attention", 48), ("sliding_attention", 64)),
        attn_gate=True,
        n_experts=256,
        n_experts_per_tok=8,
        moe_intermediate=512,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        n_shared_experts=1,
        first_k_dense=1,
    )


def laguna_xs2_l5() -> ModelConfig:
    """The benchmark's one-chip cut: the dense layer and the one whole
    period after it (full, sliding x 3, full), every width as published."""
    return laguna_xs2(5)


def laguna_tiny() -> ModelConfig:
    """One dense layer and one period at toy widths (CPU tests,
    ``--rehearse-cpu``): 6 and 8 query heads over 2 kv heads of 32, window
    16 = one page, a half-rotated YaRN head stretched over 64 original
    positions, 16 experts top-2 and a shared one."""
    return ModelConfig(
        vocab_size=512,
        hidden=128,
        n_layers=5,
        n_heads=8,
        n_kv_heads=2,
        head_dim=32,
        intermediate=256,
        rope_theta=500000.0,
        rms_eps=1e-6,
        max_seq_len=512,
        layer_types=(_PERIOD * 2)[:5],
        sliding_window=16,
        rope_by_type=(
            ("full_attention", (
                ("rope_type", "yarn"), ("rope_theta", 500000.0),
                ("factor", 64.0), ("original_max_position_embeddings", 64),
                ("beta_fast", 64.0), ("beta_slow", 1.0),
                ("attention_factor", 1.4158883083359672),
                ("partial_rotary_factor", 0.5),
            )),
            _ROPE_BY_TYPE[1],
        ),
        heads_by_type=(("full_attention", 6), ("sliding_attention", 8)),
        attn_gate=True,
        n_experts=16,
        n_experts_per_tok=2,
        moe_intermediate=64,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        n_shared_experts=1,
        first_k_dense=1,
    )

"""Mellum2-12B-A2.5B (JetBrains, ``model_type: "mellum"``): 64 routed
experts of width 896, top-8 with renormalised weights and no shared expert,
in every layer; GQA 32 q / 4 kv heads of 128; three window-1024 layers
(default RoPE) then one full-attention layer (YaRN, factor 16 over 8192,
cos/sin times 1.2772588722239782), θ = 500 000 for both. The layer body is
``models/llama.py``'s; only the config differs. Source:
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json

Assumed, because the config file cannot settle it: no q/k normalisation
(it names none), a router without bias, and no multi-token-prediction head
(not in the config). ``intermediate_size`` 7168 is used by no layer: every
entry of ``mlp_layer_types`` is ``sparse``.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

_ROPE_BY_TYPE = (
    ("full_attention", (
        ("rope_type", "yarn"), ("rope_theta", 500000.0), ("factor", 16.0),
        ("original_max_position_embeddings", 8192), ("beta_fast", 32.0),
        ("beta_slow", 1.0), ("attention_factor", 1.2772588722239782),
    )),
    ("sliding_attention", (("rope_type", "default"), ("rope_theta", 500000.0))),
)


def mellum2_12b_a2_5b(n_layers: int = 28) -> ModelConfig:
    """The published model; ``n_layers`` keeps the first so many layers
    (whole periods of sliding ×3, full: the benchmark's one-chip cut)."""
    if n_layers % len(_PERIOD):
        raise ValueError("n_layers must hold whole periods of 4 layers")
    return ModelConfig(
        vocab_size=98304,
        hidden=2304,
        n_layers=n_layers,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        intermediate=7168,
        rope_theta=500000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        max_seq_len=32768,  # the config declares 131072; served: 32768
        layer_types=_PERIOD * (n_layers // len(_PERIOD)),
        sliding_window=1024,
        rope_by_type=_ROPE_BY_TYPE,
        n_experts=64,
        n_experts_per_tok=8,
        moe_intermediate=896,
        norm_topk_prob=True,
    )


def mellum2_12b_a2_5b_l12() -> ModelConfig:
    return mellum2_12b_a2_5b(12)


def mellum2_tiny() -> ModelConfig:
    """One period at toy widths (CPU tests, ``--rehearse-cpu``): 8 experts
    top-2, window 16, and a YaRN stretch over 64 original positions so
    that a test prompt passes every boundary the real sizes have."""
    return ModelConfig(
        vocab_size=512,
        hidden=128,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        intermediate=256,
        rope_theta=500000.0,
        rms_eps=1e-6,
        max_seq_len=512,
        layer_types=_PERIOD,
        sliding_window=16,
        rope_by_type=(
            ("full_attention", (
                ("rope_type", "yarn"), ("rope_theta", 500000.0),
                ("factor", 16.0), ("original_max_position_embeddings", 64),
                ("beta_fast", 32.0), ("beta_slow", 1.0),
                ("attention_factor", 1.2772588722239782),
            )),
            _ROPE_BY_TYPE[1],
        ),
        n_experts=8,
        n_experts_per_tok=2,
        moe_intermediate=64,
        norm_topk_prob=True,
    )

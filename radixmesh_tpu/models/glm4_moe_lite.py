"""GLM-4.7-Flash (Z.ai, ``model_type: "glm4_moe_lite"``): latent attention
(MLA: queries through a 768-wide bottleneck, 20 heads of 192 + 64 with the
rotary on the 64, one cache row of 512 + 64 a token and layer, values of
256 a head), a dense first layer of width 10 240, then sparse layers of 64
routed experts of width 1 536 (sigmoid scores, top-4 chosen by score + a
learned bias, weights from the scores, normalised, times 1.8) beside one
shared expert; θ = 1e6 without scaling, eps 1e-5, untied head. The layer
body is ``models/llama.py``'s; only the config differs. Source:
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json

Left out: the multi-token-prediction module (``num_nextn_predict_layers``
1: one more sparse layer and a projection that drafts token t+2). It
changes no logit of the model and plain decoding ignores it.

Assumed, because the config file cannot settle it: the rotary pairing
(rotate_half, as every other model here: the config has no
``rope_interleave`` key), the selection bias (a seeded draw: random
weights), ``torch_dtype`` bfloat16.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig


def glm47_flash(n_layers: int = 47) -> ModelConfig:
    """The published model; ``n_layers`` keeps the first so many layers
    (the dense one and the sparse ones after it: the benchmark's one-chip
    cut)."""
    return ModelConfig(
        vocab_size=154880,
        hidden=2048,
        n_layers=n_layers,
        n_heads=20,
        n_kv_heads=20,
        head_dim=256,  # qk_nope_head_dim + qk_rope_head_dim
        intermediate=10240,
        rope_theta=1000000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        max_seq_len=40960,  # the config declares 202752; served: 40960
        n_experts=64,
        n_experts_per_tok=4,
        moe_intermediate=1536,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=1.8,
        n_shared_experts=1,
        first_k_dense=1,
        q_lora_rank=768,
        kv_lora_rank=512,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
    )


def glm47_flash_l8() -> ModelConfig:
    return glm47_flash(8)


def glm47_flash_tiny() -> ModelConfig:
    """Toy widths with every mechanism (CPU tests, ``--rehearse-cpu``):
    one dense layer then two sparse ones, 8 experts top-2 beside a shared
    one, 4 heads of 16 + 8 over a 32 + 8 row, values of 24."""
    return ModelConfig(
        vocab_size=512,
        hidden=128,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        head_dim=24,
        intermediate=256,
        rope_theta=1000000.0,
        rms_eps=1e-5,
        max_seq_len=2048,  # the rehearsed cell's documents reach 768 + a tail
        n_experts=8,
        n_experts_per_tok=2,
        moe_intermediate=64,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=1.8,
        n_shared_experts=1,
        first_k_dense=1,
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=24,
    )

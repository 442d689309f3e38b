"""LFM2-24B-A2B (Liquid AI, ``model_type: "lfm2_moe"``): 40 blocks of which
30 are **gated short convolutions** and 10 grouped-query attention
(``layer_types``: conv, conv, full_attention, conv, repeated; attention at
layers 2, 6, ..., 38), each followed by an FFN — SwiGLU of width 11 776 in
the first two blocks, then 64 experts of width 1 536 (sigmoid scores,
top-4 chosen by score + a learned bias, weights from the scores alone,
normalised, times 1). A conv block leaves no cache row: it carries the
last ``conv_L_cache - 1`` = 2 positions of its gated input, 2 048
channels wide, whatever the context length — a state that lives in slots
of its own beside the paged K/V (``cache/kv_pool.py``) and that the radix
tree hands back only where it was snapshotted (``cache/radix_tree.py``).
The attention blocks are GQA 32 / 8 heads of 64 with RMSNorm over every q
and k head before the rotary (theta 1e6); two kv heads share a 128-lane
pool row (``ModelConfig.pair_kv_heads``). The layer bodies are
``models/llama.py``'s; only the config differs. Source:
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json

The block (the family's public modelling code, ``transformers``
``lfm2_moe``): ``h += Op(RMSNorm(h; operator_norm))``, ``h +=
FFN(RMSNorm(h; ffn_norm))``; conv operator ``[B, C, x] = split3(u W_in)``,
``z = B * x``, ``c_t = sum_k w[:, k] * z_{t-2+k}``, output ``(C * c)
W_out``; eps 1e-5; one more RMSNorm after the last block, then the tied
embedding as the head.

Assumed, because the catalog's copy of the config has no key for it:
``head_dim`` = hidden / heads = 64; the per-head q/k RMSNorm and its
weights; the order of ``in_proj``'s three parts (immaterial under random
weights; ``--weights`` stays refused); no activation inside the conv
operator; tied embeddings; ``torch_dtype`` bfloat16. The router's
normaliser adds 1e-20 here (``ops/moe.py::route``, shared with GLM) where
the family's code adds 1e-6: 5e-7 of a weight, under the rounding of
whatever is compared.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig

_PERIOD = ("conv", "conv", "full_attention", "conv")


def lfm2_24b_a2b() -> ModelConfig:
    """The published model (for shapes: 48 GB of bf16 weights)."""
    return ModelConfig(
        vocab_size=65536,
        hidden=2048,
        n_layers=40,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        intermediate=11776,
        rope_theta=1000000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=True,
        max_seq_len=32768,  # the config declares 128000; served: 32768
        layer_types=_PERIOD * 10,
        conv_L_cache=3,
        qk_norm=True,
        pair_kv_heads=True,
        n_experts=64,
        n_experts_per_tok=4,
        moe_intermediate=1536,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=1.0,
        first_k_dense=2,
    )


def lfm2_24b_a2b_ep8() -> ModelConfig:
    """The benchmark's one-chip cut: ALL 40 published layers and the whole
    vocabulary as rank 0 of an 8-way expert-parallel deployment (experts
    0-7 of 64 in every sparse layer, the router 64 wide, top-4; attention
    and conv layers replicated). No width and no depth differs from the
    source: 3.76 B parameters, 7.5 GB."""
    return lfm2_24b_a2b().replace(ep_rank=0, ep_size=8)


def lfm2_tiny() -> ModelConfig:
    """Toy widths with every mechanism (CPU tests, ``--rehearse-cpu``):
    three periods (12 layers: 9 conv, 3 attention — two dense-FFN layers, a
    scanned run of two periods and a left-over of two layers), 8 experts
    top-2, 4 / 2 heads of 64 paired into one 128-wide row, 3 taps."""
    return ModelConfig(
        vocab_size=512,
        hidden=128,
        n_layers=12,
        n_heads=4,
        n_kv_heads=2,
        head_dim=64,
        intermediate=256,
        rope_theta=1000000.0,
        rms_eps=1e-5,
        tie_embeddings=True,
        max_seq_len=2048,
        layer_types=_PERIOD * 3,
        conv_L_cache=3,
        qk_norm=True,
        pair_kv_heads=True,
        n_experts=8,
        n_experts_per_tok=2,
        moe_intermediate=64,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=1.0,
        first_k_dense=2,
    )

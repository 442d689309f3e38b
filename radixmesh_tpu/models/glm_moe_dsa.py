"""GLM-5.2 (Z.ai, ``model_type: "glm_moe_dsa"``): GLM-4.7's block at other
numbers — latent attention (MLA: queries through a 2048-wide bottleneck,
64 heads of 192 + 64 with the rotary on the 64, one cache row of 512 + 64 a
token and layer, values of 256 a head), three leading dense layers of
width 12 288, then sparse layers of 256 routed experts of width 2 048
(sigmoid scores, top-8 chosen by score + a learned bias, weights from the
scores, normalised, times 2.5) beside one shared expert; θ = 8e6, eps 1e-5,
untied head — plus **learned sparse attention** (DSA): a layer whose
``indexer_types`` entry is "full" holds an indexer (32 index queries of
128 from the query bottleneck, one 128-wide index key a token from the
hidden state, a weight a head) and attends the 2 048 best-scoring cached
tokens; a "shared" layer attends the set of the nearest full layer before
it. The layer body is ``models/llama.py``'s and the selection
``ops/dsa.py``'s; only the config differs. Source:
https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json

Left out: the multi-token-prediction module (``num_nextn_predict_layers``
1; ``index_share_for_mtp_iteration`` then touches nothing), the Hadamard
rotation of index queries and keys (an orthogonal map: it changes no
score) and their fp8 storage (the index key is cached in bf16).

Assumed, because the config file cannot settle it: the rotary pairing
(rotate_half, as every other model here, for the heads and the indexer
alike: ``rope_interleave`` / ``indexer_rope_interleave`` are the other
pairing of the same rotation), that the FIRST 64 of an index head's 128
elements rotate, LayerNorm with a bias and eps 1e-6 on the index key, the
selection bias and the LayerNorm bias (seeded draws: random weights),
``torch_dtype`` bfloat16.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig

_PERIOD = ("full", "shared", "shared", "shared")


def _indexer_types(n_layers: int) -> tuple:
    """The published list: the three leading layers full, then the period
    (full, shared, shared, shared) shifted by ``index_skip_topk_offset``
    3 — layers 3-5 share layer 2's selection, layer 6 is full, ..."""
    return tuple(
        "full" if i < 3 else _PERIOD[(i - 2) % 4] for i in range(n_layers)
    )


def glm52(n_layers: int = 78) -> ModelConfig:
    """The published model (for shapes: no chip holds a layer of it)."""
    return ModelConfig(
        vocab_size=154880,
        hidden=6144,
        n_layers=n_layers,
        n_heads=64,
        n_kv_heads=64,
        head_dim=256,  # qk_nope_head_dim + qk_rope_head_dim
        intermediate=12288,
        rope_theta=8000000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        max_seq_len=65536,  # the config declares 1048576; served: 65536
        n_experts=256,
        n_experts_per_tok=8,
        moe_intermediate=2048,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        n_shared_experts=1,
        first_k_dense=3,
        q_lora_rank=2048,
        kv_lora_rank=512,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
        index_n_heads=32,
        index_head_dim=128,
        index_topk=2048,
        indexer_types=_indexer_types(n_layers),
    )


def glm52_ep16() -> ModelConfig:
    """The benchmark's one-chip cut: published layers 2-8 (the last
    leading dense layer, full; 3-5 shared from it; 6 full; 7-8 shared),
    rank 0's share of a 16-way expert-parallel deployment (experts 0-15
    of 256, the router 256 wide, top-8) and an eighth of the vocabulary
    (19 360 ids). No width differs from the source."""
    return glm52(7).replace(
        first_k_dense=1,
        indexer_types=_indexer_types(78)[2:9],
        vocab_size=19360,
        ep_rank=0,
        ep_size=16,
    )


def glm52_tiny() -> ModelConfig:
    """Toy widths with every mechanism (CPU tests, ``--rehearse-cpu``):
    one dense layer then five sparse ones, indexers on layers 0 and 4
    (full, shared, shared, shared, full, shared), 4 index heads of 16 (8
    of them rotary), the 24 best tokens attended; 16 experts top-4 beside a
    shared one, 4 heads of 16 + 8 over a 32 + 8 row, values of 24."""
    return ModelConfig(
        vocab_size=512,
        hidden=128,
        n_layers=6,
        n_heads=4,
        n_kv_heads=4,
        head_dim=24,
        intermediate=256,
        rope_theta=8000000.0,
        rms_eps=1e-5,
        max_seq_len=2048,
        n_experts=16,
        n_experts_per_tok=4,
        moe_intermediate=64,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        n_shared_experts=1,
        first_k_dense=1,
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=24,
        index_n_heads=4,
        index_head_dim=16,
        index_topk=24,
        indexer_types=("full", "shared", "shared", "shared", "full", "shared"),
    )

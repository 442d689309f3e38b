"""Phi-4-mini-flash-reasoning (Microsoft, ``model_type: "phi4flash"``): the
decoder-hybrid-decoder "SambaY". 32 blocks in two halves. The
*self-decoder* (layers 0-15) alternates a **Mamba-1 selective state-space
layer** (even) with **window-512 attention** (odd). Layer 16 is one more
Mamba layer whose scan output is kept per token as the *memory*; layer 17
is the model's only full-attention layer and the only one of the upper
half that writes K/V. The *cross-decoder* (18-31) alternates a **Gated
Memory Unit** (even: no token mixing of its own, it gates layer 16's
memory at the same position) with **cross-attention onto layer 17's K/V**
(odd: a query projection only). All 16 attention layers use differential
attention; LayerNorm (weight and bias) replaces RMSNorm; there is no
positional encoding at all. Every block is ``h += Op(LN(h))``, ``h +=
SwiGLU(LN'(h))``; a last LayerNorm, then the tied embedding as the head.
Source:
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json

What a token leaves behind (``models/llama.py``): layer 17's K and V —
20 kv heads of 64 as 10 paired rows of 128, 5 120 B, for every token
(``kv_pool_shape``); the 8 window layers' — 40 960 B, wanted for the last
512 tokens of a row only, a group of rows with an allocator of its own
(``window_pool_shape``); and nothing in a Mamba layer, which carries ``[16,
5120]`` float32 and the last 3 inputs of its convolution whatever the
context length: 3 225 600 B a snapshot over the 9 of them (``state_shape``,
``conv_state_shape``). The layer bodies are ``models/llama.py``'s
(``_hybrid_*``); only the config is here.

Assumed, because the catalog's copy of the config has no key for it (the
family's public modelling code and paper state them): the Mamba sizes
``d_inner`` = 2 x hidden, ``d_state`` 16, ``d_conv`` 4, ``dt_rank`` = hidden
/ 16 = 160 (``mb_per_layer`` 2 places the layers); a convolution bias;
differential attention — which heads pair: query head ``4w + 2*half + g``
scores against kv head ``2w + half`` and reads ``(v_{2w} | v_{2w+1})``;
pairs ``(q_{4w+g}, q_{4w+2+g})``, a choice that is immaterial under random
weights —, ``lambda_0 = 0.8 - 0.6 exp(-0.3 l)`` with ``l`` the block's
index, an RMSNorm over the pair's 128 outputs; no rotary and no embedding
scale; ``head_dim`` = hidden / heads = 64; a window of 512 keys, the
query's own among them; the memory is the scan's output before the ``z``
gate, with the ``D`` term; bf16 weights, activations and cache rows,
float32 recurrent state.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig


def _layer_types(lower_pairs: int, upper_pairs: int) -> tuple:
    return (("mamba", "sliding_attention") * lower_pairs
            + ("mamba", "full_attention")
            + ("gmu", "cross_attention") * upper_pairs)


def phi4_mini_flash_reasoning() -> ModelConfig:
    """The published model, whole: 3.85 B parameters, 7.17 GiB in bf16."""
    return ModelConfig(
        vocab_size=200064,
        hidden=2560,
        n_layers=32,
        n_heads=40,
        n_kv_heads=20,
        head_dim=64,
        intermediate=10240,
        rms_eps=1e-5,  # layer_norm_eps
        qkv_bias=True,
        attn_out_bias=True,
        tie_embeddings=True,
        # The config declares 262144; served: 16384. A launch's page table is
        # scalar-prefetched whole by the paged kernels (table + run flags: 8 B
        # a row and page), and 64 rows x 2048 pages pass the chip's 1 MiB of
        # SMEM (PR 41, call 2); 64 x 1024 fit.
        max_seq_len=16384,
        layer_types=_layer_types(8, 7),
        sliding_window=512,
        pair_kv_heads=True,
        diff_attention=True,
        use_rope=False,
        layer_norm=True,
        ssm_state=16,
        ssm_conv=4,
        ssm_expand=2,
        ssm_dt_rank=160,
    )


def phi4flash_tiny() -> ModelConfig:
    """Toy widths with every mechanism (CPU tests, ``--rehearse-cpu``): 8
    layers = 2 (mamba, window) pairs + the memory layer + the full layer +
    1 (gmu, cross) pair, hidden 256, 4 / 2 heads of 64 (one paired row),
    window 32 = 2 pages, ``d_state`` 16."""
    return ModelConfig(
        vocab_size=512,
        hidden=256,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        head_dim=64,
        intermediate=512,
        rms_eps=1e-5,
        qkv_bias=True,
        attn_out_bias=True,
        tie_embeddings=True,
        max_seq_len=2048,
        layer_types=_layer_types(2, 1),
        sliding_window=32,
        pair_kv_heads=True,
        diff_attention=True,
        use_rope=False,
        layer_norm=True,
        ssm_state=16,
        ssm_conv=4,
        ssm_expand=2,
        ssm_dt_rank=16,
    )

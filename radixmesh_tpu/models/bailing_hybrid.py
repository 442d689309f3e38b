"""Ling-3.0-flash (inclusionAI, ``model_type: "bailing_hybrid"``,
"~125B-A5.5B"): 42 layers of hidden 2560, layer ``l`` LATENT attention
where ``(l + 1) % 6 == 0`` (``layer_group_size`` 6: layers 5, 11, ..., 41)
and a **KDA linear-attention layer** otherwise (35 of them): the gated
delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692; ``ops/kda.py``), 32 heads of 128 keys and values behind
a depth-wise convolution of 4 taps, no positional encoding. The latent
layers are GLM-4.7-Flash's with two departures the config states: NO query
bottleneck (``q_lora_rank`` null: ``wq`` direct, no query norm) and a
head-wise output gate (``sigmoid(h W_g)``, one number a head, before
``wo``); 32 heads of 128 + 64 with the rotary on the 64 (theta 6e6), one
cache row of 512 + 64 a token and layer, values of 128. Layers 0-1 have a
dense SwiGLU of 6144, layers 2-41 512 routed experts of 768 (sigmoid
scores + a learned bias, GROUP-LIMITED: 8 groups of 64, the 4 best groups
by the sum of their two best, top-8 among their 256, normalised, times
2.5) beside one shared expert. RMSNorm eps 1e-6, no bias, an untied head.
Source:
https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json

What a token leaves behind (``models/llama.py``): 1 280 B in each latent
layer (``kv_pool_shape``: the pool's layer axis counts the latent layers
only) and nothing in a KDA layer, which carries ``[32 * 128, 128]``
float32 = 2 MiB and the last 3 inputs of its three convolutions whatever
the context length (``state_shape`` / ``conv_state_shape``): 12.4 MiB a
snapshot over 6 such layers. The layer bodies are ``models/llama.py``'s;
only the config is here.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``
1, ``mtp_loss_scaling_factor`` 0): it changes no logit of the model. NOT
implemented: the clipped SwiGLU of the published model's last eight layers
(``expert_swiglu_limit_list`` 4 from layer 35, ``share_expert_swiglu_limit_
list`` 5 / 7 from layer 34; 0 = none everywhere below): the config gives
the limits and not the clip's form. The benchmark's cut holds layers 1-7,
where both lists are 0; the 42-layer preset describes SIZES (parameter
count, state and cache bytes) and is not a model to serve.

Assumed, because the config cannot settle it (each also in
``benchmarks/configs/ling-3.0-flash-bf16-ep8.json``):

- ``W_alpha`` and the output gate ``W_g`` of a KDA layer are FULL
  projections [2560, 4096] (``no_kda_lora: true``, ``use_kda_lora:
  false``); the published size agrees (a KDA layer 63.05 M outside its
  FFN, the model 124.4 B with 5.4 B active a token against "~125B-A5.5B";
  rank-128 pairs would give 4.8 B active).
- the safe gate: ``g = kda_lower_bound * sigmoid(exp(A_log) * (h W_alpha
  + dt_bias))`` in (-5, 0) (``kda_safe_gate`` + ``kda_lower_bound``, the
  open-source linear-attention kernels' reading), not ``-exp(A_log) *
  softplus(.)``; ``exp(A_log)`` a head drawn in [1, 16], ``dt_bias`` a
  seeded draw.
- ``use_qk_norm`` is KDA's L2 normalisation of q and k a head (q then
  times 128^-1/2); the latent layers have the ``kv_a`` norm only — there
  is no bottleneck to norm, and RMS norms over ``head_dim`` would not
  survive the absorbed decode path unnoticed.
- the KDA output norm is an RMSNorm over each head's 128 values with the
  head's own learned weight (``group_norm_size`` 1), gated element by
  element (``W_g`` [2560, 4096]).
- rotary pairing ``rotate_half``, as every model here (``rope_interleave:
  true`` is the other pairing of the same rotation; loading a checkpoint
  is refused).
- ``e_score_correction_bias``: a seeded draw, normal x 0.02.
- float32 for a KDA layer's matrix state, bf16 for its convolution inputs.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig


def _layer_types(n_layers: int, first: int = 0) -> tuple:
    """Published layers ``first .. first + n_layers - 1``: latent where
    ``(l + 1) % 6 == 0``, KDA otherwise."""
    return tuple("full_attention" if (l + 1) % 6 == 0 else "kda"
                 for l in range(first, first + n_layers))


def ling3_flash(**cut) -> ModelConfig:
    """The published model, whole (124.4 B parameters); ``cut`` overrides
    what a share of it holds."""
    cfg = dict(
        vocab_size=157184,
        hidden=2560,
        n_layers=42,
        n_heads=32,
        n_kv_heads=32,
        head_dim=192,  # qk_nope_head_dim + qk_rope_head_dim
        intermediate=6144,
        rope_theta=6000000.0,
        rms_eps=1e-6,
        tie_embeddings=False,
        # The config declares 262144; served: 16384. A launch's page table
        # of 64 rows x 1024 pages is what the chip's scalar memory takes
        # (models/phi4flash.py; PERF.md section 7).
        max_seq_len=16384,
        layer_types=_layer_types(42),
        n_experts=512,
        n_experts_per_tok=8,
        moe_intermediate=768,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        n_group=8,
        topk_group=4,
        n_shared_experts=1,
        first_k_dense=2,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        attn_gate=True,
        kda_heads=32,
        kda_head_dim=128,
        kda_conv=4,
        kda_gate_lower_bound=-5.0,
    )
    return ModelConfig(**(cfg | cut))


def ling3_flash_ep8() -> ModelConfig:
    """The benchmark's one-chip cut (``benchmarks/configs/
    ling-3.0-flash-bf16-ep8.json``): published layers 1-7 — KDA with the
    dense FFN, then KDA, KDA, KDA, latent (layer 5), KDA, KDA with experts:
    the leading dense layers once and one whole period in the published
    5 : 1 — and the share one of EIGHT chips holds of each layer: experts
    0-63 of 512 (the first of the 8 routing groups; the router keeps its
    512 outputs), an eighth of the vocabulary; attention, KDA and the
    shared expert whole."""
    return ling3_flash(
        n_layers=7, layer_types=_layer_types(7, first=1), first_k_dense=1,
        vocab_size=157184 // 8, ep_size=8, ep_rank=0)


def ling_tiny() -> ModelConfig:
    """Toy widths with every mechanism (CPU tests, ``--rehearse-cpu``):
    KDA with the dense FFN, KDA, latent, KDA; 4 KDA heads of 16 behind a
    convolution of 4 taps; 4 latent heads of 16 + 8 over a 32 + 8 row, no
    query bottleneck, the head-wise gate; 16 experts in 4 groups, 2 groups
    kept, top-2 beside a shared one."""
    return ModelConfig(
        vocab_size=512,
        hidden=128,
        n_layers=4,
        n_heads=4,
        n_kv_heads=4,
        head_dim=24,
        intermediate=256,
        rope_theta=6000000.0,
        rms_eps=1e-6,
        max_seq_len=2048,
        layer_types=("kda", "kda", "full_attention", "kda"),
        n_experts=16,
        n_experts_per_tok=2,
        moe_intermediate=64,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        n_group=4,
        topk_group=2,
        n_shared_experts=1,
        first_k_dense=1,
        q_lora_rank=0,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        attn_gate=True,
        kda_heads=4,
        kda_head_dim=16,
        kda_conv=4,
        kda_gate_lower_bound=-5.0,
    )

"""AI21-Jamba2-3B (AI21 Labs, ``model_type: "jamba"``, "Jamba2-3B / Jamba
Reasoning 3B"): 28 layers of hidden 2560, each ``x += Mixer_i(RMSNorm(x));
x += MLP(RMSNorm'(x))``. ``Mixer_i`` is attention iff ``i % 14 == 7``
(layers 7 and 21: ``attn_layer_period`` 14, ``attn_layer_offset`` 7), else a
**Mamba-1 selective state-space operator**; ``num_experts`` = 1, so every
MLP is the dense SwiGLU ``W_down(silu(W_gate u) * W_up u)`` of 8192. RMSNorm
eps 1e-6, no bias but the convolution's and ``dt_proj``'s, NO positional
encoding of any kind, no embedding or logit scale, the head is the
embedding transposed, vocabulary 65 536. Source:
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json

- *Mamba* (``d_inner`` 5120 = ``mamba_expand`` x hidden, ``N`` = 16, ``R`` =
  ``mamba_dt_rank`` = 160, 4 taps): ``(x, z) = W_in u``; ``x <- silu(conv4(x)
  + b_conv)`` (depth-wise, causal); ``(delta, B, C) = W_x x`` (160 | 16 |
  16); **``delta <- RMSNorm_160(delta)``, ``B <- RMSNorm_16(B)``, ``C <-
  RMSNorm_16(C)``** (each with a learned weight: the family's
  ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``; ``ssm_inner_norms``);
  ``Delta = softplus(W_dt delta + b_dt)``; ``A = -exp(A_log)`` [5120, 16];
  ``h_t = exp(Delta_t A) * h_{t-1} + (Delta_t x_t) B_t^T``; ``y_t = h_t C_t +
  D * x_t``; ``Op = W_out(y * silu(z))``.
- *Attention*: 20 query heads and ONE KV head of 128 (``head_dim`` = 2560 /
  20), causal softmax at ``1/sqrt(128)``, no rotary.

Per layer 104.16 M (Mamba) / 76.68 M (attention) parameters; 26 x 104.16 +
2 x 76.68 + 167.77 (embedding) = 3 029 M, the card's 3 B.

What a token leaves behind (``models/llama.py``): 1 024 B of K/V for the
WHOLE model (one KV head of 128, K and V, bf16, two layers:
``kv_pool_shape`` ``[2, 2, 1, slots, 128]``) and nothing in a Mamba layer,
which carries ``[16, 5120]`` float32 and the last 3 inputs of its
convolution whatever the context length: 9 318 400 B a snapshot over the 26
of them (``state_shape``, ``conv_state_shape``). The layers are kinds of
``models/llama.py``'s by-kind body (``params["mamba"]`` / ``params["attn"]``,
``_scan_mixed``: two units of 14 layers); only the config is here.

Assumed, because the catalog's copy of the config has no key for it: which
layers are attention (the catalog lists the order as not given; period and
offset are read the way the family's public modelling code reads them);
``head_dim`` = hidden / heads = 128; the three inner norms (not a key); the
initialisers as ``models/phi4flash.py`` has them (``A_log = log(1..16)``,
``dt`` bias the inverse softplus of a log-uniform [1e-3, 1e-1], ``D`` = 1)
and inner-norm weights 1 + 0.1 x normal; float32 recurrent state, bf16
convolution inputs, weights, activations and K/V.
"""

from __future__ import annotations

from radixmesh_tpu.models.llama import ModelConfig


def _layer_types(n_layers: int, period: int, offset: int) -> tuple:
    return tuple("full_attention" if i % period == offset else "mamba"
                 for i in range(n_layers))


def jamba2_3b() -> ModelConfig:
    """The published model, whole: 3.03 B parameters, 5.64 GiB in bf16."""
    return ModelConfig(
        vocab_size=65536,
        hidden=2560,
        n_layers=28,
        n_heads=20,
        n_kv_heads=1,
        head_dim=128,
        intermediate=8192,
        rms_eps=1e-6,
        tie_embeddings=True,
        # The config declares 262144; served: 131072 — what 64 rows reach
        # with the 1 024 page-table entries a launch's scalar memory holds
        # (8 B a row and page: ROADMAP R11) at this model's page of 128.
        max_seq_len=131072,
        layer_types=_layer_types(28, 14, 7),
        use_rope=False,
        ssm_state=16,
        ssm_conv=4,
        ssm_expand=2,
        ssm_dt_rank=160,
        ssm_inner_norms=True,
    )


def jamba_tiny() -> ModelConfig:
    """Toy widths with every mechanism (CPU tests, ``--rehearse-cpu``): 8
    layers = 2 periods of 4 with the attention layer at offset 1 (layers 1
    and 5), hidden 256, 4 query heads on one KV head of 64, ``d_state`` 16,
    the three inner norms."""
    return ModelConfig(
        vocab_size=512,
        hidden=256,
        n_layers=8,
        n_heads=4,
        n_kv_heads=1,
        head_dim=64,
        intermediate=512,
        rms_eps=1e-6,
        tie_embeddings=True,
        max_seq_len=4096,
        layer_types=_layer_types(8, 4, 1),
        use_rope=False,
        ssm_state=16,
        ssm_conv=4,
        ssm_expand=2,
        ssm_dt_rank=16,
        ssm_inner_norms=True,
    )

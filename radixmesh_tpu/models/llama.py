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
- **The cache row is the config's** (``models/glm4_moe_lite.py``):
  ``ModelConfig.kv_row`` says what a token leaves in a layer — K and V per
  kv head, or, for latent attention (``kv_lora_rank``), ONE row shared by
  every head. ``_attn_in`` then returns the absorbed query and that row,
  and the three entry points attend it as multi-query attention whose
  values are the row's head (``ops/mla_attention.py``); ``_attn_out``
  un-absorbs. A config's leading dense layers (``first_k_dense``) are a
  stack of their own, scanned before the sparse ones by the same body
  (``_scan_layers``).
- **Learned sparse attention, and a second kind of cache state**
  (``models/glm_moe_dsa.py``, ``ops/dsa.py``): a layer with an indexer
  ("full") scores every cached token against the token's cached index key
  — a plane of its own beside the pool, ``ModelConfig.kv_index_shape``,
  addressed by the pool's slots — and attends the ``index_topk`` best; a
  "shared" layer attends the set of the full layer before it, which rides
  the layer scan (a ``lax.cond`` on the layer's table row: shared layers
  run no scoring and no selection). ``decode_step`` and
  ``prefill_chunk_paged`` take ``index_pool`` and return it after
  ``kv_pool``; a page table within ``index_topk`` tokens takes the dense
  latent kernels unchanged.
- **An expert share** (``ModelConfig.ep_rank`` / ``ep_size``): the expert
  weights hold this chip's experts only; the layer routes over all of
  them and computes its own part (``ops/moe.py``).
- **Linear-attention layers beside latent attention** (``models/
  bailing_hybrid.py``, ``ops/kda.py``): a layer of kind "kda" (the gated
  delta rule with a per-channel decay) leaves no cache row; it carries a
  float32 matrix a head and its convolutions' last inputs in STATE SLOTS
  (``state_shape`` / ``conv_state_shape``), read and written inside the
  layer: a chunk launch reads a row's slot and writes the states the
  engine asks for, a decode launch updates the row's slot in place and,
  behind the layers, copies it to the boundary slot for the rows whose
  token completed a page. The latent layers beside them (``params["mla"]``:
  no query bottleneck, a head-wise output gate) own the pool's rows, and
  the layers run through ``_scan_mixed`` by their static kind.
- **Blocks of one sublayer** (``models/nemotron_h.py``, ``ops/ssd.py``): a
  config with "mamba2" layers names BLOCKS in ``layer_types`` — a Mamba-2
  mixer ("mamba2"), plain GQA ("full_attention") or the expert FFN ("moe")
  alone, ``x + f(norm(x))`` with one ``f`` and one pre-norm each — every
  kind's leaves a stack of its own, through the same ``_scan_mixed`` and
  the same three layer bodies (``_sublayers``). A Mamba-2 block carries a
  float32 matrix a head in the state slots as a kda layer does.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from radixmesh_tpu.cache.kv_pool import ALL_LAYERS, write_runs_all
from radixmesh_tpu.ops.attention import (
    attend_prefill,
    paged_chunk_attention,
    paged_decode_attention,
)
from radixmesh_tpu.ops.mla_attention import (
    latent_chunk_attention,
    latent_decode_attention,
    latent_prefill_attention,
)
from radixmesh_tpu.ops import dsa
from radixmesh_tpu.ops.moe import GROUP_STATS as MOE_STATS, SHARE_STATS, STATS, moe_ffn
from radixmesh_tpu.ops.norm import layer_norm, rms_norm
from radixmesh_tpu.ops.rope import apply_rope, rope_frequencies, yarn_frequencies
from radixmesh_tpu.ops import kda
from radixmesh_tpu.ops.ssm import selective_scan, ssm_step
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
    # The router: "softmax" over all experts, or "sigmoid" scores chosen
    # by score + a learned per-expert bias and weighed by the score alone.
    # The chosen weights are multiplied by ``routed_scaling_factor``.
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # Experts every token takes beside the routed ones (one SwiGLU of
    # width ``n_shared_experts * moe_intermediate``), and the number of
    # leading layers whose FFN is the dense one of width ``intermediate``
    # (their leaves are a stack of their own, ``params["dense_layers"]``).
    n_shared_experts: int = 0
    first_k_dense: int = 0
    # Latent attention (MLA) when ``kv_lora_rank`` > 0: queries through a
    # ``q_lora_rank`` bottleneck, ``head_dim`` = ``qk_nope_head_dim`` +
    # ``qk_rope_head_dim`` (rotary on the second part only), and ONE cache
    # row a token and layer, ``kv_lora_rank + qk_rope_head_dim`` elements
    # (stored in whole 128-lane tiles), shared by every head (``kv_row``);
    # ``n_kv_heads`` shapes nothing.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Learned sparse attention (DSA) when ``index_topk`` > 0, over latent
    # attention: a layer whose ``indexer_types`` entry is "full" scores
    # every cached token with ``index_n_heads`` index queries against the
    # token's cached index key (``index_head_dim`` wide, a cache plane of
    # its own: ``kv_index_shape``) and attends the ``index_topk`` best; a
    # "shared" layer has no indexer and attends the set of the nearest
    # full layer before it (the first layer is full).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    indexer_types: tuple | None = None
    # An expert share: this chip holds experts ``[ep_rank * E / ep_size,
    # (ep_rank + 1) * E / ep_size)`` of every sparse layer (``E`` =
    # ``n_experts``, the router's width). It routes over all ``E``,
    # computes its own experts' part and counts what went elsewhere
    # (``ops/moe.py``); on one chip there is no exchange.
    ep_rank: int = 0
    ep_size: int = 1
    # Gated short-convolution layers (``layer_types`` entry "conv", beside
    # "full_attention"): such a layer leaves NO cache row; it carries the
    # last ``conv_L_cache - 1`` positions of its gated input, ``hidden``
    # wide, whatever the context length (``state_shape``). Its leaves are
    # a stack of their own (``params["conv"]``), the attention layers'
    # another (``params["attn"]``), and the pool's layer axis counts the
    # attention layers only.
    conv_L_cache: int = 0
    # RMSNorm over each q and each k head (a learned [head_dim] weight)
    # before the rotary.
    qk_norm: bool = False
    # Two kv heads of 64 side by side in one 128-lane pool row: the pool
    # holds ``n_kv_heads / 2`` rows of 128 a token, plane and layer (no
    # padding: a 64-wide row is stored 128 wide by the device's tiling),
    # and the paged kernels see that many "wide" kv heads (``_pair_heads``).
    pair_kv_heads: bool = False
    # Selective state-space layers (``layer_types`` entry "mamba": Mamba-1,
    # ``ssm_state`` > 0), in one of two layouts. INTERLEAVED with plain GQA
    # "full_attention" layers (``models/jamba.py``): every layer "norm,
    # operator, add, norm, SwiGLU, add" under RMSNorm, a layer of the
    # by-kind body (``_scan_mixed``: ``params["mamba"]`` / ``params["attn"]``),
    # ``ssm_inner_norms`` True: RMSNorm with a learned weight over each of
    # delta, B and C as ``x_proj`` gives them. Or a decoder-hybrid-decoder
    # (``cross_decoder``; ``models/
    # phi4flash.py``): pairs of (mamba, sliding_attention), one more mamba
    # layer whose scan output is kept per token as the MEMORY, the one
    # full_attention layer, then pairs of ("gmu", "cross_attention") — a
    # gmu layer gates the memory, a cross_attention layer has a query
    # projection only and reads the full layer's K/V. A mamba layer
    # leaves no cache row: it carries ``[ssm_state, ssm_expand * hidden]``
    # float32 and the last ``ssm_conv - 1`` inputs of its convolution
    # (``state_shape`` / ``conv_state_shape``). The cache rows are two
    # groups with an allocator each: the full layer's (``kv_pool_shape``,
    # every token) and the sliding layers' (``window_pool_shape``, the
    # last ``sliding_window`` tokens of a row).
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    ssm_inner_norms: bool = False
    # Differential attention: heads pair up, the second softmax map is
    # subtracted ``lambda`` times, an RMSNorm over the pair's 2 * head_dim
    # outputs follows (``_diff_combine``). ``use_rope`` False: no
    # positional encoding. ``layer_norm``: LayerNorm with weight and bias
    # where the others take RMSNorm. ``attn_out_bias``: a bias on wo.
    diff_attention: bool = False
    use_rope: bool = True
    layer_norm: bool = False
    attn_out_bias: bool = False
    # Attention layers of one model that differ in SHAPE (``models/
    # laguna.py``): ``heads_by_type`` = ``((layer type, query heads),
    # ...)`` over the same kv heads. ``wq`` / ``wo`` of a sliding and of a
    # full layer then cannot lie in one stack: each kind's attention
    # matrices are a stack of its own (``params[<layer type>]``), the
    # norms and the FFN stay per layer, and the layers run through
    # ``_scan_mixed`` by their static kind. ``n_heads`` is the most any
    # kind has. The sliding layers' rows are the WINDOW group
    # (``window_pool_shape``), the full layers' the pool's; such a config
    # carries no state, so what the tree keeps for a resume is a window
    # tail alone. A ``rope_by_type`` entry may give ``partial_rotary_factor``:
    # the first so much of each head rotates (as two halves), the rest
    # passes. ``attn_gate``: every head's attention output is multiplied
    # by ``sigmoid(h W_g)`` of the layer's normed input (``wg`` [hidden,
    # heads], in the kind's stack) before ``wo``.
    heads_by_type: tuple | None = None
    attn_gate: bool = False
    # Linear-attention layers (``layer_types`` entry "kda", beside
    # "full_attention" layers of LATENT attention; ``models/
    # bailing_hybrid.py``): the gated delta rule with a per-channel decay
    # (``ops/kda.py``), ``kda_heads`` heads of ``kda_head_dim`` keys and
    # values, behind a depth-wise convolution of ``kda_conv`` taps. Such a
    # layer leaves NO cache row: it carries one ``[kda_head_dim,
    # kda_head_dim]`` float32 matrix a head and the last ``kda_conv - 1``
    # inputs of its convolutions, whatever the context length
    # (``state_shape`` / ``conv_state_shape``). Its leaves are a stack of
    # their own (``params["kda"]``), the latent layers' another
    # (``params["mla"]``, with ``wg`` under ``attn_gate``), and the pool's
    # layer axis counts the latent layers only. The gate is ``g =
    # kda_gate_lower_bound * sigmoid(exp(A_log) * (h W_a + dt_bias))``.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    kda_gate_lower_bound: float = -5.0
    # Group-limited routing of a sigmoid router: the experts are
    # ``n_group`` runs, the ``topk_group`` best groups (by the sum of their
    # two best score + bias) stay, the experts are chosen among theirs
    # (``ops/moe.py::route``). 1: no groups.
    n_group: int = 1
    topk_group: int = 1
    # Mamba-2 blocks (``layer_types`` entry "mamba2"; ``models/
    # nemotron_h.py``, ``ops/ssd.py``): ``ssd_heads`` heads of
    # ``ssd_head_dim`` channels, B and C of ``ssd_state`` shared by the heads
    # of each of ``ssd_groups`` groups, behind a depth-wise convolution of
    # ``ssd_conv`` taps with a bias. ``layer_types`` then names BLOCKS of
    # one sublayer: "mamba2", "full_attention" (plain GQA; ``use_rope`` may
    # be False) or "moe" (the expert FFN alone), a stack a kind. A mamba2
    # block leaves no cache row: it carries ``[ssd_head_dim, ssd_state]``
    # float32 a head and the last ``ssd_conv - 1`` inputs of its convolution
    # (``state_shape`` / ``conv_state_shape``). ``mlp_gated`` False: experts
    # (and the shared one, ``moe_shared_intermediate`` wide) without a gate
    # matrix, ``W_down relu(W_up x)^2``.
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_groups: int = 0
    ssd_state: int = 0
    ssd_conv: int = 0
    mlp_gated: bool = True
    moe_shared_intermediate: int = 0
    # Flags this config's programs need of the TPU compiler, for the entry
    # point that starts the backend to put into ``LIBTPU_INIT_ARGS`` BEFORE
    # it exists (``launch.py::_apply_libtpu_args``; libtpu reads them once).
    libtpu_init_args: tuple = ()

    def __post_init__(self):
        if self.heads_by_type is not None:
            self._check_kinds()
        elif self.attn_gate and not self.has_kda:
            raise ValueError(
                "attn_gate's weights live in the attention stacks of a "
                "config with heads_by_type or kda layers: no other config "
                "has them")
        if self.layer_types is not None and (
            self.ssm_state or {"mamba", "gmu", "cross_attention"}
            & set(self.layer_types)
        ):
            self._check_hybrid()
        elif self.layer_types is not None:
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.n_layers or not (
                kinds <= {"sliding_attention", "full_attention"}
                or kinds <= {"conv", "full_attention"}
                or kinds <= {"kda", "full_attention"}
                or kinds <= {"mamba2", "moe", "full_attention"}
            ):
                raise ValueError(
                    f"layer_types must name {self.n_layers} layers as "
                    f"sliding_attention or full_attention, or as conv or "
                    f"full_attention, or as kda or full_attention, or (blocks "
                    f"of one sublayer) as mamba2, moe or full_attention, got "
                    f"{len(self.layer_types)} of {sorted(kinds)}"
                )
            if "sliding_attention" in kinds and self.sliding_window < 2:
                raise ValueError("sliding layers need sliding_window >= 2")
            missing = kinds - {t for t, _ in self.rope_by_type or ()}
            if missing and not kinds & {"conv", "kda", "mamba2"}:
                raise ValueError(f"rope_by_type lacks {sorted(missing)}")
        if self.has_conv:
            taps = self.conv_L_cache
            if taps < 2 or ((taps - 1) * self.hidden) % 128:
                raise ValueError(
                    f"conv layers need conv_L_cache >= 2 and (conv_L_cache - "
                    f"1) * hidden in whole 128-lane rows, got conv_L_cache="
                    f"{taps}, hidden={self.hidden}"
                )
            if "full_attention" not in self.layer_types:
                raise ValueError(
                    "layer_types names no full_attention layer: the paged "
                    "pool and the radix tree need one layer that owns rows"
                )
            if self.latent or self.qkv_bias or self.rope_by_type:
                raise ValueError(
                    "conv layers go with plain GQA attention layers under "
                    "one RoPE: no latent attention, qkv_bias or rope_by_type"
                )
        elif self.conv_L_cache:
            raise ValueError("conv_L_cache belongs to a config with conv layers")
        if self.has_kda:
            if not (self.kda_heads > 0 and self.kda_head_dim > 0
                    and self.kda_conv >= 2 and self.kda_gate_lower_bound < 0):
                raise ValueError(
                    "kda layers need kda_heads, kda_head_dim > 0, kda_conv >= "
                    "2 and a negative kda_gate_lower_bound")
            if (not self.latent or "full_attention" not in self.layer_types
                    or self.qkv_bias or self.rope_by_type or self.pair_kv_heads
                    or self.dsa):
                raise ValueError(
                    "kda layers go with latent full_attention layers under "
                    "one RoPE (the paged pool and the radix tree need one "
                    "layer that owns rows): no qkv_bias, rope_by_type, "
                    "pair_kv_heads or learned sparse attention")
        elif self.kda_heads or self.kda_conv:
            raise ValueError("kda_heads / kda_conv belong to a config with kda layers")
        self._check_blocks()
        if self.pair_kv_heads and (
            self.latent or self.head_dim != 64 or self.n_kv_heads % 2
        ):
            raise ValueError(
                "pair_kv_heads lays two kv heads of 64 in one 128-lane row: "
                "it needs head_dim == 64, an even n_kv_heads and no latent "
                "attention"
            )
        if self.diff_attention and not self.has_ssm:
            raise ValueError(
                "diff_attention's weights live in the attention stacks of a "
                "config with mamba layers: no other config has them")
        if self.qk_norm and not self.has_conv:
            raise ValueError(
                "qk_norm's weights live in the attention stack of a config "
                "with conv layers (params[\"attn\"]): no other config has them")
        if self.n_experts and not (
            0 < self.n_experts_per_tok <= self.n_experts
            and self.moe_intermediate > 0
        ):
            raise ValueError(
                "n_experts needs 0 < n_experts_per_tok <= n_experts and "
                "moe_intermediate > 0"
            )
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_scoring {self.router_scoring!r}")
        if not 0 <= self.first_k_dense <= self.n_layers or (
            (self.first_k_dense or self.n_shared_experts) and not self.n_experts
        ):
            raise ValueError(
                "first_k_dense and n_shared_experts belong to a config with "
                "experts, and first_k_dense to [0, n_layers]"
            )
        if self.kv_lora_rank and not (
            self.q_lora_rank >= 0 and self.v_head_dim > 0
            and self.qk_rope_head_dim > 0 and self.qk_rope_head_dim % 2 == 0
            and self.head_dim == self.qk_nope_head_dim + self.qk_rope_head_dim
        ):
            raise ValueError(
                "latent attention needs v_head_dim and an even "
                "qk_rope_head_dim > 0, and head_dim == qk_nope_head_dim + "
                "qk_rope_head_dim (q_lora_rank 0: queries without a "
                "bottleneck)"
            )
        if self.index_topk and not (
            self.latent and self.q_lora_rank > 0 and self.index_n_heads > 0
            and self.index_head_dim >= self.qk_rope_head_dim
            and self.indexer_types is not None
            and len(self.indexer_types) == self.n_layers
            and set(self.indexer_types) <= {"full", "shared"}
            and self.indexer_types[0] == "full"
        ):
            raise ValueError(
                "learned sparse attention needs latent attention with a query "
                "bottleneck, index_n_heads > 0, index_head_dim >= qk_rope_head_dim and "
                f"indexer_types naming {self.n_layers} layers as full or "
                "shared, the first of them full"
            )
        if not (0 <= self.ep_rank < self.ep_size) or (
            self.ep_size > 1
            and (not self.n_experts or self.n_experts % self.ep_size)
        ):
            raise ValueError(
                "an expert share needs 0 <= ep_rank < ep_size and n_experts "
                "divisible by ep_size"
            )
        if self.n_group > 1 and not (
            self.router_scoring == "sigmoid" and self.n_experts
            and self.n_experts % self.n_group == 0
            and 0 < self.topk_group <= self.n_group
            and self.n_experts // self.n_group >= 2
            and self.n_experts_per_tok
            <= self.topk_group * (self.n_experts // self.n_group)
        ):
            raise ValueError(
                "group-limited routing needs a sigmoid router, n_experts in "
                "n_group runs of two or more, 0 < topk_group <= n_group and "
                "n_experts_per_tok within the kept groups' experts")

    def _check_kinds(self) -> None:
        """A config whose attention kinds are stacks of their own."""
        heads = dict(self.heads_by_type)
        kinds = set(self.layer_types or ())
        if (not kinds or kinds != set(heads)
                or kinds != {"sliding_attention", "full_attention"}):
            raise ValueError(
                "heads_by_type names the query heads of sliding_attention "
                "and of full_attention, and layer_types holds both: the "
                "window group and the pool need a layer each; got "
                f"{sorted(heads)} for layers of {sorted(kinds)}")
        if any(h <= 0 or h % self.n_kv_heads for h in heads.values()) or (
                self.n_heads != max(heads.values())):
            raise ValueError(
                "every kind's query heads are a multiple of n_kv_heads, and "
                f"n_heads the most of them; got {heads}, n_heads="
                f"{self.n_heads}, n_kv_heads={self.n_kv_heads}")
        if self.sliding_window < 2 or self.sliding_window % 16:
            raise ValueError(
                "the window layers' rows are given back by whole pages: "
                "sliding_window must be a multiple of the page (16), got "
                f"{self.sliding_window}")
        if (self.latent or self.qkv_bias or self.pair_kv_heads or self.qk_norm
                or self.layer_norm or not self.use_rope):
            raise ValueError(
                "attention kinds in stacks of their own are plain GQA layers "
                "under RMSNorm and a RoPE each: no latent attention, "
                "qkv_bias, pair_kv_heads, qk_norm or layer_norm")
        for kind, pairs in self.rope_by_type or ():
            rot = dict(pairs).get("partial_rotary_factor", 1.0) * self.head_dim
            if rot != int(rot) or int(rot) % 2 or not 0 < rot <= self.head_dim:
                raise ValueError(
                    f"{kind}: partial_rotary_factor must leave an even "
                    f"number of rotating dims within head_dim, got {rot}")

    def _check_blocks(self) -> None:
        """A config with mamba2 layers: blocks of one sublayer."""
        if not self.has_mamba2:
            if (self.ssd_heads or self.ssd_conv or not self.mlp_gated
                    or self.moe_shared_intermediate or "moe" in (
                        self.layer_types or ())):
                raise ValueError(
                    "ssd_heads / ssd_conv, moe blocks, un-gated experts "
                    "(mlp_gated False) and moe_shared_intermediate belong to "
                    "a config with mamba2 layers")
            return
        hp = 128 // max(self.ssd_head_dim, 1)
        if not (self.ssd_heads > 0 and self.ssd_groups > 0 and self.ssd_conv >= 2
                and self.ssd_state > 0 and self.ssd_state % 8 == 0
                and 0 < self.ssd_head_dim <= 128 and 128 % self.ssd_head_dim == 0
                and self.ssd_heads % self.ssd_groups == 0
                and (self.ssd_heads // self.ssd_groups) % hp == 0):
            raise ValueError(
                "mamba2 layers need ssd_heads in ssd_groups equal groups, "
                "ssd_conv >= 2, ssd_state in whole sublanes (a multiple of 8) "
                "and a group's heads of ssd_head_dim filling whole 128-lane "
                "rows (ops/ssd.py's state slot)")
        if ("full_attention" not in self.layer_types
                or "moe" not in self.layer_types or not self.n_experts
                or self.first_k_dense or self.latent or self.qkv_bias
                or self.rope_by_type or self.pair_kv_heads or self.dsa
                or self.n_shared_experts > 1):
            raise ValueError(
                "mamba2 blocks go with plain GQA full_attention blocks (the "
                "paged pool and the radix tree need one that owns rows) and "
                "moe blocks of routed experts beside at most one shared "
                "expert (moe_shared_intermediate): no first_k_dense, latent "
                "attention, qkv_bias, rope_by_type, pair_kv_heads or learned "
                "sparse attention")

    def _check_hybrid(self) -> None:
        """A config with mamba / gmu / cross_attention layers runs as one
        of two layouts (class docstring: interleaved with plain attention
        layers, or the decoder-hybrid-decoder); anything else is refused by
        name."""
        types = tuple(self.layer_types)
        kinds = set(types)
        known = {"mamba", "sliding_attention", "full_attention", "gmu",
                 "cross_attention"}
        if len(types) != self.n_layers or not kinds <= known:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, got "
                f"{len(types)} of {sorted(kinds)}")
        if not (self.ssm_state > 0 and self.ssm_conv >= 2 and self.ssm_dt_rank > 0
                and (self.ssm_expand * self.hidden) % 128 == 0):
            raise ValueError(
                "mamba layers need ssm_state, ssm_dt_rank > 0, ssm_conv >= 2 "
                "and ssm_expand * hidden in whole 128-lane rows")
        if not self.cross_decoder:
            # mamba layers interleaved with plain attention layers
            if kinds != {"mamba", "full_attention"} or (
                    self.n_experts or self.latent or self.qkv_bias
                    or self.rope_by_type or self.pair_kv_heads or self.layer_norm
                    or self.attn_out_bias or self.qk_norm or self.attn_gate):
                raise ValueError(
                    "layer_types with mamba layers run as one of two layouts: "
                    "mamba layers interleaved with plain GQA full_attention "
                    "layers (the paged pool and the radix tree need one layer "
                    "that owns rows) under RMSNorm with dense SwiGLU FFNs — no "
                    "experts, latent attention, qkv_bias, rope_by_type, "
                    "pair_kv_heads, layer_norm or output bias —, or "
                    "(diff_attention) (mamba, sliding_attention) pairs, mamba, "
                    f"full_attention, (gmu, cross_attention) pairs; got {types}")
            return
        full = [i for i, t in enumerate(types) if t == "full_attention"]
        for i, t in enumerate(types):
            if t == "cross_attention" and not any(f < i for f in full):
                raise ValueError(
                    f"layer {i} is cross_attention with no full_attention "
                    "layer below it: it has no K/V to read")
            if t == "gmu" and "mamba" not in types[:i]:
                raise ValueError(
                    f"layer {i} is gmu with no mamba layer below it: it has "
                    "no memory to gate")
        if "mamba" not in kinds or len(full) != 1 or full[0] < 1:
            raise ValueError(
                "a config with state-space layers needs mamba layers and "
                "exactly one full_attention layer above the first of them")
        f = full[0]
        low, up = types[:f - 1], types[f + 1:]
        if (types[f - 1] != "mamba" or not low
                or low != ("mamba", "sliding_attention") * (len(low) // 2)
                or up != ("gmu", "cross_attention") * (len(up) // 2)):
            raise ValueError(
                "layer_types this program runs: (mamba, sliding_attention) "
                "pairs, mamba, full_attention, (gmu, cross_attention) pairs; "
                f"got {types}")
        if "sliding_attention" in kinds and (
                self.sliding_window < 2 or self.sliding_window % 16):
            raise ValueError(
                "the window layers' rows are given back by whole pages: "
                "sliding_window must be a multiple of the page (16), got "
                f"{self.sliding_window}")
        if not (self.diff_attention and self.pair_kv_heads
                and self.n_heads == 2 * self.n_kv_heads and not self.use_rope
                and self.layer_norm and self.qkv_bias and self.attn_out_bias
                and self.tie_embeddings) or self.n_experts or self.latent:
            raise ValueError(
                "a config with state-space layers runs differential "
                "attention over paired heads of 64 (n_heads = 2 * "
                "n_kv_heads), no rotary, LayerNorm, qkv and output biases, "
                "a tied head, dense FFNs: nothing else is implemented")
        if self.ssm_inner_norms:
            raise ValueError(
                "ssm_inner_norms belongs to mamba layers interleaved with "
                "full_attention layers: the decoder-hybrid-decoder's Mamba "
                "operator has none")

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def has_ssm(self) -> bool:
        return bool(self.layer_types) and "mamba" in self.layer_types

    @property
    def cross_decoder(self) -> bool:
        """Mamba layers in the decoder-hybrid-decoder layout (``_hybrid_*``)
        — what differential attention or any of its other layer kinds asks
        for — and not interleaved with plain attention layers (a kind of
        the by-kind body)."""
        kinds = set(self.layer_types or ())
        return bool(kinds and (
            self.diff_attention or kinds & {"gmu", "cross_attention"}
            or {"mamba", "sliding_attention"} <= kinds))

    @property
    def carried_state(self) -> bool:
        """Do the state slots' two arrays ride the by-kind body's carry,
        read and written inside the layer (``matrix_state``, or mamba
        layers interleaved with attention)?"""
        return bool(self.matrix_state or (self.has_ssm and not self.cross_decoder))

    def _layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types or ())
                     if t == kind)

    @property
    def mamba_layers(self) -> tuple[int, ...]:
        """The selective state-space layers (each carries a state); the
        last of them is the one whose scan output is the memory."""
        return self._layers_of("mamba")

    @property
    def window_layers(self) -> tuple[int, ...]:
        """The sliding layers that own the rows of the WINDOW group
        (``window_pool_shape``): those of a config whose layer kinds are
        stacks of their own (mamba layers, or ``heads_by_type``); none
        where window and full layers share one scanned body and one pool
        array."""
        if self.has_ssm or self.heads_by_type:
            return self._layers_of("sliding_attention")
        return ()

    def heads_of(self, kind: str) -> int:
        """Query heads of a layer of ``kind``."""
        return dict(self.heads_by_type or ()).get(kind, self.n_heads)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.hidden

    @property
    def has_window(self) -> bool:
        return bool(self.layer_types) and "sliding_attention" in self.layer_types

    @property
    def has_conv(self) -> bool:
        return bool(self.layer_types) and "conv" in self.layer_types

    @property
    def has_kda(self) -> bool:
        return bool(self.layer_types) and "kda" in self.layer_types

    @property
    def kda_layers(self) -> tuple[int, ...]:
        """The linear-attention layers (each carries a state)."""
        return self._layers_of("kda")

    @property
    def kda_inner(self) -> int:
        """Channels of q, of k and of v of a kda layer."""
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_conv_rows(self) -> int:
        """128-lane rows of a kda layer's convolution inputs in a state
        slot: ``kda_conv - 1`` positions of q, k and v, zeros up to a
        whole row."""
        return -(-(self.kda_conv - 1) * 3 * self.kda_inner // 128)

    @property
    def has_mamba2(self) -> bool:
        return bool(self.layer_types) and "mamba2" in self.layer_types

    @property
    def mamba2_layers(self) -> tuple[int, ...]:
        """The Mamba-2 blocks (each carries a state)."""
        return self._layers_of("mamba2")

    @property
    def ssd_inner(self) -> int:
        """Channels of a mamba2 block's input ``u`` and gate ``z``."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def ssd_conv_channels(self) -> int:
        """Channels of a mamba2 block's convolution: ``u``, ``B`` and ``C``."""
        return self.ssd_inner + 2 * self.ssd_groups * self.ssd_state

    @property
    def ssd_conv_rows(self) -> int:
        """128-lane rows of a mamba2 block's convolution inputs in a state
        slot (zeros up to a whole row)."""
        return -(-(self.ssd_conv - 1) * self.ssd_conv_channels // 128)

    @property
    def matrix_state(self) -> str | None:
        """The kind of a config whose layers carry a float32 MATRIX a head
        in the state slots — megabytes a snapshot, read and written inside
        the layer, copied at page boundaries, evicted in order — or None:
        "kda" (delta-rule linear attention) or "ssd" (Mamba-2)."""
        return "kda" if self.has_kda else "ssd" if self.has_mamba2 else None

    @property
    def own_stacks(self) -> bool:
        """Do the operators' leaves lie in a stack a kind (``_scan_mixed``)?"""
        return bool(self.has_conv or self.heads_by_type or self.carried_state)

    @property
    def conv_layers(self) -> tuple[int, ...]:
        """The gated short-convolution layers (each carries a state)."""
        return tuple(i for i, t in enumerate(self.layer_types or ())
                     if t == "conv")

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """The layers that own cache rows: all of them, or, beside conv
        layers, the attention layers alone."""
        if self.window_layers or self.carried_state:
            # the full layers: the window layers' rows are another group,
            # a kda or mamba layer or a mamba2 / moe block leaves none
            return self._layers_of("full_attention")
        if not self.has_conv:
            return tuple(range(self.n_layers))
        return tuple(i for i, t in enumerate(self.layer_types) if t != "conv")

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def dsa(self) -> bool:
        return self.index_topk > 0

    @property
    def full_layers(self) -> tuple[int, ...]:
        """The layers that hold an indexer (and a plane of index keys)."""
        return tuple(i for i, t in enumerate(self.indexer_types or ())
                     if t == "full")

    @property
    def held_experts(self) -> int:
        return self.n_experts // self.ep_size

    @property
    def rope_dim(self) -> int:
        """Width of the slice of a head that rotates."""
        return self.qk_rope_head_dim if self.latent else self.head_dim

    @property
    def kv_row(self) -> tuple[int, int, int]:
        """What one token leaves in one layer of the cache, as ``(planes,
        heads, width)``: K and V per kv head, or the one latent row (its
        first ``kv_lora_rank`` elements are the values too)."""
        if self.latent:
            # kv_lora_rank + qk_rope_head_dim elements and zeros up to whole
            # 128-lane tiles: the device's tiled layout stores a 576-wide
            # row as 640 whatever its logical width, and a kernel can only
            # DMA what is aligned to the tiling.
            elements = self.kv_lora_rank + self.qk_rope_head_dim
            return (1, 1, -(-elements // 128) * 128)
        if self.pair_kv_heads:
            return (2, self.n_kv_heads // 2, 2 * self.head_dim)
        return (2, self.n_kv_heads, self.head_dim)

    def kv_pool_shape(self, num_slots: int) -> tuple[int, int, int, int, int]:
        """The paged pool of this config: ``[planes, layers, heads, slots,
        width]`` (``cache/kv_pool.py`` allocates it, the entry points below
        view it as pages); ``layers`` counts the layers that own rows
        (``attn_layers``)."""
        planes, heads, width = self.kv_row
        return (planes, len(self.attn_layers), heads, num_slots, width)

    def window_pool_shape(self, num_slots: int) -> tuple | None:
        """The WINDOW group of a config whose layer kinds are stacks of
        their own (``window_layers``: beside mamba layers, or attention
        kinds of two shapes): the sliding layers' rows, ``[planes, sliding
        layers, heads, slots, width]``, under an allocator of its own — a
        row of the batch holds them for the last ``sliding_window`` tokens
        only and gives the pages before back (``engine/engine.py``). None
        for every other config: its window layers, if any, share the one
        pool array."""
        if not self.window_layers:
            return None
        planes, heads, width = self.kv_row
        return (planes, len(self.window_layers), heads, num_slots, width)

    def default_window_slots(self, max_batch: int, page_size: int,
                             wave_tokens: int = 4096) -> int:
        """Slots of the window group, derived: every batch row's window
        and the two pages around it, the window tails of the snapshots the
        tree has to keep (five for every four rows: what a waiting caller
        of a closed loop of two callers a row resumes from, and the shared
        preambles; a live row's own snapshots go first under pressure and
        share pages with the row), one prefill wave's chunk rows, the
        scratch page. The same whether a snapshot holds a state beside its
        tail or the tail alone. 0 without the group."""
        if not self.window_layers:
            return 0
        w = self.sliding_window
        want = (max_batch * (w + 2 * page_size) + (5 * max_batch // 4) * w
                + wave_tokens + page_size)
        return -(-want // page_size) * page_size

    def conv_state_shape(self, num_states: int) -> tuple | None:
        """Beside ``state_shape`` of a config with mamba or kda layers:
        the last ``ssm_conv - 1`` (``kda_conv - 1``: of q, k and v) inputs
        of each layer's convolution, ``[mamba | kda layers, slots, rows,
        128]`` in the model's dtype, under the state slots' allocator (one
        snapshot = one slot of both arrays)."""
        if self.has_kda:
            return (len(self.kda_layers), num_states, self.kda_conv_rows, 128)
        if self.has_mamba2:
            return (len(self.mamba2_layers), num_states, self.ssd_conv_rows, 128)
        if not self.has_ssm:
            return None
        rows = (self.ssm_conv - 1) * self.ssm_inner // 128
        return (len(self.mamba_layers), num_states, rows, 128)

    def state_slot_bytes(self) -> int:
        """Bytes of one snapshot (every state-bearing layer)."""
        item = jnp.dtype(self.dtype).itemsize
        if self.has_ssm:
            per = (self.ssm_state * 4 + (self.ssm_conv - 1) * item) * self.ssm_inner
            return len(self.mamba_layers) * per
        if self.has_kda:
            per = (self.kda_inner * self.kda_head_dim * 4
                   + self.kda_conv_rows * 128 * item)
            return len(self.kda_layers) * per
        if self.has_mamba2:
            per = (self.ssd_inner * self.ssd_state * 4
                   + self.ssd_conv_rows * 128 * item)
            return len(self.mamba2_layers) * per
        if self.has_conv:
            return (len(self.conv_layers) * (self.conv_L_cache - 1)
                    * self.hidden * item)
        return 0

    def state_shape(self, num_states: int) -> tuple[int, int, int, int] | None:
        """The state slots beside the pool, ``[conv layers, slots, rows,
        128]``: one slot holds, for every conv layer, the ``conv_L_cache -
        1`` positions x ``hidden`` channels the layer carries, laid in
        whole 128-lane rows (a ``[2, hidden]`` minor pair would be stored
        16 rows tall by the device's tiling). Addressed by an allocator of
        its own (``cache/kv_pool.py``): the unit is a snapshot, not a
        token. A config with mamba layers: the recurrent part, ``[mamba
        layers, slots, ssm_state, ssm_inner]`` FLOAT32 (``state_dtype``;
        the convolutions' part is ``conv_state_shape``). A config with kda
        layers: every head's matrix, ``[kda layers, slots, kda_heads *
        kda_head_dim, kda_head_dim]`` FLOAT32 — key channels down, value
        channels across, 2 MiB a layer and slot at 32 heads of 128
        (``ops/kda.py``). A config with mamba2 blocks: ``[mamba2 blocks,
        slots, ssd_inner / 128 * ssd_state, 128]`` FLOAT32 (``ops/ssd.py::
        pack_state``), 2 MiB a block and slot at 64 heads of 64 x 128. None
        for a config with none of them."""
        if self.has_kda:
            return (len(self.kda_layers), num_states, self.kda_inner,
                    self.kda_head_dim)
        if self.has_mamba2:
            return (len(self.mamba2_layers), num_states,
                    self.ssd_inner // 128 * self.ssd_state, 128)
        if self.has_ssm:
            return (len(self.mamba_layers), num_states, self.ssm_state,
                    self.ssm_inner)
        if not self.has_conv:
            return None
        rows = (self.conv_L_cache - 1) * self.hidden // 128
        return (len(self.conv_layers), num_states, rows, 128)

    @property
    def state_dtype(self):
        return jnp.float32 if self.has_ssm or self.matrix_state else self.dtype

    def default_state_slots(self, num_slots: int, max_batch: int) -> int:
        """How many state slots go with a pool of ``num_slots`` rows,
        counted BY BYTES: wanted are a working and a boundary slot for
        every batch row, the scratch slot, and a snapshot for every 100
        rows of the pool (a turn of an agent session leaves two snapshots
        and some 200-700 rows), in whole 256s — as long as the snapshots
        take no more than a quarter of what the pool's rows take. A
        240 KiB snapshot (gated convolutions) never meets that bound: 2 048
        slots beside 196 608 rows are 0.47 of 7.5 GiB. A 3 MiB snapshot
        (selective state-space layers) does — 6 682 slots would be 20 GiB
        beside 3 GiB of rows — and gets what the quarter buys, never fewer
        than five a batch row and the scratch slot (its working and
        boundary slot, the snapshot its prompt's publish leaves, the one
        a waiting session of a closed loop of two callers a row resumes
        from, and one for the shared preambles and the churn between
        them). A 12.4 MiB snapshot (kda layers' matrices) beside a latent
        row of 1 280 B a token is the same case, further out: the quarter
        of 524 288 rows buys 12, and the five a batch row hold (20.4 MiB of
        mamba2 blocks beside 3 KiB a token: the same). 0 for a config whose
        layers carry no state."""
        if not (self.has_conv or self.has_ssm or self.matrix_state):
            return 0
        want = -(-(1 + 2 * max_batch + num_slots // 100) // 256) * 256
        planes, heads, width = self.kv_row
        row_bytes = (planes * len(self.attn_layers) * heads * width
                     * jnp.dtype(self.dtype).itemsize)
        afford = num_slots * row_bytes // 4 // self.state_slot_bytes()
        return min(want, max(1 + 5 * max_batch, afford))

    def kv_index_shape(self, num_slots: int) -> tuple[int, int, int] | None:
        """The index-key plane beside the pool, ``[full layers, slots,
        index_head_dim]``, addressed by the pool's slot numbers; None for
        a config without an indexer."""
        if not self.dsa:
            return None
        return (len(self.full_layers), num_slots, self.index_head_dim)

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


def _attn_dims(cfg: ModelConfig, n_heads: int | None = None) -> dict:
    """``name -> (in, out)`` of a layer's attention matrices (``n_heads``:
    the query heads of the layer's kind, where kinds differ)."""
    H = cfg.hidden
    if cfg.latent:
        q_out = cfg.n_heads * cfg.head_dim
        kv_out = cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        # queries through a bottleneck, or (q_lora_rank 0) projected directly
        q_dims = {"wq_a": (H, cfg.q_lora_rank),
                  "wq_b": (cfg.q_lora_rank, q_out)} if cfg.q_lora_rank else {
                      "wq": (H, q_out)}
        gate = {"wg": (H, cfg.n_heads)} if cfg.attn_gate else {}
        return q_dims | {
            "wkv_a": (H, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "wkv_b": (cfg.kv_lora_rank, kv_out),
            "wo": (cfg.n_heads * cfg.v_head_dim, H),
        } | gate
    n_heads = n_heads or cfg.n_heads
    qd, kvd = n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    dims = {"wq": (H, qd), "wk": (H, kvd), "wv": (H, kvd), "wo": (qd, H)}
    if cfg.attn_gate:
        dims["wg"] = (H, n_heads)
    return dims


def _ffn_dims(hidden: int, width: int, prefix: str = "w_",
              gated: bool = True) -> dict:
    gate = {prefix + "gate": (hidden, width)} if gated else {}
    return gate | {prefix + "up": (hidden, width),
                   prefix + "down": (width, hidden)}


def _draw_stack(keys, n: int, dims: dict, cfg: ModelConfig, quant_axis) -> dict:
    """One stack of ``n`` layers: the norms and every ``[n, in, out]``
    matrix of ``dims`` (contraction is each layer block's axis 0), one key
    a matrix in the order of ``dims``."""
    stack = {
        "attn_norm": jnp.ones((n, cfg.hidden), dtype=cfg.dtype),
        "mlp_norm": jnp.ones((n, cfg.hidden), dtype=cfg.dtype),
    }
    if cfg.latent and not cfg.has_kda:  # (beside kda layers: in params["mla"])
        if cfg.q_lora_rank:
            stack["q_norm"] = jnp.ones((n, cfg.q_lora_rank), dtype=cfg.dtype)
        stack["kv_norm"] = jnp.ones((n, cfg.kv_lora_rank), dtype=cfg.dtype)
    for k, (name, (i_dim, o_dim)) in zip(keys, dims.items()):
        stack[name], s = _draw_blocks(
            k, n, (i_dim, o_dim), i_dim, cfg.dtype, quant_axis
        )
        if s is not None:
            stack[name + "_s"] = s
    return stack


def _draw_operators(key, cfg: ModelConfig) -> dict:
    """The operators' leaves of a config with conv layers, a stack a kind:
    ``conv`` — ``in_proj`` [H, 3H] (the gates B, C and the input x, in
    that order), the depthwise kernel ``conv_w`` [taps, H] (tap 0 the
    oldest position) and ``out_proj`` [H, H] — and ``attn``: the four
    attention matrices and the per-head norms' weights (seeded draws
    around 1, so that they are exercised)."""
    H, D = cfg.hidden, cfg.head_dim
    n_conv, n_attn = len(cfg.conv_layers), len(cfg.attn_layers)
    kc = jax.random.split(key, 8)
    conv = {}
    for k, (name, (i_dim, o_dim)) in zip(kc, {
        "in_proj": (H, 3 * H), "out_proj": (H, H),
    }.items()):
        conv[name], _ = _draw_blocks(k, n_conv, (i_dim, o_dim), i_dim,
                                     cfg.dtype, None)
    conv["conv_w"], _ = _draw_blocks(
        kc[2], n_conv, (cfg.conv_L_cache, H), cfg.conv_L_cache, cfg.dtype, None)
    attn = {}
    for k, (name, (i_dim, o_dim)) in zip(kc[3:], _attn_dims(cfg).items()):
        attn[name], _ = _draw_blocks(k, n_attn, (i_dim, o_dim), i_dim,
                                     cfg.dtype, None)
    if cfg.qk_norm:
        for k, name in zip(jax.random.split(kc[7]), ("q_head_norm", "k_head_norm")):
            attn[name] = (
                1.0 + 0.1 * jax.random.normal(k, (n_attn, D), jnp.float32)
            ).astype(cfg.dtype)
    return {"conv": conv, "attn": attn}


def _draw_kda(key, cfg: ModelConfig) -> dict:
    """The operators' leaves of a config with kda layers, a stack a kind:
    ``kda`` — ``wq`` / ``wk`` / ``wv`` [H, C] (C = ``kda_inner``), the
    depth-wise kernel ``conv_w`` [taps, 3C] over their concatenation (tap 0
    the oldest position), the gate's ``w_alpha`` [H, C] (a FULL projection),
    ``dt_bias`` [C] and ``A_log`` [heads] (``exp(A_log)`` uniform in [1,
    16]: the gate is driven through its whole range), ``w_beta`` [H, heads],
    the output norm's weight ``o_norm`` [C] (a head's own 128 each: a
    seeded draw around 1), the output gate ``wg`` [H, C] and ``wo`` [C, H]
    — and ``mla``: the latent layers' matrices (``_attn_dims``: ``wq``
    direct, ``wg`` [H, heads]) and ``kv_norm``."""
    H, C, nh = cfg.hidden, cfg.kda_inner, cfg.kda_heads
    n_k, n_m = len(cfg.kda_layers), len(cfg.attn_layers)
    keys = iter(jax.random.split(key, 24))
    f32 = jnp.float32

    def mats(n: int, dims: dict) -> dict:
        return {name: _draw_blocks(next(keys), n, shape, shape[0], cfg.dtype, None)[0]
                for name, shape in dims.items()}

    op = mats(n_k, {"wq": (H, C), "wk": (H, C), "wv": (H, C), "w_alpha": (H, C),
                    "w_beta": (H, nh), "wg": (H, C), "wo": (C, H)})
    op["conv_w"], _ = _draw_blocks(
        next(keys), n_k, (cfg.kda_conv, 3 * C), cfg.kda_conv, cfg.dtype, None)
    op["A_log"] = jnp.log(jax.random.uniform(next(keys), (n_k, nh), f32, 1.0, 16.0))
    op["dt_bias"] = 0.5 * jax.random.normal(next(keys), (n_k, C), f32)
    op["o_norm"] = (1.0 + 0.1 * jax.random.normal(next(keys), (n_k, C), f32)
                    ).astype(cfg.dtype)
    mla = mats(n_m, _attn_dims(cfg))
    mla["kv_norm"] = jnp.ones((n_m, cfg.kv_lora_rank), dtype=cfg.dtype)
    return {"kda": op, "mla": mla}


def _draw_blocks_of_one(key, cfg: ModelConfig) -> dict:
    """The pytree of a config with mamba2 layers (blocks of one sublayer)
    but its embedding and head: ``layers`` — every block's ONE pre-norm
    (``attn_norm``, whatever the block) — and a stack a kind: ``mamba2``
    (``in_proj`` [H, di + (di + 2 G N) + heads]: the gate z, the
    convolution's u | B | C, dt; ``conv_w`` [taps, di + 2 G N], tap 0 the
    oldest, ``conv_b``; ``dt_bias`` the inverse softplus of a log-uniform
    [1e-3, 1e-1] floored at 1e-4, ``A_log = log(uniform[1, 16])``, ``D``
    around 1 (the family's initialisation: decays from about 0.2 to 0.999 a
    token); the gated norm's weight ``ssd_norm`` [di]; ``out_proj``),
    ``attn`` (``wq`` / ``wk`` / ``wv`` / ``wo``) and ``moe`` (``w_router``,
    ``router_bias``, the held experts' ``w_up`` / ``w_down`` [n, E_held, .,
    .], their width stored in whole 128-lane rows, and the shared expert's
    ``ws_up`` / ``ws_down`` — plus the gates under ``mlp_gated``)."""
    H, di, nh = cfg.hidden, cfg.ssd_inner, cfg.ssd_heads
    n_m, n_a = len(cfg.mamba2_layers), len(cfg.attn_layers)
    n_e, E = cfg.layer_types.count("moe"), cfg.held_experts
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 32))

    def mats(n: int, dims: dict) -> dict:
        return {name: _draw_blocks(next(keys), n, shape, shape[0], cfg.dtype, None)[0]
                for name, shape in dims.items()}

    def near(shape, centre: float, spread: float, dtype=cfg.dtype):
        return (centre + spread * jax.random.normal(next(keys), shape, f32)
                ).astype(dtype)

    m2 = mats(n_m, {"in_proj": (H, di + cfg.ssd_conv_channels + nh),
                    "out_proj": (di, H)})
    m2["conv_w"], _ = _draw_blocks(
        next(keys), n_m, (cfg.ssd_conv, cfg.ssd_conv_channels), cfg.ssd_conv,
        cfg.dtype, None)
    m2["conv_b"] = near((n_m, cfg.ssd_conv_channels), 0.0, 0.02)
    dt = jnp.maximum(jnp.exp(
        jax.random.uniform(next(keys), (n_m, nh), f32)
        * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3)), 1e-4)
    m2["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    m2["A_log"] = jnp.log(jax.random.uniform(next(keys), (n_m, nh), f32, 1.0, 16.0))
    m2["D"] = near((n_m, nh), 1.0, 0.1, f32)
    m2["ssd_norm"] = near((n_m, di), 1.0, 0.1)
    moe = mats(n_e, {"w_router": (H, cfg.n_experts)} | _ffn_dims(
        H, cfg.moe_shared_intermediate, "ws_", cfg.mlp_gated))
    # The routed experts are STORED in whole 128-lane rows (1856 -> 1920):
    # zero columns of w_up / rows of w_down, exact (relu(0)^2 = 0, silu(0) *
    # 0 = 0). At the published width the grouped product's operand is
    # relaid by a copy of the whole stack every launch (1.5 GiB: the
    # compile-only tool, benchmarks/tools/aot_fit_ssd.py).
    F = cfg.moe_intermediate
    pad = -F % 128
    for name, (i_dim, o_dim) in _ffn_dims(H, F, gated=cfg.mlp_gated).items():
        w, _ = _draw_blocks(next(keys), n_e * E, (i_dim, o_dim), i_dim, cfg.dtype, None)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)) if i_dim == F
                    else ((0, 0), (0, 0), (0, pad)))
        moe[name] = w.reshape(n_e, E, *w.shape[1:])
    moe["router_bias"] = near((n_e, cfg.n_experts), 0.0, 0.02, f32)
    return {
        "layers": {"attn_norm": jnp.ones((cfg.n_layers, H), dtype=cfg.dtype)},
        "mamba2": m2, "attn": mats(n_a, _attn_dims(cfg)), "moe": moe,
        "final_norm": jnp.ones((H,), dtype=cfg.dtype),
    }


def _draw_kinds(key, cfg: ModelConfig) -> dict:
    """The attention matrices of a config with ``heads_by_type``: a stack a
    kind, ``{layer type: {wq, wk, wv, wo[, wg]}}`` over that kind's layers
    in layer order, each of its own shape."""
    out = {}
    for i, (kind, heads) in enumerate(cfg.heads_by_type):
        n = cfg.layer_types.count(kind)
        dims = _attn_dims(cfg, heads)
        out[kind] = {
            name: _draw_blocks(k, n, shape, shape[0], cfg.dtype, None)[0]
            for k, (name, shape) in zip(
                jax.random.split(jax.random.fold_in(key, i), len(dims)),
                dims.items())
        }
    return out


@partial(jax.jit, static_argnames=("cfg", "weight_quant", "mesh"))
def _init_params(key, cfg: ModelConfig, weight_quant, mesh) -> dict:
    # Contraction axis of a block to quantize along, or None for float.
    def axis(a):
        return a if weight_quant is not None else None

    keys = jax.random.split(key, 9)
    H, V = cfg.hidden, cfg.vocab_size
    if cfg.cross_decoder:
        params = _draw_hybrid(jax.random.fold_in(key, 106), cfg)
        n_blk = _vocab_blocks(V, H, H * cfg.intermediate)
        w, _ = _draw_blocks(keys[7], n_blk, (V // n_blk, H), H, cfg.dtype, None)
        return params | {"embed": w.reshape(V, H)}
    if cfg.has_mamba2:
        params = _draw_blocks_of_one(jax.random.fold_in(key, 109), cfg)
        return _draw_vocab(params, cfg, keys, axis, mesh, cfg.moe_shared_intermediate)
    L = cfg.n_layers - cfg.first_k_dense  # layers of the main stack
    ffn_width = cfg.moe_intermediate if cfg.n_experts else cfg.intermediate
    ffn = _ffn_dims(H, ffn_width)
    # Beside conv layers the operators' leaves are stacks of their own
    # (below): the per-layer stacks keep the norms and the FFN.
    op_dims = {} if (cfg.has_conv or cfg.heads_by_type or cfg.has_kda
                     or cfg.has_ssm) else _attn_dims(cfg)
    dims = dict(op_dims)
    dims |= {"w_router": (H, cfg.n_experts)} if cfg.n_experts else ffn
    layers = _draw_stack(keys, L, dims, cfg, axis(0))
    if cfg.n_experts:
        # [L, E, in, out], one expert's block at a time: the experts this
        # chip holds (all of them without a share).
        E = cfg.held_experts
        for k, (name, (i_dim, o_dim)) in zip(
            jax.random.split(keys[6], 3), ffn.items()
        ):
            w, _ = _draw_blocks(k, L * E, (i_dim, o_dim), i_dim, cfg.dtype, None)
            layers[name] = w.reshape(L, E, i_dim, o_dim)
    if cfg.n_shared_experts:
        shared = _ffn_dims(H, cfg.n_shared_experts * cfg.moe_intermediate, "ws_")
        for k, (name, (i_dim, o_dim)) in zip(
            jax.random.split(jax.random.fold_in(key, 101), 3), shared.items()
        ):
            layers[name], _ = _draw_blocks(
                k, L, (i_dim, o_dim), i_dim, cfg.dtype, None
            )
    if cfg.router_scoring == "sigmoid":
        # The learned selection bias (the source's e_score_correction_bias):
        # a seeded non-zero draw, so that choosing by score + bias and
        # weighing by the score differ. Small beside the scores' own spread
        # (~0.2): the trained bias BALANCES the experts' load, and a draw
        # of 0.1 left 38 of 64 experts hit by 32 rows where 0.02 leaves 55
        # (an unbiased router 56) while still changing half the choices.
        layers["router_bias"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 103), (L, cfg.n_experts), jnp.float32
        )
    if cfg.qkv_bias:
        qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        layers["bq"] = jnp.zeros((L, qd), dtype=cfg.dtype)
        layers["bk"] = jnp.zeros((L, kvd), dtype=cfg.dtype)
        layers["bv"] = jnp.zeros((L, kvd), dtype=cfg.dtype)
    params = {"final_norm": jnp.ones((H,), dtype=cfg.dtype), "layers": layers}
    if cfg.dsa:
        # The indexers, stacked over the FULL layers only (a shared layer
        # has none): index queries from the query bottleneck, one key a
        # token from the hidden state (LayerNorm with a bias: a seeded
        # non-zero draw, so that it is exercised), a weight a head.
        n_full = len(cfg.full_layers)
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        ik = jax.random.split(jax.random.fold_in(key, 104), 4)
        idx = {}
        for k, (name, (i_dim, o_dim)) in zip(ik, {
            "wq": (cfg.q_lora_rank, Hi * Di), "wk": (H, Di), "ww": (H, Hi),
        }.items()):
            idx[name], _ = _draw_blocks(
                k, n_full, (i_dim, o_dim), i_dim, cfg.dtype, None
            )
        idx["k_norm"] = jnp.ones((n_full, Di), dtype=cfg.dtype)
        idx["k_bias"] = (0.02 * jax.random.normal(ik[3], (n_full, Di))).astype(
            cfg.dtype
        )
        params["indexer"] = idx
    if cfg.first_k_dense:
        params["dense_layers"] = _draw_stack(
            jax.random.split(jax.random.fold_in(key, 102), 8),
            cfg.first_k_dense,
            op_dims | _ffn_dims(H, cfg.intermediate), cfg, axis(0),
        )
    if cfg.has_conv:
        params |= _draw_operators(jax.random.fold_in(key, 105), cfg)
    if cfg.heads_by_type:
        params |= _draw_kinds(jax.random.fold_in(key, 107), cfg)
    if cfg.has_kda:
        params |= _draw_kda(jax.random.fold_in(key, 108), cfg)
    if cfg.has_ssm:
        params |= _draw_interleaved(jax.random.fold_in(key, 110), cfg)
    return _draw_vocab(params, cfg, keys, axis, mesh, ffn_width)


def _draw_vocab(params: dict, cfg: ModelConfig, keys, axis, mesh,
                ffn_width: int) -> dict:
    """``_init_params``' tail: the embedding and the head, drawn in blocks no
    larger than a layer's largest slab, and the mesh's constraint."""
    H, V = cfg.hidden, cfg.vocab_size
    qd = cfg.n_heads * cfg.head_dim
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
    if cfg.latent:
        # Engine refuses a mesh for such a config (no cache axis to shard
        # by head); the names say what a tp share would split.
        for name in ("wq", "wk", "wv"):
            del axes["layers"][name]
        if cfg.q_lora_rank:
            axes["layers"] |= {
                "wq_a": ("layer", "embed", "q_lora"),
                "q_norm": ("layer", "q_lora"),
                "wq_b": ("layer", "q_lora", "q_heads"),
            }
        else:
            axes["layers"]["wq"] = ("layer", "embed", "q_heads")
        if cfg.attn_gate:
            axes["layers"]["wg"] = ("layer", "embed", "q_heads")
        axes["layers"] |= {
            "wkv_a": ("layer", "embed", "kv_lora"),
            "kv_norm": ("layer", "kv_lora"),
            "wkv_b": ("layer", "kv_lora", "q_heads"),
        }
    if cfg.dsa:
        axes["indexer"] = {
            "wq": ("layer", "q_lora", "index"),
            "wk": ("layer", "embed", "index"),
            "ww": ("layer", "embed", "index"),
            "k_norm": ("layer", "index"),
            "k_bias": ("layer", "index"),
        }
    if cfg.n_shared_experts:
        axes["layers"] |= {
            "ws_gate": ("layer", "embed", "ffn"),
            "ws_up": ("layer", "embed", "ffn"),
            "ws_down": ("layer", "ffn", "embed"),
        }
    if cfg.router_scoring == "sigmoid":
        axes["layers"]["router_bias"] = ("layer", "expert")
    if cfg.first_k_dense:
        dense = {k: v for k, v in axes["layers"].items()
                 if not k.startswith(("ws_", "w_router", "router_bias"))}
        axes["dense_layers"] = dense | {
            "w_gate": ("layer", "embed", "ffn"),
            "w_up": ("layer", "embed", "ffn"),
            "w_down": ("layer", "ffn", "embed"),
        }
    if cfg.heads_by_type:
        # Engine refuses a mesh for such a config; the names say what a tp
        # share would split (the gate by head, as wq's columns).
        ops = {name: axes["layers"].pop(name) for name in ("wq", "wk", "wv", "wo")}
        for stack in ("dense_layers",) if cfg.first_k_dense else ():
            for name in ops:
                del axes[stack][name]
        if cfg.attn_gate:
            ops["wg"] = ("layer", "embed", "q_heads")
        for kind, _ in cfg.heads_by_type:
            axes[kind] = dict(ops)
    if cfg.has_kda:
        # Engine refuses a mesh for such a config; the names say what a tp
        # share would split (a kda layer by head, as attention).
        names = [name for name in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "wg")
                 if name in axes["layers"]]
        axes["mla"] = {name: axes["layers"].pop(name) for name in names}
        for name in names if cfg.first_k_dense else ():
            del axes["dense_layers"][name]
        by_head = ("layer", "embed", "q_heads")
        axes["kda"] = {
            "wq": by_head, "wk": by_head, "wv": by_head, "w_alpha": by_head,
            "w_beta": by_head, "wg": by_head, "wo": ("layer", "q_heads", "embed"),
            "conv_w": ("layer", None, "q_heads"), "A_log": ("layer", "q_heads"),
            "dt_bias": ("layer", "q_heads"), "o_norm": ("layer", "q_heads"),
        }
    if cfg.has_ssm and not cfg.cross_decoder:
        # Engine refuses a mesh for such a config; the names say what a tp
        # share would split (a mamba operator by channel, as the FFN).
        axes["attn"] = {name: axes["layers"].pop(name)
                        for name in ("wq", "wk", "wv", "wo")}
        by_channel = ("layer", "ffn")
        axes["mamba"] = {
            "in_x": ("layer", "embed", "ffn"), "in_z": ("layer", "embed", "ffn"),
            "x_proj": ("layer", "ffn", None), "dt_proj": ("layer", None, "ffn"),
            "out_proj": ("layer", "ffn", "embed"), "conv_w": ("layer", None, "ffn"),
            "conv_b": by_channel, "dt_bias": by_channel, "D": by_channel,
            "A_log": ("layer", None, "ffn")}
        if cfg.ssm_inner_norms:
            axes["mamba"] |= {"dt_norm": ("layer", None), "b_norm": ("layer", None),
                              "c_norm": ("layer", None)}
    if cfg.has_mamba2:
        # Engine refuses a mesh for such a config; the names say what a tp
        # share would split (a mamba2 block by head: one group a chip).
        by_head = ("layer", "embed", "q_heads")
        axes["layers"] = {"attn_norm": ("layer", "embed")}
        axes["attn"] = {"wq": by_head, "wk": ("layer", "embed", "kv_heads"),
                        "wv": ("layer", "embed", "kv_heads"),
                        "wo": ("layer", "q_heads", "embed")}
        axes["mamba2"] = {
            "in_proj": by_head, "out_proj": ("layer", "q_heads", "embed"),
            "conv_w": ("layer", None, "q_heads"), "conv_b": ("layer", "q_heads"),
            "dt_bias": ("layer", "q_heads"), "A_log": ("layer", "q_heads"),
            "D": ("layer", "q_heads"), "ssd_norm": ("layer", "q_heads")}
        axes["moe"] = {
            "w_router": ("layer", "embed", "expert"),
            "router_bias": ("layer", "expert"),
            "w_up": ("layer", "expert", "embed", "ffn"),
            "w_down": ("layer", "expert", "ffn", "embed"),
            "ws_up": ("layer", "embed", "ffn"), "ws_down": ("layer", "ffn", "embed")}
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


def _wmm(lp: dict, name: str, eq: str, x: jnp.ndarray, **einsum_kw):
    """Dense matmul honoring W8A16 storage (``ops/wquant.py``): int8
    weights feed the MXU as bf16 (only HBM *streaming* shrinks — compute
    precision is unchanged) and the per-out-channel scale applies to the
    output, which is exact for per-out-channel quantization."""
    w = lp[name]
    if w.dtype == jnp.int8:
        y = jnp.einsum(eq, x, w.astype(x.dtype), precision=_PREC, **einsum_kw)
        y = y * lp[name + "_s"]
        # The f32 scale would otherwise promote the whole activation
        # stream to f32 from the first quantized layer on — cast back
        # unless the caller asked for a widened output (the logits head).
        if "preferred_element_type" not in einsum_kw:
            y = y.astype(x.dtype)
        return y
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
    # Without the barrier XLA folds the reshape to heads below into the
    # products, which then want each weight as [heads, D, H]: a layer scan
    # slices wq / wk / wv out of their stacks and copies the slices,
    # transposed, before every product — 2.3 ms of Qwen2-7B's 14.2 ms
    # decode step where the products alone take 0.7. Behind it each
    # product reads its stack at the layer's index, as the FFN's do, in
    # every program (PERF.md §5, PR 40).
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    B, S = x.shape[:2]
    # -1: the layer's own head count where kinds differ (heads_by_type).
    q = q.reshape(B, S, -1 if cfg.heads_by_type else cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:  # over each head, before the rotary
        q = rms_norm(q, lp["q_head_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_head_norm"], cfg.rms_eps)
    return q, k, v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)


def _latent_split(lp: dict, cfg: ModelConfig):
    """``wkv_b`` [r, Hq * (nope + v)] as its two per-head parts:
    ``(W_k [r, Hq, nope], W_v [r, Hq, v])``."""
    w = lp["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


def _latent_cq(lp: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The normalised query bottleneck ``RMSNorm(W_qa x)`` [B, S, q_lora]:
    what the heads' queries and an indexer's queries are projected from."""
    return rms_norm(_wmm(lp, "wq_a", "bsh,hr->bsr", x), lp["q_norm"], cfg.rms_eps)


def _latent_qkv(lp: dict, x: jnp.ndarray, cfg: ModelConfig, positions, inv_freq,
                c_q: jnp.ndarray | None = None):
    """Latent attention's inputs from ``x`` [B, S, H]: the ABSORBED query
    ``q_nope W_k^T || RoPE(q_rope)`` [B, S, Hq, W] and the cache row
    ``RMSNorm(c_kv) || RoPE(k_rope)`` [B, S, 1, W] — key of every head, and
    in its first ``r`` elements their value too; both end in zeros up to
    the row's stored width ``W`` (``ModelConfig.kv_row``). Scores of the
    two against each other are ``q_nope . k_nope + q_rope . k_rope`` with
    K derived from the row: the same function, never materialised."""
    B, S = x.shape[:2]
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if not cfg.q_lora_rank:  # no bottleneck, no query norm
        q = _wmm(lp, "wq", "bsh,hd->bsd", x)
    else:
        if c_q is None:
            c_q = _latent_cq(lp, x, cfg)
        q = _wmm(lp, "wq_b", "bsr,rd->bsd", c_q)
    if B * S < (cfg.q_lora_rank or cfg.hidden):
        # Fewer tokens than the weight has rows (a decode step): without
        # the barrier XLA lays wq_b out for the heads' batched product
        # below and copies the layer's slice of it, transposed — 2 x 67 MB
        # a layer at 2048 x 16384, 1.9 ms of a decode step — where
        # transposing these few activations costs nothing (PERF.md, PR 33).
        q = jax.lax.optimization_barrier(q)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    q_rope = apply_rope(q[..., dn:], positions, inv_freq)
    kv = _wmm(lp, "wkv_a", "bsh,hr->bsr", x)
    c_kv = rms_norm(kv[..., :r], lp["kv_norm"], cfg.rms_eps)
    k_rope = apply_rope(kv[..., None, r:], positions, inv_freq)
    pad = cfg.kv_row[2] - r - cfg.qk_rope_head_dim
    row = jnp.concatenate(
        [c_kv[..., None, :], k_rope, jnp.zeros((B, S, 1, pad), k_rope.dtype)],
        axis=-1,
    )
    with jax.named_scope("rm_mla_absorb"):
        q_abs = jnp.einsum(
            "bsqn,rqn->bsqr", q[..., :dn], _latent_split(lp, cfg)[0],
            precision=_PREC,
        )
    q_pad = jnp.zeros((B, S, cfg.n_heads, pad), q_rope.dtype)
    return jnp.concatenate([q_abs, q_rope, q_pad], axis=-1), row


def _index_in(ip: dict, c_q: jnp.ndarray, h: jnp.ndarray, cfg: ModelConfig,
              positions, inv_freq):
    """A full layer's indexer on ``h`` [B, S, H] and the query bottleneck
    ``c_q``: index queries [B, S, Hi, Di] and the token's index key
    [B, S, Di] — the first ``qk_rope_head_dim`` elements of each rotated
    with the layer's RoPE — and the heads' weights [B, S, Hi] float32,
    already times ``Hi^-1/2 * Di^-1/2``."""
    B, S = h.shape[:2]
    Hi, Di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim

    def rotate(t):  # [B, S, heads, Di]
        return jnp.concatenate(
            [apply_rope(t[..., :dr], positions, inv_freq), t[..., dr:]], axis=-1
        )

    qi = jnp.einsum("bsr,rd->bsd", c_q, ip["wq"], precision=_PREC)
    ki = jnp.einsum("bsh,hd->bsd", h, ip["wk"], precision=_PREC)
    k32 = ki.astype(jnp.float32)
    k32 = k32 - jnp.mean(k32, axis=-1, keepdims=True)
    k32 = k32 * jax.lax.rsqrt(jnp.mean(k32 * k32, axis=-1, keepdims=True) + 1e-6)
    ki = (k32 * ip["k_norm"].astype(jnp.float32)
          + ip["k_bias"].astype(jnp.float32)).astype(h.dtype)
    wi = jnp.einsum(
        "bsh,hj->bsj", h.astype(jnp.float32), ip["ww"].astype(jnp.float32),
        precision=_PREC,
    ) * (Hi ** -0.5 * Di ** -0.5)
    return rotate(qi.reshape(B, S, Hi, Di)), rotate(ki[:, :, None])[:, :, 0], wi


def _attn_in(lp: dict, x: jnp.ndarray, cfg: ModelConfig, positions, inv_freq,
             rope_scale, c_q: jnp.ndarray | None = None):
    """``(q, k, v)`` of ``x`` [B, S, H], rotated, as the attention ops
    take them: per-head K and V, or for latent attention the absorbed
    query, the cache row as the one shared key head, and ``v`` None (the
    values are the row's first ``kv_lora_rank`` elements)."""
    if cfg.latent:
        return *_latent_qkv(lp, x, cfg, positions, inv_freq, c_q), None
    q, k, v = _qkv(lp, x, cfg)
    if not cfg.use_rope:  # no positional encoding
        return q, k, v
    q = apply_rope(q, positions, inv_freq, rope_scale)
    k = apply_rope(k, positions, inv_freq, rope_scale)
    return q, k, v


def _mlp(lp: dict, x: jnp.ndarray, prefix: str = "w_") -> jnp.ndarray:
    if prefix + "gate" not in lp:  # un-gated: relu(up)^2
        up = _wmm(lp, prefix + "up", "bsh,hi->bsi", x)
        return _wmm(lp, prefix + "down", "bsi,ih->bsh", jnp.square(jax.nn.relu(up)))
    gate = jax.nn.silu(_wmm(lp, prefix + "gate", "bsh,hi->bsi", x))
    up = _wmm(lp, prefix + "up", "bsh,hi->bsi", x)
    return _wmm(lp, prefix + "down", "bsi,ih->bsh", gate * up)


def _pair_heads(cfg: ModelConfig, q, k, v):
    """``(q, k, v)`` of ``[B, S, heads, 64]`` as the paged kernels take
    them under ``pair_kv_heads``: kv heads ``2j`` and ``2j + 1`` side by
    side as wide head ``j`` ``[B, S, Hkv / 2, 128]`` (a reshape: what the
    pool stores), and every query ``[B, S, Hq, 128]`` zero in its
    partner's 64 lanes — its scores against the wide key are its scores
    against its own key, exactly, and its own 64 lanes of the output
    (``_own_lanes``) are its attention over its own values. The logits'
    factor stays ``head_dim ** -0.5``: it is passed, not derived from the
    row's width."""
    B, S, _, D = q.shape
    wide = cfg.n_kv_heads // 2
    return (_pair_q(cfg, q), k.reshape(B, S, wide, 2 * D),
            v.reshape(B, S, wide, 2 * D))


def _pair_q(cfg: ModelConfig, q: jnp.ndarray) -> jnp.ndarray:
    """``_pair_heads``' queries alone (a layer that projects no K / V)."""
    B, S, Hq, D = q.shape
    wide, g = cfg.n_kv_heads // 2, Hq // cfg.n_kv_heads
    own = jnp.eye(2, dtype=q.dtype)[:, None, :, None]  # [half, 1, lanes' half, 1]
    return (q.reshape(B, S, wide, 2, g, 1, D) * own).reshape(B, S, Hq, 2 * D)


def _own_lanes(cfg: ModelConfig, out: jnp.ndarray) -> jnp.ndarray:
    """``_pair_heads``' output ``[B, S, Hq, 128]`` cut to each query
    head's own 64 lanes: ``[B, S, Hq, 64]``."""
    B, S, Hq, W = out.shape
    wide, g, D = cfg.n_kv_heads // 2, Hq // cfg.n_kv_heads, W // 2
    o = out.reshape(B, S, wide, 2, g, 2, D)
    return jnp.stack([o[:, :, :, 0, :, 0], o[:, :, :, 1, :, 1]], axis=3).reshape(
        B, S, Hq, D)


def _state_rows(cfg: ModelConfig, slots_state: jnp.ndarray) -> jnp.ndarray:
    """State slots' content ``[..., rows, 128]`` as positions x channels
    ``[..., conv_L_cache - 1, hidden]``."""
    return slots_state.reshape(
        *slots_state.shape[:-2], cfg.conv_L_cache - 1, cfg.hidden)


def _short_conv(lp: dict, h: jnp.ndarray, state: jnp.ndarray, cfg: ModelConfig,
                snap_at: jnp.ndarray | None) -> tuple:
    """The gated short convolution on ``h`` [B, S, H] from ``state`` [B,
    taps - 1, H] (the gated input ``z`` of the positions before the first:
    zeros at position 0): ``[Bg, Cg, x] = h W_in``; ``z = Bg * x``; ``c_t
    = sum_k w[k] * z_{t - (taps - 1) + k}`` (depthwise, causal); the
    operator's output ``(Cg * c) W_out``. Returns it with the state after
    ``snap_at`` [B, K] tokens of these ``S`` each (``[B, K, taps - 1,
    H]``; 0 hands back ``state``): an index a row, so a padded row's
    state is exact at its true end, and a snapshot inside a chunk is an
    input, not a shape. ``snap_at`` None: the one state after all ``S``
    (a decode step's: a slice, no gather)."""
    taps, S = cfg.conv_L_cache, h.shape[1]
    with jax.named_scope("rm_shortconv"):
        gates = _wmm(lp, "in_proj", "bsh,hd->bsd", h)
        b_gate, c_gate, x_in = jnp.split(gates, 3, axis=-1)
        z = b_gate * x_in
        zs = jnp.concatenate([state.astype(z.dtype), z], axis=1)  # [B, S+taps-1, H]
        w = lp["conv_w"].astype(jnp.float32)
        c = sum(w[k] * zs[:, k:k + S].astype(jnp.float32) for k in range(taps))
        y = _wmm(lp, "out_proj", "bsh,hd->bsd", c_gate * c.astype(z.dtype))
        if snap_at is None:
            return y, zs[:, None, S:]
        at = snap_at[:, :, None] + jnp.arange(taps - 1)  # [B, K, taps-1]
        states = jax.vmap(lambda rows, ix: rows[ix])(zs, at)
    return y, states


def _conv_store(conv: jnp.ndarray, rows: int) -> jnp.ndarray:
    """A kda or mamba2 layer's convolution inputs ``[..., taps - 1,
    channels]`` as a state slot holds them: ``[..., rows, 128]``, zeros up
    to a whole row."""
    flat = conv.reshape(*conv.shape[:-2], -1)
    pad = rows * 128 - flat.shape[-1]
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((*flat.shape[:-1], pad), flat.dtype)], axis=-1)
    return flat.reshape(*flat.shape[:-1], rows, 128)


def _conv_load(stored: jnp.ndarray, positions: int, channels: int) -> jnp.ndarray:
    """``_conv_store``'s inverse."""
    flat = stored.reshape(*stored.shape[:-2], -1)[..., :positions * channels]
    return flat.reshape(*flat.shape[:-1], positions, channels)


def _kda_conv_store(cfg: ModelConfig, conv: jnp.ndarray) -> jnp.ndarray:
    return _conv_store(conv, cfg.kda_conv_rows)


def _kda_conv_load(cfg: ModelConfig, stored: jnp.ndarray) -> jnp.ndarray:
    return _conv_load(stored, cfg.kda_conv - 1, 3 * cfg.kda_inner)


def _ssd_conv_load(cfg: ModelConfig, stored: jnp.ndarray) -> jnp.ndarray:
    return _conv_load(stored, cfg.ssd_conv - 1, cfg.ssd_conv_channels)


def _mamba2(lp: dict, h: jnp.ndarray, conv0: jnp.ndarray, cfg: ModelConfig,
            snap_at: jnp.ndarray | None, run) -> tuple:
    """The Mamba-2 mixer on its normed input ``h`` [B, S, H] from the
    ``ssd_conv - 1`` inputs of its convolution before the first position
    (``conv0`` [B, taps - 1, channels]): ``[z | xBC | dt] = h W_in``; ``xBC
    = SiLU(conv(xBC) + b)``; ``Delta = softplus(dt + dt_bias)``, the
    log-decay ``g = -exp(A_log) Delta`` a head; ``y, extra = run(Delta u, g,
    B, C)`` is the recurrence through the state slots (``ops/ssd.py``); then
    the skip ``D u``, the gate FIRST and an RMS norm inside each group, and
    ``W_out``. Returns ``(f [B, S, H], the convolution's inputs before
    snap_at [B, K, taps - 1, channels], extra)``."""
    B, S = h.shape[:2]
    nh, P, G, N = cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_groups, cfg.ssd_state
    di, f32 = cfg.ssd_inner, jnp.float32
    with jax.named_scope("rm_ssd"):
        zxd = _wmm(lp, "in_proj", "bsh,hd->bsd", h)
        xbc, conv = kda.short_conv(
            zxd[..., di:di + cfg.ssd_conv_channels], lp["conv_w"], conv0,
            snap_at, bias=lp["conv_b"])
        u = xbc[..., :di].reshape(B, S, nh, P)
        bm, cm = (t.reshape(B, S, G, N)
                  for t in jnp.split(xbc[..., di:], 2, axis=-1))
        delta = jax.nn.softplus(zxd[..., -nh:].astype(f32) + lp["dt_bias"])
        y, extra = run(delta[..., None] * u, -jnp.exp(lp["A_log"]) * delta, bm, cm)
        y = (y + lp["D"][:, None] * u).reshape(B, S, G, di // G)
        y = y * jax.nn.silu(zxd[..., :di].astype(f32)).reshape(y.shape)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
        y = y.reshape(B, S, di) * lp["ssd_norm"].astype(f32)
        return _wmm(lp, "out_proj", "bsk,kh->bsh", y.astype(h.dtype)), conv, extra


def _kda_in(lp: dict, h: jnp.ndarray, conv0: jnp.ndarray, cfg: ModelConfig,
            snap_at: jnp.ndarray | None):
    """A kda layer's inputs from its normed input ``h`` [B, S, H] and the
    ``kda_conv - 1`` inputs of its convolutions before the first position
    (``conv0`` [B, taps - 1, 3C]): ``(q, k, v [B, S, heads, d] float32 —
    convolved, SiLU, q and k of unit length, q times d^-1/2 —, the
    log-decay g [B, S, heads, d] in (lower bound, 0), beta [B, S, heads],
    the convolutions' inputs before ``snap_at`` [B, K] positions [B, K,
    taps - 1, 3C])`` (``ops/kda.py::short_conv``)."""
    B, S = h.shape[:2]
    nh, d = cfg.kda_heads, cfg.kda_head_dim
    f32 = jnp.float32
    qkv = jnp.concatenate(
        [_wmm(lp, name, "bsh,hd->bsd", h) for name in ("wq", "wk", "wv")], axis=-1)
    y, conv = kda.short_conv(qkv, lp["conv_w"], conv0, snap_at)
    q, k, v = (t.reshape(B, S, nh, d) for t in jnp.split(y, 3, axis=-1))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    a = _wmm(lp, "w_alpha", "bsh,hd->bsd", h).astype(f32) + lp["dt_bias"]
    g = cfg.kda_gate_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["A_log"])[:, None] * a.reshape(B, S, nh, d))
    beta = jax.nn.sigmoid(_wmm(lp, "w_beta", "bsh,hq->bsq", h).astype(f32))
    return unit(q) * d ** -0.5, unit(k), v, g, beta, conv


def _kda_out(lp: dict, o: jnp.ndarray, h: jnp.ndarray, cfg: ModelConfig):
    """A kda layer's output from ``o`` [B, S, heads, d] float32: an RMSNorm
    over each head (its own learned weight), times ``sigmoid(h W_g)``
    element by element, then ``wo``."""
    B, S, nh, d = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
    o = o * lp["o_norm"].astype(jnp.float32).reshape(nh, d)
    gate = jax.nn.sigmoid(_wmm(lp, "wg", "bsh,hd->bsd", h).astype(jnp.float32))
    return _wmm(lp, "wo", "bsk,kh->bsh",
                (o.reshape(B, S, nh * d) * gate).astype(h.dtype))


# ---------------------------------------------------------------------------
# decoder-hybrid-decoder: state-space, window, full, gated-memory and
# cross-attention layers (models/phi4flash.py)
# ---------------------------------------------------------------------------


def _draw_mamba(keys, cfg: ModelConfig) -> dict:
    """The Mamba-1 operators' stack (``_draw_hybrid``'s docstring has the
    leaves), one key of the iterator ``keys`` a draw; under
    ``ssm_inner_norms`` also ``dt_norm`` [R], ``b_norm`` / ``c_norm`` [N],
    seeded draws around 1."""
    H, di, N, R = cfg.hidden, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    n_m, f32 = len(cfg.mamba_layers), jnp.float32

    def near(width: int, centre: float, spread: float):
        return (centre + spread * jax.random.normal(next(keys), (n_m, width), f32)
                ).astype(cfg.dtype)

    mamba = {name: _draw_blocks(next(keys), n_m, (i, o), i, cfg.dtype, None)[0]
             for name, (i, o) in {
                 "in_x": (H, di), "in_z": (H, di), "x_proj": (di, R + 2 * N),
                 "dt_proj": (R, di), "out_proj": (di, H)}.items()}
    mamba["conv_w"], _ = _draw_blocks(
        next(keys), n_m, (cfg.ssm_conv, di), cfg.ssm_conv, cfg.dtype, None)
    mamba["conv_b"] = near(di, 0.0, 0.02)
    dt = jnp.exp(jax.random.uniform(next(keys), (n_m, di), f32)
                 * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
    mamba["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    mamba["A_log"] = jnp.broadcast_to(
        jnp.log(jnp.arange(1, N + 1, dtype=f32))[None, :, None], (n_m, N, di))
    mamba["D"] = jnp.ones((n_m, di), f32)
    if cfg.ssm_inner_norms:
        mamba |= {"dt_norm": near(R, 1.0, 0.1), "b_norm": near(N, 1.0, 0.1),
                  "c_norm": near(N, 1.0, 0.1)}
    return mamba


def _draw_interleaved(key, cfg: ModelConfig) -> dict:
    """The operators' leaves of a config whose mamba layers are interleaved
    with attention layers, a stack a kind: ``mamba`` (``_draw_mamba``) and
    ``attn`` (``wq`` / ``wk`` / ``wv`` / ``wo``)."""
    keys = iter(jax.random.split(key, 24))
    mamba = _draw_mamba(keys, cfg)
    attn = {name: _draw_blocks(next(keys), len(cfg.attn_layers), shape, shape[0],
                               cfg.dtype, None)[0]
            for name, shape in _attn_dims(cfg).items()}
    return {"mamba": mamba, "attn": attn}


def _draw_hybrid(key, cfg: ModelConfig) -> dict:
    """The pytree of a config with mamba layers: ``layers`` — the two
    LayerNorms (weight and bias) and the SwiGLU of every layer, one stack
    — and a stack a kind for the operators: ``mamba`` (``in_x`` / ``in_z`` [H,
    di] each: the input projection's two halves, stored apart as the FFN's
    gate and up are — the same bytes, and every product of the operator
    then makes or reads a tensor ``di`` wide, which no other layer's does:
    how a trace without framework names finds it; ``conv_w`` [taps, di], tap 0 the oldest, ``conv_b``;
    ``x_proj`` [di, R + 2N]: delta, B, C; ``dt_proj`` [R, di], ``dt_bias``;
    ``A_log`` [N, di]; ``D``; ``out_proj``), ``attn`` (the sliding layers
    then the full one: ``wq`` / ``wk`` / ``wv`` and biases, ``wo`` /
    ``bo``, the four lambda vectors [D], ``subln`` [2D]), ``gmu`` (``w_g``
    [H, di], ``w_out`` [di, H]) and ``cross`` (``attn`` without ``wk`` /
    ``wv``). ``A_log = log(1..N)`` a channel, ``dt_bias`` the inverse
    softplus of a log-uniform [1e-3, 1e-1], ``D = 1`` (the family's
    initialisation): a normal draw makes the state vanish or overflow
    within a page. Norm weights are seeded draws around 1 and the biases
    small non-zero draws, so that they are exercised."""
    H, di, D = cfg.hidden, cfg.ssm_inner, cfg.head_dim
    qd, kvd = cfg.n_heads * D, cfg.n_kv_heads * D
    L = cfg.n_layers
    n_c = len(cfg._layers_of("cross_attention"))
    n_a = len(cfg.window_layers) + 1
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 64))

    def mats(n: int, dims: dict) -> dict:
        return {name: _draw_blocks(next(keys), n, (i, o), i, cfg.dtype, None)[0]
                for name, (i, o) in dims.items()}

    def near(n: int, width: int, centre: float, spread: float, dtype=cfg.dtype):
        return (centre + spread * jax.random.normal(next(keys), (n, width), f32)
                ).astype(dtype)

    def diff_leaves(n: int) -> dict:
        out = {"bq": near(n, qd, 0.0, 0.02), "bo": near(n, H, 0.0, 0.02),
               "subln": near(n, 2 * D, 1.0, 0.1)}
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            out[name] = near(n, D, 0.0, 0.1, f32)
        return out

    layers = mats(L, _ffn_dims(H, cfg.intermediate))
    for name in ("attn_norm", "mlp_norm"):
        layers[name] = near(L, H, 1.0, 0.1)
        layers[name + "_b"] = near(L, H, 0.0, 0.02)
    mamba = _draw_mamba(keys, cfg)
    attn = mats(n_a, {"wq": (H, qd), "wk": (H, kvd), "wv": (H, kvd),
                      "wo": (qd, H)})
    attn |= diff_leaves(n_a) | {"bk": near(n_a, kvd, 0.0, 0.02),
                                "bv": near(n_a, kvd, 0.0, 0.02)}
    gmu = mats(n_c, {"w_g": (H, di), "w_out": (di, H)})
    cross = mats(n_c, {"wq": (H, qd), "wo": (qd, H)}) | diff_leaves(n_c)
    return {
        "layers": layers, "mamba": mamba, "attn": attn, "gmu": gmu,
        "cross": cross,
        "final_norm": near(1, H, 1.0, 0.1)[0],
        "final_norm_b": near(1, H, 0.0, 0.02)[0],
    }


def _stack_at(stack: dict, i) -> dict:
    """Layer ``i`` of a stack, indexed where it lies (``_scan_mixed``'s
    rule: the consumer's fusion reads the slice, none is built)."""
    return jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)
        if isinstance(i, jax.Array) else w[i], stack)


def _ln(lp: dict, name: str, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return layer_norm(x, lp[name], cfg.rms_eps, lp[name + "_b"])


def _lambda_init(layer) -> jnp.ndarray:
    """Differential attention's ``lambda_0`` of layer ``layer`` (its
    depth in the model, traced under a scan)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def _diff_combine(ap: dict, attn: jnp.ndarray, cfg: ModelConfig, layer) -> jnp.ndarray:
    """The two softmax maps of every head pair, as ``_pair_heads`` laid
    them — ``attn`` [B, S, Hq, 2D]: query head ``(wide, half, g)``, half 0
    scored against the wide head's first key, half 1 against its second,
    all 2D output lanes each (over ``v1 | v2``) — combined: ``a1 - lambda
    * a2``, RMSNorm over the 2D lanes, times ``1 - lambda_0``. Returns [B,
    S, Hq * D] as ``wo`` takes it."""
    B, S, Hq, W = attn.shape
    wide, g = cfg.n_kv_heads // 2, Hq // cfg.n_kv_heads
    a = attn.astype(jnp.float32).reshape(B, S, wide, 2, g, W)
    lam0 = _lambda_init(layer)
    lam = (jnp.exp(jnp.sum(ap["lambda_q1"] * ap["lambda_k1"]))
           - jnp.exp(jnp.sum(ap["lambda_q2"] * ap["lambda_k2"])) + lam0)
    o = rms_norm(a[:, :, :, 0] - lam * a[:, :, :, 1], ap["subln"], cfg.rms_eps)
    return (o * (1.0 - lam0)).astype(attn.dtype).reshape(B, S, Hq * W // 2)


def _diff_out(ap: dict, attn: jnp.ndarray, cfg: ModelConfig, layer) -> jnp.ndarray:
    """Differential attention's output projection on the kernels' (or
    the jnp attention's) paired-row result."""
    with jax.named_scope("rm_diff_attn"):
        o = _diff_combine(ap, attn, cfg, layer)
    return _wmm(ap, "wo", "bsk,kh->bsh", o) + ap["bo"]


def _cross_q(cp: dict, u: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """A cross-attention layer's queries, paired: [B, S, Hq, 2D]."""
    B, S = u.shape[:2]
    q = jax.lax.optimization_barrier(_wmm(cp, "wq", "bsh,hd->bsd", u)) + cp["bq"]
    return _pair_q(cfg, q.reshape(B, S, cfg.n_heads, cfg.head_dim))


def _conv_rows(cfg: ModelConfig, stored: jnp.ndarray) -> jnp.ndarray:
    """Conv-state slots' content ``[..., rows, 128]`` as positions x
    channels ``[..., ssm_conv - 1, ssm_inner]``."""
    return stored.reshape(*stored.shape[:-2], cfg.ssm_conv - 1, cfg.ssm_inner)


def _mamba(mp: dict, u: jnp.ndarray, h0: jnp.ndarray, conv0: jnp.ndarray,
           cfg: ModelConfig, snap_at: jnp.ndarray | None, scan_kw: dict) -> tuple:
    """The Mamba-1 operator on ``u`` [B, S, H] from the state ``h0`` [B, N,
    di] float32 and the ``ssm_conv - 1`` inputs of the convolution before
    position 0 of these, ``conv0`` [B, taps - 1, di]. Returns ``(Op [B, S,
    H], y [B, S, di] — the scan's output before the z gate, with the D
    term: the MEMORY where this is the memory layer —, h [B, K, N, di],
    conv [B, K, taps - 1, di])``: the state after ``snap_at`` [B, K]
    positions each (``ops/ssm.py``); ``snap_at`` None: one position, one
    state (a decode step: no kernel, no gather). Under ``ssm_inner_norms``
    delta, B and C pass an RMSNorm each as ``x_proj`` gives them."""
    taps, N, R, S = cfg.ssm_conv, cfg.ssm_state, cfg.ssm_dt_rank, u.shape[1]
    f32 = jnp.float32
    with jax.named_scope("rm_ssm"):
        x, z = jax.lax.optimization_barrier((
            _wmm(mp, "in_x", "bsh,hd->bsd", u), _wmm(mp, "in_z", "bsh,hd->bsd", u)))
        xs = jnp.concatenate([conv0.astype(x.dtype), x], axis=1)  # [B, S+taps-1, di]
        w = mp["conv_w"].astype(f32)
        xc = sum(w[k] * xs[:, k:k + S].astype(f32) for k in range(taps))
        xc = jax.nn.silu(xc + mp["conv_b"].astype(f32))
        dbc = _wmm(mp, "x_proj", "bsd,dr->bsr", xc.astype(x.dtype))

        def normed(t, name):  # the inner norms of an interleaved layer
            return rms_norm(t, mp[name], cfg.rms_eps) if cfg.ssm_inner_norms else t

        dt = jnp.einsum("bsr,rd->bsd", normed(dbc[..., :R], "dt_norm"),
                        mp["dt_proj"], precision=_PREC, preferred_element_type=f32)
        dt = jax.nn.softplus(dt + mp["dt_bias"])
        b = normed(dbc[..., R:R + N], "b_norm").astype(f32)
        c = normed(dbc[..., R + N:], "c_norm").astype(f32)
        a = -jnp.exp(mp["A_log"])
        if snap_at is None:
            y, h = ssm_step(xc[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, mp["D"], h0)
            y, h, conv = y[:, None], h[:, None], xs[:, None, S:]
        else:
            y, h = selective_scan(xc, dt, b, c, a, mp["D"], h0, snap_at, **scan_kw)
            at = snap_at[:, :, None] + jnp.arange(taps - 1)
            conv = jax.vmap(lambda rows, ix: rows[ix])(xs, at)
        gated = (y * jax.nn.silu(z.astype(f32))).astype(u.dtype)
        out = _wmm(mp, "out_proj", "bsd,dh->bsh", gated)
    return out, y.astype(u.dtype), h, conv


def _states_in(cfg: ModelConfig, pools: tuple, op_idx, fresh, state_in) -> tuple:
    """What a chunk's Mamba layer ``op_idx`` starts from: each row's ``(h,
    conv)`` in slot ``state_in`` of the state slots ``pools``, zeros where
    ``fresh`` [B, 1, 1] (the chunk starts at position 0)."""
    h_pool, conv_pool = pools
    return (jnp.where(fresh, 0, h_pool[op_idx, state_in]),
            jnp.where(fresh, 0, _conv_rows(cfg, conv_pool[op_idx, state_in])))


def _states_out(pools: tuple, op_idx, written, h, conv) -> tuple:
    """``pools`` with the layer's states after ``snap_at`` tokens (``_mamba``'s
    ``h`` / ``conv`` [B, K, ...]) in the slots ``written`` [B * K]: inside
    the layer, the arrays updated in place."""
    h_pool, conv_pool = pools
    return (h_pool.at[op_idx, written].set(h.reshape(-1, *h.shape[2:])),
            conv_pool.at[op_idx, written].set(
                conv.reshape(written.shape[0], -1, 128).astype(conv_pool.dtype)))


def _gmu(gp: dict, u: jnp.ndarray, memory: jnp.ndarray) -> jnp.ndarray:
    """A Gated Memory Unit: no token mixing of its own, it gates the
    memory layer's scan output at the same position."""
    with jax.named_scope("rm_gmu"):
        gate = jax.nn.silu(_wmm(gp, "w_g", "bsh,hd->bsd", u))
        return _wmm(gp, "w_out", "bsd,dh->bsh", memory * gate)


def _hybrid_plan(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """``(lower pairs, memory layer, full layer, upper pairs)``."""
    full = cfg.attn_layers[0]
    return (full - 1) // 2, full - 1, full, (cfg.n_layers - full - 1) // 2


def _hybrid_lower(cfg: ModelConfig, params: dict, carry, mamba_layer, window_layer):
    """The self-decoder: ``(mamba, sliding_attention)`` pairs as ONE
    traced unit under ``lax.scan``, then the memory layer. ``mamba_layer
    (carry, lp, mp, op_idx) -> (carry, y, ys)``, ``window_layer(carry, lp,
    ap, op_idx, layer) -> (carry, ys)``; returns ``(carry, memory, ys of
    the mamba layers [n_m, ...], ys of the window layers)``."""
    pairs, mem, _, _ = _hybrid_plan(cfg)
    per_layer, mamba, attn = params["layers"], params["mamba"], params["attn"]

    def unit(carry, i):
        carry, _, ys_m = mamba_layer(
            carry, _stack_at(per_layer, 2 * i), _stack_at(mamba, i), i)
        carry, ys_w = window_layer(
            carry, _stack_at(per_layer, 2 * i + 1), _stack_at(attn, i), i,
            2 * i + 1)
        return carry, (ys_m, ys_w)

    carry, (ys_m, ys_w) = jax.lax.scan(unit, carry, jnp.arange(pairs))
    carry, memory, last = mamba_layer(
        carry, _stack_at(per_layer, mem), _stack_at(mamba, pairs), pairs)
    if last is not None:  # a launch that collects its states over the layers
        ys_m = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b[None]]), ys_m, last)
    return carry, memory, ys_m, ys_w


def _hybrid_upper(cfg: ModelConfig, params: dict, carry, memory, cross_layer):
    """The cross-decoder: ``(gmu, cross_attention)`` pairs as one traced
    unit under ``lax.scan``. ``carry`` leads with ``x``; ``cross_layer
    (carry, u, cp, layer) -> (carry, paired attention output)``."""
    _, _, full, pairs = _hybrid_plan(cfg)
    per_layer = params["layers"]

    def unit(carry, i):
        x, *rest = carry
        lp = _stack_at(per_layer, full + 1 + 2 * i)
        x = x + _gmu(_stack_at(params["gmu"], i), _ln(lp, "attn_norm", x, cfg),
                     memory)
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        layer = full + 2 + 2 * i
        lp = _stack_at(per_layer, layer)
        cp = _stack_at(params["cross"], i)
        carry, attn = cross_layer(
            (x, *rest), _ln(lp, "attn_norm", x, cfg), cp, layer)
        x, *rest = carry
        x = x + _diff_out(cp, attn, cfg, layer)
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return (x, *rest), None

    if pairs:
        carry, _ = jax.lax.scan(unit, carry, jnp.arange(pairs))
    return carry


def _hybrid_qkv(ap: dict, u: jnp.ndarray, cfg: ModelConfig, dtype=None):
    """A self-attention layer's paired ``(q [B, S, Hq, 2D], k, v [B, S,
    Hkv / 2, 2D])``, K and V in the pool's dtype."""
    q, k, v = _pair_heads(cfg, *_qkv(ap, u, cfg))
    dtype = dtype or k.dtype
    return q, k.astype(dtype), v.astype(dtype)


def _hybrid_prefill(params, cfg: ModelConfig, tokens, positions, new_lengths,
                    snap_at):
    """``prefill_forward`` of a config with mamba layers: one launch of
    the whole prompt from a zero state, nothing cached. The self-decoder
    runs every position; the full layer's attention and the cross-decoder
    run each row's LAST position only (``new_lengths - 1``). Returns
    ``(logits [B, 1, V], K / V of the full layer [1, B, S, ...], K / V of
    the window layers [n, B, S, ...], (h, conv) states after ``snap_at``
    tokens ([n_m, B, K, ...]))``."""
    B, S = tokens.shape
    x = _embed_lookup(params, tokens)
    scale = cfg.head_dim ** -0.5
    kv_end = jnp.full((B,), S, jnp.int32)
    window = jnp.asarray(cfg.sliding_window, jnp.int32)
    h0 = jnp.zeros((B, cfg.ssm_state, cfg.ssm_inner), jnp.float32)
    conv0 = jnp.zeros((B, cfg.ssm_conv - 1, cfg.ssm_inner), x.dtype)

    def mamba_layer(x, lp, mp, op_idx):
        op, y, h, conv = _mamba(mp, _ln(lp, "attn_norm", x, cfg), h0, conv0,
                                cfg, snap_at, {})
        x = x + op
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return x, y, (h, conv.reshape(*conv.shape[:2], -1, 128))

    def window_layer(x, lp, ap, op_idx, layer):
        q, k, v = _hybrid_qkv(ap, _ln(lp, "attn_norm", x, cfg), cfg)
        attn = attend_prefill(q, k, v, positions, kv_end, window=window,
                              scale=scale)
        x = x + _diff_out(ap, attn, cfg, layer)
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return x, (k, v)

    x, memory, states, (wk, wv) = _hybrid_lower(
        cfg, params, x, mamba_layer, window_layer)
    pairs, _, full, _ = _hybrid_plan(cfg)
    last = (new_lengths - 1)[:, None]  # [B, 1]
    lp, ap = _stack_at(params["layers"], full), _stack_at(params["attn"], pairs)
    q, fk, fv = _hybrid_qkv(ap, _ln(lp, "attn_norm", x, cfg), cfg)
    x = jnp.take_along_axis(x, last[:, :, None], axis=1)
    memory = jnp.take_along_axis(memory, last[:, :, None], axis=1)
    q = jnp.take_along_axis(q, last[:, :, None, None], axis=1)

    def attend_last(q):  # one query a row against the full layer's keys
        return attend_prefill(q, fk, fv, last, new_lengths, scale=scale)

    x = x + _diff_out(ap, attend_last(q), cfg, full)
    x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
    (x,) = _hybrid_upper(
        cfg, params, (x,), memory,
        lambda carry, u, cp, layer: (carry, attend_last(_cross_q(cp, u, cfg))))
    return (_logits(params, cfg, x), fk[None], fv[None], wk, wv, states)


def _hybrid_top(params, cfg: ModelConfig, x, memory, q, fk, fv, kv_pool, slots,
                page_table, lengths, page_size: int):
    """The full layer's attention and the cross-decoder for ONE position a
    row — a decode step's, or a prefill's last: ``x``, ``memory`` [B, 1,
    ·], the position's paired K / V of the full layer ``fk`` / ``fv`` [B,
    Hkv / 2, 2D], its slot and its length (keys through itself). Every
    one of these layers is a launch of the fused decode kernel on the
    pool's layer 0: it writes the position's row — the full layer's, the
    same bytes again in a cross layer — and attends the row's pages.
    Returns ``(x, kv_pool)``."""
    pairs, _, full, _ = _hybrid_plan(cfg)
    pool_shape, pages_shape = kv_pool.shape, _pages_shape(kv_pool, page_size)
    scale = cfg.head_dim ** -0.5

    def attend(kv_pool, q):  # q [B, 1, Hq, 2D]
        with jax.named_scope("rm_shared_kv_attn"):
            attn, kv_pages = paged_decode_attention(
                q[:, 0], fk, fv, kv_pool.reshape(pages_shape), slots,
                page_table, lengths, 0, scale=scale)
        return kv_pages.reshape(pool_shape), attn[:, None]

    lp, ap = _stack_at(params["layers"], full), _stack_at(params["attn"], pairs)
    kv_pool, attn = attend(kv_pool, q)
    x = x + _diff_out(ap, attn, cfg, full)
    x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))

    def cross_layer(carry, u, cp, layer):
        x, kv_pool = carry
        kv_pool, attn = attend(kv_pool, _cross_q(cp, u, cfg))
        return (x, kv_pool), attn

    return _hybrid_upper(cfg, params, (x, kv_pool), memory, cross_layer)


def _hybrid_chunk(params, cfg: ModelConfig, tokens, positions, kv_pool, slots,
                  page_table, kv_lengths, page_size, kv_block_pages, use_kernel,
                  interpret, logits_at, window_pool, window_slots, window_table,
                  state_pool, state_in, snap_at, state_out):
    """``prefill_chunk_paged`` of a config with mamba layers. The
    self-decoder runs the chunk: a mamba layer from the state in slot
    ``state_in`` (zeros where the chunk starts at position 0) through the
    scan kernel, the states after ``snap_at`` tokens to slots
    ``state_out``; a sliding layer attends its window of the WINDOW group
    through ``window_table`` and the chunk's own rows, which go to
    ``window_slots`` after the scan. The full layer's K / V of every
    position go to ``slots``; its attention and the cross-decoder run the
    position ``logits_at`` of each row only (``_hybrid_top``: that row's
    last real one in its final chunk; in any other chunk its result is
    nobody's). Returns ``(logits [B, 1, V], kv_pool, window_pool, (h,
    conv))``."""
    B, C = tokens.shape
    x = _embed_lookup(params, tokens)
    w_pages = window_pool.reshape(_pages_shape(window_pool, page_size))
    prior_lengths = jnp.minimum(positions[:, 0], kv_lengths)
    scale = cfg.head_dim ** -0.5
    window = jnp.asarray(cfg.sliding_window, jnp.int32)
    scan_kw = {} if use_kernel is None else {
        "use_kernel": use_kernel, "interpret": interpret}
    # A layer reads its rows' state at ``state_in`` and writes the states
    # wanted to ``state_out`` INSIDE the layer, as a decode step does: the
    # slots' arrays ride the scan, updated in place (collected over the
    # layers instead, 64 rows x 3 states x 9 layers are 0.6 GB of float32).
    fresh = (positions[:, 0] == 0)[:, None, None]
    written = state_out.reshape(-1)

    def mamba_layer(carry, lp, mp, op_idx):
        x, *pools = carry
        h0, conv0 = _states_in(cfg, pools, op_idx, fresh, state_in)
        op, y, h, conv = _mamba(mp, _ln(lp, "attn_norm", x, cfg), h0, conv0, cfg,
                                snap_at, scan_kw)
        pools = _states_out(pools, op_idx, written, h, conv)
        x = x + op
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return (x, *pools), y, None

    def window_layer(carry, lp, ap, op_idx, layer):
        x, *pools = carry
        q, k, v = _hybrid_qkv(ap, _ln(lp, "attn_norm", x, cfg), cfg,
                              window_pool.dtype)
        attn = paged_chunk_attention(
            q, k, v, w_pages, window_table, positions, prior_lengths,
            kv_lengths, op_idx, kv_block_pages=kv_block_pages,
            use_kernel=use_kernel, interpret=interpret, window=window,
            scale=scale)
        x = x + _diff_out(ap, attn, cfg, layer)
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return (x, *pools), (k, v)

    (x, h_pool, conv_pool), memory, _, (wk, wv) = _hybrid_lower(
        cfg, params, (x, *state_pool), mamba_layer, window_layer)
    new_w = jnp.stack([wk, wv]).transpose(0, 1, 4, 2, 3, 5)
    window_pool, = write_runs_all(
        [(window_pool, new_w, ALL_LAYERS)], window_slots, page_size)

    pairs, _, full, _ = _hybrid_plan(cfg)
    lp, ap = _stack_at(params["layers"], full), _stack_at(params["attn"], pairs)
    q, fk, fv = _hybrid_qkv(ap, _ln(lp, "attn_norm", x, cfg), cfg, kv_pool.dtype)
    new_f = jnp.stack([fk, fv])[:, None].transpose(0, 1, 4, 2, 3, 5)
    kv_pool, = write_runs_all([(kv_pool, new_f, ALL_LAYERS)], slots, page_size)
    at = jnp.zeros((B,), jnp.int32) if logits_at is None else logits_at

    def pick(t):  # [B, C, ...] -> [B, 1, ...] at each row's position
        ix = at.reshape(B, *([1] * (t.ndim - 1)))
        return jnp.take_along_axis(t, ix, axis=1)

    x, kv_pool = _hybrid_top(
        params, cfg, pick(x), pick(memory), pick(q), pick(fk)[:, 0], pick(fv)[:, 0],
        kv_pool, pick(slots)[:, 0], page_table, pick(positions)[:, 0] + 1,
        page_size)
    return _logits(params, cfg, x), kv_pool, window_pool, (h_pool, conv_pool)


def _hybrid_decode(params, cfg: ModelConfig, tokens, kv_pool, slots, page_table,
                   lengths, page_size, window_pool, window_slots, window_table,
                   state_pool, state_slots):
    """``decode_step`` of a config with mamba layers: one position a row.
    A mamba layer reads its state at the row's working slot
    ``state_slots[0]`` INSIDE the layer and writes the new one back there
    and to ``state_slots[1]`` (the boundary slot when this token completes
    a page, else slot 0) — the slots' arrays ride the layer scan as the
    pools do, updated in place; a sliding layer is a launch of the fused
    decode kernel on the WINDOW group with its window. Returns ``(logits
    [B, V], kv_pool, window_pool, (h, conv))``."""
    B = tokens.shape[0]
    x = _embed_lookup(params, tokens)[:, None, :]
    w_shape, w_pages = window_pool.shape, _pages_shape(window_pool, page_size)
    scale = cfg.head_dim ** -0.5
    window = jnp.asarray(cfg.sliding_window, jnp.int32)
    read, written = state_slots[0], state_slots.reshape(-1)

    def mamba_layer(carry, lp, mp, op_idx):
        x, window_pool, h_pool, conv_pool = carry
        op, y, h, conv = _mamba(
            mp, _ln(lp, "attn_norm", x, cfg), h_pool[op_idx, read],
            _conv_rows(cfg, conv_pool[op_idx, read]), cfg, None, {})
        h, conv = h[:, 0], conv[:, 0].reshape(B, -1, 128).astype(conv_pool.dtype)
        h_pool = h_pool.at[op_idx, written].set(jnp.concatenate([h, h]))
        conv_pool = conv_pool.at[op_idx, written].set(jnp.concatenate([conv, conv]))
        x = x + op
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return (x, window_pool, h_pool, conv_pool), y, None

    def window_layer(carry, lp, ap, op_idx, layer):
        x, window_pool, h_pool, conv_pool = carry
        q, k, v = _hybrid_qkv(ap, _ln(lp, "attn_norm", x, cfg), cfg,
                              window_pool.dtype)
        with jax.named_scope("rm_window_attn"):
            attn, pages = paged_decode_attention(
                q[:, 0], k[:, 0], v[:, 0], window_pool.reshape(w_pages),
                window_slots, window_table, lengths, op_idx, window=window,
                scale=scale)
        x = x + _diff_out(ap, attn[:, None], cfg, layer)
        x = x + _mlp(lp, _ln(lp, "mlp_norm", x, cfg))
        return (x, pages.reshape(w_shape), h_pool, conv_pool), None

    (x, window_pool, h_pool, conv_pool), memory, _, _ = _hybrid_lower(
        cfg, params, (x, window_pool, *state_pool), mamba_layer, window_layer)
    pairs, _, full, _ = _hybrid_plan(cfg)
    lp, ap = _stack_at(params["layers"], full), _stack_at(params["attn"], pairs)
    q, fk, fv = _hybrid_qkv(ap, _ln(lp, "attn_norm", x, cfg), cfg, kv_pool.dtype)
    x, kv_pool = _hybrid_top(
        params, cfg, x, memory, q, fk[:, 0], fv[:, 0], kv_pool, slots,
        page_table, lengths, page_size)
    return (_logits(params, cfg, x)[:, 0], kv_pool, window_pool,
            (h_pool, conv_pool))


class UnsupportedForModel(ValueError):
    """A serving option that this model's layers cannot run under."""


def unsupported_reason(cfg: ModelConfig, **options) -> str | None:
    """Why ``options`` cannot serve ``cfg``, or None. A config with
    experts, window layers or a latent cache row runs on one chip in bf16
    through
    ``prefill_forward`` / ``prefill_chunk_paged`` / ``decode_step`` and
    nothing else; every other launch path assumes the dense layer and is
    refused here by name, so that none of them serves such a model with a
    dense layer's arithmetic. ``options`` are ``Engine``'s keyword names
    (a falsy value is "not asked for")."""
    if not (cfg.n_experts or cfg.has_window or cfg.latent or cfg.has_conv
            or cfg.has_ssm or cfg.matrix_state):
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
    if cfg.has_conv or cfg.window_layers or cfg.carried_state:
        # A prefix is handed back only where its state (or its window tail)
        # was snapshotted: whatever moves or advertises a prefix without it.
        named |= {
            "mesh_cache": "a prefix ring (cache/mesh_cache.py: an advertised "
                          "prefix would promise a hit the state cannot serve)",
            "stream_publish_tokens": "mid-decode publishing "
                                     "(--stream-publish-tokens)",
        }
    asked = [text for key, text in named.items() if options.get(key)]
    if mesh is not None and any(n > 1 for n in dict(mesh.shape).values()):
        asked.append(f"a device mesh {dict(mesh.shape)} (tp / sp / pp)")
    if not asked:
        return None
    kinds = " and ".join(
        k for k, on in (("sparse experts", cfg.n_experts),
                        ("an expert share", cfg.ep_size > 1),
                        ("window attention", cfg.has_window),
                        ("a latent (MLA) cache row", cfg.latent),
                        ("learned sparse attention", cfg.dsa),
                        ("gated-convolution layers with a recurrent state",
                         cfg.has_conv),
                        ("state-space layers with a recurrent state",
                         cfg.has_ssm),
                        ("linear-attention (kda) layers with a recurrent "
                         "matrix state", cfg.has_kda),
                        ("Mamba-2 (ssd) layers with a recurrent matrix state "
                         "in blocks of one sublayer (a mixer or an FFN alone)",
                         cfg.has_mamba2),
                        ("un-gated (relu2) experts", not cfg.mlp_gated),
                        ("a cross-decoder that reads one layer's K/V",
                         cfg.cross_decoder),
                        ("attention kinds of two shapes in stacks of their "
                         "own (a gated output, a partly rotated head)",
                         cfg.heads_by_type),
                        ("a window group of rows resumed from a tail",
                         cfg.window_layers and not cfg.has_ssm)) if on
    )
    return (
        f"a model with {kinds} does not support " + "; ".join(asked)
        + ": it serves on one chip, bf16 weights and KV (ROADMAP D6 / R4)"
    )


def refuse_unsupported(cfg: ModelConfig, **options) -> None:
    reason = unsupported_reason(cfg, **options)
    if reason is not None:
        raise UnsupportedForModel(reason)


def _type_rope(cfg: ModelConfig, kind: str) -> tuple:
    """``(inv_freq [rot / 2], cos/sin factor, rot)`` of layer type ``kind``
    from ``rope_by_type``: default, or YaRN with its attention factor, over
    the ``rot`` = ``partial_rotary_factor * head_dim`` leading dims of a
    head that rotate (all of them unless the entry says otherwise)."""
    rp = dict(dict(cfg.rope_by_type)[kind])
    theta = float(rp.get("rope_theta", cfg.rope_theta))
    rot = int(rp.get("partial_rotary_factor", 1.0) * cfg.head_dim)
    if rp.get("rope_type", "default") == "yarn":
        inv = yarn_frequencies(
            rot, theta, float(rp["factor"]),
            int(rp["original_max_position_embeddings"]),
            float(rp.get("beta_fast", 32.0)),
            float(rp.get("beta_slow", 1.0)),
            bool(rp.get("truncate", True)),
        )
        return inv, float(rp["attention_factor"]), rot
    if rp.get("rope_type", "default") == "default":
        return rope_frequencies(rot, theta), 1.0, rot
    raise ValueError(f"unknown rope_type {rp['rope_type']!r}")


def _kind_attn_in(lp: dict, h: jnp.ndarray, cfg: ModelConfig, kind: str,
                  positions):
    """``(q, k, v)`` of a layer of attention kind ``kind`` (a config with
    ``heads_by_type``): the kind's head count from its own ``wq``, its own
    RoPE over the leading ``rot`` dims of every head, the rest passing."""
    q, k, v = _qkv(lp, h, cfg)
    inv, scale, rot = _type_rope(cfg, kind)
    scale = None if scale == 1.0 else scale

    def rotate(t):
        if rot == cfg.head_dim:
            return apply_rope(t, positions, inv, scale)
        return jnp.concatenate(
            [apply_rope(t[..., :rot], positions, inv, scale), t[..., rot:]],
            axis=-1)

    return rotate(q), rotate(k), v


def _kind_attn_out(lp: dict, attn: jnp.ndarray, h: jnp.ndarray,
                   cfg: ModelConfig) -> jnp.ndarray:
    """``_attn_out`` behind the head-wise output gate: ``attn`` [B, S, Hq,
    D] times ``sigmoid(h W_g)`` [B, S, Hq] of the layer's normed input."""
    if cfg.attn_gate:
        gate = jax.nn.sigmoid(
            _wmm(lp, "wg", "bsh,hq->bsq", h).astype(jnp.float32))
        attn = (attn.astype(jnp.float32) * gate[..., None]).astype(attn.dtype)
    return _attn_out(lp, attn, cfg)


def _layer_tables(cfg: ModelConfig) -> dict | None:
    """Per-layer inputs scanned beside the weights of a model whose
    layers differ in kind: ``inv_freq`` [L, D/2] and ``rope_scale`` [L]
    (each layer type's RoPE: default, or YaRN with its attention factor on
    cos/sin) and ``window`` [L] (keys a query sees; 0 = all). None for a
    model of one layer kind, whose body closes over one ``inv_freq``."""
    tables = {}
    if cfg.dsa:
        # Which layers hold an indexer, and for each layer the place of
        # its (a shared layer: the nearest earlier) full layer in the
        # indexer stack and the index-key plane.
        full = [t == "full" for t in cfg.indexer_types]
        tables |= {
            "dsa_full": jnp.asarray(full),
            "dsa_slot": jnp.asarray(np.cumsum(full) - 1, jnp.int32),
        }
    if cfg.layer_types is None or cfg.has_conv or cfg.carried_state:
        return tables or None
    by_type = {kind: _type_rope(cfg, kind)[:2] for kind, _ in cfg.rope_by_type}
    return tables | {
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


def _pages_shape(kv_pool: jnp.ndarray, page_size: int) -> tuple:
    """The pool ``[planes, L, heads, slots, width]`` viewed as pages
    ``[planes, L, heads, slots // page, page, width]`` (a pure reshape)."""
    planes, n_layers, heads, num_slots, width = kv_pool.shape
    return (planes, n_layers, heads, num_slots // page_size, page_size, width)


def _rope_of(inv_freq, table: dict | None):
    """``(inv_freq, cos/sin factor, window)`` of one layer: its row of
    ``_layer_tables``, or the model's one RoPE (``inv_freq``, computed
    once outside the scan) and no window."""
    if table is None or "inv_freq" not in table:
        return inv_freq, None, None
    return table["inv_freq"], table["rope_scale"], table["window"]


# ``moe`` counters live on the device as uint32 [site, counter, (lo, hi)]:
# 64 bits a counter (a day of serving overflows 32), no int64 on the chip.
MOE_SITES = ("decode", "prefill")


def new_moe_stats(share: bool = False, groups: bool = False) -> jnp.ndarray:
    """Zeroed counters: ``ops/moe.py``'s four a site, the fifth of a
    config with an expert share, the six of a router with groups."""
    names = MOE_STATS if groups else SHARE_STATS if share else STATS
    return jnp.zeros((len(MOE_SITES), len(names), 2), jnp.uint32)


def read_moe_stats(stats) -> dict:
    """The device counters as ``{site: {counter: int}}`` (a host read:
    waits for the step that wrote them)."""
    a = np.asarray(stats).astype(np.uint64)
    return {
        site: {
            name: int(a[i, j, 0] + (a[i, j, 1] << np.uint64(32)))
            for j, name in enumerate(MOE_STATS[: a.shape[1]])
        }
        for i, site in enumerate(MOE_SITES)
    }


def _bump_moe_stats(stats, site: str, delta: jnp.ndarray) -> jnp.ndarray:
    """``stats[site] += delta`` ([n_counters] int32, non-negative) with
    the carry into the high word."""
    if stats is None:
        stats = jnp.zeros((len(MOE_SITES), delta.shape[0], 2), jnp.uint32)
    i = MOE_SITES.index(site)
    lo = stats[i, :, 0] + delta.astype(jnp.uint32)
    hi = stats[i, :, 1] + (lo < stats[i, :, 0]).astype(jnp.uint32)
    return stats.at[i].set(jnp.stack([lo, hi], axis=-1))


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _scanned_layers(layers: dict) -> dict:
    """What the layer scan slices per layer: every stacked leaf but the
    expert weights, which the expert kernel indexes itself (``_ffn``)."""
    if "w_router" not in layers:
        return layers
    return {k: v for k, v in layers.items() if k not in _EXPERT_WEIGHTS}


def _scan_layers(cfg: ModelConfig, params: dict, layer, carry):
    """``lax.scan`` of ``layer(carry, xs, sparse)`` over the model's
    layers, ``xs = (layer index, the layer's leaves, its row of
    _layer_tables)``: one scan, or, for a config whose first layers have
    the dense FFN (leaves of another shape), one over them and one over
    the rest. Returns ``(carry, ys)``; ``ys`` of two scans are joined
    along the layer axis, but for what only the sparse layers give (the
    dense scan's None)."""
    if cfg.own_stacks:
        return _scan_mixed(cfg, params, layer, carry)
    kd = cfg.first_k_dense
    stacks = [(0, params["dense_layers"], False)] if kd else []
    stacks.append((kd, params["layers"], bool(cfg.n_experts)))
    tables = _layer_tables(cfg)
    joined = None
    for first, stack, sparse in stacks:
        n = stack["attn_norm"].shape[0]
        xs = (first + jnp.arange(n), _scanned_layers(stack),
              jax.tree_util.tree_map(lambda t: t[first:first + n], tables))
        carry, ys = jax.lax.scan(partial(layer, sparse=sparse), carry, xs)
        if joined is None:
            joined = ys
        else:
            joined = jax.tree_util.tree_map(
                lambda a, b: b if a is None else jnp.concatenate([a, b]),
                joined, ys, is_leaf=lambda a: a is None,
            )
    return carry, joined


def _mixed_runs(cfg: ModelConfig) -> list[tuple[int, int, int]]:
    """A config with conv layers as runs ``(first layer, layers a unit,
    units)``: the leading dense-FFN layers (one unit), then the smallest
    period of operator kinds that the rest repeats (its FFNs are of one
    kind), as many whole units as fit, and what is left over (one unit). A run of one
    unit is traced layer by layer; a run of several is ONE traced unit
    under ``lax.scan`` — compile time stays flat in depth."""
    kd = cfg.first_k_dense
    body = cfg.layer_types[kd:]
    period = next(
        p for p in range(1, len(body) + 1)
        if all(body[i] == body[i % p] for i in range(len(body)))
    )
    units = len(body) // period
    if units < 2:
        period, units = len(body), 1
    runs = [(0, kd, 1), (kd, period, units),
            (kd + period * units, len(body) - period * units, 1)]
    return [r for r in runs if r[1]]


def _layer_kinds(cfg: ModelConfig) -> list[str]:
    """The name of each layer's operator stack in the pytree: ``conv`` /
    ``attn`` beside conv layers, the layer type itself where attention
    kinds differ in shape (``heads_by_type``)."""
    if cfg.has_conv:
        return ["conv" if t == "conv" else "attn" for t in cfg.layer_types]
    if cfg.has_kda:
        return ["kda" if t == "kda" else "mla" for t in cfg.layer_types]
    if cfg.has_ssm:
        return ["mamba" if t == "mamba" else "attn" for t in cfg.layer_types]
    if cfg.has_mamba2:  # blocks: a mixer or the expert FFN alone
        return ["attn" if t == "full_attention" else t for t in cfg.layer_types]
    return list(cfg.layer_types)


def _scan_mixed(cfg: ModelConfig, params: dict, layer, carry):
    """``_scan_layers`` for a config whose operators differ in kind (conv
    and attention, or attention layers of two shapes: leaves of other
    shapes, in stacks of their own, named by ``_layer_kinds``).
    ``layer(carry, xs, sparse, kind)`` gets ``xs = (layer index, the
    layer's leaves — its norms and FFN from its per-layer stack, its
    operator's from the stack of its kind — and a table row {"op_idx":
    the layer's place in its kind's stack})`` and returns ``(carry, ys)``
    with ``ys`` a dict whose entries are None where the layer has nothing
    to give; the result's entries stack what the layers that gave
    something gave, in layer order. Every stack is indexed where it lies
    (no slice of a stack is built: the conv stack is a gigabyte)."""
    kd = cfg.first_k_dense
    kinds = _layer_kinds(cfg)
    # Each layer's place in its kind's stack.
    place = [kinds[:l].count(k) for l, k in enumerate(kinds)]
    per_layer = _scanned_layers(params["layers"])

    def at(stack, i):
        return jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)
            if isinstance(i, jax.Array) else w[i], stack)

    def unit(carry, first, unit_idx, n, stride):
        """Layers ``first + unit_idx * n + [0, n)``; ``stride`` maps a kind
        to its layers a unit (``unit_idx`` is traced under the scan)."""
        ys = []
        for j in range(n):
            l0 = first + j  # the layer of unit 0: its kind is every unit's
            kind = kinds[l0]
            l_idx = l0 + unit_idx * n
            op_idx = place[l0] + unit_idx * stride[kind]
            dense = l0 < kd
            lp = at(params["dense_layers"], l_idx) if dense else at(
                per_layer, l_idx - kd)
            # (a moe block's expert weights stay stacked: ``_scanned_layers``)
            lp = lp | at(_scanned_layers(params[kind]), op_idx)
            carry, y = layer(
                carry, (l_idx, lp, {"op_idx": op_idx}),
                sparse=bool(cfg.n_experts) and not dense, kind=kind)
            ys.append(y)
        return carry, ys

    def gather(ys_run):
        """``{key: [layers that gave it, ...]}`` of one run's ``ys``: a
        list over the unit's layers, each leaf leading with the unit axis
        — [units, ...] a layer -> [units * layers, ...], in layer order."""
        out = {}
        for key in ys_run[0]:
            gave = [y[key] for y in ys_run if y[key] is not None]
            if gave:
                out[key] = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a, axis=1).reshape(-1, *a[0].shape[1:]),
                    *gave)
        return out

    parts = []
    for first, n, units in _mixed_runs(cfg):
        stride = collections.Counter(kinds[first:first + n])
        if units == 1:
            carry, ys = unit(carry, first, 0, n, stride)
            ys = jax.tree_util.tree_map(lambda a: a[None], ys)
        else:
            carry, ys = jax.lax.scan(
                lambda c, i: unit(c, first, i, n, stride), carry,
                jnp.arange(units))
        parts.append(gather(ys))
    joined = {}
    # sorted: a set of strings iterates in the process's hash order, and the
    # order the joins are traced in is part of the compile cache's key
    for key in sorted({k for p in parts for k in p}):
        joined[key] = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *[p[key] for p in parts if key in p])
    return carry, joined


def _ffn(params: dict, lp: dict, l_idx, x: jnp.ndarray, cfg: ModelConfig,
         site: str, valid: jnp.ndarray | None = None, sparse: bool = True,
         **kernel_kw):
    """Layer ``l_idx``'s feed-forward on ``x`` [B, S, H]: ``_mlp``, or the
    sparse expert layer for a config with experts (a static choice; it
    takes the STACKED expert weights and the layer's index, because a
    scan's per-layer slice of them handed to a kernel is a copy).
    Returns ``(y, aux)``; ``aux`` is None for the dense layer (``sparse``
    False: a layer of a dense config, or one of a sparse config's leading
    dense layers), else ``(expert ids [B, S, k], this launch's counter
    increments [4])``, the counters over the tokens ``valid`` [B, S]
    marks."""
    if not sparse:
        return _mlp(lp, x), None
    B, S, H = x.shape
    stacked = params["moe" if cfg.has_mamba2 else "layers"]
    y, ids, stats = moe_ffn(
        x.reshape(B * S, H), lp["w_router"], stacked.get("w_gate"),
        stacked["w_up"], stacked["w_down"],
        layer=l_idx - cfg.first_k_dense,  # its place in the sparse stack
        k=cfg.n_experts_per_tok, norm_topk=cfg.norm_topk_prob,
        scoring=cfg.router_scoring, bias=lp.get("router_bias"),
        scale=cfg.routed_scaling_factor,
        site=site, valid=None if valid is None else valid.reshape(B * S),
        first_held=cfg.ep_rank * cfg.held_experts,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        **kernel_kw,
    )
    y = y.reshape(B, S, H)
    if cfg.n_shared_experts:  # every token takes it: no assignment
        y = y + _mlp(lp, x, "ws_")
    return y, (ids.reshape(B, S, -1), stats)


def _sublayers(params: dict, lp: dict, xs, x, h, op, cfg: ModelConfig,
               site: str, valid, sparse: bool, kind: str, **moe_kw) -> tuple:
    """What follows a block's operator ``op`` in the three ``mixed_layer``s:
    the residual, then the FFN behind its own pre-norm — or, in a config
    whose blocks hold ONE sublayer (mamba2 layers), a mixer's residual
    alone, and for a "moe" block (``op`` None) the expert FFN alone on the
    block's one pre-norm ``h``, at its place in the moe stack. Returns
    ``(x, aux)``."""
    l_idx, _, table = xs
    if cfg.has_mamba2:
        if kind != "moe":
            return x + op, None
        # (beside ``radixmesh_ssd_chunk``, whose row slices the compiler
        # keeps in VMEM, the grouped product asks for none of its own)
        y, aux = _ffn(params, lp, table["op_idx"], h, cfg, site, valid, True,
                      fit_vmem=True, **moe_kw)
        return x + y, aux
    x = x + op
    h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    y, aux = _ffn(params, lp, l_idx, h2, cfg, site, valid, sparse, **moe_kw)
    return x + y, aux


def _moe_aux(cfg: ModelConfig, per_layer, moe_stats, site: str,
             **extra) -> tuple:
    """The trailing ``aux`` of an entry point: ``()`` for a dense model,
    else one dict — the scan's stacked expert ids [L, B, S, k] and the
    counters with this launch added (and what ``extra`` names)."""
    if not cfg.n_experts:
        return ()
    ids, stats = per_layer
    return ({
        "expert_ids": ids,
        "moe_stats": _bump_moe_stats(moe_stats, site, jnp.sum(stats, axis=0)),
        **extra,
    },)


def _indexer_of(params: dict, table: dict) -> dict:
    """The weights of the indexer of the layer whose table row this is."""
    return jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(
            w, table["dsa_slot"], 0, keepdims=False),
        params["indexer"],
    )


def _selected(cfg: ModelConfig, positions) -> dict:
    """``aux``'s entry for the logits hook: the token positions each full
    layer selected for the sampled query of every row,
    ``index_positions`` [L_full, B, 1, K] (-1: nothing); nothing where
    the launch attended every row."""
    if positions is None:
        return {}
    return {"index_positions": _of_full_layers(cfg, positions)}


def _of_full_layers(cfg: ModelConfig, stacked: jnp.ndarray) -> jnp.ndarray:
    """What the layer scan stacked a layer [L, ...] — index keys,
    selected positions — cut to the full layers (a shared layer's entry
    is zeros, or its full layer's again): [L_full, ...]."""
    return stacked[np.asarray(cfg.full_layers)]


def _full_attn_scope(cfg: ModelConfig, decode: bool = False):
    """``rm_full_attn`` around the attention of a config whose other layers
    carry a state in the by-kind body (a trace tells its few attention
    layers apart by scope): all three programs' beside mamba layers, a
    decode step's where the blocks hold one sublayer."""
    return (jax.named_scope("rm_full_attn")
            if cfg.has_ssm or (decode and cfg.has_mamba2)
            else contextlib.nullcontext())


def _attn_out(lp: dict, attn: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The wo projection shared by every forward variant: attn is
    [B, S, Hq·D] (or already [B, S, Hq, D])."""
    B, S = attn.shape[:2]
    if cfg.latent:
        # attn is the heads' mix of latent rows [B, S, Hq, r]: their values.
        with jax.named_scope("rm_mla_unabsorb"):
            attn = jnp.einsum(
                "bsqr,rqv->bsqv", attn, _latent_split(lp, cfg)[1],
                precision=_PREC,
            )
    # One [Hq * D, H] product: written per head ("bsqd,qdh->bsh" on wo
    # viewed [Hq, D, H]) the layer scan writes its slice of wo out before
    # the product — 0.2 GB a layer at 64 heads of 256 into 6144, 3.7 ms of
    # a 35 ms latent decode step (PERF.md, PR 33); at Qwen2-7B 0.5 ms a
    # step, as much as the product itself (PR 40). A tp mesh shards the
    # flat rows by head all the same ("q_heads" leads them).
    return _wmm(lp, "wo", "bsk,kh->bsh", attn.reshape(B, S, -1))


def _logits(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.layer_norm:
        x = layer_norm(x, params["final_norm"], cfg.rms_eps, params["final_norm_b"])
    else:
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
    snap_at: jnp.ndarray | None = None,  # [B, K] conv states wanted after
):
    """Prefill new tokens against an optional cached prefix.

    Ragged prefixes are **right-aligned** in the ``P_max`` prefix region
    (row ``b`` occupies ``[P_max - prefix_lengths[b], P_max)``); the front
    padding is masked via ``kv_start``, so batched prefill with different
    hit lengths is exact. Pass ``P_max = 0`` arrays for no cache.

    Returns ``(logits [B,S,V], new_k [L,B,S,Hkv,D], new_v [...])`` (for
    latent attention ``cached_k`` / ``new_k`` are cache rows
    [L,B,·,1,width], ``cached_v`` is not read and ``new_v`` is None) — the
    caller writes new_k/new_v into the paged pool at the slots the radix
    tree allocated, which is how a served prompt becomes a reusable cached
    prefix (the contract the reference's commented-out scheduler hooks
    sketch, ``radix_cache.py:439-519``). A config with an indexer returns
    the new tokens' index keys [L_full, B, S, Di] as a fourth value (the
    caller writes them at the same slots), and a config with experts
    ``aux`` as the last (module docstring); ``new_lengths`` keeps the
    bucket's padding out of its counters. A config with conv layers takes
    no cached prefix either (a prefix is resumed from a state slot:
    ``prefill_chunk_paged``), returns ``new_k`` / ``new_v`` over its
    attention layers only and as the pool stores them, and after them the
    conv layers' states after ``snap_at`` tokens each, ``[L_conv, B, K,
    rows, 128]`` (the caller writes them to state slots).
    """
    p_max = cached_k.shape[2]
    if cfg.cross_decoder:
        if p_max or new_lengths is None or snap_at is None:
            raise ValueError(
                "prefill_forward of a model with state-space layers takes no "
                "cached prefix (a prefix resumes from a state slot: "
                "prefill_chunk_paged) and needs new_lengths and snap_at")
        return _hybrid_prefill(params, cfg, tokens, positions, new_lengths, snap_at)
    one_rope = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    x = _embed_lookup(params, tokens)
    if cfg.own_stacks and p_max:
        raise ValueError(
            "prefill_forward takes no cached prefix for a model with conv or "
            "kda layers or a window group (a prefix resumes from a state slot "
            "or a window tail: prefill_chunk_paged)"
        )
    s_new = tokens.shape[1]
    pad = p_max - prefix_lengths  # [B] front padding per row
    # Index-space position of query t (abs position p) inside the context
    # buffer [pad | prefix | new]: p + pad.
    attn_pos = positions + pad[:, None]
    kv_end = jnp.full_like(prefix_lengths, p_max + s_new)
    valid = None
    if new_lengths is not None:
        valid = jnp.arange(s_new)[None, :] < new_lengths[:, None]
    if cfg.dsa and p_max:
        raise ValueError(
            "prefill_forward takes no cached prefix for a model with learned "
            "sparse attention (the prefix's index keys lie in pages: "
            "prefill_chunk_paged)"
        )
    # Past index_topk tokens the selection engages: as a mask over the
    # dense scores (this path holds at most long_prefill_threshold tokens).
    select = cfg.dsa and s_new > cfg.index_topk
    causal = jnp.tril(jnp.ones((s_new, s_new), bool))[None] if select else None
    mask0 = jnp.zeros((tokens.shape[0], s_new, s_new) if select else (), bool)

    def mixed_layer(carry, xs, sparse, kind):
        """A layer of a config whose operators are stacks of their own
        (``_scan_mixed``): conv / attention, or attention kinds of two
        shapes — whose sliding layers' K / V go out apart (``wk`` /
        ``wv``: the window group's)."""
        x, mask = carry
        l_idx, lp, _ = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        k = v = states = wk = wv = None
        if kind == "conv":
            zero = jnp.zeros(
                (x.shape[0], cfg.conv_L_cache - 1, cfg.hidden), x.dtype)
            op, states = _short_conv(lp, h, zero, cfg, snap_at)
            states = states.reshape(*states.shape[:2], -1, 128)
        elif kind == "kda":
            # from a zero state; the states after ``snap_at`` go out
            B = x.shape[0]
            q, k, v, g, beta, conv = _kda_in(
                lp, h, jnp.zeros((B, cfg.kda_conv - 1, 3 * cfg.kda_inner), x.dtype),
                cfg, snap_at)
            # no slot to read, every row fresh: the states land in slots of
            # a launch's own (slot 0 is nobody's) and go out as they lie
            n = snap_at.size
            o, snaps = kda.kda_chunk(
                q, k, v, g, beta,
                jnp.zeros((1, 1 + n, cfg.kda_inner, cfg.kda_head_dim), jnp.float32),
                0, jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.int32), snap_at,
                1 + jnp.arange(n, dtype=jnp.int32).reshape(snap_at.shape))
            op = _kda_out(lp, o, h, cfg)
            k = v = None
            states = (snaps[0, 1:].reshape(*snap_at.shape, *snaps.shape[2:]),
                      _kda_conv_store(cfg, conv))
        elif kind == "mamba2":
            # as a kda layer: from a zero state, the states go out
            from radixmesh_tpu.ops import ssd  # (only such a config's import)
            B, n = x.shape[0], snap_at.size

            def run(*seqs):
                y, snaps = ssd.ssd_chunk(
                    *seqs, jnp.zeros((1, 1 + n, *cfg.state_shape(1)[2:]), jnp.float32),
                    0, jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.int32),
                    snap_at, 1 + jnp.arange(n, dtype=jnp.int32).reshape(snap_at.shape))
                return y, snaps[0, 1:].reshape(*snap_at.shape, *snaps.shape[2:])

            op, conv, snaps = _mamba2(
                lp, h, jnp.zeros((B, cfg.ssd_conv - 1, cfg.ssd_conv_channels), x.dtype),
                cfg, snap_at, run)
            states = (snaps, _conv_store(conv, cfg.ssd_conv_rows))
        elif kind == "mamba":
            # from a zero state; the states after ``snap_at`` go out
            B = x.shape[0]
            op, _, hs, conv = _mamba(
                lp, h, jnp.zeros((B, cfg.ssm_state, cfg.ssm_inner), jnp.float32),
                jnp.zeros((B, cfg.ssm_conv - 1, cfg.ssm_inner), x.dtype),
                cfg, snap_at, {})
            states = (hs, conv.reshape(*conv.shape[:2], -1, 128))
        elif kind == "moe":
            op = None
        elif kind == "mla":
            q, k, v = _attn_in(lp, h, cfg, positions, one_rope, None)
            op = _kind_attn_out(lp, latent_prefill_attention(
                q, k, attn_pos, kv_end, pad, cfg.kv_lora_rank,
                cfg.head_dim ** -0.5), h, cfg)
        elif cfg.heads_by_type:
            q, k, v = _kind_attn_in(lp, h, cfg, kind, positions)
            sliding = kind == "sliding_attention"
            op = _kind_attn_out(lp, attend_prefill(
                q, k, v, attn_pos, kv_end, kv_start=pad,
                window=jnp.asarray(cfg.sliding_window) if sliding else None,
            ), h, cfg)
            if sliding:
                k, v, wk, wv = None, None, k, v
        else:
            q, k, v = _attn_in(lp, h, cfg, positions, one_rope, None)
            with _full_attn_scope(cfg):
                attn = attend_prefill(q, k, v, attn_pos, kv_end, kv_start=pad)
            op = _attn_out(lp, attn, cfg)
            if cfg.pair_kv_heads:  # as the pool stores them
                _, k, v = _pair_heads(cfg, q, k, v)
        x, aux = _sublayers(params, lp, xs, x, h, op, cfg, "prefill", valid,
                            sparse, kind)
        return (x, mask), {"k": k, "v": v, "aux": aux, "state": states,
                           "wk": wk, "wv": wv}

    if cfg.own_stacks:
        (x, _), ys = _scan_layers(cfg, params, mixed_layer, (x, mask0))
        groups = (ys["wk"], ys["wv"]) if cfg.heads_by_type else (ys["state"],)
        return (_logits(params, cfg, x), ys["k"], ys.get("v"), *groups,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "prefill"))

    def layer(carry, xs, sparse):
        x, mask = carry
        l_idx, lp, table = xs
        ck = jax.lax.dynamic_index_in_dim(cached_k, l_idx, 0, keepdims=False)
        inv_freq, rope_scale, window = _rope_of(one_rope, table)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        c_q = _latent_cq(lp, h, cfg) if cfg.dsa else None
        q, k, v = _attn_in(lp, h, cfg, positions, inv_freq, rope_scale, c_q)
        k_ctx = jnp.concatenate([ck, k], axis=1)  # [B, P_max + S, Hkv, D]
        ki = None
        if cfg.dsa:

            def full(mask):
                qi, ki, wi = _index_in(
                    _indexer_of(params, table), c_q, h, cfg, positions, inv_freq)
                if select:
                    ki = ki.astype(cfg.dtype)  # score what the cache will hold
                    mask = dsa.dense_selection_mask(
                        dsa.own_scores(qi, wi, ki, causal), cfg.index_topk)
                return ki, mask

            ki, mask = jax.lax.cond(
                table["dsa_full"], full,
                lambda mask: (jnp.zeros(
                    (*h.shape[:2], cfg.index_head_dim), h.dtype), mask),
                mask,
            )
        if select:
            attn = dsa.masked_prefill_attention(
                q, k[:, :, 0], mask & causal, cfg.kv_lora_rank,
                cfg.head_dim ** -0.5,
            )
        elif cfg.latent:
            attn = latent_prefill_attention(
                q, k_ctx, attn_pos, kv_end, pad, cfg.kv_lora_rank,
                cfg.head_dim ** -0.5,
            )
        else:
            cv = jax.lax.dynamic_index_in_dim(cached_v, l_idx, 0, keepdims=False)
            v_ctx = jnp.concatenate([cv, v], axis=1)
            attn = attend_prefill(
                q, k_ctx, v_ctx, attn_pos, kv_end, kv_start=pad, window=window
            )
        x = x + _attn_out(lp, attn, cfg)
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        y, aux = _ffn(params, lp, l_idx, h2, cfg, "prefill", valid, sparse)
        return (x + y, mask), (k, v, aux, ki)

    (x, _), (new_k, new_v, aux, new_ki) = _scan_layers(
        cfg, params, layer, (x, mask0))
    index_keys = (_of_full_layers(cfg, new_ki),) if cfg.dsa else ()
    return (_logits(params, cfg, x), new_k, new_v, *index_keys,
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
    inv_freq = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
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
    donate_argnames=("kv_scale", "index_pool", "state_pool", "window_pool"),
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
    index_pool: jnp.ndarray | None = None,  # [L_full, num_slots, Di] (donated)
    state_pool: jnp.ndarray | None = None,  # [L_conv, S, rows, 128] (donated)
    state_in: jnp.ndarray | None = None,  # [B] state slot a row resumes from
    snap_at: jnp.ndarray | None = None,  # [B, K] states wanted after ... tokens
    state_out: jnp.ndarray | None = None,  # [B, K] the slots they go to
    window_pool: jnp.ndarray | None = None,  # the window group (donated)
    window_slots: jnp.ndarray | None = None,  # [B, C] its slot per chunk token
    window_table: jnp.ndarray | None = None,  # [B, max_pages] its pages
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
    docstring); its counters leave out the chunk's padding. A config with
    an indexer takes ``index_pool`` and returns it after ``kv_pool``, the
    chunk's index keys written at ``slots``; once the context (the page
    table's width and the chunk) passes ``index_topk`` its queries attend
    selected rows only (``ops/dsa.py``). A config with conv layers takes
    ``state_pool`` and returns it after ``kv_pool``: each row's
    convolutions start from the state in slot ``state_in`` (zeros where
    the chunk starts at position 0), and the states after ``snap_at``
    tokens of the chunk go to slots ``state_out`` — the row's working
    slot at its true end, a snapshot wherever the tree will cut; a slot
    nobody reads (slot 0) takes what is not wanted. A config with mamba
    layers takes the window group too (``window_pool`` and the row's slots
    and page table IN it), ``state_pool`` as ``(h, conv)``, and returns
    ``(logits [B, 1, V], kv_pool, window_pool, state_pool)``
    (``_hybrid_chunk``). A config with attention kinds of two shapes
    (``heads_by_type``) takes the window group and no state: its sliding
    layers read and write there, and it returns ``(logits, kv_pool,
    window_pool, aux)``.
    """
    refuse_unsupported(cfg, kv_quant=kv_scale is not None, device_mesh=mesh)
    if cfg.cross_decoder:
        return _hybrid_chunk(
            params, cfg, tokens, positions, kv_pool, slots, page_table,
            kv_lengths, page_size, kv_block_pages, use_kernel, interpret,
            logits_at, window_pool, window_slots, window_table, state_pool,
            state_in, snap_at, state_out)
    one_rope = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    x = _embed_lookup(params, tokens)  # [B, C, H]
    kv_pages = kv_pool.reshape(_pages_shape(kv_pool, page_size))
    num_slots = kv_pool.shape[3]
    B, C = tokens.shape
    select = cfg.dsa and page_table.shape[1] * page_size + C > cfg.index_topk
    sel0 = index_pages = None
    if cfg.dsa:
        index_pages = index_pool.reshape(
            index_pool.shape[0], num_slots // page_size, page_size, -1)
        sel0 = dsa.empty_selection(
            B, C, C, page_table.shape[1], page_size, cfg.index_topk
        ) if select else None
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
    # what every kernel of the chunk takes (the experts', the scan's)
    kernel_kw = {} if use_kernel is None else {
        "use_kernel": use_kernel, "interpret": interpret,
    }

    if cfg.heads_by_type:
        w_pages = window_pool.reshape(_pages_shape(window_pool, page_size))
    if cfg.has_conv:
        # Every conv layer's state of every row, read once before the
        # layers and written once after them: the state array stays out of
        # the layer scan (and of any branch) as the pool does.
        resumed = _state_rows(cfg, state_pool[:, state_in])  # [Lc, B, t-1, H]
        resumed = jnp.where(
            (positions[:, 0] == 0)[None, :, None, None], 0, resumed)

    def mixed_layer(carry, xs, sparse, kind):
        """A layer of a config whose operators are stacks of their own
        (``_scan_mixed``). An attention kind picks its group of rows by its
        static name: a sliding layer reads the WINDOW group's pages under
        that group's table, a full layer the pool's."""
        x, sel = carry
        l_idx, lp, table = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        k = v = states = wk = wv = None
        if kind == "conv":
            mine = jax.lax.dynamic_index_in_dim(
                resumed, table["op_idx"], 0, keepdims=False)
            op, states = _short_conv(lp, h, mine, cfg, snap_at)
            states = states.reshape(*states.shape[:2], -1, 128)
        elif kind == "kda":
            # The layer reads its rows' slots and writes the states after
            # ``snap_at`` to ``state_out`` HERE: the two arrays ride the
            # carry, no layer's snapshots are kept for a later scatter (the
            # matrices never leave the kernel but to their slots).
            s_pool, c_pool = sel
            i = table["op_idx"]
            fresh = positions[:, 0] == 0
            q, k, v, g, beta, conv = _kda_in(
                lp, h, jnp.where(
                    fresh[:, None, None], 0,
                    _kda_conv_load(cfg, c_pool[i, state_in])),
                cfg, snap_at)
            o, s_pool = kda.kda_chunk(
                q, k, v, g, beta, s_pool, i, state_in, fresh, snap_at, state_out,
                use_kernel=use_kernel, interpret=interpret)
            op = _kda_out(lp, o, h, cfg)
            k = v = None
            conv = _kda_conv_store(cfg, conv).astype(c_pool.dtype)
            sel = (s_pool, c_pool.at[i, state_out.reshape(-1)].set(
                conv.reshape(-1, *conv.shape[2:])))
        elif kind == "mamba2":
            # as a kda layer: the slots are read and written HERE
            from radixmesh_tpu.ops import ssd
            s_pool, c_pool = sel
            i = table["op_idx"]
            fresh = positions[:, 0] == 0
            op, conv, s_pool = _mamba2(
                lp, h, jnp.where(fresh[:, None, None], 0,
                                 _ssd_conv_load(cfg, c_pool[i, state_in])),
                cfg, snap_at, lambda *seqs: ssd.ssd_chunk(
                    *seqs, s_pool, i, state_in, fresh, snap_at, state_out,
                    use_kernel=use_kernel, interpret=interpret))
            conv = _conv_store(conv, cfg.ssd_conv_rows).astype(c_pool.dtype)
            sel = (s_pool, c_pool.at[i, state_out.reshape(-1)].set(
                conv.reshape(-1, *conv.shape[2:])))
        elif kind == "mamba":
            # as a kda layer: the slots are read and written HERE
            h0, conv0 = _states_in(
                cfg, sel, table["op_idx"], (positions[:, 0] == 0)[:, None, None],
                state_in)
            op, _, hs, conv = _mamba(lp, h, h0, conv0, cfg, snap_at, kernel_kw)
            sel = _states_out(sel, table["op_idx"], state_out.reshape(-1), hs, conv)
        elif kind == "moe":
            op = None
        elif kind == "mla":
            q, k, _ = _attn_in(lp, h, cfg, positions, one_rope, None)
            k = k.astype(kv_pool.dtype)  # attend what the pool will hold
            attn = latent_chunk_attention(
                q, k[:, :, 0], kv_pages, page_table, positions, prior_lengths,
                kv_lengths, table["op_idx"], cfg.kv_lora_rank,
                cfg.head_dim ** -0.5, kv_block_pages=kv_block_pages,
                use_kernel=use_kernel, interpret=interpret)
            op = _kind_attn_out(lp, attn, h, cfg)
        elif cfg.heads_by_type:
            q, k, v = _kind_attn_in(lp, h, cfg, kind, positions)
            k, v = k.astype(kv_pool.dtype), v.astype(kv_pool.dtype)
            sliding = kind == "sliding_attention"
            attn = paged_chunk_attention(
                q, k, v, w_pages if sliding else kv_pages,
                window_table if sliding else page_table, positions,
                prior_lengths, kv_lengths, table["op_idx"],
                kv_block_pages=kv_block_pages, use_kernel=use_kernel,
                interpret=interpret,
                window=jnp.asarray(cfg.sliding_window) if sliding else None,
            )
            op = _kind_attn_out(lp, attn, h, cfg)
            if sliding:
                k, v, wk, wv = None, None, k, v
        else:
            q, k, v = _attn_in(lp, h, cfg, positions, one_rope, None)
            scale = None
            if cfg.pair_kv_heads:
                q, k, v = _pair_heads(cfg, q, k, v)
                scale = cfg.head_dim ** -0.5
            k, v = k.astype(kv_pool.dtype), v.astype(kv_pool.dtype)
            with _full_attn_scope(cfg):
                attn = paged_chunk_attention(
                    q, k, v, kv_pages, page_table, positions, prior_lengths,
                    kv_lengths, table["op_idx"], kv_block_pages=kv_block_pages,
                    use_kernel=use_kernel, interpret=interpret, scale=scale,
                )
            if cfg.pair_kv_heads:
                attn = _own_lanes(cfg, attn)
            op = _attn_out(lp, attn, cfg)
        x, aux = _sublayers(params, lp, xs, x, h, op, cfg, "prefill", valid,
                            sparse, kind, **kernel_kw)
        return (x, sel), {"k": k, "v": v, "aux": aux, "state": states,
                          "wk": wk, "wv": wv}

    def layer(carry, xs, sparse):
        x, sel = carry
        l_idx, lp, table = xs
        inv_freq, rope_scale, window = _rope_of(one_rope, table)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        c_q = _latent_cq(lp, h, cfg) if cfg.dsa else None
        q, k, v = _attn_in(lp, h, cfg, positions, inv_freq, rope_scale, c_q)
        if kv_scale is not None:
            # Quantize NOW and attend the dequantized copy, so the chunk
            # sees exactly what any later pool read will see (the fused
            # decode kernel keeps the same invariant) — otherwise logits
            # drift between a speculative verify pass and plain decode.
            from radixmesh_tpu.ops.quant import quantize_for_store

            k_int, v_int, k_sc, v_sc, k, v = quantize_for_store(k, v)
        ki = None
        if cfg.dsa:

            def full(sel):
                qi, ki, wi = _index_in(
                    _indexer_of(params, table), c_q, h, cfg, positions, inv_freq)
                ki = ki.astype(index_pool.dtype)  # score what the pool holds
                if select:
                    sel = dsa.chunk_select(
                        qi, wi, ki, index_pages, page_table, prior_lengths,
                        kv_lengths, table["dsa_slot"], cfg.index_topk,
                        use_kernel=use_kernel, interpret=interpret,
                    )
                return ki, sel

            ki, sel = jax.lax.cond(
                table["dsa_full"], full,
                lambda sel: (jnp.zeros(
                    (B, C, cfg.index_head_dim), index_pool.dtype), sel),
                sel,
            )
        if cfg.latent:
            k = k.astype(kv_pool.dtype)  # attend what the pool will hold
        if select:
            attn = dsa.chunk_attend(
                q, k[:, :, 0], sel, kv_pages, l_idx, cfg.kv_lora_rank,
                cfg.head_dim ** -0.5, use_kernel=use_kernel,
                interpret=interpret,
            )
        elif cfg.latent:
            attn = latent_chunk_attention(
                q, k[:, :, 0], kv_pages, page_table, positions, prior_lengths,
                kv_lengths, l_idx, cfg.kv_lora_rank, cfg.head_dim ** -0.5,
                kv_block_pages=kv_block_pages, use_kernel=use_kernel,
                interpret=interpret,
            )
        else:
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
        y, aux = _ffn(params, lp, l_idx, h2, cfg, "prefill", valid, sparse,
                      **kernel_kw)
        x = x + y
        if kv_scale is not None:
            return (x, sel), (k_int, v_int, k_sc, v_sc)
        picked = None
        if select:  # what the sampled position selected, for the logits hook
            at = jnp.zeros((B,), jnp.int32) if logits_at is None else logits_at
            picked = jnp.take_along_axis(
                sel.positions, at[:, None, None], axis=1)
        if cfg.latent:
            return (x, sel), (k, None, aux, ki, picked)
        return (x, sel), (
            k.astype(kv_pool.dtype), v.astype(kv_pool.dtype), aux, ki, picked)

    def pick(x):
        if logits_at is None:
            return x
        return jnp.take_along_axis(x, logits_at[:, None, None], axis=1)

    if kv_scale is not None:
        (x, _), (new_k, new_v, new_ks, new_vs) = _scan_layers(
            cfg, params, layer, (x, None))
        # Already quantized in-layer (same ints attention saw); scatter the
        # int8 payloads + scales, by whole pages: scan stacks
        # [L, B, C, Hkv(, D)] → the pool takes [2, L, Hkv, B, C(, D)].
        new_kv = jnp.stack([new_k, new_v]).transpose(0, 1, 4, 2, 3, 5)
        new_s = jnp.stack([new_ks, new_vs]).transpose(0, 1, 4, 2, 3)
        kv_pool, kv_scale = write_runs_all(
            [(kv_pool, new_kv, ALL_LAYERS), (kv_scale, new_s, ALL_LAYERS)],
            slots, page_size)
        return _logits(params, cfg, pick(x)), kv_pool, kv_scale
    if cfg.heads_by_type:
        (x, _), ys = _scan_layers(cfg, params, mixed_layer, (x, None))
        # Each group's rows at its own slots, by whole pages.
        kv_pool, = write_runs_all(
            [(kv_pool, jnp.stack([ys["k"], ys["v"]]).transpose(0, 1, 4, 2, 3, 5),
              ALL_LAYERS)], slots, page_size)
        window_pool, = write_runs_all(
            [(window_pool,
              jnp.stack([ys["wk"], ys["wv"]]).transpose(0, 1, 4, 2, 3, 5),
              ALL_LAYERS)], window_slots, page_size)
        return (_logits(params, cfg, pick(x)), kv_pool, window_pool,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "prefill"))
    if cfg.carried_state:
        (x, state_pool), ys = _scan_layers(
            cfg, params, mixed_layer, (x, tuple(state_pool)))
        new_kv = ys["k"][None] if cfg.latent else jnp.stack([ys["k"], ys["v"]])
        kv_pool, = write_runs_all(
            [(kv_pool, new_kv.transpose(0, 1, 4, 2, 3, 5), ALL_LAYERS)],
            slots, page_size)
        return (_logits(params, cfg, pick(x)), kv_pool, state_pool,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "prefill"))
    if cfg.has_conv:
        (x, _), ys = _scan_layers(cfg, params, mixed_layer, (x, None))
        new_kv = jnp.stack([ys["k"], ys["v"]]).transpose(0, 1, 4, 2, 3, 5)
        kv_pool, = write_runs_all(
            [(kv_pool, new_kv, ALL_LAYERS)], slots, page_size)
        states = ys["state"]  # [Lc, B, K, rows, 128]
        state_pool = state_pool.at[:, state_out.reshape(-1)].set(
            states.reshape(states.shape[0], -1, *states.shape[3:]))
        return (_logits(params, cfg, pick(x)), kv_pool, state_pool,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "prefill"))
    (x, _), (new_k, new_v, aux, new_ki, picked) = _scan_layers(
        cfg, params, layer, (x, sel0))
    # One write for the whole chunk across all layers, by whole pages (each
    # row of ``slots`` is a run: cache/kv_pool.py): scan stacks
    # [L, B, C, Hkv, D] a plane; the pool takes [planes, L, Hkv, B, C, D].
    planes = [new_k] if new_v is None else [new_k, new_v]
    new_kv = jnp.stack(planes).transpose(0, 1, 4, 2, 3, 5)
    writes = [(kv_pool, new_kv, ALL_LAYERS)]
    if cfg.dsa:  # the chunk's index keys, at the same slots
        writes.append(
            (index_pool, _of_full_layers(cfg, new_ki), (slice(None),)))
    pools = write_runs_all(writes, slots, page_size)
    return (_logits(params, cfg, pick(x)), *pools,
            *_moe_aux(cfg, aux, moe_stats, "prefill",
                      **_selected(cfg, picked)))


@partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "mesh"),
    donate_argnums=(3,),
    donate_argnames=("kv_scale", "index_pool", "state_pool", "window_pool"),
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
    index_pool: jnp.ndarray | None = None,  # [L_full, num_slots, Di] (donated)
    state_pool: jnp.ndarray | None = None,  # [L_conv, S, rows, 128] (donated)
    state_slots: jnp.ndarray | None = None,  # [2, B]: working slot, second slot
    window_pool: jnp.ndarray | None = None,  # the window group (donated)
    window_slots: jnp.ndarray | None = None,  # [B] its slot for this token
    window_table: jnp.ndarray | None = None,  # [B, max_pages] its pages
    decode_groups: jnp.ndarray | None = None,  # rows that read the same pages
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
    ``lengths`` is 1 is an idle batch row and stays out of its counters.
    A config with an indexer takes ``index_pool`` and returns it after
    ``kv_pool``; once the page table is wider than ``index_topk`` tokens
    a full layer scores the row's index keys and selects, and every layer
    reads the selected rows only (``ops/dsa.py``). A config with conv
    layers takes ``state_pool`` and returns it after ``kv_pool``: a row's
    state is read from its working slot ``state_slots[0]`` and the new one
    written back there, and to ``state_slots[1]`` as well — the row's
    boundary slot when this token completes a page (the state the tree
    will want at release), slot 0 otherwise (one array: one upload a
    step). A config with mamba layers takes the window group too and
    returns ``(logits, kv_pool, window_pool, state_pool)``
    (``_hybrid_decode``); a config with attention kinds of two shapes
    takes the window group alone and returns ``(logits, kv_pool,
    window_pool, aux)``. A config with a latent row may be told which
    rows hold the same pages first (``ops/mla_attention.py::decode_groups``,
    from the launch's page table; its shape depends on ``B`` alone): the
    latent decode kernel then reads those pages once for all of them."""
    if cfg.cross_decoder:
        refuse_unsupported(cfg, kv_quant=kv_scale is not None, device_mesh=mesh)
        return _hybrid_decode(
            params, cfg, tokens, kv_pool, slots, page_table, lengths, page_size,
            window_pool, window_slots, window_table, state_pool, state_slots)
    return _decode_core(
        params, cfg, tokens, kv_pool, slots, page_table, lengths, page_size,
        mesh, kv_scale, moe_stats, index_pool, state_pool, state_slots,
        decode_groups, window_pool, window_slots, window_table,
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
    index_pool: jnp.ndarray | None = None,
    state_pool: jnp.ndarray | None = None,
    state_slots: jnp.ndarray | None = None,
    decode_groups: jnp.ndarray | None = None,
    window_pool: jnp.ndarray | None = None,
    window_slots: jnp.ndarray | None = None,
    window_table: jnp.ndarray | None = None,
):
    refuse_unsupported(cfg, kv_quant=kv_scale is not None, device_mesh=mesh)
    one_rope = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    positions = lengths - 1  # [B]
    live = (lengths > 1)[:, None]  # idle rows ride with lengths == 1
    x = _embed_lookup(params, tokens)[:, None, :]  # [B, 1, H]
    B = tokens.shape[0]
    num_slots = kv_pool.shape[3]
    pool_shape = kv_pool.shape
    pages_shape = _pages_shape(kv_pool, page_size)

    scales_shape = (
        2, cfg.n_layers, cfg.n_kv_heads, num_slots // page_size, page_size,
    )

    select = cfg.dsa and page_table.shape[1] * page_size + 1 > cfg.index_topk
    sel0 = dsa.empty_selection(
        B, 1, 1, page_table.shape[1], page_size, cfg.index_topk
    ) if select else None
    index_shape = None if index_pool is None else index_pool.shape
    index_pages_shape = None if index_pool is None else (
        index_shape[0], num_slots // page_size, page_size, index_shape[2])

    if cfg.has_conv:
        # Read once, written once (prefill_chunk_paged): 2 x hidden a row
        # and conv layer, never the state array inside the scan.
        resumed = _state_rows(cfg, state_pool[:, state_slots[0]])  # [Lc, B, t-1, H]

    def mixed_layer(carry, xs, sparse, kind):
        """A layer of a config whose operators are stacks of their own
        (``_scan_mixed``). An attention kind writes and reads its own group
        of rows, picked by its static name: a sliding layer is a launch of
        the fused decode kernel on the WINDOW group with its window, a full
        layer one on the pool; both arrays ride the carry in place."""
        x, kv_pool, window_pool = carry
        l_idx, lp, table = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        states = None
        if kind == "conv":
            mine = jax.lax.dynamic_index_in_dim(
                resumed, table["op_idx"], 0, keepdims=False)
            op, states = _short_conv(lp, h, mine, cfg, None)
            states = states.reshape(B, -1, 128)
        elif kind == "kda":
            # The row's working slot, read and written in place; the state
            # arrays ride the carry where another config's window group does.
            s_pool, c_pool = window_pool
            i, work = table["op_idx"], state_slots[0]
            q, k, v, g, beta, conv = _kda_in(
                lp, h, _kda_conv_load(cfg, c_pool[i, work]), cfg, None)
            o, s_pool = kda.kda_decode(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s_pool, i, work)
            window_pool = (s_pool, c_pool.at[i, work].set(
                _kda_conv_store(cfg, conv[:, 0]).astype(c_pool.dtype)))
            op = _kda_out(lp, o[:, None], h, cfg)
        elif kind == "mamba2":
            from radixmesh_tpu.ops import ssd
            s_pool, c_pool = window_pool
            i, work = table["op_idx"], state_slots[0]

            def run(*seqs):
                y, pool = ssd.ssd_decode(*(t[:, 0] for t in seqs), s_pool, i, work)
                return y[:, None], pool

            op, conv, s_pool = _mamba2(
                lp, h, _ssd_conv_load(cfg, c_pool[i, work]), cfg, None, run)
            window_pool = (s_pool, c_pool.at[i, work].set(_conv_store(
                conv[:, 0], cfg.ssd_conv_rows).astype(c_pool.dtype)))
        elif kind == "mamba":
            # the row's working slot, read and written in place
            h_pool, c_pool = window_pool
            i, work = table["op_idx"], state_slots[0]
            op, _, hs, conv = _mamba(
                lp, h, h_pool[i, work], _conv_rows(cfg, c_pool[i, work]), cfg,
                None, {})
            window_pool = (h_pool.at[i, work].set(hs[:, 0]), c_pool.at[i, work].set(
                conv[:, 0].reshape(B, -1, 128).astype(c_pool.dtype)))
        elif kind == "moe":
            op = None
        elif kind == "mla":
            q, k, _ = _attn_in(lp, h, cfg, positions[:, None], one_rope, None)
            attn, pages = latent_decode_attention(
                q[:, 0], k[:, 0, 0].astype(kv_pool.dtype),
                kv_pool.reshape(pages_shape), slots, page_table, lengths,
                table["op_idx"], cfg.kv_lora_rank, cfg.head_dim ** -0.5,
                decode_groups)
            kv_pool = pages.reshape(pool_shape)
            op = _kind_attn_out(lp, attn[:, None], h, cfg)
        elif kind == "sliding_attention":
            q, k, v = _kind_attn_in(lp, h, cfg, kind, positions[:, None])
            with jax.named_scope("rm_window_attn"):
                attn, pages = paged_decode_attention(
                    q[:, 0], k[:, 0].astype(window_pool.dtype),
                    v[:, 0].astype(window_pool.dtype),
                    window_pool.reshape(_pages_shape(window_pool, page_size)),
                    window_slots, window_table, lengths, table["op_idx"],
                    window=jnp.asarray(cfg.sliding_window, jnp.int32))
            window_pool = pages.reshape(window_pool.shape)
            op = _kind_attn_out(lp, attn[:, None], h, cfg)
        elif cfg.heads_by_type:
            q, k, v = _kind_attn_in(lp, h, cfg, kind, positions[:, None])
            with jax.named_scope("rm_full_attn"):
                attn, pages = paged_decode_attention(
                    q[:, 0], k[:, 0].astype(kv_pool.dtype),
                    v[:, 0].astype(kv_pool.dtype), kv_pool.reshape(pages_shape),
                    slots, page_table, lengths, table["op_idx"])
            kv_pool = pages.reshape(pool_shape)
            op = _kind_attn_out(lp, attn[:, None], h, cfg)
        else:
            q, k, v = _attn_in(lp, h, cfg, positions[:, None], one_rope, None)
            scale = None
            if cfg.pair_kv_heads:
                q, k, v = _pair_heads(cfg, q, k, v)
                scale = cfg.head_dim ** -0.5
            with _full_attn_scope(cfg, decode=True):
                attn, kv_pages = paged_decode_attention(
                    q[:, 0], k[:, 0].astype(kv_pool.dtype),
                    v[:, 0].astype(kv_pool.dtype), kv_pool.reshape(pages_shape),
                    slots, page_table, lengths, table["op_idx"], scale=scale,
                )
            kv_pool = kv_pages.reshape(pool_shape)
            attn = attn[:, None]
            if cfg.pair_kv_heads:
                attn = _own_lanes(cfg, attn)
            op = _attn_out(lp, attn, cfg)
        x, aux = _sublayers(params, lp, xs, x, h, op, cfg, "decode", live,
                            sparse, kind)
        return (x, kv_pool, window_pool), {"aux": aux, "state": states}

    if cfg.heads_by_type:
        (x, kv_pool, window_pool), ys = _scan_layers(
            cfg, params, mixed_layer, (x, kv_pool, window_pool))
        return (_logits(params, cfg, x)[:, 0], kv_pool, window_pool,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "decode"))
    if cfg.carried_state:
        (x, kv_pool, state_pool), ys = _scan_layers(
            cfg, params, mixed_layer, (x, kv_pool, tuple(state_pool)))
        # The boundary copies: the rows whose token completed a page keep
        # the state they leave in their boundary slot (state_slots[1]; 0:
        # none), slot to slot over every layer, behind the layers.
        state_pool = tuple(
            kda.copy_slots(a, state_slots[0], state_slots[1]) for a in state_pool)
        return (_logits(params, cfg, x)[:, 0], kv_pool, state_pool,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "decode"))
    if cfg.has_conv:
        (x, kv_pool, _), ys = _scan_layers(
            cfg, params, mixed_layer, (x, kv_pool, None))
        states = ys["state"]  # [Lc, B, rows, 128]
        state_pool = state_pool.at[:, state_slots.reshape(-1)].set(
            jnp.concatenate([states, states], axis=1))
        return (_logits(params, cfg, x)[:, 0], kv_pool, state_pool,
                *_moe_aux(cfg, ys.get("aux"), moe_stats, "decode"))

    def layer(carry, xs, sparse):
        x, kv_pool, kv_scale, index_pool, sel = carry
        l_idx, lp, table = xs
        inv_freq, rope_scale, window = _rope_of(one_rope, table)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        c_q = _latent_cq(lp, h, cfg) if cfg.dsa else None
        q, k, v = _attn_in(  # [B,1,*,D]
            lp, h, cfg, positions[:, None], inv_freq, rope_scale, c_q
        )
        if cfg.dsa:
            index_pages = index_pool.reshape(index_pages_shape)

            def full(sel):
                qi, ki, wi = _index_in(
                    _indexer_of(params, table), c_q, h, cfg,
                    positions[:, None], inv_freq)
                ki = ki[:, 0].astype(index_pool.dtype)
                if select:
                    sel = dsa.decode_select(
                        qi[:, 0], wi[:, 0], ki, index_pages, page_table,
                        lengths, table["dsa_slot"], cfg.index_topk)
                return ki, sel

            # The branch READS the index pool and returns the key alone: a
            # pool that a branch returns is copied whole, in and out.
            ki, sel = jax.lax.cond(
                table["dsa_full"], full,
                lambda sel: (jnp.zeros((B, index_shape[2]), index_pool.dtype), sel),
                sel,
            )
            at = (table["dsa_slot"], slots)
            index_pool = index_pool.at[at].set(
                jnp.where(table["dsa_full"], ki, index_pool[at]))
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
        elif select:
            attn, kv_pages = dsa.decode_attend(
                q[:, 0], k[:, 0, 0].astype(kv_pool.dtype), sel,
                kv_pool.reshape(pages_shape), slots, l_idx, cfg.kv_lora_rank,
                cfg.head_dim ** -0.5,
            )
        elif cfg.latent:
            attn, kv_pages = latent_decode_attention(
                q[:, 0],
                k[:, 0, 0].astype(kv_pool.dtype),
                kv_pool.reshape(pages_shape),
                slots,
                page_table,
                lengths,
                l_idx,
                cfg.kv_lora_rank,
                cfg.head_dim ** -0.5,
                decode_groups,
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
        kv_pool = kv_pages.reshape(pool_shape)
        x = x + _attn_out(lp, attn[:, None], cfg)
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        y, aux = _ffn(params, lp, l_idx, h2, cfg, "decode", live, sparse)
        return (x + y, kv_pool, kv_scale, index_pool, sel), (
            aux, sel.positions if select else None)

    (x, kv_pool, kv_scale, index_pool, _), (aux, picked) = _scan_layers(
        cfg, params, layer, (x, kv_pool, kv_scale, index_pool, sel0)
    )
    logits = _logits(params, cfg, x)[:, 0]
    if kv_scale is not None:
        return logits, kv_pool, kv_scale
    pools = (kv_pool,) if index_pool is None else (kv_pool, index_pool)
    return logits, *pools, *_moe_aux(
        cfg, aux, moe_stats, "decode", **_selected(cfg, picked))


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

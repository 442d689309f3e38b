"""Attention: prefill (dense causal with cached-prefix reuse) and decode
(paged, reading radix-cache pages).

This is the seam the reference leaves open — its commented-out SGLang
scheduler hooks show where a model runtime would consume the radix cache's
``MatchResult.device_indices`` (``radix_cache.py:439-519``). Here that
contract is realized for TPU:

- ``attend_prefill``: new tokens attend causally to themselves *and* to an
  already-cached prefix gathered from the paged KV pool — the prefix-reuse
  path that turns a radix-cache hit into skipped prefill FLOPs.
- ``paged_attention``: decode-step attention over non-contiguous KV pages
  via the Pallas kernel (``ops/paged_attention.py``) on TPU, with a
  gather-based jnp reference used on CPU and as the numerics oracle.

All dense math is einsum-based so XLA maps it onto the MXU; softmax runs in
fp32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _softmax_scale(head_dim: int, scale):
    """The factor on the logits: ``scale`` as given, or 1 / sqrt(head_dim)
    (a latent-attention caller's queries are wider than the head whose
    width sets its scale: ``ops/mla_attention.py``)."""
    if scale is None:
        return 1.0 / jnp.sqrt(jnp.asarray(head_dim, dtype=jnp.float32))
    return jnp.asarray(scale, dtype=jnp.float32)


def _in_window(distance, window):
    """Is a key ``distance`` positions behind its query inside a window of
    ``window`` keys (the query's own among them)? 0 means no window."""
    return (window <= 0) | (distance < window)


@partial(jax.jit, static_argnames=())
def attend_prefill(
    q: jnp.ndarray,  # [B, S_new, Hq, D]
    k: jnp.ndarray,  # [B, S_ctx, Hkv, D]  (cached prefix ++ new, rotated)
    v: jnp.ndarray,  # [B, S_ctx, Hkv, D]
    q_positions: jnp.ndarray,  # [B, S_new] index-space positions of q tokens
    kv_lengths: jnp.ndarray,  # [B] valid context end (index space)
    kv_start: jnp.ndarray | None = None,  # [B] valid context begin (ragged pad)
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
) -> jnp.ndarray:
    """Causal attention where queries start mid-context (after a cached
    prefix): query at index-space position p attends to kv indices in
    ``[kv_start, min(p+1, kv_lengths))``. ``kv_start`` masks front padding
    when ragged cached prefixes are right-aligned into a fixed-size prefix
    region (see ``models/llama.py::prefill_forward``). ``window`` (a
    traced scalar; the query's own key counts) hides keys further back
    than that on a window layer. Returns [B, S_new, Hq, D]."""
    B, S_new, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    # Group queries instead of repeating K/V (a Hq/Hkv-fold memory copy on
    # long contexts); inputs stay in their native dtype (bf16 rides the MXU
    # one-pass), accumulation and softmax are fp32, and HIGHEST stops XLA
    # from demoting fp32 inputs to bf16 multiplies (the TPU default).
    qg = q.reshape(B, S_new, Hkv, G, D)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        qg,
        k,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    logits = logits * _softmax_scale(D, scale)
    kv_pos = jnp.arange(k.shape[1])[None, None, None, None, :]  # [1,1,1,1,K]
    causal = kv_pos <= q_positions[:, None, None, :, None]  # [B,1,1,Q,K]
    valid = kv_pos < kv_lengths[:, None, None, None, None]
    if kv_start is not None:
        valid = valid & (kv_pos >= kv_start[:, None, None, None, None])
    if window is not None:
        valid = valid & _in_window(
            q_positions[:, None, None, :, None] - kv_pos, window
        )
    logits = jnp.where(causal & valid, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd",
        weights,
        v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(B, S_new, Hq, D).astype(q.dtype)


@partial(jax.jit, static_argnames=())
def attend_decode_ref(
    q: jnp.ndarray,  # [B, Hq, D] one new token per sequence
    k_pages: jnp.ndarray,  # [Hkv, P, page, D] head-major paged pool (one layer)
    v_pages: jnp.ndarray,  # [Hkv, P, page, D]
    page_table: jnp.ndarray,  # [B, max_pages] page ids (padded arbitrarily)
    lengths: jnp.ndarray,  # [B] context length incl. current token
    k_scales: jnp.ndarray | None = None,  # [Hkv, P, page] int8-pool scales
    v_scales: jnp.ndarray | None = None,
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
) -> jnp.ndarray:
    """Gather-based paged decode attention — the numerics oracle for the
    Pallas kernel and the CPU execution path. With ``*_scales`` the pages
    are int8 and value ≈ page · scale (``ops/quant.py``)."""
    B, Hq, D = q.shape
    Hkv, _, page, _ = k_pages.shape
    G = Hq // Hkv
    max_ctx = page_table.shape[1] * page
    # [Hkv, B, maxp, page, D] → token-major [B, ctx, Hkv, D]; queries are
    # grouped rather than repeating K/V.
    k = k_pages[:, page_table].reshape(Hkv, B, max_ctx, D).transpose(1, 2, 0, 3)
    v = v_pages[:, page_table].reshape(Hkv, B, max_ctx, D).transpose(1, 2, 0, 3)
    if k_scales is not None:
        ks = k_scales[:, page_table].reshape(Hkv, B, max_ctx).transpose(1, 2, 0)
        vs = v_scales[:, page_table].reshape(Hkv, B, max_ctx).transpose(1, 2, 0)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    qg = q.reshape(B, Hkv, G, D)
    scale = _softmax_scale(D, scale)
    logits = (
        jnp.einsum(
            "bhgd,bkhd->bhgk",
            qg,
            k,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        * scale
    )
    kv_pos = jnp.arange(max_ctx)[None, None, None, :]
    valid = kv_pos < lengths[:, None, None, None]
    if window is not None:  # the query sits at lengths - 1
        valid = valid & _in_window(
            lengths[:, None, None, None] - 1 - kv_pos, window
        )
    logits = jnp.where(valid, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd",
        weights,
        v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(B, Hq, D).astype(q.dtype)


@partial(jax.jit, static_argnames=("kv_block_pages",))
def attend_prefill_paged(
    q: jnp.ndarray,  # [B, C, Hq, D] one chunk of new tokens
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] full-pool pages view
    page_table: jnp.ndarray,  # [B, max_pages] this request's pages, in order
    q_positions: jnp.ndarray,  # [B, C] absolute positions of the chunk
    kv_lengths: jnp.ndarray,  # [B] valid context tokens (incl. this chunk)
    layer: jnp.ndarray | int,
    kv_block_pages: int = 32,
    kv_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Blockwise causal attention for CHUNKED prefill over the paged pool
    (SURVEY §5 long-context): a chunk of C queries attends to the whole
    context so far — cached prefix plus already-written chunk K/V — by
    scanning fixed-size KV page blocks with an online softmax. Peak memory
    is O(C · block), never O(S²): a 32k prompt prefills in C-token chunks
    against pages, where the dense path (``attend_prefill``) would
    materialize a 32k×32k score tensor. Blocks are gathered per scan step
    (one block resident at a time), contracted on the MXU in fp32.

    ``max_pages`` must be a multiple of ``kv_block_pages`` (callers bucket
    both to powers of two). Returns [B, C, Hq, D].
    """
    m, l, acc = _page_block_softmax(
        q, kv_pages, page_table, q_positions, kv_lengths, layer, kv_block_pages,
        kv_scales,
    )
    # Padded queries (chunk tail) can end with l == 0; their rows are
    # discarded by the caller — emit 0 instead of NaN so nothing poisons
    # downstream reductions.
    B, C, Hq, D = q.shape
    out = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, Hq, D).astype(q.dtype)


def _page_block_softmax(
    q: jnp.ndarray,  # [B, C, Hq, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D]
    page_table: jnp.ndarray,  # [B, max_pages]
    q_positions: jnp.ndarray,  # [B, C]
    kv_bound: jnp.ndarray,  # [B] tokens of pool context to attend (< bound)
    layer: jnp.ndarray | int,
    kv_block_pages: int,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] int8 pools
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
):
    """Shared core of the chunked-prefill attentions: scan fixed-size page
    blocks of one layer's pool context, maintaining the online softmax
    ``(m, l, acc)`` in [B, Hkv, G, C, ·] layout. Causal vs ``q_positions``
    and bounded by ``kv_bound`` per row. Callers normalize (and may merge
    further blocks — ``attend_chunk_hybrid`` adds the chunk itself dense)."""
    B, C, Hq, D = q.shape
    _, _, Hkv, _, page, _ = kv_pages.shape
    G = Hq // Hkv
    max_pages = page_table.shape[1]
    assert max_pages % kv_block_pages == 0, (max_pages, kv_block_pages)
    n_blocks = max_pages // kv_block_pages
    bk = kv_block_pages * page  # tokens per block

    scale = _softmax_scale(D, scale)
    # [B, Hkv, G, C, D] so every block step is one fp32 MXU contraction.
    qg = (q.astype(jnp.float32) * scale).reshape(B, C, Hkv, G, D).transpose(
        0, 2, 3, 1, 4
    )
    k_layer = kv_pages[0, layer]  # [Hkv, P, page, D]
    v_layer = kv_pages[-1, layer]  # a one-plane pool: its rows are the values too
    ks_layer = vs_layer = None
    if kv_scales is not None:
        ks_layer = kv_scales[0, layer]  # [Hkv, P, page]
        vs_layer = kv_scales[1, layer]
    qpos = q_positions[:, None, None, :, None]  # [B,1,1,C,1]
    bound = kv_bound[:, None, None, None, None]

    def block(carry, blk):
        m, l, acc = carry
        pids = jax.lax.dynamic_slice(
            page_table, (0, blk * kv_block_pages), (B, kv_block_pages)
        )  # [B, bp]
        # [Hkv, B, bp, page, D] → [B, Hkv, bk, D]
        k = k_layer[:, pids].reshape(Hkv, B, bk, D).transpose(1, 0, 2, 3)
        v = v_layer[:, pids].reshape(Hkv, B, bk, D).transpose(1, 0, 2, 3)
        if ks_layer is not None:
            ks = ks_layer[:, pids].reshape(Hkv, B, bk).transpose(1, 0, 2)
            vs = vs_layer[:, pids].reshape(Hkv, B, bk).transpose(1, 0, 2)
            k = k.astype(jnp.float32) * ks[..., None]
            v = v.astype(jnp.float32) * vs[..., None]
        s = jax.lax.dot_general(
            qg,
            k.astype(jnp.float32),
            dimension_numbers=(((4,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )  # [B, Hkv, G, C, bk]
        kv_pos = (blk * bk + jnp.arange(bk))[None, None, None, None, :]
        ok = (kv_pos <= qpos) & (kv_pos < bound)
        if window is not None:
            ok = ok & _in_window(qpos - kv_pos, window)
        s = jnp.where(ok, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # _NEG_INF-masked lanes give exp(_NEG_INF - m_new) == 0 exactly
        # (m_new >= first-block valid scores > _NEG_INF for real queries).
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p,
            v.astype(jnp.float32),
            dimension_numbers=(((4,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )  # [B, Hkv, G, C, D]
        acc_new = acc * corr + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, C, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, C, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, C, D), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(block, (m0, l0, acc0), jnp.arange(n_blocks))
    return m, l, acc


@partial(jax.jit, static_argnames=("kv_block_pages",))
def attend_chunk_hybrid(
    q: jnp.ndarray,  # [B, C, Hq, D] one chunk of new tokens
    k_cur: jnp.ndarray,  # [B, C, Hkv, D] this chunk's K (post-rope)
    v_cur: jnp.ndarray,  # [B, C, Hkv, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] full-pool pages view
    page_table: jnp.ndarray,  # [B, max_pages] this request's pages, in order
    q_positions: jnp.ndarray,  # [B, C] absolute positions of the chunk
    prior_lengths: jnp.ndarray,  # [B] context tokens BEFORE this chunk
    kv_lengths: jnp.ndarray,  # [B] valid context incl. this chunk
    layer: jnp.ndarray | int,
    kv_block_pages: int = 32,
    kv_scales: jnp.ndarray | None = None,
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
) -> jnp.ndarray:
    """Chunk attention with the current chunk's K/V taken DENSE from the
    layer activations instead of read back out of the pool: prior context
    (cached prefix + earlier chunks) streams blockwise from pages, the
    chunk itself is one causal dense block, and the two merge through the
    shared online softmax. This is what lets chunked prefill keep the pool
    OUT of the layer-scan carry (one scatter per chunk call, after the
    scan) — with the pool as a carry, XLA materialized a full pool copy
    per layer (the decode path had the same bug; ``paged_decode_fused``).
    Returns [B, C, Hq, D]."""
    B, C, Hq, D = q.shape
    Hkv = k_cur.shape[2]
    m, l, acc = _page_block_softmax(
        q, kv_pages, page_table, q_positions, prior_lengths, layer,
        kv_block_pages, kv_scales, window, scale,
    )
    scale = _softmax_scale(D, scale)
    qg = (q.astype(jnp.float32) * scale).reshape(
        B, C, Hkv, Hq // Hkv, D
    ).transpose(0, 2, 3, 1, 4)
    qpos = q_positions[:, None, None, :, None]  # [B,1,1,C,1]

    # Final block: the chunk itself, dense causal in absolute positions.
    kc = k_cur.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B, Hkv, C, D]
    vc = v_cur.astype(jnp.float32).transpose(0, 2, 1, 3)
    s2 = jax.lax.dot_general(
        qg, kc,
        dimension_numbers=(((4,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )  # [B, Hkv, G, C, C]
    kv_abs = prior_lengths[:, None, None, None, None] + jnp.arange(C)[
        None, None, None, None, :
    ]
    ok2 = (kv_abs <= qpos) & (
        kv_abs < kv_lengths[:, None, None, None, None]
    )
    if window is not None:
        ok2 = ok2 & _in_window(qpos - kv_abs, window)
    s2 = jnp.where(ok2, s2, _NEG_INF)
    m_f = jnp.maximum(m, jnp.max(s2, axis=-1, keepdims=True))
    p2 = jnp.exp(s2 - m_f)
    corr = jnp.exp(m - m_f)
    l_f = l * corr + jnp.sum(p2, axis=-1, keepdims=True)
    acc_f = acc * corr + jax.lax.dot_general(
        p2, vc,
        dimension_numbers=(((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    out = jnp.where(l_f > 0, acc_f / jnp.maximum(l_f, 1e-30), 0.0)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, Hq, D).astype(q.dtype)


def kernel_shape_ok(head_dim: int) -> bool:
    """Shapes the Pallas kernels can tile: the width of a pool row must be
    a lane multiple of 128 for the DMA tiling. A head of 64 gets there two
    to a row (``ModelConfig.pair_kv_heads``: the kernels then see the
    128-wide row, every query zero in its partner's lanes, the logits'
    factor passed in); without the pairing (llama3.2-1b, whose tp layouts
    split its 8 kv heads one a chip) and for every tiny preset's 32 the
    model runs the jnp reference on any backend, and ``Engine`` says so
    once at start on a TPU."""
    return head_dim % 128 == 0


def default_use_kernel(head_dim: int) -> bool:
    """HOST-side answer to "will a launch from this process run the
    Pallas kernels": a TPU backend and a tileable ``head_dim``. The
    engine publishes it in the dispatch records (it selects nothing
    there); the pp stage bodies take it as their default. INSIDE a jitted
    launch the choice follows the lowering target instead (``_by_lowering_target``), so an
    ahead-of-time compile for a TPU topology from a CPU host lowers the
    kernels, not the reference. The two agree whenever a launch runs on
    this process's default backend — always, for an engine."""
    return jax.default_backend() == "tpu" and kernel_shape_ok(head_dim)


def _dispatch(use_kernel, head_dim: int, kernel_fn, reference_fn, *args):
    """Kernel-vs-reference dispatch shared by every paged-attention entry
    (single-chip and sharded). ``use_kernel=None`` follows the LOWERING
    TARGET: the Pallas kernel when the computation is lowered for a TPU,
    the jnp reference for any other target or an untileable ``head_dim``.
    ``lax.platform_dependent`` resolves the branch at lowering time, so
    the compiler never sees a conditional and only the chosen branch is
    lowered (Mosaic is never asked to lower for a CPU). An explicit
    ``use_kernel`` is honored as given (tests in interpret mode, the pp
    stage bodies)."""
    if use_kernel is None:
        if not kernel_shape_ok(head_dim):
            return reference_fn(*args)
        return jax.lax.platform_dependent(
            *args, tpu=kernel_fn, default=reference_fn
        )
    return (kernel_fn if use_kernel else reference_fn)(*args)


def batch_bucket(n: int, floor: int = 1) -> int:
    """The smallest power of two ≥ ``n`` (≥ ``floor``): the ``bucket``
    of the engine's decode dispatch record (/debug/state)."""
    b = max(1, int(floor))
    n = max(1, int(n))
    while b < n:
        b <<= 1
    return b


def paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    use_kernel: bool | None = None,
) -> jnp.ndarray:
    """Decode attention over radix-cache pages: the Pallas TPU kernel or
    the jnp reference, chosen by ``_dispatch``."""
    from radixmesh_tpu.ops.paged_attention import paged_attention_kernel

    return _dispatch(
        use_kernel, q.shape[-1], paged_attention_kernel, attend_decode_ref,
        q, k_pages, v_pages, page_table, lengths,
    )


def paged_attention_pool_kernel_sharded(
    q: jnp.ndarray,  # [B, Hq, D] — Hq sharded over tp
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] — Hkv sharded over tp
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    layer: jnp.ndarray | int,
    mesh,
    tp_axis: str = "tp",
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] — Hkv sharded
) -> jnp.ndarray:
    """Tensor-parallel wrapper for the Pallas pool kernel: ``shard_map``
    over the tp mesh axis so each chip runs the kernel on its local head
    shard of every page (heads are embarrassingly parallel in attention —
    no collective here; the downstream ``wo`` contraction's psum is XLA's).
    A ``pallas_call`` can't be auto-partitioned by GSPMD, hence the
    explicit map (SURVEY §7 stage 7; VERDICT round-1 weak #4)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel

    layer_arr = jnp.asarray(layer, dtype=jnp.int32).reshape(1)
    in_specs = [
        P(None, tp_axis, None),
        P(None, None, tp_axis, None, None, None),
        P(None, None),
        P(None),
        P(None),
    ]
    args = [q, kv_pages, page_table, lengths, layer_arr]
    if kv_scales is not None:
        # Per-(token, head) scales shard with their heads.
        in_specs.append(P(None, None, tp_axis, None, None))
        args.append(kv_scales)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, tp_axis, None),
        check_vma=False,  # pallas_call outputs carry no vma annotation
    )
    def local(q, kv, pt, ln, l, *maybe_scales):
        sc = maybe_scales[0] if maybe_scales else None
        return paged_attention_pool_kernel(
            q, kv, pt, ln, l[0], interpret=interpret, kv_scales=sc
        )

    return local(*args)


def paged_attention_pool(
    q: jnp.ndarray,  # [B, Hq, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] full-pool pages view
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    layer: jnp.ndarray | int,
    use_kernel: bool | None = None,
    mesh=None,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] int8 pools
) -> jnp.ndarray:
    """Decode attention reading ``layer``'s pages straight out of the whole
    multi-layer pool — the scan-over-layers hot path (``decode_step``): no
    per-layer pool slice is ever materialized in HBM. With ``mesh``, the
    TPU kernel runs tensor-parallel via ``shard_map`` (heads sharded); the
    jnp path needs no wrapper — GSPMD partitions it from input shardings."""

    def kernel(q, kv_pages, page_table, lengths, layer, kv_scales):
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            return paged_attention_pool_kernel_sharded(
                q, kv_pages, page_table, lengths, layer, mesh,
                kv_scales=kv_scales,
            )
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel

        return paged_attention_pool_kernel(
            q, kv_pages, page_table, lengths, layer, kv_scales=kv_scales
        )

    def reference(q, kv_pages, page_table, lengths, layer, kv_scales):
        k_pages, v_pages = kv_pages[0, layer], kv_pages[1, layer]
        if kv_scales is not None:
            return attend_decode_ref(
                q, k_pages, v_pages, page_table, lengths,
                kv_scales[0, layer], kv_scales[1, layer],
            )
        return attend_decode_ref(q, k_pages, v_pages, page_table, lengths)

    return _dispatch(
        use_kernel, q.shape[-1], kernel, reference,
        q, kv_pages, page_table, lengths, jnp.asarray(layer, jnp.int32),
        kv_scales,
    )


def paged_chunk_attention_kernel_sharded(
    q: jnp.ndarray,  # [B, C, Hq, D] — Hq sharded over tp
    k_cur: jnp.ndarray,  # [B, C, Hkv, D] — Hkv sharded over tp
    v_cur: jnp.ndarray,
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] — Hkv sharded over tp
    page_table: jnp.ndarray,
    prior_lengths: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    layer: jnp.ndarray | int,
    mesh,
    tp_axis: str = "tp",
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] — Hkv sharded
) -> jnp.ndarray:
    """Tensor-parallel chunk-prefill kernel: heads are embarrassingly
    parallel, so each chip runs the Pallas chunk kernel on its local head
    shard of every page (same shape of wrapper as
    ``paged_attention_pool_kernel_sharded`` — a ``pallas_call`` can't be
    auto-partitioned by GSPMD)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from radixmesh_tpu.ops.paged_attention import paged_chunk_attention_kernel

    layer_arr = jnp.asarray(layer, dtype=jnp.int32).reshape(1)
    in_specs = [
        P(None, None, tp_axis, None),
        P(None, None, tp_axis, None),
        P(None, None, tp_axis, None),
        P(None, None, tp_axis, None, None, None),
        P(None, None),
        P(None),
        P(None),
        P(None),
    ]
    args = [q, k_cur, v_cur, kv_pages, page_table, prior_lengths,
            kv_lengths, layer_arr]
    if kv_scales is not None:
        in_specs.append(P(None, None, tp_axis, None, None))
        args.append(kv_scales)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, None, tp_axis, None),
        check_vma=False,
    )
    def local(q, kc, vc, kv, pt, pr, ln, l, *maybe_scales):
        sc = maybe_scales[0] if maybe_scales else None
        return paged_chunk_attention_kernel(
            q, kc, vc, kv, pt, pr, ln, l[0], interpret=interpret,
            kv_scales=sc,
        )

    return local(*args)


def paged_chunk_attention(
    q: jnp.ndarray,  # [B, C, Hq, D]
    k_cur: jnp.ndarray,  # [B, C, Hkv, D] this chunk's K (post-rope)
    v_cur: jnp.ndarray,  # [B, C, Hkv, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D]
    page_table: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, C] — canonical: prior + arange(C)
    prior_lengths: jnp.ndarray,  # [B]
    kv_lengths: jnp.ndarray,  # [B]
    layer: jnp.ndarray | int,
    kv_block_pages: int = 32,
    use_kernel: bool | None = None,
    mesh=None,
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
) -> jnp.ndarray:
    """Chunk-prefill attention with the ``_dispatch`` choice, mirroring
    ``paged_decode_attention``: the Pallas chunk kernel or the jnp
    ``attend_chunk_hybrid``. The kernel derives causal masks from
    ``prior_lengths`` + chunk offsets, which is exact for the canonical
    ``q_positions`` every serving path produces (chunked prefill AND the
    speculative verify chunk); the jnp path masks against ``q_positions``
    directly. With ``mesh`` carrying a tp axis the kernel runs per-chip on
    its head shard via shard_map."""

    def kernel(q, k_cur, v_cur, kv_pages, page_table, q_positions,
               prior_lengths, kv_lengths, layer, kv_scales, window):
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            _no_window_on_a_mesh(window)
            return paged_chunk_attention_kernel_sharded(
                q, k_cur, v_cur, kv_pages, page_table, prior_lengths,
                kv_lengths, layer, mesh, interpret=interpret,
                kv_scales=kv_scales,
            )
        from radixmesh_tpu.ops.paged_attention import paged_chunk_attention_kernel

        return paged_chunk_attention_kernel(
            q, k_cur, v_cur, kv_pages, page_table, prior_lengths,
            kv_lengths, layer, interpret=interpret, kv_scales=kv_scales,
            window=window, scale=scale,
        )

    def reference(q, k_cur, v_cur, kv_pages, page_table, q_positions,
                  prior_lengths, kv_lengths, layer, kv_scales, window):
        return attend_chunk_hybrid(
            q, k_cur, v_cur, kv_pages, page_table, q_positions,
            prior_lengths, kv_lengths, layer, kv_block_pages=kv_block_pages,
            kv_scales=kv_scales, window=window, scale=scale,
        )

    return _dispatch(
        use_kernel, q.shape[-1], kernel, reference,
        q, k_cur, v_cur, kv_pages, page_table, q_positions, prior_lengths,
        kv_lengths, jnp.asarray(layer, jnp.int32), kv_scales, window,
    )


def _no_window_on_a_mesh(window) -> None:
    """The tensor-parallel kernel wrappers know no window; ``Engine``
    refuses a window model on a mesh at start-up, and this keeps any other
    caller from getting full attention where it asked for a window."""
    if window is not None:
        raise NotImplementedError(
            "window attention on a tensor-parallel mesh is not supported"
        )


def paged_decode_fused_sharded(
    q: jnp.ndarray,  # [B, Hq, D] — Hq sharded over tp
    k_new: jnp.ndarray,  # [B, Hkv, D] — Hkv sharded over tp
    v_new: jnp.ndarray,
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] — Hkv sharded over tp
    slots: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    layer: jnp.ndarray | int,
    mesh,
    tp_axis: str = "tp",
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] — Hkv sharded
):
    """Tensor-parallel fused decode kernel: each chip writes + attends its
    local kv-head shard (heads are embarrassingly parallel; the pool's
    head axis is sharded to match, so writes are chip-local too)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

    layer_arr = jnp.asarray(layer, dtype=jnp.int32).reshape(1)
    in_specs = [
        P(None, tp_axis, None),
        P(None, tp_axis, None),
        P(None, tp_axis, None),
        P(None, None, tp_axis, None, None, None),
        P(None),
        P(None, None),
        P(None),
        P(None),
    ]
    out_specs = [
        P(None, tp_axis, None),
        P(None, None, tp_axis, None, None, None),
    ]
    args = [q, k_new, v_new, kv_pages, slots, page_table, lengths, layer_arr]
    if kv_scales is not None:
        in_specs.append(P(None, None, tp_axis, None, None))
        out_specs.append(P(None, None, tp_axis, None, None))
        args.append(kv_scales)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )
    def local(q, kn, vn, kv, sl, pt, ln, l, *maybe_scales):
        sc = maybe_scales[0] if maybe_scales else None
        # Return arity (2- vs 3-tuple) already follows kv_scales, matching
        # the conditional out_specs.
        return paged_decode_fused_kernel(
            q, kn, vn, kv, sl, pt, ln, l[0], interpret=interpret, kv_scales=sc
        )

    return local(*args)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, Hq, D]
    k_new: jnp.ndarray,  # [B, Hkv, D] this token's K (post-rope)
    v_new: jnp.ndarray,  # [B, Hkv, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D]
    slots: jnp.ndarray,  # [B]
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] incl. current token
    layer: jnp.ndarray | int,
    use_kernel: bool | None = None,
    mesh=None,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] — updated too
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
):
    """One decode step's KV write + paged attention, fused.

    On TPU this is a single aliased ``pallas_call`` — the pool buffer flows
    through unchanged (zero copies in the layer scan; the XLA scatter +
    separate kernel read used to cost a full pool copy per layer). The jnp
    reference (any other lowering target, untileable head_dim) scatters
    then attends the oracle way. Returns ``(attn [B, Hq, D], kv_pages)``
    — plus the updated ``kv_scales`` for int8 pools.
    """

    def kernel(q, k_new, v_new, kv_pages, slots, page_table, lengths, layer,
               kv_scales, window):
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            _no_window_on_a_mesh(window)
            return paged_decode_fused_sharded(
                q, k_new, v_new, kv_pages, slots, page_table, lengths, layer,
                mesh, kv_scales=kv_scales,
            )
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        return paged_decode_fused_kernel(
            q, k_new, v_new, kv_pages, slots, page_table, lengths, layer,
            kv_scales=kv_scales, window=window, scale=scale,
        )

    def reference(q, k_new, v_new, kv_pages, slots, page_table, lengths,
                  layer, kv_scales, window):
        page = kv_pages.shape[4]
        pg, off = slots // page, slots % page
        # ``layer`` is an ARRAY index: the advanced indices (layer, pg,
        # off) are then non-adjacent, so the broadcast batch axis lands
        # FIRST → target [B, Hkv, D].
        if kv_scales is not None:
            from radixmesh_tpu.ops.quant import quantize_kv

            kq, ks = quantize_kv(k_new, axis=-1)
            vq, vs = quantize_kv(v_new, axis=-1)
            kv_pages = kv_pages.at[0, layer, :, pg, off].set(kq)
            kv_pages = kv_pages.at[1, layer, :, pg, off].set(vq)
            kv_scales = kv_scales.at[0, layer, :, pg, off].set(ks)
            kv_scales = kv_scales.at[1, layer, :, pg, off].set(vs)
            attn = attend_decode_ref(
                q, kv_pages[0, layer], kv_pages[1, layer], page_table,
                lengths, kv_scales[0, layer], kv_scales[1, layer],
                window=window,
            )
            return attn, kv_pages, kv_scales
        kv_pages = kv_pages.at[0, layer, :, pg, off].set(k_new)
        kv_pages = kv_pages.at[1, layer, :, pg, off].set(v_new)
        attn = attend_decode_ref(
            q, kv_pages[0, layer], kv_pages[1, layer], page_table, lengths,
            window=window, scale=scale,
        )
        return attn, kv_pages

    return _dispatch(
        use_kernel, q.shape[-1], kernel, reference,
        q, k_new, v_new, kv_pages, slots, page_table, lengths,
        jnp.asarray(layer, jnp.int32), kv_scales, window,
    )

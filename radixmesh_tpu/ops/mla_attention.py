"""Latent attention (MLA) over the paged cache: every head attends ONE
shared row a token.

The cache row of a latent-attention layer is ``c_kv || k_rope``
(``models/llama.py::_latent_qkv``): ``kv_lora_rank`` normalised latent
elements and the rotated rotary key, shared by all heads. With the query
absorbed (``q_nope W_k^T || q_rope``) attention over such rows is
multi-query attention with one key head as wide as the row, whose values
are the row's first ``v_width`` elements — the same bytes, read once:

    s_ij = scale * q_i . row_j      o_i = sum_j softmax(s_i)_j row_j[:v_width]

The pool is ``[1, L, 1, slots, width]`` (one plane, one head), viewed as
pages ``[1, L, 1, P, page, width]``. Three entry points, one per model
entry point, each the Pallas kernel when lowered for a TPU and the
``ops/attention.py`` reference (K = V = the row, the output cut to
``v_width``) for any other target:

- ``latent_decode_attention`` — ``radixmesh_mla_decode``: one program a
  sequence, the row's blocks of pages chain-prefetched through ONE double
  buffer (scores over the whole row, values over its head, one DMA), the
  new token's row written through the aliased pool and folded in from
  VMEM, as ``_mh_fused_kernel`` does for K/V per head.
- ``latent_chunk_attention`` — ``radixmesh_mla_chunk``: a chunk of new
  tokens over a latent prefix that lies in pages, then over the chunk's
  own rows as one dense causal block.
- ``latent_prefill_attention`` — new tokens over a gathered context
  (``prefill_forward``; ``attend_prefill``, no kernel).
"""

# meshcheck: file-ok[timeout-audit] every wait() in this file is a
# pallas device-semaphore / copy-descriptor wait — a kernel DSL op
# completing an async device DMA, not a thread parking on a peer.

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from radixmesh_tpu.ops.attention import (
    attend_chunk_hybrid,
    attend_decode_ref,
    attend_prefill,
)
from radixmesh_tpu.ops.paged_attention import (
    _MASK,
    _GatedCopy,
    _block_geometry,
    _contig_flags,
)

__all__ = [
    "latent_decode_attention",
    "latent_chunk_attention",
    "latent_prefill_attention",
    "mla_decode_kernel",
    "mla_chunk_kernel",
]

_VMEM_LIMIT = 64 * 1024 * 1024
_DECODE_BLOCK_TOKENS = 512  # a block's DMA: 512 rows of 1152 B = 0.6 MB
_CHUNK_BLOCK_TOKENS = 256


def _by_target(use_kernel, kernel_fn, reference_fn, *args):
    """The kernel for a TPU lowering, the reference for any other target;
    an explicit ``use_kernel`` is honoured (``ops/attention._dispatch``)."""
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=kernel_fn, default=reference_fn
        )
    return (kernel_fn if use_kernel else reference_fn)(*args)


def latent_prefill_attention(
    q: jnp.ndarray,  # [B, S, Hq, W] absorbed queries
    rows: jnp.ndarray,  # [B, S_ctx, 1, W] cached rows ++ new rows
    q_positions: jnp.ndarray,  # [B, S] index-space positions
    kv_lengths: jnp.ndarray,  # [B]
    kv_start: jnp.ndarray | None,
    v_width: int,
    scale: float,
) -> jnp.ndarray:
    """New tokens over a gathered latent context: ``[B, S, Hq, v_width]``."""
    out = attend_prefill(
        q, rows, rows, q_positions, kv_lengths, kv_start=kv_start, scale=scale
    )
    return out[..., :v_width]


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


class _RowBlockCopy(_GatedCopy):
    """A block of pages of one layer's latent rows: one ``(n_pages, page,
    W)`` descriptor when the pages are consecutive, else one a page."""

    def __init__(self, src, buf, sem, page_table_ref, flat_offset, n_pages,
                 contig):
        first = page_table_ref[flat_offset]
        self._contig = contig
        self._n = n_pages
        self._run = pltpu.make_async_copy(src.at[pl.ds(first, n_pages)], buf, sem)
        if n_pages > 1:
            self._pages = [
                pltpu.make_async_copy(
                    src.at[page_table_ref[flat_offset + i]], buf.at[i], sem
                )
                for i in range(n_pages)
            ]


def _fold_block(q, rows, seen_cols, seen_rows, m_scr, l_scr, acc_scr, v_width):
    """One block of the online softmax: ``q`` [R, W] (pre-scaled) against
    ``rows`` [bk, W]. ``seen_cols`` [R, bk] masks the scores and
    ``seen_rows`` [bk, 1] zeroes the values of masked rows, so that
    whatever a fetched-but-masked page holds adds exactly 0; None for
    either: everything is visible, and nothing is spent on masking."""
    s = jax.lax.dot_general(  # [R, bk]
        q, rows, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if seen_cols is not None:
        s = jnp.where(seen_cols, s, _MASK)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)
    if seen_cols is not None:
        p = jnp.where(seen_cols, p, 0.0)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    v = rows[:, :v_width]
    if seen_rows is not None:
        v = jnp.where(seen_rows, v, jnp.zeros_like(v))
    pv = jax.lax.dot_general(  # [R, v_width]
        p.astype(rows.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * corr[:, :1] + pv


def _mla_decode_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B] context length INCLUDING the current token
    page_table_ref,  # SMEM [B * padded] flattened
    contig_ref,  # SMEM [B * nblocks]
    slots_ref,  # SMEM [B] pool slot receiving this token's row
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1] double-buffer slot, persists across programs
    init_flag_ref,  # SMEM [1] 1 until the first program cold-starts
    q_ref,  # [R, W] pre-scaled, pool dtype (R = heads padded)
    new_ref,  # [1, W] this token's row
    pool_hbm,  # [1, L, 1, P, page, W]
    pool_out,  # aliased
    o_ref,  # [R, v_width] float32
    m_scr, l_scr, acc_scr, buf, row_scr, sems, w_sem,
    *,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    v_width: int,
):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    hbm_len = length - 1  # the current token is folded in from VMEM
    bk = page * pages_per_block
    nblocks = pages_per_seq // pages_per_block
    src = pool_hbm.at[0, layer, 0]  # [P, page, W]

    slot = slots_ref[b]
    pg, off = slot // page, slot % page
    dst = pool_out.at[0, layer, 0, pg]  # (page, W)
    read_page = pltpu.make_async_copy(dst, row_scr, w_sem)
    write_page = pltpu.make_async_copy(row_scr, dst, w_sem)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _write():
        read_page.start()
        read_page.wait()
        mine = jax.lax.broadcasted_iota(jnp.int32, row_scr.shape, 0) == off
        row_scr[...] = jnp.where(
            mine, jnp.broadcast_to(new_ref[...], row_scr.shape), row_scr[...]
        )
        write_page.start()

    def copy(bb, ii, s):
        return _RowBlockCopy(
            src, buf.at[s], sems.at[s], page_table_ref,
            bb * pages_per_seq + ii * pages_per_block, pages_per_block,
            contig_ref[bb * nblocks + ii],
        )

    def next_block(i):
        """Grid-order successor of block ``i`` of this row: its next
        block, or block 0 of the next row that has any."""

        def next_row():
            nb = jax.lax.fori_loop(
                b + 1, batch_size,
                lambda _, x: jnp.where(
                    (x < batch_size)
                    & (lengths_ref[jax.lax.clamp(0, x, batch_size - 1)] < 2),
                    x + 1, x,
                ),
                b + 1,
            )
            return (nb, 0)

        return jax.lax.cond(i * bk < hbm_len, lambda: (b, i), next_row)

    def block(i, tail: bool):
        first = init_flag_ref[0]
        init_flag_ref[0] = 0
        s = buffer_index_ref[0]
        nb, ni = next_block(i + 1)

        @pl.when(first != 0)
        def _cold_start():
            copy(b, i, s).start()

        @pl.when(nb < batch_size)
        def _prefetch_next():
            ns = 1 - s
            copy(nb, ni, ns).start()
            buffer_index_ref[0] = ns

        copy(b, i, s).wait()
        rows = buf[s].reshape(bk, -1)
        q = q_ref[...]
        if tail:  # the row's last block: positions past hbm_len are masked
            cols = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (q.shape[0], bk), 1)
            seen_rows = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) < hbm_len
            _fold_block(q, rows, cols < hbm_len, seen_rows,
                        m_scr, l_scr, acc_scr, v_width)
        else:
            _fold_block(q, rows, None, None, m_scr, l_scr, acc_scr, v_width)

    @pl.when(length > 0)
    def _program():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        n_full = hbm_len // bk
        jax.lax.fori_loop(0, n_full, lambda i, _: block(i, False), None)

        @pl.when(n_full * bk < hbm_len)
        def _tail():
            block(n_full, True)

        q = q_ref[...]
        cur = new_ref[...]  # [1, W]
        s_cur = jnp.sum(  # [R, 1]
            q.astype(jnp.float32) * cur.astype(jnp.float32), axis=-1,
            keepdims=True,
        )
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s_cur)
        p_cur = jnp.exp(s_cur - m_new[:, :1])  # [R, 1]
        corr = jnp.exp(m_prev - m_new)
        l_fin = l_scr[...] * corr + p_cur
        acc = acc_scr[...] * corr[:, :1] + p_cur * cur[:, :v_width].astype(
            jnp.float32)
        o_ref[...] = (acc / l_fin[:, :1]).astype(o_ref.dtype)
        write_page.wait()


@functools.partial(
    jax.jit, static_argnames=("v_width", "scale", "block_tokens", "interpret")
)
def mla_decode_kernel(
    q: jnp.ndarray,  # [B, Hq, W] absorbed queries
    new_row: jnp.ndarray,  # [B, W] this token's row, pool dtype
    pool_pages: jnp.ndarray,  # [1, L, 1, P, page, W]
    slots: jnp.ndarray,  # [B]
    page_table: jnp.ndarray,  # [B, max_pages]
    lengths: jnp.ndarray,  # [B] incl. this token
    layer: jnp.ndarray | int,
    *,
    v_width: int,
    scale: float,
    block_tokens: int = _DECODE_BLOCK_TOKENS,
    interpret: bool = False,
):
    """The fused decode step of a latent layer: writes ``new_row`` at
    ``slots`` through the aliased pool and returns ``(o [B, Hq, v_width]
    in q's dtype, pool_pages)``."""
    B, Hq, W = q.shape
    P, page = pool_pages.shape[3:5]
    dtype = pool_pages.dtype
    page_table, ppb, padded = _block_geometry(
        page_table, page, max(1, block_tokens // page))
    lengths = jnp.asarray(lengths, jnp.int32)
    contig = _contig_flags(page_table, jnp.maximum(lengths - 1, 0), page, ppb, P)
    rows = -(-Hq // 16) * 16  # a whole sublane tile of either dtype
    qk = jnp.zeros((B, rows, W), dtype).at[:, :Hq].set(
        (q.astype(jnp.float32) * scale).astype(dtype))
    kernel = functools.partial(
        _mla_decode_kernel, page=page, pages_per_block=ppb,
        pages_per_seq=padded, batch_size=B, v_width=v_width,
    )
    n_scalars = 7
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((None, 1, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((None, rows, v_width), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, v_width), jnp.float32),
            pltpu.VMEM((2, ppb, page, W), dtype),
            pltpu.VMEM((page, W), dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    pool_out, out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool_pages.shape, dtype),
            jax.ShapeDtypeStruct((B, rows, v_width), jnp.float32),
        ],
        input_output_aliases={n_scalars + 2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="radixmesh_mla_decode",
    )(
        lengths,
        jnp.asarray(page_table, jnp.int32).reshape(-1),
        contig,
        jnp.asarray(slots, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32),
        qk,
        new_row.astype(dtype).reshape(B, 1, W),
        pool_pages,
    )
    return out[:, :Hq].astype(q.dtype), pool_out


def latent_decode_attention(
    q: jnp.ndarray,  # [B, Hq, W]
    new_row: jnp.ndarray,  # [B, W] pool dtype
    pool_pages: jnp.ndarray,  # [1, L, 1, P, page, W]
    slots: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] incl. this token
    layer: jnp.ndarray | int,
    v_width: int,
    scale: float,
    use_kernel: bool | None = None,
    interpret: bool = False,
):
    """One decode step of a latent layer, write and attention fused:
    ``(o [B, Hq, v_width], pool_pages)``."""

    def kernel(q, new_row, pool_pages, slots, page_table, lengths, layer):
        return mla_decode_kernel(
            q, new_row, pool_pages, slots, page_table, lengths, layer,
            v_width=v_width, scale=scale, interpret=interpret,
        )

    def reference(q, new_row, pool_pages, slots, page_table, lengths, layer):
        page = pool_pages.shape[4]
        pool_pages = pool_pages.at[0, layer, 0, slots // page, slots % page].set(
            new_row)
        rows = pool_pages[0, layer]  # [1, P, page, W]
        out = attend_decode_ref(q, rows, rows, page_table, lengths, scale=scale)
        return out[..., :v_width], pool_pages

    return _by_target(
        use_kernel, kernel, reference, q, new_row, pool_pages, slots,
        page_table, lengths, jnp.asarray(layer, jnp.int32),
    )


# ---------------------------------------------------------------------------
# chunk prefill
# ---------------------------------------------------------------------------


def _mla_chunk_kernel(
    # scalar prefetch
    prior_ref,  # SMEM [B] tokens in pages before this chunk
    kvlen_ref,  # SMEM [B] valid context incl. this chunk
    page_table_ref,  # SMEM [B * padded]
    contig_ref,  # SMEM [B * nblocks]
    layer_ref,  # SMEM [1]
    q_ref,  # [c_block * G, W] pre-scaled, row r = token r // G, head r % G
    cur_ref,  # [C, W] the chunk's own rows
    pool_hbm,  # [1, L, 1, P, page, W]
    o_ref,  # [c_block * G, v_width] float32
    m_scr, l_scr, acc_scr, buf, sems,
    *,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    c_block: int,
    group: int,
    v_width: int,
):
    b, cb = pl.program_id(0), pl.program_id(1)
    prior, kvlen = prior_ref[b], kvlen_ref[b]
    bk = page * pages_per_block
    nblocks = pages_per_seq // pages_per_block
    src = pool_hbm.at[0, layer_ref[0], 0]
    q = q_ref[...]
    n_rows = q.shape[0]

    def copy(i, s):
        return _RowBlockCopy(
            src, buf.at[s], sems.at[s], page_table_ref,
            b * pages_per_seq + i * pages_per_block, pages_per_block,
            contig_ref[b * nblocks + i],
        )

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    n_blocks = pl.cdiv(prior, bk)

    @pl.when(n_blocks > 0)
    def _cold_start():
        copy(0, 0).start()

    def body(i, _):
        s = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch_next():
            copy(i + 1, 1 - s).start()

        copy(i, s).wait()
        # Every query sits at or after ``prior``: the page part needs the
        # prior bound alone.
        cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, (n_rows, bk), 1)
        seen_rows = i * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0) < prior
        _fold_block(q, buf[s].reshape(bk, -1), cols < prior, seen_rows,
                    m_scr, l_scr, acc_scr, v_width)
        return ()

    jax.lax.fori_loop(0, n_blocks, body, ())

    # The chunk itself, causal in chunk coordinates: key c_k sits at
    # prior + c_k, query row r at prior + cb * c_block + r // group.
    cur = cur_ref[...]
    C = cur.shape[0]
    c_q = cb * c_block + jax.lax.broadcasted_iota(
        jnp.int32, (n_rows, C), 0) // group
    c_k = jax.lax.broadcasted_iota(jnp.int32, (n_rows, C), 1)
    _fold_block(q, cur, (c_k <= c_q) & (prior + c_k < kvlen), None,
                m_scr, l_scr, acc_scr, v_width)
    l_fin = l_scr[...][:, :1]
    o_ref[...] = jnp.where(
        l_fin > 0, acc_scr[...] / jnp.maximum(l_fin, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _chunk_rows(chunk: int, group: int, max_rows: int = 1024) -> int:
    """Queries a program: the largest power-of-two divisor of ``chunk``
    whose rows (tokens x heads) stay within ``max_rows``."""
    cblk = 1
    while chunk % (cblk * 2) == 0 and cblk * 2 * group <= max_rows:
        cblk *= 2
    return cblk


@functools.partial(
    jax.jit, static_argnames=("v_width", "scale", "block_tokens", "interpret")
)
def mla_chunk_kernel(
    q: jnp.ndarray,  # [B, C, Hq, W] absorbed queries of the chunk
    cur_rows: jnp.ndarray,  # [B, C, W] the chunk's own rows, pool dtype
    pool_pages: jnp.ndarray,  # [1, L, 1, P, page, W]
    page_table: jnp.ndarray,  # [B, max_pages]
    prior_lengths: jnp.ndarray,  # [B]
    kv_lengths: jnp.ndarray,  # [B]
    layer: jnp.ndarray | int,
    *,
    v_width: int,
    scale: float,
    block_tokens: int = _CHUNK_BLOCK_TOKENS,
    interpret: bool = False,
) -> jnp.ndarray:
    """A chunk's queries over the latent prefix in pages, then over the
    chunk's own rows: ``[B, C, Hq, v_width]``. Query positions are
    canonical, ``prior_lengths + arange(C)`` (``paged_chunk_attention_kernel``'s
    contract)."""
    B, C, Hq, W = q.shape
    P, page = pool_pages.shape[3:5]
    dtype = pool_pages.dtype
    page_table, ppb, padded = _block_geometry(
        page_table, page, max(1, block_tokens // page))
    contig = _contig_flags(page_table, prior_lengths, page, ppb, P)
    cblk = _chunk_rows(C, Hq)
    n_rows = cblk * Hq
    qk = (q.astype(jnp.float32) * scale).astype(dtype).reshape(B, C * Hq, W)
    kernel = functools.partial(
        _mla_chunk_kernel, page=page, pages_per_block=ppb, pages_per_seq=padded,
        c_block=cblk, group=Hq, v_width=v_width,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, C // cblk),
        in_specs=[
            pl.BlockSpec((None, n_rows, W), lambda b, cb, *_: (b, cb, 0)),
            pl.BlockSpec((None, C, W), lambda b, cb, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, n_rows, v_width),
                               lambda b, cb, *_: (b, cb, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_rows, 128), jnp.float32),
            pltpu.VMEM((n_rows, 128), jnp.float32),
            pltpu.VMEM((n_rows, v_width), jnp.float32),
            pltpu.VMEM((2, ppb, page, W), dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C * Hq, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="radixmesh_mla_chunk",
    )(
        jnp.asarray(prior_lengths, jnp.int32),
        jnp.asarray(kv_lengths, jnp.int32),
        jnp.asarray(page_table, jnp.int32).reshape(-1),
        contig,
        jnp.asarray(layer, jnp.int32).reshape(1),
        qk,
        cur_rows.astype(dtype),
        pool_pages,
    )
    return out.reshape(B, C, Hq, v_width).astype(q.dtype)


def latent_chunk_attention(
    q: jnp.ndarray,  # [B, C, Hq, W]
    cur_rows: jnp.ndarray,  # [B, C, W]
    pool_pages: jnp.ndarray,  # [1, L, 1, P, page, W]
    page_table: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, C] canonical: prior + arange(C)
    prior_lengths: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    layer: jnp.ndarray | int,
    v_width: int,
    scale: float,
    kv_block_pages: int = 32,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunk-prefill attention of a latent layer: ``[B, C, Hq, v_width]``."""

    def kernel(q, cur_rows, pool_pages, page_table, q_positions,
               prior_lengths, kv_lengths, layer):
        return mla_chunk_kernel(
            q, cur_rows, pool_pages, page_table, prior_lengths, kv_lengths,
            layer, v_width=v_width, scale=scale, interpret=interpret,
        )

    def reference(q, cur_rows, pool_pages, page_table, q_positions,
                  prior_lengths, kv_lengths, layer):
        cur = cur_rows[:, :, None, :]
        out = attend_chunk_hybrid(
            q, cur, cur, pool_pages, page_table, q_positions, prior_lengths,
            kv_lengths, layer, kv_block_pages=kv_block_pages, scale=scale,
        )
        return out[..., :v_width]

    return _by_target(
        use_kernel, kernel, reference, q, cur_rows, pool_pages, page_table,
        q_positions, prior_lengths, kv_lengths, jnp.asarray(layer, jnp.int32),
    )

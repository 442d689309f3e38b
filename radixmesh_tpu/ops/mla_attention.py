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
  VMEM, as ``_mh_fused_kernel`` does for K/V per head. Rows of a launch
  that hold the same pages first (a prefix the radix tree gave to each of
  them: ``decode_groups``, from the launch's page table) have those pages
  read ONCE before, by a shared pass under the same name that folds them
  into all of those rows' queries, and go on from there over their own.
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
import numpy as np
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
    _block_chain,
    _block_geometry,
    _contig_flags,
)

__all__ = [
    "latent_decode_attention",
    "latent_chunk_attention",
    "latent_prefill_attention",
    "chunk_admits",
    "decode_groups",
    "mla_decode_kernel",
    "mla_chunk_kernel",
]

_VMEM_LIMIT = 64 * 1024 * 1024
_DECODE_BLOCK_TOKENS = 512  # a block's DMA: 512 rows of 1152 B = 0.6 MB
# A shared pass of the decode kernel folds each block into the queries of
# up to 8 rows, stacked in tiles of 4 rows' heads (4 x 20 = 80 query rows:
# whole sublane tiles of either dtype). On the chip a fold costs ~0.8 us
# and 6 ns a query row (PERF.md §6, PR 42): a pass of up to 4 members
# folds 80 rows, a larger one 160; 12 and 16 members a pass (240 and 320
# rows a fold) measured slower than two passes of 8, 4 and 6 slower than 8.
_GROUP_MEMBERS = 8
_TILE_MEMBERS = 4
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


def _row_block_copier(pool_hbm, layer, buf, sems, page_table_ref, contig_ref,
                      pages_per_seq, pages_per_block):
    """``copy(row, i, s)``: block ``i`` of page-table row ``row``, one
    layer's latent rows, into buffer slot ``s``."""
    src = pool_hbm.at[0, layer, 0]  # [P, page, W]
    nblocks = pages_per_seq // pages_per_block

    def copy(row, i, s):
        return _RowBlockCopy(
            src, buf.at[s], sems.at[s], page_table_ref,
            row * pages_per_seq + i * pages_per_block, pages_per_block,
            contig_ref[row * nblocks + i],
        )

    return copy


def _mla_shared_kernel(
    # scalar prefetch
    blocks_ref,  # SMEM [P] whole blocks the pass streams; 0: an empty pass
    count_ref,  # SMEM [P] member rows of the pass
    rep_ref,  # SMEM [P] the member whose page-table row the pass reads
    page_table_ref,  # SMEM [B * padded] flattened
    contig_ref,  # SMEM [B * nblocks]
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1]
    init_flag_ref,  # SMEM [1]
    q_ref,  # [tiles * R, W] the members' queries stacked, pre-scaled
    pool_hbm,  # [1, L, 1, P, page, W]
    m_out, l_out, acc_out,  # HBM [P, tiles * R, .]: the members' state, float32
    m_scr, l_scr, acc_scr, buf, sems, out_sem,
    *,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    n_passes: int,
    tile_rows: int,
    tile_members: int,
    v_width: int,
):
    """A shared pass: the pages that several rows hold alike, read ONCE
    and folded into all of those rows' queries, a tile of rows at a time.
    Every block is whole and visible to every member (``decode_groups``),
    so nothing is masked. Only the tiles that hold a member are computed
    and written: an empty pass moves nothing."""
    p = pl.program_id(0)
    count = count_ref[p]
    bk = page * pages_per_block
    n_tiles = q_ref.shape[0] // tile_rows
    held = pl.cdiv(count, tile_members)  # tiles that hold a member

    def of_held_rows(fn):
        """``fn`` of the stacked rows as far as members reach: 1, 2, 4, ...
        tiles, whichever is the least that holds them (a fold costs ~0.7 us
        however few rows it has, so one fold, never one a tile)."""
        lo, k = 0, 1
        while lo < n_tiles:
            k = min(k, n_tiles)

            @pl.when((lo < held) & (held <= k))
            def _these(k=k):
                fn(pl.ds(0, k * tile_rows))

            lo, k = k, 2 * k

    copy = _row_block_copier(pool_hbm, layer_ref[0], buf, sems, page_table_ref,
                             contig_ref, pages_per_seq, pages_per_block)
    fetch = _block_chain(
        p, n_passes, lambda g: rep_ref[g], lambda g: 0,
        lambda g: blocks_ref[g] * bk, bk, copy, buffer_index_ref, init_flag_ref,
    )

    def state_out(at):
        return [
            pltpu.make_async_copy(scr.at[at], out.at[p, at], out_sem)
            for scr, out in ((m_scr, m_out), (l_scr, l_out), (acc_scr, acc_out))
        ]

    @pl.when(blocks_ref[p] > 0)
    def _pass():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def block(i, _):
            s = fetch(i)
            of_held_rows(lambda at: _fold_block(
                q_ref[at], buf[s].reshape(bk, -1), None, None,
                m_scr.at[at], l_scr.at[at], acc_scr.at[at], v_width))

        jax.lax.fori_loop(0, blocks_ref[p], block, None)
        of_held_rows(lambda at: [c.start() for c in state_out(at)])
        of_held_rows(lambda at: [c.wait() for c in state_out(at)])


def _mla_decode_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B] context length INCLUDING the current token
    start_ref,  # SMEM [B] blocks a shared pass has folded already
    page_table_ref,  # SMEM [B * padded] flattened
    contig_ref,  # SMEM [B * nblocks]
    slots_ref,  # SMEM [B] pool slot receiving this token's row
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1] double-buffer slot, persists across programs
    init_flag_ref,  # SMEM [1] 1 until the first program cold-starts
    q_ref,  # [R, W] pre-scaled, pool dtype (R = heads padded)
    new_ref,  # [1, W] this token's row
    m0_ref, l0_ref, acc0_ref,  # the row's state after its shared pass
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

    copy = _row_block_copier(pool_hbm, layer, buf, sems, page_table_ref,
                             contig_ref, pages_per_seq, pages_per_block)
    fetch = _block_chain(
        b, batch_size, lambda g: g, lambda g: start_ref[g],
        lambda g: lengths_ref[g] - 1, bk, copy, buffer_index_ref, init_flag_ref,
    )

    def block(i, tail: bool):
        s = fetch(i)
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
        m_scr[...] = m0_ref[...]
        l_scr[...] = l0_ref[...]
        acc_scr[...] = acc0_ref[...]
        n_full = hbm_len // bk
        jax.lax.fori_loop(
            start_ref[b], n_full, lambda i, _: block(i, False), None)

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


def _decode_block_pages(page: int, max_pages: int, block_tokens: int) -> int:
    """Pages in one block of the decode kernel at this table width."""
    return min(max(1, block_tokens // page), max_pages)


def decode_groups(
    page_table: np.ndarray,  # [B, max_pages], the launch's table (host)
    lengths: np.ndarray,  # [B] context incl. this token
    rows,  # the live rows
    page: int,
    block_tokens: int = _DECODE_BLOCK_TOKENS,
) -> tuple[np.ndarray, dict]:
    """Which rows of a decode launch read the same pages first, from the
    launch's own page table: the ``groups`` argument of the decode kernel,
    ``[max(1, B // 2), 1 + _GROUP_MEMBERS]`` int32 — a shape of the row
    bucket alone — and what it holds.

    Rows whose tables agree on their first page are one group; what the
    group shares is the longest common prefix of its members' tables, cut
    to whole kernel blocks and to ``min(length) - 1`` tokens, so that every
    shared block is wholly visible to every member. One level of sharing; a
    group of one, or one that shares less than a block, is no group. A line
    of ``groups`` is one shared pass: the pages it reads, then up to
    ``_GROUP_MEMBERS`` member rows (-1: none) — a larger group takes
    several passes, each reading the prefix again.

    The dict: ``groups``, ``members``, ``passes``, and ``shared_tokens`` —
    the rows a layer does NOT have to read because a group's prefix is
    needed once, not once a member (a group split over passes still counts
    once: what the split reads again is waste)."""
    B, width = page_table.shape
    ppb = _decode_block_pages(page, width, block_tokens)
    bk = ppb * page
    groups = np.full((max(1, B // 2), 1 + _GROUP_MEMBERS), -1, np.int32)
    groups[:, 0] = 0
    stats = {"groups": 0, "members": 0, "passes": 0, "shared_tokens": 0}
    live = np.asarray([r for r in rows if lengths[r] > 1], np.intp)
    if live.size < 2:
        return groups, stats
    order = live[np.argsort(page_table[live, 0], kind="stable")]
    cuts = np.flatnonzero(np.diff(page_table[order, 0])) + 1
    for rows_g in np.split(order, cuts):
        if rows_g.size < 2:
            continue
        same = (page_table[rows_g] == page_table[rows_g[0]]).all(axis=0)
        common = width if same.all() else int(np.argmin(same))
        blocks = min(common // ppb, (int(lengths[rows_g].min()) - 1) // bk)
        if blocks < 1:
            continue
        stats["groups"] += 1
        stats["members"] += int(rows_g.size)
        stats["shared_tokens"] += (int(rows_g.size) - 1) * blocks * bk
        for at in range(0, rows_g.size, _GROUP_MEMBERS):
            part = rows_g[at:at + _GROUP_MEMBERS]
            groups[stats["passes"], 0] = blocks * ppb
            groups[stats["passes"], 1:1 + part.size] = part
            stats["passes"] += 1
    return groups, stats


def _no_groups(batch: int) -> jnp.ndarray:
    """``decode_groups`` of a launch in which nothing is shared."""
    return jnp.full((max(1, batch // 2), 1 + _GROUP_MEMBERS), -1, jnp.int32
                    ).at[:, 0].set(0)


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
    groups: jnp.ndarray | None = None,  # ``decode_groups``; None: no group
    *,
    v_width: int,
    scale: float,
    block_tokens: int = _DECODE_BLOCK_TOKENS,
    interpret: bool = False,
):
    """The fused decode step of a latent layer: writes ``new_row`` at
    ``slots`` through the aliased pool and returns ``(o [B, Hq, v_width]
    in q's dtype, pool_pages)``.

    Two launches, both ``radixmesh_mla_decode``. The shared passes first:
    each line of ``groups`` streams the pages its members hold alike once,
    for the members' queries stacked in tiles, and leaves each member's
    ``(m, l, acc)``. Then a program a row, from that state and from the
    block where the shared pass stopped — a row in no group from
    ``(-inf, 0, 0)`` and block 0. The same recurrence over the same blocks
    in the same order as one pass over the whole row: on the chip the
    output is the ungrouped launch's, bit for bit. A launch in which no
    row is grouped skips the first launch (a branch on ``groups``, inside
    the one program)."""
    B, Hq, W = q.shape
    P, page = pool_pages.shape[3:5]
    dtype = pool_pages.dtype
    page_table, ppb, padded = _block_geometry(
        page_table, page, _decode_block_pages(page, page_table.shape[1],
                                              block_tokens))
    lengths = jnp.asarray(lengths, jnp.int32)
    contig = _contig_flags(page_table, jnp.maximum(lengths - 1, 0), page, ppb, P)
    flat_table = jnp.asarray(page_table, jnp.int32).reshape(-1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    rows = -(-Hq // 16) * 16  # a whole sublane tile of either dtype
    qk = jnp.zeros((B, rows, W), dtype).at[:, :Hq].set(
        (q.astype(jnp.float32) * scale).astype(dtype))
    params = dict(page=page, pages_per_block=ppb, pages_per_seq=padded,
                  v_width=v_width)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)
    block_buffer = pltpu.VMEM((2, ppb, page, W), dtype)
    block_sems = pltpu.SemaphoreType.DMA((2,))

    # Which rows share what: a line of ``groups`` a pass.
    if groups is None:
        groups = _no_groups(B)
    n_passes, M = groups.shape[0], groups.shape[1] - 1
    tile = _TILE_MEMBERS
    if M % tile:
        raise ValueError(f"{M} members a pass are no whole tiles of {tile}")
    tile_rows = -(-tile * Hq // 16) * 16
    stacked = M // tile * tile_rows
    members = groups[:, 1:]
    shared_blocks = groups[:, 0] // ppb
    listed = (members >= 0) & (shared_blocks > 0)[:, None]
    # Each row's place among the passes' members, and where its own blocks
    # start.
    place = jnp.full((B,), -1, jnp.int32).at[
        jnp.where(listed, members, B).reshape(-1)
    ].set(jnp.arange(n_passes * M, dtype=jnp.int32), mode="drop")
    grouped = place >= 0
    place = jnp.maximum(place, 0)
    start = jnp.where(grouped, shared_blocks[place // M], 0)
    fresh = (-jnp.inf, 0.0, 0.0)  # (m, l, acc) of a row nothing was folded into
    widths = (128, 128, v_width)

    def shared_passes(qk, pool_pages):
        q_stack = jnp.where(
            listed[:, :, None, None], qk[jnp.maximum(members, 0), :Hq], 0
        ).reshape(n_passes, M // tile, tile * Hq, W)
        q_stack = jnp.pad(
            q_stack, ((0, 0), (0, 0), (0, tile_rows - tile * Hq), (0, 0))
        ).reshape(n_passes, stacked, W)
        state = pl.pallas_call(
            functools.partial(
                _mla_shared_kernel, n_passes=n_passes, tile_rows=tile_rows,
                tile_members=tile, **params),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=8,
                grid=(n_passes,),
                in_specs=[
                    pl.BlockSpec((None, stacked, W), lambda p, *_: (p, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
                scratch_shapes=[
                    *(pltpu.VMEM((stacked, w), jnp.float32) for w in widths),
                    block_buffer,
                    block_sems,
                    pltpu.SemaphoreType.DMA,
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((n_passes, stacked, w), jnp.float32)
                for w in widths
            ],
            compiler_params=compiler_params,
            interpret=interpret,
            name="radixmesh_mla_decode",
        )(
            shared_blocks,
            listed.sum(axis=1, dtype=jnp.int32),
            jnp.maximum(members[:, 0], 0),
            flat_table,
            contig,
            layer,
            jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32),
            q_stack,
            pool_pages,
        )

        def of_row(x, fill):  # [passes, stacked, w] -> [B, rows, w]
            # (a tile that held no row was never written: ``grouped``)
            x = x.reshape(n_passes * M // tile, tile_rows, -1)[:, :tile * Hq]
            x = x.reshape(n_passes * M, Hq, -1)[place]
            x = jnp.where(grouped[:, None, None], x, fill)
            return jnp.pad(
                x, ((0, 0), (0, rows - Hq), (0, 0)), constant_values=fill)

        return tuple(map(of_row, state, fresh))

    def nothing_shared(qk, pool_pages):
        return tuple(jnp.full((B, rows, w), fill, jnp.float32)
                     for w, fill in zip(widths, fresh))

    # A launch whose rows share nothing (most launches of most traffic)
    # pays for neither the stacking nor the passes.
    m0, l0, acc0 = jax.lax.cond(
        jnp.any(listed), shared_passes, nothing_shared, qk, pool_pages)

    def row_spec(width):
        return pl.BlockSpec((None, rows, width), lambda b, *_: (b, 0, 0))

    n_scalars = 8
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(B,),
        in_specs=[
            row_spec(W),
            pl.BlockSpec((None, 1, W), lambda b, *_: (b, 0, 0)),
            *map(row_spec, widths),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), row_spec(v_width)],
        scratch_shapes=[
            *(pltpu.VMEM((rows, w), jnp.float32) for w in widths),
            block_buffer,
            pltpu.VMEM((page, W), dtype),
            block_sems,
            pltpu.SemaphoreType.DMA,
        ],
    )
    pool_out, out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, batch_size=B, **params),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool_pages.shape, dtype),
            jax.ShapeDtypeStruct((B, rows, v_width), jnp.float32),
        ],
        input_output_aliases={n_scalars + 5: 0},
        compiler_params=compiler_params,
        interpret=interpret,
        name="radixmesh_mla_decode",
    )(
        lengths,
        start,
        flat_table,
        contig,
        jnp.asarray(slots, jnp.int32),
        layer,
        jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32),
        qk,
        new_row.astype(dtype).reshape(B, 1, W),
        m0, l0, acc0,
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
    groups: jnp.ndarray | None = None,  # ``decode_groups`` of the launch
    use_kernel: bool | None = None,
    interpret: bool = False,
):
    """One decode step of a latent layer, write and attention fused:
    ``(o [B, Hq, v_width], pool_pages)``. ``groups`` says which rows read
    the same pages first (the kernel reads those once; the reference
    gathers every row's context and has no use for it)."""
    if groups is None:
        groups = _no_groups(q.shape[0])

    def kernel(q, new_row, pool_pages, slots, page_table, lengths, layer,
               groups):
        return mla_decode_kernel(
            q, new_row, pool_pages, slots, page_table, lengths, layer, groups,
            v_width=v_width, scale=scale, interpret=interpret,
        )

    def reference(q, new_row, pool_pages, slots, page_table, lengths, layer,
                  groups):
        page = pool_pages.shape[4]
        pool_pages = pool_pages.at[0, layer, 0, slots // page, slots % page].set(
            new_row)
        rows = pool_pages[0, layer]  # [1, P, page, W]
        out = attend_decode_ref(q, rows, rows, page_table, lengths, scale=scale)
        return out[..., :v_width], pool_pages

    return _by_target(
        use_kernel, kernel, reference, q, new_row, pool_pages, slots,
        page_table, lengths, jnp.asarray(layer, jnp.int32), groups,
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


def chunk_admits(chunk: int, group: int, width: int, v_width: int,
                 itemsize: int) -> bool:
    """Whether ``mla_chunk_kernel`` holds a chunk this wide, from the
    shapes alone: a program keeps the chunk's own rows and its queries
    double-buffered, its float32 output and accumulators, and folds the
    whole chunk at once — scores, their exponentials and the mask, each
    ``[rows, chunk]`` float32 — inside ``_VMEM_LIMIT`` (the planner's
    widest chunk, ``engine/prefill_plan.py::chunk_width``)."""
    rows = _chunk_rows(chunk, group) * group
    held = (2 * (chunk + rows) * width * itemsize  # own rows, queries
            + 3 * rows * v_width * 4 + 2 * rows * 128 * 4  # out x 2, acc, m, l
            + 2 * _CHUNK_BLOCK_TOKENS * width * itemsize  # the prefix block
            + 3 * rows * chunk * 4)  # the fold of the chunk itself
    return held <= _VMEM_LIMIT


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

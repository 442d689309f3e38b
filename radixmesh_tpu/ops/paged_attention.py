"""Pallas TPU decode kernels: attention over non-contiguous radix-cache pages.

This is the op SURVEY §7 calls the hard part (a): the radix cache hands the
scheduler a *page table* (page ids into the paged KV pool, arbitrary order,
shared across requests that share a prefix), and decode attention must
gather those pages without materializing a dense [B, max_ctx, H, D] copy in
HBM — the copy is exactly the bandwidth decode can't afford.

Round-5 redesign (VERDICT round-4 weak #1/#2: 23% HBM utilization on the
decode hot path, int8 slower than bf16). On-chip, DMA *descriptor issue
rate* — not bytes — bounded the round-4 kernels (B × Hkv × blocks × ppb × 2
copies of 4 KB each per launch). Three structural changes:

1. **Run-coalesced block DMAs.** The slot allocator hands out whole pages
   and sequences mostly extend in place, so a compute block's pages are
   usually *consecutive* ids. The wrapper precomputes a per-(row, block)
   flag (``_contig_flags``); flagged blocks move as ONE descriptor
   (``src.at[pl.ds(first, ppb)]`` — contiguous per head, 32 KB+), unflagged
   blocks fall back to per-page copies. Flags ride scalar prefetch, so the
   gate costs one SMEM read per block.
2. **Heads-batched programs by default.** ``fuse_heads`` (grid ``(B,)``,
   all kv heads per DMA and per MXU contraction) is now the default
   whenever the double-buffered block fits VMEM (``_auto_fuse_heads``);
   the per-head grid ``(B, Hkv)`` remains for huge-Hkv configs and as an
   explicit override. Together with (1): ~2 descriptors per *block* per
   sequence instead of ~2 per *page* per (sequence, head) — two orders of
   magnitude fewer descriptor issues at the headline shape.
3. **Prepared scales for int8 pools.** Round 4 fetched per-token scale
   rows inside the kernel (2 extra strided DMAs per page + lane-rotation
   games — measured 0.688x bf16 on chip, the scale traffic costing more
   than the halved KV bytes saved). Now the *wrapper* gathers the page
   table's scales in one XLA gather (``_prep_scales`` →
   ``[2, B, Hkv, nblocks, bk]`` ≈ 4 MB/layer at the headline shape, ~3% of
   the KV bytes int8 saves) and the kernel reads aligned ``(1, bk)`` rows
   from a pipelined VMEM input — zero in-kernel scale DMAs, zero rotation,
   and the page-size-divides-128 constraint disappears entirely.

Design (shared by both grids):

- The KV pool pages stay in HBM (``memory_space=ANY``); page table,
  lengths, coalescing flags, and layer index ride scalar prefetch (SMEM)
  so DMA source addresses are computable before the body runs.
- Each program loops over *compute blocks* of ``pages_per_block`` pages,
  bounded by the sequence's true length — short sequences cost short
  loops, not ``max_pages`` iterations.
- Block DMAs are **chain-prefetched across grid steps**: while block ``i``
  is being contracted on the MXU, the copy for the *next* block — which
  may belong to the next program — is already in flight in the other half
  of a double buffer. DMA latency is exposed once per kernel launch.
- Online softmax (running max / sum / fp32 accumulator in VMEM scratch)
  across the block loop; GQA by blocking the query as [G, D] per kv head
  (per-head grid) or [Hkv, G, D] batched (fused-heads grid).

Entry points (all with jnp oracles in ``ops/attention.py``, parity pinned
by ``tests/test_ops.py`` in interpreter mode and on the chip at the served
geometry by ``ops/kernel_check.py``; each ``pallas_call`` carries a stable
``radixmesh_*`` name so a device trace can be reduced per kernel):

- ``paged_attention_pool_kernel`` — read-only attention over ``length``
  tokens already resident in pool pages.
- ``paged_decode_fused_kernel`` — the decode hot path: ALSO writes the
  current token's K/V row into the pool through an **aliased** output
  (``input_output_aliases``), so the pool buffer flows through the layer
  scan with zero copies. The freshly written row is never read back from
  HBM within the call: HBM blocks are masked to ``length - 1`` and the
  current token's contribution is folded in from VMEM — which also kills
  the read-after-write hazard with cross-program block prefetch.
- ``paged_chunk_attention_kernel`` — prefill: prior pool pages streamed
  through the online softmax, the current chunk folded in as one dense
  causal block.
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

__all__ = [
    "paged_attention_kernel",
    "paged_attention_pool_kernel",
    "paged_chunk_attention_kernel",
    "paged_decode_fused_kernel",
]

# exp(finite - MASK) == 0 without the NaN risk of -inf - -inf.
_MASK = -0.7 * float(np.finfo(np.float32).max)

def _contig_flags(
    page_table: jnp.ndarray,  # [B, padded] int32 (already block-padded)
    hbm_lengths: jnp.ndarray,  # [B] tokens resident in HBM pages per row
    page: int,
    ppb: int,
    num_pages: int,
) -> jnp.ndarray:
    """Per-(row, block) coalescing flags ``[B * nblocks] int32``: 1 when
    the block's VALID page-table entries are consecutive ascending ids
    AND the full ``[first, first + ppb)`` range is in bounds — then the
    kernel fetches the whole block with one ``pl.ds(first, ppb)``
    descriptor. Entries past the row's valid page count are pads whose
    fetched rows the kernel masks by position anyway, so they neither
    veto coalescing nor make the coalesced fetch unsafe (any byte that
    could differ from the table's pad target is masked — including
    another sequence's in-flight RMW page, whose rewritten bytes are
    identical except the masked row). Masking is total, not just
    score-level: the block loops zero BOTH factors of the p·v
    contraction at masked positions, so even NaN/Inf resident in a
    fetched-but-unreferenced pool page (or its scale rows) contributes
    an exact 0 — there is no finite-pool invariant to uphold."""
    B, padded = page_table.shape
    nblocks = padded // ppb
    pt = page_table.reshape(B, nblocks, ppb)
    first = pt[:, :, :1]
    expect = first + jnp.arange(ppb, dtype=page_table.dtype)[None, None, :]
    pages_used = (jnp.asarray(hbm_lengths, jnp.int32) + page - 1) // page
    valid = jnp.clip(
        pages_used[:, None] - jnp.arange(nblocks, dtype=jnp.int32)[None, :] * ppb,
        0,
        ppb,
    )
    pos = jnp.arange(ppb, dtype=jnp.int32)[None, None, :]
    ok = jnp.all((pt == expect) | (pos >= valid[:, :, None]), axis=-1)
    ok = ok & (first[:, :, 0] + ppb <= num_pages) & (first[:, :, 0] >= 0)
    return ok.astype(jnp.int32).reshape(-1)


def _prep_scales(
    kv_scales: jnp.ndarray,  # [2, L, Hkv, P, page] f32 — per-token pool scales
    layer: jnp.ndarray | int,
    page_table: jnp.ndarray,  # [B, padded] int32 (already block-padded)
    page: int,
    ppb: int,
) -> jnp.ndarray:
    """Gather the page table's per-token scales once in XLA →
    ``[2, B, Hkv, nblocks, bk]`` f32, which the kernels read as aligned
    ``(1, bk)`` lane rows from a pipelined VMEM input. Replaces round 4's
    in-kernel scale-row DMAs + lane rotations (the measured cause of the
    int8 slowdown); costs one 16-wide-slice gather per decode step."""
    B, padded = page_table.shape
    nblocks = padded // ppb
    sc = jax.lax.dynamic_index_in_dim(
        kv_scales, jnp.asarray(layer, jnp.int32).reshape(()), axis=1,
        keepdims=False,
    )  # [2, Hkv, P, page]
    g = sc[:, :, page_table]  # [2, Hkv, B, padded, page]
    Hkv = sc.shape[1]
    return (
        g.transpose(0, 2, 1, 3, 4)
        .reshape(2, B, Hkv, nblocks, ppb * page)
    )


def _window_bounds(starts_ref, b, batch_size: int, bk: int, hbm_len):
    """``(first_block, visible)`` of a block loop. Without ``starts_ref``
    (every layer that attends its whole context) the loop starts at block
    0 and a position is visible below ``hbm_len``: the code the kernels
    always had. With it (a window layer) row ``bb`` starts at the block
    that holds ``starts_ref[bb]`` — pages wholly before the window are
    never fetched — and a position is visible from ``starts_ref[b]`` on.
    The chain prefetch asks ``first_block`` of the NEXT row too, so what
    it fetches is what that row's loop consumes first."""
    if starts_ref is None:
        return (lambda bb: 0), (lambda pos: pos < hbm_len)
    start = starts_ref[b]

    def first_block(bb):
        return starts_ref[jax.lax.clamp(0, bb, batch_size - 1)] // bk

    return first_block, (lambda pos: (pos < hbm_len) & (pos >= start))


class _GatedCopy:
    """A compute block's HBM→VMEM gather with two runtime-selected DMA
    plans: ``_run`` (one coalesced descriptor, taken when the ``contig``
    flag from ``_contig_flags`` is set) or ``_pages`` (per-page copies).
    Start and wait are gated by the same SMEM-derived flag, so issued and
    awaited transfers always match — the invariant both paths' semaphore
    accounting depends on, kept in exactly one place."""

    _contig = None
    _n = 1
    _run = None
    _pages = ()

    def start(self):
        if self._n == 1:
            self._run.start()
            return

        @pl.when(self._contig != 0)
        def _():
            self._run.start()

        @pl.when(self._contig == 0)
        def _():
            for c in self._pages:
                c.start()

    def wait(self):
        if self._n == 1:
            self._run.wait()
            return

        @pl.when(self._contig != 0)
        def _():
            self._run.wait()

        @pl.when(self._contig == 0)
        def _():
            for c in self._pages:
                c.wait()


class _BlockCopy(_GatedCopy):
    """One kv head's block: coalesced = one contiguous
    ``(n_pages, page, D)`` descriptor, fragmented = per-page ``(page, D)``
    copies."""

    def __init__(self, kv_hbm, which, layer, head, buf, sem, page_table_ref,
                 flat_offset, n_pages, contig):
        src = kv_hbm.at[which, layer, head]
        first = page_table_ref[flat_offset]
        self._contig = contig
        self._n = n_pages
        self._run = pltpu.make_async_copy(
            src.at[pl.ds(first, n_pages)], buf, sem
        )
        if n_pages > 1:
            self._pages = [
                pltpu.make_async_copy(
                    src.at[page_table_ref[flat_offset + i]], buf.at[i], sem
                )
                for i in range(n_pages)
            ]


class _MhBlockCopy(_GatedCopy):
    """All-heads analog of ``_BlockCopy``: each descriptor moves the
    strided ``(Hkv, …)`` slab for every kv head — coalesced blocks as one
    ``(Hkv, n_pages, page, D)`` descriptor (``Hkv`` segments of
    ``n_pages·page·D`` contiguous bytes each), fragmented blocks as
    per-page ``(Hkv, page, D)`` copies."""

    def __init__(self, kv_hbm, which, layer, buf, sem, page_table_ref,
                 flat_offset, n_pages, contig):
        src = kv_hbm.at[which, layer]  # [Hkv, P, page, D]
        first = page_table_ref[flat_offset]
        self._contig = contig
        self._n = n_pages
        self._run = pltpu.make_async_copy(
            src.at[:, pl.ds(first, n_pages)], buf, sem
        )
        if n_pages > 1:
            self._pages = [
                pltpu.make_async_copy(
                    src.at[:, page_table_ref[flat_offset + i]],
                    buf.at[:, i],
                    sem,
                )
                for i in range(n_pages)
            ]


def _run_block_loop(
    *,
    b,
    h,
    layer,
    hbm_len,  # tokens resident in HBM pages for THIS program's sequence
    q,  # [G, D] fp32, pre-scaled
    lengths_ref,
    page_table_ref,
    contig_ref,  # SMEM [B * nblocks] coalescing flags (_contig_flags)
    buffer_index_ref,
    init_flag_ref,
    kv_hbm,
    k_buf,
    v_buf,
    sems,
    m_scr,
    l_scr,
    acc_scr,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    num_kv_heads: int,
    min_length: int,  # lengths_ref value below which a row has no HBM work
    prep_ref=None,  # VMEM (2, nblocks, bk) f32 prepared scales (int8 pools)
    starts_ref=None,  # SMEM [B] first visible token per row (window layers)
):
    """Initialize the online-softmax scratch and contract ``hbm_len``
    tokens of HBM pages into it, chain-prefetching block DMAs across grid
    programs. Shared by the read-only and fused kernels (their only
    difference here is how many trailing tokens live outside HBM:
    ``min_length`` is 1 / 2 respectively). With ``prep_ref`` the pages
    are int8 and dequantization folds into the contractions: scores scale
    by the per-token k-scale row, probabilities by the v-scale row — the
    int8 tiles feed the MXU directly, halving the block DMA bytes."""
    bk = page * pages_per_block
    nblocks = pages_per_seq // pages_per_block
    quantized = prep_ref is not None
    first_block, visible = _window_bounds(starts_ref, b, batch_size, bk, hbm_len)

    def block_copies(bb, hh, ii, slot):
        off = bb * pages_per_seq + ii * pages_per_block
        contig = contig_ref[bb * nblocks + ii]
        return [
            _BlockCopy(kv_hbm, 0, layer, hh, k_buf.at[slot], sems.at[slot, 0],
                       page_table_ref, off, pages_per_block, contig),
            _BlockCopy(kv_hbm, 1, layer, hh, v_buf.at[slot], sems.at[slot, 1],
                       page_table_ref, off, pages_per_block, contig),
        ]

    def next_indices(i):
        """Grid-order successor of block ``i`` of this (b, h) program,
        skipping sequences with no HBM work."""

        def advance_b():
            nb = jax.lax.fori_loop(
                b + 1,
                batch_size,
                lambda _, x: jnp.where(
                    jnp.logical_and(
                        x < batch_size,
                        lengths_ref[jax.lax.clamp(0, x, batch_size - 1)]
                        < min_length,
                    ),
                    x + 1,
                    x,
                ),
                b + 1,
            )
            return (nb, 0, first_block(nb))

        def advance_h():
            return jax.lax.cond(
                h + 1 < num_kv_heads, lambda: (b, h + 1, first_block(b)),
                advance_b,
            )

        return jax.lax.cond(i * bk < hbm_len, lambda: (b, h, i), advance_h)

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(i, _):
        init_flag = init_flag_ref[0]
        init_flag_ref[0] = 0
        slot = buffer_index_ref[0]
        nb, nh, ni = next_indices(i + 1)

        @pl.when(init_flag)
        def _cold_start():
            for c in block_copies(b, h, i, slot):
                c.start()

        @pl.when(nb < batch_size)
        def _prefetch_next():
            nslot = jnp.where(slot == 0, 1, 0)
            for c in block_copies(nb, nh, ni, nslot):
                c.start()
            buffer_index_ref[0] = nslot

        cs = block_copies(b, h, i, slot)
        cs[0].wait()
        k = k_buf[slot].astype(jnp.float32).reshape(bk, -1)  # [bk, D]
        s = jax.lax.dot_general(  # [G, bk]
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            s = s * prep_ref[0, pl.ds(i, 1), :]  # (1, bk) k-scales
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(visible(pos), s, _MASK)

        m_prev = m_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)  # [G, 1]
        m_new = jnp.maximum(m_prev, m_blk)  # lane-replicated [G, D]
        p = jnp.exp(s - m_new[:, :1])  # [G, bk]
        corr = jnp.exp(m_prev - m_new)
        l_blk = jnp.sum(p, axis=-1, keepdims=True)
        l_scr[...] = l_scr[...] * corr + l_blk
        m_scr[...] = m_new

        cs[1].wait()
        if quantized:
            p = p * prep_ref[1, pl.ds(i, 1), :]  # (1, bk) v-scales
        v = v_buf[slot].astype(jnp.float32).reshape(bk, -1)  # [bk, D]
        # Masked columns must contribute EXACT zeros to p·v: coalesced
        # pad fetches can stage pages no table entry references, and if
        # one ever holds NaN/Inf, 0·NaN = NaN would poison the
        # accumulator (ADVICE round-5 #1). Zero BOTH factors — p (pad
        # v-scale rows may be non-finite) and v (pad pool bytes may be).
        p = jnp.where(visible(pos), p, 0.0)
        v = jnp.where(
            visible(i * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)),
            v,
            0.0,
        )
        pv = jax.lax.dot_general(  # [G, D]
            p, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        return ()

    jax.lax.fori_loop(first_block(b), pl.cdiv(hbm_len, bk), body, ())


def _kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B]
    page_table_ref,  # SMEM [B * blocks_padded * ppb] flattened
    contig_ref,  # SMEM [B * nblocks] coalescing flags
    layer_ref,  # SMEM [1] — which layer's pages to read
    buffer_index_ref,  # SMEM [1] — double-buffer slot, persists across programs
    init_flag_ref,  # SMEM [1] — 1 until the very first program cold-starts
    # then: inputs (q_ref, kv_hbm[, prep]), outputs (o_ref) and scratch —
    # the quantized variant inserts the prepared-scale input, so the tail
    # is unpacked by flag.
    *refs,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    num_kv_heads: int,
    quantized: bool,
):
    if quantized:
        (q_ref, kv_hbm, prep_ref, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, sems) = refs
    else:
        q_ref, kv_hbm, o_ref, m_scr, l_scr, acc_scr, k_buf, v_buf, sems = refs
        prep_ref = None
    b, h = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    length = lengths_ref[b]

    # Rows with no work still get a deterministic (zero) output — never
    # whatever happened to be resident in VMEM.
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _program():
        q = q_ref[...].astype(jnp.float32)  # pre-scaled by the wrapper
        _run_block_loop(
            b=b, h=h, layer=layer, hbm_len=length, q=q,
            lengths_ref=lengths_ref, page_table_ref=page_table_ref,
            contig_ref=contig_ref,
            buffer_index_ref=buffer_index_ref, init_flag_ref=init_flag_ref,
            kv_hbm=kv_hbm, k_buf=k_buf, v_buf=v_buf, sems=sems,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
            page=page, pages_per_block=pages_per_block,
            pages_per_seq=pages_per_seq, batch_size=batch_size,
            num_kv_heads=num_kv_heads, min_length=1,
            prep_ref=prep_ref,
        )
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _mh_block_loop(
    *,
    b,
    layer,
    hbm_len,  # tokens resident in HBM pages for THIS program's sequence
    q,  # (Hkv, G, D) f32, pre-scaled
    lengths_ref,
    page_table_ref,
    contig_ref,  # SMEM [B * nblocks] coalescing flags
    buffer_index_ref,
    init_flag_ref,
    kv_hbm,
    k_buf,
    v_buf,
    sems,
    m_scr,
    l_scr,
    acc_scr,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    num_kv_heads: int,
    min_length: int,  # lengths_ref value below which a row has no HBM work
    prep_ref=None,  # VMEM (2, Hkv, nblocks, bk) f32 prepared scales
    starts_ref=None,  # SMEM [B] first visible token per row (window layers)
):
    """The heads-batched analog of ``_run_block_loop``: one program per
    SEQUENCE, ``(Hkv, G, ·)`` batched MXU contractions, chain-prefetched
    ``_MhBlockCopy`` DMAs (one descriptor per block per K/V when the
    block's pages coalesce). Shared by the read-only and fused mh kernels
    (min_length 1 / 2, exactly like the per-head pair).

    DELIBERATE duplication of ``_run_block_loop``'s machinery (parity
    pinned by tests/test_ops.py): the per-head grid survives as the
    fallback for configs whose all-heads block would blow VMEM, and
    merging a head axis into it would couple both paths' shapes. (The
    GQA group axis rides implicitly in ``q``'s shape.)"""
    bk = page * pages_per_block
    nblocks = pages_per_seq // pages_per_block
    Hkv = num_kv_heads
    quantized = prep_ref is not None
    first_block, visible = _window_bounds(starts_ref, b, batch_size, bk, hbm_len)

    def block_copies(bb, ii, slot):
        off = bb * pages_per_seq + ii * pages_per_block
        contig = contig_ref[bb * nblocks + ii]
        return [
            _MhBlockCopy(kv_hbm, 0, layer, k_buf.at[slot], sems.at[slot, 0],
                         page_table_ref, off, pages_per_block, contig),
            _MhBlockCopy(kv_hbm, 1, layer, v_buf.at[slot], sems.at[slot, 1],
                         page_table_ref, off, pages_per_block, contig),
        ]

    def next_indices(i):
        """Grid-order successor of block ``i`` of program ``b``, skipping
        sequences with no HBM work (mirrors ``_run_block_loop`` minus the
        head axis)."""

        def advance_b():
            nb = jax.lax.fori_loop(
                b + 1,
                batch_size,
                lambda _, x: jnp.where(
                    jnp.logical_and(
                        x < batch_size,
                        lengths_ref[jax.lax.clamp(0, x, batch_size - 1)]
                        < min_length,
                    ),
                    x + 1,
                    x,
                ),
                b + 1,
            )
            return (nb, first_block(nb))

        return jax.lax.cond(i * bk < hbm_len, lambda: (b, i), advance_b)

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(i, _):
        init_flag = init_flag_ref[0]
        init_flag_ref[0] = 0
        slot = buffer_index_ref[0]
        nb, ni = next_indices(i + 1)

        @pl.when(init_flag)
        def _cold_start():
            for c in block_copies(b, i, slot):
                c.start()

        @pl.when(nb < batch_size)
        def _prefetch_next():
            nslot = jnp.where(slot == 0, 1, 0)
            for c in block_copies(nb, ni, nslot):
                c.start()
            buffer_index_ref[0] = nslot

        cs = block_copies(b, i, slot)
        cs[0].wait()
        # (Hkv, ppb, page, D) → (Hkv, bk, D): middle collapse, minor
        # dim untouched — a supported relayout-free reshape.
        k = k_buf[slot].astype(jnp.float32).reshape(Hkv, bk, -1)
        s = jax.lax.dot_general(  # (Hkv, G, bk), heads-batched MXU
            q, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            s = s * prep_ref[0, :, pl.ds(i, 1), :]  # (Hkv, 1, bk) k-scales
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(visible(pos), s, _MASK)

        m_prev = m_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)  # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, m_blk)  # lane-replicated (Hkv, G, D)
        p = jnp.exp(s - m_new[:, :, :1])  # (Hkv, G, bk)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new

        cs[1].wait()
        if quantized:
            p = p * prep_ref[1, :, pl.ds(i, 1), :]  # (Hkv, 1, bk) v-scales
        v = v_buf[slot].astype(jnp.float32).reshape(Hkv, bk, -1)
        # Exact zeros at masked positions (see _run_block_loop): pad
        # fetches may stage unreferenced pages; NaN/Inf there (or in pad
        # scale rows) must not ride 0·NaN into the accumulator.
        p = jnp.where(visible(pos), p, 0.0)
        v = jnp.where(
            visible(i * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)),
            v,
            0.0,
        )
        pv = jax.lax.dot_general(  # (Hkv, G, D)
            p, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        return ()

    jax.lax.fori_loop(first_block(b), pl.cdiv(hbm_len, bk), body, ())


def _mh_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B]
    page_table_ref,  # SMEM [B * blocks_padded * ppb] flattened
    contig_ref,  # SMEM [B * nblocks]
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1]
    init_flag_ref,  # SMEM [1]
    *refs,  # q_ref, kv_hbm[, prep], o_ref, scratch — unpacked by flag
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    num_kv_heads: int,
    group: int,
    quantized: bool,
):
    """Heads-fused read-only pool attention: grid ``(B,)`` (see
    ``_mh_block_loop``)."""
    if quantized:
        (q_ref, kv_hbm, prep_ref, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, sems) = refs
    else:
        q_ref, kv_hbm, o_ref, m_scr, l_scr, acc_scr, k_buf, v_buf, sems = refs
        prep_ref = None
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    Hkv, G = num_kv_heads, group

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _program():
        q = q_ref[...].astype(jnp.float32).reshape(Hkv, G, -1)  # pre-scaled
        _mh_block_loop(
            b=b, layer=layer, hbm_len=length, q=q,
            lengths_ref=lengths_ref, page_table_ref=page_table_ref,
            contig_ref=contig_ref,
            buffer_index_ref=buffer_index_ref, init_flag_ref=init_flag_ref,
            kv_hbm=kv_hbm, k_buf=k_buf, v_buf=v_buf, sems=sems,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
            page=page, pages_per_block=pages_per_block,
            pages_per_seq=pages_per_seq, batch_size=batch_size,
            num_kv_heads=num_kv_heads, min_length=1,
            prep_ref=prep_ref,
        )
        out = acc_scr[...] / l_scr[...]
        o_ref[...] = out.reshape(Hkv * G, -1).astype(o_ref.dtype)


def _mh_fused_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B] context length INCLUDING the current token
    page_table_ref,  # SMEM [B * blocks_padded * ppb] flattened
    contig_ref,  # SMEM [B * nblocks]
    slots_ref,  # SMEM [B] pool slot receiving this token's K/V
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1]
    init_flag_ref,  # SMEM [1]
    *refs,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    num_kv_heads: int,
    group: int,
    quantized: bool,
    windowed: bool = False,
):
    """Heads-fused decode step: the ``_fused_kernel`` contract (write the
    current token's K/V row through the aliased pool output, fold it in
    from VMEM) at grid ``(B,)`` — the page-row RMW also moves all heads
    per DMA (2 reads + 2 writes per SEQUENCE instead of per (b, h)).
    Quantized pools receive the row already quantized (``k_new``/``v_new``
    int8, written verbatim) PLUS its dequantized twin (``k_deq``/``v_deq``
    = int8 · scale, computed by the wrapper) for the VMEM fold-in — so
    attention sees bit-exactly what any later pool read will see, with
    zero in-kernel scale handling. The scale POOL is updated by the
    wrapper with one XLA scatter. ``windowed``: one more scalar-prefetch
    array, the first visible token of each row (``_window_bounds``)."""
    starts_ref = None
    if windowed:
        starts_ref, *refs = refs
    if quantized:
        (q_ref, k_new_ref, v_new_ref, k_deq_ref, v_deq_ref, kv_hbm, prep_ref,
         kv_out, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, row_scr, sems, w_sem) = refs
    else:
        (q_ref, k_new_ref, v_new_ref, kv_hbm,
         kv_out, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, row_scr, sems, w_sem) = refs
        k_deq_ref, v_deq_ref, prep_ref = k_new_ref, v_new_ref, None
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    hbm_len = length - 1
    Hkv, G = num_kv_heads, group

    slot = slots_ref[b]
    pg, off = slot // page, slot % page

    def page_window(which):
        return kv_out.at[which, layer, :, pg]  # (Hkv, page, D) strided

    rk = pltpu.make_async_copy(page_window(0), row_scr.at[0], w_sem)
    rv = pltpu.make_async_copy(page_window(1), row_scr.at[1], w_sem)
    wk = pltpu.make_async_copy(row_scr.at[0], page_window(0), w_sem)
    wv = pltpu.make_async_copy(row_scr.at[1], page_window(1), w_sem)

    k_cur = k_deq_ref[...].astype(jnp.float32)  # (Hkv, 1, D)
    v_cur = v_deq_ref[...].astype(jnp.float32)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _write():
        rk.start()
        rv.start()
        rk.wait()
        rv.wait()
        mask = jax.lax.broadcasted_iota(jnp.int32, row_scr.shape[1:], 1) == off
        row_scr[0] = jnp.where(
            mask, jnp.broadcast_to(k_new_ref[...], row_scr.shape[1:]), row_scr[0]
        )
        row_scr[1] = jnp.where(
            mask, jnp.broadcast_to(v_new_ref[...], row_scr.shape[1:]), row_scr[1]
        )
        wk.start()
        wv.start()

    @pl.when(length > 0)
    def _program():
        q = q_ref[...].astype(jnp.float32).reshape(Hkv, G, -1)  # pre-scaled
        _mh_block_loop(
            b=b, layer=layer, hbm_len=hbm_len, q=q,
            lengths_ref=lengths_ref, page_table_ref=page_table_ref,
            contig_ref=contig_ref,
            buffer_index_ref=buffer_index_ref, init_flag_ref=init_flag_ref,
            kv_hbm=kv_hbm, k_buf=k_buf, v_buf=v_buf, sems=sems,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
            page=page, pages_per_block=pages_per_block,
            pages_per_seq=pages_per_seq, batch_size=batch_size,
            num_kv_heads=num_kv_heads, min_length=2,
            prep_ref=prep_ref, starts_ref=starts_ref,
        )
        s_cur = jax.lax.dot_general(  # (Hkv, G, 1)
            q, k_cur,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s_cur)
        p_cur = jnp.exp(s_cur - m_new[:, :, :1])  # (Hkv, G, 1)
        corr = jnp.exp(m_prev - m_new)
        l_fin = l_scr[...] * corr + p_cur
        acc_fin = acc_scr[...] * corr + p_cur * v_cur
        out = acc_fin / l_fin
        o_ref[...] = out.reshape(Hkv * G, -1).astype(o_ref.dtype)
        wk.wait()
        wv.wait()


def _fused_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B] context length INCLUDING the current token
    page_table_ref,  # SMEM [B * blocks_padded * ppb] flattened
    contig_ref,  # SMEM [B * nblocks]
    slots_ref,  # SMEM [B] pool slot receiving this token's K/V
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1]
    init_flag_ref,  # SMEM [1]
    # then inputs (q, k_new, v_new[, k_deq, v_deq], kv_hbm[, prep]),
    # outputs (kv_out, o_ref) and scratch — unpacked by flag.
    *refs,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    num_kv_heads: int,
    quantized: bool,
    windowed: bool = False,
):
    """Fused decode attention: write this token's K/V row into the pool
    (replacing the XLA scatter — the pool is aliased through the call, so
    the scan carry never copies) and attend over all ``length`` tokens,
    the current one folded in from VMEM (see module docstring). Quantized
    pools follow the ``_mh_fused_kernel`` contract: int8 row written
    verbatim, dequantized twin folded in, scale pool scattered by the
    wrapper. ``windowed`` as in ``_mh_fused_kernel``."""
    starts_ref = None
    if windowed:
        starts_ref, *refs = refs
    if quantized:
        (q_ref, k_new_ref, v_new_ref, k_deq_ref, v_deq_ref, kv_hbm, prep_ref,
         kv_out, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, row_scr, sems, w_sem) = refs
    else:
        (q_ref, k_new_ref, v_new_ref, kv_hbm,
         kv_out, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, row_scr, sems, w_sem) = refs
        k_deq_ref, v_deq_ref, prep_ref = k_new_ref, v_new_ref, None
    b, h = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    length = lengths_ref[b]
    hbm_len = length - 1  # tokens resident in HBM pages

    slot = slots_ref[b]
    pg, off = slot // page, slot % page
    # Write through the ALIASED output ref (same HBM buffer as kv_hbm on
    # hardware; in interpret mode the alias is simulated by a copy, so
    # writing the input would be lost). Sublane tiling forbids partial
    # slices on the page axis, so read-modify-write the WHOLE page: every
    # other row (earlier, immutable tokens — or never-read future slots)
    # is rewritten byte-identical, so racing block reads are unaffected.
    def page_window(which):
        return kv_out.at[which, layer, h, pg]  # [page, D], full-dim slice

    rk = pltpu.make_async_copy(page_window(0), row_scr.at[0], w_sem)
    rv = pltpu.make_async_copy(page_window(1), row_scr.at[1], w_sem)
    wk = pltpu.make_async_copy(row_scr.at[0], page_window(0), w_sem)
    wv = pltpu.make_async_copy(row_scr.at[1], page_window(1), w_sem)

    # Current token, dequantized where the pool is int8 so attention sees
    # the pool's eventual contents bit-exactly.
    k_cur = k_deq_ref[...].astype(jnp.float32)  # [1, D]
    v_cur = v_deq_ref[...].astype(jnp.float32)

    o_ref[...] = jnp.zeros_like(o_ref)  # deterministic for length==0 rows

    @pl.when(length > 0)
    def _write():
        rk.start()
        rv.start()
        rk.wait()
        rv.wait()
        mask = jax.lax.broadcasted_iota(jnp.int32, row_scr.shape[1:], 0) == off
        new_k_row = jnp.broadcast_to(k_new_ref[...], row_scr.shape[1:])
        new_v_row = jnp.broadcast_to(v_new_ref[...], row_scr.shape[1:])
        row_scr[0] = jnp.where(mask, new_k_row, row_scr[0])
        row_scr[1] = jnp.where(mask, new_v_row, row_scr[1])
        wk.start()
        wv.start()

    @pl.when(length > 0)
    def _program():
        q = q_ref[...].astype(jnp.float32)  # pre-scaled by the wrapper
        _run_block_loop(
            b=b, h=h, layer=layer, hbm_len=hbm_len, q=q,
            lengths_ref=lengths_ref, page_table_ref=page_table_ref,
            contig_ref=contig_ref,
            buffer_index_ref=buffer_index_ref, init_flag_ref=init_flag_ref,
            kv_hbm=kv_hbm, k_buf=k_buf, v_buf=v_buf, sems=sems,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
            page=page, pages_per_block=pages_per_block,
            pages_per_seq=pages_per_seq, batch_size=batch_size,
            num_kv_heads=num_kv_heads, min_length=2,
            prep_ref=prep_ref, starts_ref=starts_ref,
        )
        # Fold in the current token from VMEM (one more online-softmax
        # step with a single-position block).
        s_cur = jax.lax.dot_general(  # [G, 1]
            q, k_cur,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s_cur)
        p_cur = jnp.exp(s_cur - m_new[:, :1])  # [G, 1]
        corr = jnp.exp(m_prev - m_new)
        l_fin = l_scr[...] * corr + p_cur
        acc_fin = acc_scr[...] * corr + p_cur * v_cur
        o_ref[...] = (acc_fin / l_fin).astype(o_ref.dtype)
        wk.wait()
        wv.wait()


def _block_geometry(page_table, page: int, pages_per_block: int | None):
    """(padded page table, ppb, padded max_pages): pad max_pages up to a
    block multiple (the pad entries index page 0, whose reads are masked
    by the length bound like every other table pad)."""
    max_pages = page_table.shape[1]
    if pages_per_block is None:
        # ~256 tokens per compute block: large enough to amortize per-block
        # overhead, small enough that double-buffered K+V fits VMEM easily.
        pages_per_block = max(1, min(max_pages, -(-256 // page)))
    ppb = min(pages_per_block, max_pages)
    blocks = -(-max_pages // ppb)
    padded = blocks * ppb
    if padded != max_pages:
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - max_pages)))
    return page_table, ppb, padded


def _auto_fuse_heads(
    Hkv: int, page: int, D: int, dtype, max_pages: int,
    pages_per_block: int | None, quantized: bool,
) -> bool:
    """Default ``fuse_heads`` policy: heads-batched programs whenever the
    VMEM the mh wrapper would actually allocate — the double-buffered
    all-heads K+V blocks at the CALLER's ``pages_per_block`` (mh default
    when unset), plus the int8 prepared-scales input block — stays within
    an 8 MB budget (production GQA shapes — Hkv 8, page 16, D 128 bf16 —
    sit near 1 MB). The per-head grid remains for huge-Hkv/page/block
    configs."""
    if pages_per_block is None:
        pages_per_block = max(1, -(-128 // page))
    ppb = min(pages_per_block, max_pages)
    itemsize = jnp.dtype(dtype).itemsize
    vmem = 2 * 2 * Hkv * ppb * page * D * itemsize
    if quantized:
        nblocks = -(-max_pages // ppb)
        vmem += 2 * Hkv * nblocks * ppb * page * 4  # prepared scales, f32
    return vmem <= 8 * 2**20


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "interpret", "fuse_heads")
)
def paged_attention_pool_kernel(
    q: jnp.ndarray,  # [B, Hq, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] — full pool pages view
    page_table: jnp.ndarray,  # [B, max_pages] int32
    lengths: jnp.ndarray,  # [B] int32
    layer: jnp.ndarray | int,  # which layer's pages to attend over
    pages_per_block: int | None = None,
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] (int8 pool)
    fuse_heads: bool | None = None,  # None → _auto_fuse_heads policy
) -> jnp.ndarray:
    """Read-only entry: the whole (multi-layer) pool rides in HBM untouched
    and the kernel DMAs only ``layer``'s pages — so a scan-over-layers
    decode step costs O(context pages) HBM traffic per layer, never a
    materialized per-layer slice (which would be O(pool size)). With
    ``kv_scales`` the pool is int8 (page DMA bytes halve) and the page
    table's scales arrive via ``_prep_scales``."""
    B, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must divide by Hkv={Hkv}")
    G = Hq // Hkv
    quantized = kv_scales is not None
    if fuse_heads is None:
        fuse_heads = _auto_fuse_heads(
            Hkv, page, D, kv_pages.dtype, page_table.shape[1],
            pages_per_block, quantized,
        )
    if fuse_heads:
        return _pool_kernel_mh(
            q, kv_pages, page_table, lengths, layer,
            pages_per_block=pages_per_block, interpret=interpret,
            kv_scales=kv_scales,
        )
    page_table, ppb, padded = _block_geometry(page_table, page, pages_per_block)
    contig = _contig_flags(page_table, lengths, page, ppb, P)

    scale = 1.0 / (D ** 0.5)
    # [B, Hq, 1, D] + a [G, D] f32 block: hints a <1x128>-friendly layout
    # for small GQA group sizes (G is often 1-4, far off the 8-sublane tile).
    q4 = (q.astype(jnp.float32) * scale).reshape(B, Hq, 1, D)
    q_spec = pl.BlockSpec((None, G, None, D), lambda b, h, *_: (b, h, 0, 0))

    kernel = functools.partial(
        _kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        num_kv_heads=Hkv,
        quantized=quantized,
    )
    in_specs = [q_spec, pl.BlockSpec(memory_space=pl.ANY)]
    if quantized:
        # Prepared scales [2, B, Hkv, nblocks, bk]: one (2, nblocks, bk)
        # slab per program, pipelined by BlockSpec.
        in_specs.append(
            pl.BlockSpec(
                (2, None, None, padded // ppb, ppb * page),
                lambda b, h, *_: (0, b, h, 0, 0),
            )
        )
    scratch = [
        pltpu.VMEM((G, D), jnp.float32),
        pltpu.VMEM((G, D), jnp.float32),
        pltpu.VMEM((G, D), jnp.float32),
        pltpu.VMEM((2, ppb, page, D), kv_pages.dtype),
        pltpu.VMEM((2, ppb, page, D), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, Hkv),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    args = [
        jnp.asarray(lengths, dtype=jnp.int32),
        jnp.asarray(page_table, dtype=jnp.int32).reshape(-1),
        contig,
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),  # double-buffer slot
        jnp.ones((1,), jnp.int32),  # cold-start flag
        q4,
        kv_pages,
    ]
    if quantized:
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="radixmesh_pool_attn",
    )(*args)
    return out.reshape(B, Hq, D).astype(q.dtype)


def _pool_kernel_mh(
    q, kv_pages, page_table, lengths, layer,
    pages_per_block: int | None = None, interpret: bool = False,
    kv_scales=None,
):
    """Heads-batched pool attention wrapper (see ``_mh_kernel``). Smaller
    default blocks than the per-head kernel: each staged block is
    ``Hkv ×`` bigger, so bk=128 keeps the double buffers ≤ ~1 MB VMEM
    at Hkv=8/D=128 bf16 — and bk=128 also means a ctx-128 row costs
    exactly one coalesced descriptor pair (the short-context regime)."""
    B, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    G = Hq // Hkv
    quantized = kv_scales is not None
    if pages_per_block is None:
        pages_per_block = max(1, -(-128 // page))
    page_table, ppb, padded = _block_geometry(page_table, page, pages_per_block)
    contig = _contig_flags(page_table, lengths, page, ppb, P)

    scale = 1.0 / (D ** 0.5)
    q4 = (q.astype(jnp.float32) * scale).reshape(B, Hq, 1, D)
    q_spec = pl.BlockSpec((None, Hq, None, D), lambda b, *_: (b, 0, 0, 0))

    kernel = functools.partial(
        _mh_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        num_kv_heads=Hkv,
        group=G,
        quantized=quantized,
    )
    in_specs = [q_spec, pl.BlockSpec(memory_space=pl.ANY)]
    if quantized:
        in_specs.append(
            pl.BlockSpec(
                (2, None, Hkv, padded // ppb, ppb * page),
                lambda b, *_: (0, b, 0, 0, 0),
            )
        )
    scratch = [
        pltpu.VMEM((Hkv, G, D), jnp.float32),
        pltpu.VMEM((Hkv, G, D), jnp.float32),
        pltpu.VMEM((Hkv, G, D), jnp.float32),
        pltpu.VMEM((2, Hkv, ppb, page, D), kv_pages.dtype),
        pltpu.VMEM((2, Hkv, ppb, page, D), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    args = [
        jnp.asarray(lengths, dtype=jnp.int32),
        jnp.asarray(page_table, dtype=jnp.int32).reshape(-1),
        contig,
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32),
        q4,
        kv_pages,
    ]
    if quantized:
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="radixmesh_pool_attn_mh",
    )(*args)
    return out.reshape(B, Hq, D).astype(q.dtype)


def _window_starts(lengths, window):
    """First visible token of each row on a layer whose window holds
    ``window`` keys, the query's own among them; 0 (everything) where
    ``window`` is 0. ``lengths`` count the query's token."""
    window = jnp.asarray(window, jnp.int32)
    return jnp.where(window > 0, jnp.maximum(lengths - window, 0), 0).astype(
        jnp.int32
    )


def _fused_decode_mh(
    q, k_new, v_new, kv_pages, slots, page_table, lengths, layer,
    pages_per_block: int | None = None, interpret: bool = False,
    kv_scales=None, window=None,
):
    """Heads-batched fused decode wrapper (see ``_mh_fused_kernel``)."""
    B, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    G = Hq // Hkv
    quantized = kv_scales is not None
    if pages_per_block is None:
        pages_per_block = max(1, -(-128 // page))
    page_table, ppb, padded = _block_geometry(page_table, page, pages_per_block)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    contig = _contig_flags(
        page_table, jnp.maximum(lengths - 1, 0), page, ppb, P
    )

    if quantized:
        from radixmesh_tpu.ops.quant import quantize_kv

        # Quantize the incoming row OUTSIDE the kernel (the SAME quantizer
        # the pool's host write path uses, so attention and later reads
        # agree bit-exactly); the kernel writes the int8 row verbatim and
        # folds in the dequantized twin. The scale POOL is updated below
        # with one XLA scatter — an in-kernel scale-row RMW costs four
        # extra serialized DMAs per program (measured 1.75x the whole
        # fused step on chip in round 3).
        k_q, k_sc = quantize_kv(k_new.astype(jnp.float32), axis=-1)
        v_q, v_sc = quantize_kv(v_new.astype(jnp.float32), axis=-1)
        # The fold-in twin stays f32: the jnp oracle attends the f32
        # dequantized row, and a bf16 round-trip here drifts later
        # layers' quantized rows by +/-1 (see tests/test_pp_serving.py's
        # bit-exact pool comparison).
        k_deq = k_q.astype(jnp.float32) * k_sc[..., None]
        v_deq = v_q.astype(jnp.float32) * v_sc[..., None]
        k_new, v_new = k_q, v_q
    else:
        k_deq, v_deq = k_new, v_new

    scale = 1.0 / (D ** 0.5)
    q4 = (q.astype(jnp.float32) * scale).reshape(B, Hq, 1, D)
    q_spec = pl.BlockSpec((None, Hq, None, D), lambda b, *_: (b, 0, 0, 0))
    kv_new_spec = pl.BlockSpec((None, Hkv, 1, D), lambda b, *_: (b, 0, 0, 0))

    kernel = functools.partial(
        _mh_fused_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        num_kv_heads=Hkv,
        group=G,
        quantized=quantized,
        windowed=window is not None,
    )
    in_specs = [q_spec, kv_new_spec, kv_new_spec]
    if quantized:
        in_specs += [kv_new_spec, kv_new_spec]
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    if quantized:
        in_specs.append(
            pl.BlockSpec(
                (2, None, Hkv, padded // ppb, ppb * page),
                lambda b, *_: (0, b, 0, 0, 0),
            )
        )
    n_scalars = 7 + (window is not None)
    # Flat arg index of kv_pages (aliased onto output 0): scalars + q +
    # k_new + v_new (+ k_deq + v_deq).
    kv_arg = n_scalars + (5 if quantized else 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), q_spec],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((2, Hkv, ppb, page, D), kv_pages.dtype),
            pltpu.VMEM((2, Hkv, ppb, page, D), kv_pages.dtype),
            pltpu.VMEM((2, Hkv, page, D), kv_pages.dtype),  # row RMW
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    args = [
        lengths,
        jnp.asarray(page_table, dtype=jnp.int32).reshape(-1),
        contig,
        jnp.asarray(slots, dtype=jnp.int32),
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32),
        *([] if window is None else [_window_starts(lengths, window)]),
        q4,
        k_new.astype(kv_pages.dtype).reshape(B, Hkv, 1, D),
        v_new.astype(kv_pages.dtype).reshape(B, Hkv, 1, D),
    ]
    if quantized:
        args += [
            k_deq.reshape(B, Hkv, 1, D),
            v_deq.reshape(B, Hkv, 1, D),
        ]
    args.append(kv_pages)
    if quantized:
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    kv_out, out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, D), jnp.float32),
        ],
        input_output_aliases={kv_arg: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="radixmesh_decode_fused_mh",
    )(*args)
    attn = out.reshape(B, Hq, D).astype(q.dtype)
    if quantized:
        scales_out = _scatter_new_scales(
            kv_scales, layer, slots, lengths, page, k_sc, v_sc
        )
        return attn, kv_out, scales_out
    return attn, kv_out


def _scatter_new_scales(kv_scales, layer, slots, lengths, page, k_sc, v_sc):
    """Scale-pool update by XLA scatter (same convention as the jnp
    fallback: an ARRAY layer index makes the advanced indices
    non-adjacent, so the batch axis lands first → [B, Hkv]), masked so
    inactive (length == 0) rows leave their target slot's scales
    untouched."""
    slots = jnp.asarray(slots, dtype=jnp.int32)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    layer_ix = jnp.asarray(layer)
    pg_b, off_b = slots // page, slots % page
    valid = (lengths > 0)[:, None]  # [B, 1] vs [B, Hkv] gathers
    cur_k = kv_scales[0, layer_ix, :, pg_b, off_b]
    cur_v = kv_scales[1, layer_ix, :, pg_b, off_b]
    scales_out = kv_scales.at[0, layer_ix, :, pg_b, off_b].set(
        jnp.where(valid, k_sc, cur_k)
    )
    scales_out = scales_out.at[1, layer_ix, :, pg_b, off_b].set(
        jnp.where(valid, v_sc, cur_v)
    )
    return scales_out


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "interpret", "fuse_heads")
)
def paged_decode_fused_kernel(
    q: jnp.ndarray,  # [B, Hq, D]
    k_new: jnp.ndarray,  # [B, Hkv, D] this token's K (post-rope)
    v_new: jnp.ndarray,  # [B, Hkv, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] — donated/aliased
    slots: jnp.ndarray,  # [B] pool slot for this token
    page_table: jnp.ndarray,  # [B, max_pages] int32
    lengths: jnp.ndarray,  # [B] context length incl. current token
    layer: jnp.ndarray | int,
    pages_per_block: int | None = None,
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] int8 pool
    fuse_heads: bool | None = None,  # None → _auto_fuse_heads policy
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
):
    """Fused decode step attention: returns ``(attn_out [B, Hq, D],
    kv_pages)`` — plus the updated ``kv_scales`` when quantized — where
    the pool buffers are the SAME memory updated in place (the caller
    threads them as scan carries with zero copies). ``window`` (a traced
    scalar, so that one scanned layer body serves window and full layers)
    limits each row to its last ``window`` tokens and starts the block
    loop at the block that holds the first of them; ``None`` compiles the
    kernel without a window."""
    B, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must divide by Hkv={Hkv}")
    G = Hq // Hkv
    quantized = kv_scales is not None
    if fuse_heads is None:
        fuse_heads = _auto_fuse_heads(
            Hkv, page, D, kv_pages.dtype, page_table.shape[1],
            pages_per_block, quantized,
        )
    if fuse_heads:
        return _fused_decode_mh(
            q, k_new, v_new, kv_pages, slots, page_table, lengths, layer,
            pages_per_block=pages_per_block, interpret=interpret,
            kv_scales=kv_scales, window=window,
        )
    page_table, ppb, padded = _block_geometry(page_table, page, pages_per_block)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    contig = _contig_flags(
        page_table, jnp.maximum(lengths - 1, 0), page, ppb, P
    )
    if quantized:
        from radixmesh_tpu.ops.quant import quantize_kv

        k_q, k_sc = quantize_kv(k_new.astype(jnp.float32), axis=-1)
        v_q, v_sc = quantize_kv(v_new.astype(jnp.float32), axis=-1)
        # The fold-in twin stays f32: the jnp oracle attends the f32
        # dequantized row, and a bf16 round-trip here drifts later
        # layers' quantized rows by +/-1 (see tests/test_pp_serving.py's
        # bit-exact pool comparison).
        k_deq = k_q.astype(jnp.float32) * k_sc[..., None]
        v_deq = v_q.astype(jnp.float32) * v_sc[..., None]
        k_new, v_new = k_q, v_q
    else:
        k_deq, v_deq = k_new, v_new

    scale = 1.0 / (D ** 0.5)
    q4 = (q.astype(jnp.float32) * scale).reshape(B, Hq, 1, D)
    q_spec = pl.BlockSpec((None, G, None, D), lambda b, h, *_: (b, h, 0, 0))
    kv_new_spec = pl.BlockSpec((None, None, 1, D), lambda b, h, *_: (b, h, 0, 0))
    new_dtype = kv_pages.dtype

    kernel = functools.partial(
        _fused_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        num_kv_heads=Hkv,
        quantized=quantized,
        windowed=window is not None,
    )
    in_specs = [q_spec, kv_new_spec, kv_new_spec]
    if quantized:
        in_specs += [kv_new_spec, kv_new_spec]
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    if quantized:
        in_specs.append(
            pl.BlockSpec(
                (2, None, None, padded // ppb, ppb * page),
                lambda b, h, *_: (0, b, h, 0, 0),
            )
        )
    n_scalars = 7 + (window is not None)
    kv_arg = n_scalars + (5 if quantized else 3)

    scratch = [
        pltpu.VMEM((G, D), jnp.float32),
        pltpu.VMEM((G, D), jnp.float32),
        pltpu.VMEM((G, D), jnp.float32),
        pltpu.VMEM((2, ppb, page, D), kv_pages.dtype),
        pltpu.VMEM((2, ppb, page, D), kv_pages.dtype),
        pltpu.VMEM((2, page, D), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SemaphoreType.DMA,
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(B, Hkv),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), q_spec],
        scratch_shapes=scratch,
    )
    args = [
        lengths,
        jnp.asarray(page_table, dtype=jnp.int32).reshape(-1),
        contig,
        jnp.asarray(slots, dtype=jnp.int32),
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),  # double-buffer slot
        jnp.ones((1,), jnp.int32),  # cold-start flag
        *([] if window is None else [_window_starts(lengths, window)]),
        q4,
        k_new.astype(new_dtype).reshape(B, Hkv, 1, D),
        v_new.astype(new_dtype).reshape(B, Hkv, 1, D),
    ]
    if quantized:
        args += [
            k_deq.reshape(B, Hkv, 1, D),
            v_deq.reshape(B, Hkv, 1, D),
        ]
    args.append(kv_pages)
    if quantized:
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, D), jnp.float32),
        ],
        input_output_aliases={kv_arg: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="radixmesh_decode_fused",
    )(*args)
    kv_out, out = res
    attn = out.reshape(B, Hq, D).astype(q.dtype)
    if quantized:
        scales_out = _scatter_new_scales(
            kv_scales, layer, slots, lengths, page, k_sc, v_sc
        )
        return attn, kv_out, scales_out
    return attn, kv_out


def _chunk_kernel(
    # scalar prefetch
    prior_ref,  # SMEM [B] pool-context tokens per row (page-part bound)
    kvlen_ref,  # SMEM [B] valid context incl. this chunk
    page_table_ref,  # SMEM [B * padded] flattened
    contig_ref,  # SMEM [B * nblocks]
    layer_ref,  # SMEM [1]
    *refs,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    chunk: int,  # C — dense keys per program
    c_block: int,  # Cblk — queries per program
    group: int,  # G — q heads per kv head
    quantized: bool,
    windowed: bool = False,
):
    """Chunk-prefill attention program for one ``(b, h, c-block)``: stream
    the row's PRIOR context from pool pages through the online softmax
    (double-buffered DMA within the program), then fold the current chunk
    in as one dense causal block from VMEM. Query positions are canonical
    (``prior + chunk offset`` — see the wrapper's contract), so masks
    derive from scalars: prior bound for the page part, intra-chunk
    causality + ``kvlen`` bound for the dense part. ``windowed``: one
    more scalar-prefetch value, the keys a query of this layer sees (its
    own among them; 0 = all). Keys further back are masked, and the page
    loop starts at the block that holds the first key the program's
    FIRST query still sees — earlier pages are not fetched."""
    window = None
    if windowed:
        window_ref, *refs = refs
        window = window_ref[0]
    if quantized:
        (q_ref, kc_ref, vc_ref, kv_hbm, prep_ref, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, sems) = refs
    else:
        (q_ref, kc_ref, vc_ref, kv_hbm, o_ref,
         m_scr, l_scr, acc_scr, k_buf, v_buf, sems) = refs
        prep_ref = None
    b, h, cb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer = layer_ref[0]
    prior = prior_ref[b]
    kvlen = kvlen_ref[b]
    bk = page * pages_per_block
    nblocks = pages_per_seq // pages_per_block
    q_rows = c_block * group

    def block_copies(i, slot):
        off = b * pages_per_seq + i * pages_per_block
        contig = contig_ref[b * nblocks + i]
        return [
            _BlockCopy(kv_hbm, 0, layer, h, k_buf.at[slot], sems.at[slot, 0],
                       page_table_ref, off, pages_per_block, contig),
            _BlockCopy(kv_hbm, 1, layer, h, v_buf.at[slot], sems.at[slot, 1],
                       page_table_ref, off, pages_per_block, contig),
        ]

    q = q_ref[...].astype(jnp.float32).reshape(q_rows, -1)  # pre-scaled
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    n_blocks = pl.cdiv(prior, bk)
    first = 0
    if windowed:
        # Query row r of this program sits at prior + cb*Cblk + r // G.
        q_pos = (
            prior + cb * c_block
            + jax.lax.broadcasted_iota(jnp.int32, (q_rows, 1), 0) // group
        )
        first = jnp.where(
            window > 0,
            jnp.maximum(prior + cb * c_block - window + 1, 0) // bk,
            0,
        )

        def in_window(kv_pos):
            return (window <= 0) | (q_pos - kv_pos < window)

    @pl.when(n_blocks > first)
    def _cold_start():
        for c in block_copies(first, jax.lax.rem(first, 2) if windowed else 0):
            c.start()

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch_next():
            for c in block_copies(i + 1, 1 - slot):
                c.start()

        cs = block_copies(i, slot)
        cs[0].wait()
        k = k_buf[slot].astype(jnp.float32).reshape(bk, -1)
        s = jax.lax.dot_general(  # [q_rows, bk]
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            s = s * prep_ref[0, pl.ds(i, 1), :]
        kv_pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # Canonical query positions sit at/after ``prior``, so the page
        # part needs only the prior bound (strictly causal already).
        seen = kv_pos < prior
        if windowed:
            seen = seen & in_window(kv_pos)
        s = jnp.where(seen, s, _MASK)

        m_prev = m_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new

        cs[1].wait()
        if quantized:
            p = p * prep_ref[1, pl.ds(i, 1), :]
        v = v_buf[slot].astype(jnp.float32).reshape(bk, -1)
        # Exact zeros at masked positions (see _run_block_loop): pad
        # fetches may stage unreferenced pages; NaN/Inf there (or in pad
        # scale rows) must not ride 0·NaN into the accumulator.
        p = jnp.where(seen, p, 0.0)
        v = jnp.where(
            i * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            < prior,
            v,
            0.0,
        )
        pv = jax.lax.dot_general(
            p, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        return ()

    jax.lax.fori_loop(first, n_blocks, body, ())

    # Dense block: the chunk itself, causal in chunk coordinates. Key
    # c_k's absolute position is prior + c_k; query row r (= c*G + g of
    # this c-block) sits at prior + cb*Cblk + c.
    kc = kc_ref[...].astype(jnp.float32)  # [C, D]
    vc = vc_ref[...].astype(jnp.float32)
    s2 = jax.lax.dot_general(  # [q_rows, C]
        q, kc,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    c_q = (
        cb * c_block
        + jax.lax.broadcasted_iota(jnp.int32, s2.shape, 0) // group
    )
    c_k = jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1)
    ok = (c_k <= c_q) & (prior + c_k < kvlen)
    if windowed:
        ok = ok & ((window <= 0) | (c_q - c_k < window))
    s2 = jnp.where(ok, s2, _MASK)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
    p2 = jnp.exp(s2 - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)
    l_fin = l_scr[...] * corr + jnp.sum(p2, axis=-1, keepdims=True)
    acc_fin = acc_scr[...] * corr + jax.lax.dot_general(
        p2, vc,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out = jnp.where(l_fin > 0, acc_fin / jnp.maximum(l_fin, 1e-30), 0.0)
    o_ref[...] = out.reshape(c_block, group, -1).astype(o_ref.dtype)


def _chunk_block(chunk: int, group: int, max_rows: int = 1024) -> int:
    """Largest power-of-two divisor of ``chunk`` whose query-row count
    (``Cblk * G``) stays within the VMEM scratch budget."""
    cblk = 1
    while (
        chunk % (cblk * 2) == 0 and cblk * 2 * group <= max_rows
    ):
        cblk *= 2
    return cblk


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "q_block", "interpret")
)
def paged_chunk_attention_kernel(
    q: jnp.ndarray,  # [B, C, Hq, D] — pre-rope'd chunk queries
    k_cur: jnp.ndarray,  # [B, C, Hkv, D] this chunk's K (post-rope, dequantized)
    v_cur: jnp.ndarray,  # [B, C, Hkv, D]
    kv_pages: jnp.ndarray,  # [2, L, Hkv, P, page, D] full pool pages view
    page_table: jnp.ndarray,  # [B, max_pages] int32
    prior_lengths: jnp.ndarray,  # [B] pool tokens BEFORE this chunk
    kv_lengths: jnp.ndarray,  # [B] valid context incl. this chunk
    layer: jnp.ndarray | int,
    pages_per_block: int | None = None,
    q_block: int | None = None,
    interpret: bool = False,
    kv_scales: jnp.ndarray | None = None,  # [2, L, Hkv, P, page] int8 pool
    window: jnp.ndarray | None = None,  # () keys a query sees; 0 = all
) -> jnp.ndarray:
    """Pallas chunk-prefill attention: SURVEY §7 hard part (a) for the
    PREFILL side (VERDICT round-3 next-step #3 "pool-page chunk
    attention"). The jnp oracle is ``ops/attention.py::attend_chunk_hybrid``
    — same online-softmax merge of prior pool pages + the dense causal
    chunk, but pages stream HBM→VMEM per (sequence, kv-head, query-block)
    program instead of gathering [B, Hkv, bk, D] copies through XLA.

    CONTRACT: query positions are canonical —
    ``q_positions == prior_lengths[:, None] + arange(C)`` (the only form
    the serving stack produces; both chunked prefill and the speculative
    verify chunk satisfy it) — so causal masks derive from
    ``prior_lengths``/``kv_lengths`` and the chunk offset alone, and the
    chunk's K/V arrive dense from the layer activations (``k_cur``
    already dequantized when the pool is int8, preserving the
    see-what-you-store invariant).

    Returns ``[B, C, Hq, D]``.
    """
    B, C, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must divide by Hkv={Hkv}")
    G = Hq // Hkv
    quantized = kv_scales is not None
    page_table, ppb, padded = _block_geometry(page_table, page, pages_per_block)
    contig = _contig_flags(page_table, prior_lengths, page, ppb, P)
    cblk = q_block if q_block is not None else _chunk_block(C, G)
    if C % cblk:
        raise ValueError(f"q_block={cblk} must divide chunk C={C}")

    scale = 1.0 / (D ** 0.5)
    # [B, Hkv, C, G, D]: kv-head-major so each program's q block is one
    # contiguous [Cblk, G, D] tile.
    q5 = (q.astype(jnp.float32) * scale).reshape(B, C, Hkv, G, D).transpose(
        0, 2, 1, 3, 4
    )
    kc = k_cur.transpose(0, 2, 1, 3)  # [B, Hkv, C, D]
    vc = v_cur.transpose(0, 2, 1, 3)
    q_spec = pl.BlockSpec(
        (None, None, cblk, G, D), lambda b, h, cb, *_: (b, h, cb, 0, 0)
    )
    kc_spec = pl.BlockSpec(
        (None, None, C, D), lambda b, h, cb, *_: (b, h, 0, 0)
    )

    kernel = functools.partial(
        _chunk_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        chunk=C,
        c_block=cblk,
        group=G,
        quantized=quantized,
        windowed=window is not None,
    )
    in_specs = [q_spec, kc_spec, kc_spec, pl.BlockSpec(memory_space=pl.ANY)]
    if quantized:
        in_specs.append(
            pl.BlockSpec(
                (2, None, None, padded // ppb, ppb * page),
                lambda b, h, cb, *_: (0, b, h, 0, 0),
            )
        )
    scratch = [
        pltpu.VMEM((cblk * G, D), jnp.float32),
        pltpu.VMEM((cblk * G, D), jnp.float32),
        pltpu.VMEM((cblk * G, D), jnp.float32),
        pltpu.VMEM((2, ppb, page, D), kv_pages.dtype),
        pltpu.VMEM((2, ppb, page, D), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + (window is not None),
        grid=(B, Hkv, C // cblk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    args = [
        jnp.asarray(prior_lengths, dtype=jnp.int32),
        jnp.asarray(kv_lengths, dtype=jnp.int32),
        jnp.asarray(page_table, dtype=jnp.int32).reshape(-1),
        contig,
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        *([] if window is None
          else [jnp.asarray(window, dtype=jnp.int32).reshape(1)]),
        q5,
        kc,
        vc,
        kv_pages,
    ]
    if quantized:
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C, G, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="radixmesh_chunk_attn",
    )(*args)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, C, Hq, D).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_kernel(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,  # [Hkv, P, page, D] head-major (PagedKVPool.pages_for_layer)
    v_pages: jnp.ndarray,  # [Hkv, P, page, D]
    page_table: jnp.ndarray,  # [B, max_pages] int32
    lengths: jnp.ndarray,  # [B] int32
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-layer convenience wrapper (tests, layer-at-a-time callers)."""
    kv_pages = jnp.stack([k_pages, v_pages])[:, None]  # [2, 1, Hkv, P, page, D]
    return paged_attention_pool_kernel(
        q, kv_pages, page_table, lengths, 0, interpret=interpret
    )

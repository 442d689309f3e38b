"""Pallas TPU decode kernels: attention over non-contiguous radix-cache pages.

This is the op SURVEY §7 calls the hard part (a): the radix cache hands the
scheduler a *page table* (page ids into the paged KV pool, arbitrary order,
shared across requests that share a prefix), and decode attention must
gather those pages without materializing a dense [B, max_ctx, H, D] copy in
HBM — the copy is exactly the bandwidth decode can't afford.

On-chip, DMA *descriptor issue rate* — not bytes — bounded the first
kernels (B × Hkv × blocks × ppb × 2 copies of 4 KB each per launch), and
then the block loop's fixed cost per block did (PERF.md §5, PR 32: ~0.35 µs
a block whatever it holds, against 0.31 µs of transfer for 128 tokens).
What the kernels do about both:

1. **Run-coalesced block DMAs.** Where a compute block's pages are
   *consecutive* ids, the block moves as ONE descriptor
   (``src.at[pl.ds(first, ppb)]``), otherwise page by page. The per-head
   decode kernels read a per-(row, block) flag precomputed by the wrapper
   (``_contig_flags``); the heads-batched kernels and the chunk kernel
   decide from a running count of the table's breaks (``_mh_tables``),
   which answers for a block that starts at ANY page. A pool under churn hands out pages in
   no order (the allocator's free list is LIFO), so served tables mostly
   take the per-page plan: it has to be cheap too.
2. **Heads-batched programs by default.** ``fuse_heads`` (grid ``(B,)``,
   all kv heads per DMA and per MXU contraction) is the default whenever
   the double-buffered block fits VMEM (``_auto_fuse_heads``); the
   per-head grid ``(B, Hkv)`` remains for huge-Hkv configs and as an
   explicit override. A heads-batched block is 512 tokens
   (``_mh_pages_per_block``), K and V in one descriptor
   (``_MhBlockCopy``): one descriptor and one wait per block when the
   pages coalesce, one descriptor per PAGE (16 KB at Hkv 4 / D 128 bf16)
   and still one wait when they do not. Only the pages a row still holds
   are moved (its last block), and a window row's first block starts at
   the page of its first visible token, so a large block reads no more
   than a small one would.
3. **The MXU sees the pool's dtype.** The heads-batched kernels contract a
   bf16 block as fetched — ``q`` bf16 and unscaled, ``1/sqrt(D)`` on the
   float32 scores, ``p`` bf16, float32 accumulation — and mask only a
   row's edge blocks (``_mh_contract``; the chunk kernel's ``_chunk_fold``
   takes the same operands and masks every block). (The MXU made one bf16 pass over
   float32 operands anyway — measured, PERF.md §6 PR 32 — so this loses
   nothing and keeps ``q`` exact.)
4. **Prepared scales for int8 pools.** The *wrapper* gathers the page
   table's scales in one XLA gather (``_prep_scales`` →
   ``[2, B, Hkv, nblocks, bk]``) and the kernel reads aligned ``(1, bk)``
   rows from a pipelined VMEM input — zero in-kernel scale DMAs, and the
   page-size-divides-128 constraint disappears. Int8 blocks are upcast
   to float32 in VMEM (scores and probabilities are scaled per key).

Design (shared by both grids):

- The KV pool pages stay in HBM (``memory_space=ANY``); page table,
  lengths, coalescing flags, and layer index ride scalar prefetch (SMEM)
  so DMA source addresses are computable before the body runs.
- Each program loops over *compute blocks* of ``pages_per_block`` pages,
  bounded by the sequence's true length — short sequences cost short
  loops, not ``max_pages`` iterations. ``pages_per_block=None`` means the
  kernel chooses: 512 tokens heads-batched, ~256 per head.
- Block DMAs are **chain-prefetched across grid steps**: while block ``i``
  is being contracted on the MXU, the copy for the *next* block — which
  may belong to the next program — is already in flight in the other half
  of a double buffer. DMA latency is exposed once per kernel launch. The
  fused heads-batched kernel fetches the NEXT program's page for the row
  write the same way (``_MhRowWrite``).
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
  through the online softmax — 512-token blocks of all kv heads, chained
  across the (row, query block) programs as the decode kernels chain
  theirs — the current chunk folded in as one dense causal block; a query
  block that holds no token of its row does nothing.
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
    "chunk_admits",
    "chunk_query_tokens",
    "decode_block_tokens",
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


class _MhBlockCopy:
    """All-heads analog of ``_BlockCopy``: K and V of every kv head in one
    descriptor, and only the ``n_valid`` leading pages of the block — the
    pages a row's last block still holds — are moved at all.

    A coalesced block (its pages one ascending run of ids: ``breaks_ref``,
    see ``_mh_tables``) moves as one ``(2, Hkv, 2^k, page, D)`` slab per
    set bit of ``n_valid``: a full power-of-two block is ONE descriptor
    of ``2·Hkv`` contiguous segments, a row's last block at most ``log2``
    of them. A fragmented block moves as ``n_valid`` copies of
    ``(2, Hkv, page, D)``. Either plan is awaited
    by the same waits: a DMA semaphore counts bytes, so one wait on a
    ``2^k``-page view of the staging buffer per set bit of ``n_valid``
    absorbs whatever descriptors brought those bytes — one wait for a full
    block. ``start`` and ``wait`` read ``n_valid`` from the same SMEM
    scalars, so issued and awaited bytes always match."""

    def __init__(self, kv_hbm, layer, buf, sem, page_table_ref, breaks_ref,
                 flat_offset, n_pages, n_valid, heads=None):
        # [2, Hkv, P, page, D], or the ``heads`` of it that ``buf`` holds
        self._src = (kv_hbm.at[:, layer] if heads is None
                     else kv_hbm.at[:, layer, heads])
        self._buf, self._sem = buf, sem
        self._table, self._offset = page_table_ref, flat_offset
        self._breaks = breaks_ref
        self._n, self._valid = n_pages, n_valid

    def _chunks(self, fn):
        """``fn(offset, size)`` under ``pl.when`` for each power-of-two
        chunk of the binary decomposition of ``n_valid``, largest first."""
        size = 1 << (self._n.bit_length() - 1)
        while size:
            off = self._valid & ~(2 * size - 1)  # the bits above this one
            pl.when((self._valid & size) != 0)(
                functools.partial(fn, off, size)
            )
            size >>= 1

    def start(self):
        first = self._table[self._offset]
        # The pages to move are one ascending run of ids when no break
        # (``_mh_tables``) lies between the first and the last of them.
        last = self._offset + jnp.maximum(self._valid, 1) - 1
        contig = self._breaks[last] == self._breaks[self._offset]

        def run(off, size):
            pltpu.make_async_copy(
                self._src.at[:, :, pl.ds(first + off, size)],
                self._buf.at[:, :, pl.ds(off, size)],
                self._sem,
            ).start()

        pl.when(contig)(lambda: self._chunks(run))

        @pl.when(jnp.logical_not(contig))
        def _():
            def page_copy(i, _):
                pltpu.make_async_copy(
                    self._src.at[:, :, self._table[self._offset + i]],
                    self._buf.at[:, :, i],
                    self._sem,
                ).start()
                return ()

            jax.lax.fori_loop(0, self._valid, page_copy, ())

    def wait(self):
        def absorb(off, size):
            view = self._buf.at[:, :, pl.ds(0, size)]
            pltpu.make_async_copy(view, view, self._sem).wait()

        self._chunks(absorb)


def _block_chain(g, n_programs, row_of, first_of, end_of, bk, copy,
                 buffer_index_ref, init_flag_ref):
    """The blocks of every program of a grid through ONE double buffer.
    Program ``g`` reads blocks ``first_of(g), first_of(g) + 1, ...`` of
    page-table row ``row_of(g)``, as far as ``end_of(g)`` tokens reach;
    ``copy(row, block, slot)`` is a block's transfer. Returns ``fetch(i)``:
    the buffer slot that holds block ``i`` of this program, with the copy
    of its grid-order successor in flight — the program's next block, or
    the first block of the next program that has any. The slot in use and
    whether anything was started yet live in SMEM and persist across
    programs."""

    def successor(i):
        def next_program():
            def empty(x):
                at = jax.lax.clamp(0, x, n_programs - 1)
                return (x < n_programs) & (first_of(at) * bk >= end_of(at))

            ng = jax.lax.while_loop(empty, lambda x: x + 1, g + 1)
            return ng, first_of(jax.lax.clamp(0, ng, n_programs - 1))

        return jax.lax.cond(i * bk < end_of(g), lambda: (g, i), next_program)

    def fetch(i):
        first = init_flag_ref[0]
        init_flag_ref[0] = 0
        s = buffer_index_ref[0]
        ng, ni = successor(i + 1)

        @pl.when(first != 0)
        def _cold_start():
            copy(row_of(g), i, s).start()

        @pl.when(ng < n_programs)
        def _prefetch_next():
            ns = 1 - s
            copy(row_of(jax.lax.clamp(0, ng, n_programs - 1)), ni, ns).start()
            buffer_index_ref[0] = ns

        copy(row_of(g), i, s).wait()
        return s

    return fetch


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


def _mh_contract(
    *, i, first_pos, kv_blk, q, scale, masked, visible, prep_ref,
    m_scr, l_scr, acc_scr,
):
    """One staged block ``kv_blk`` ``(2, Hkv, ppb, page, D)`` through the
    online softmax. The MXU sees ``q`` and K/V in ``q``'s dtype — the
    pool's own for a float pool, so a bf16 block is contracted as it was
    fetched, with float32 accumulation (a product of two bf16 values is
    exact in float32: q·k loses nothing against float32 operands), and
    ``1/sqrt(D)`` (``scale``) is applied to the float32 scores. Scores,
    running max and sum, ``exp``, correction and accumulator are float32;
    ``p`` enters p·v in the operand dtype. ``masked`` is static: an
    interior block holds only visible positions and runs with no select;
    an edge block (a row's last, a window row's first) zeroes BOTH
    factors of p·v at every invisible position (see ``_contig_flags``)."""
    _, Hkv, ppb, page, D = kv_blk.shape
    bk = ppb * page
    quantized = prep_ref is not None
    # (Hkv, ppb, page, D) -> (Hkv, bk, D): middle collapse, minor dim
    # untouched — a supported relayout-free reshape.
    k = kv_blk[0].reshape(Hkv, bk, D).astype(q.dtype)
    s = jax.lax.dot_general(  # (Hkv, G, bk), heads-batched MXU
        q, k,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        s = s * scale
    if quantized:
        s = s * prep_ref[0, :, pl.ds(i, 1), :]  # (Hkv, 1, bk) k-scales
    if masked:
        seen = visible(
            first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        )
        s = jnp.where(seen, s, _MASK)

    m_prev = m_scr[...]
    m_blk = jnp.max(s, axis=-1, keepdims=True)  # (Hkv, G, 1)
    m_new = jnp.maximum(m_prev, m_blk)  # lane-replicated (Hkv, G, D)
    p = jnp.exp(s - m_new[:, :, :1])  # (Hkv, G, bk)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new

    if quantized:
        p = p * prep_ref[1, :, pl.ds(i, 1), :]  # (Hkv, 1, bk) v-scales
    v = kv_blk[1].reshape(Hkv, bk, D)
    if masked:
        # Exact zeros at masked positions (see _run_block_loop): pages
        # past a row's end were not moved, so the buffer holds there
        # whatever it held; NaN/Inf in it (or in pad scale rows) must not
        # ride 0·NaN into the accumulator.
        p = jnp.where(seen, p, 0.0)
        v = jnp.where(
            visible(first_pos + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)),
            v.astype(jnp.float32),
            0.0,
        )
    pv = jax.lax.dot_general(  # (Hkv, G, D)
        p.astype(q.dtype), v.astype(q.dtype),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * corr + pv


def _mh_block_loop(
    *,
    b,
    layer,
    tail: int,  # trailing tokens of lengths_ref that are NOT in pool pages
    q,  # (Hkv, G, D) MXU operand (see _mh_operands)
    scale,  # applied to the f32 scores; None when q arrives pre-scaled
    lengths_ref,
    page_table_ref,  # SMEM [B * pages_per_seq + ppb] (_mh_tables)
    breaks_ref,  # SMEM, same shape: breaks of consecutiveness (_mh_tables)
    buffer_index_ref,
    init_flag_ref,
    kv_hbm,
    kv_buf,  # VMEM (2, 2, Hkv, ppb, page, D): double buffer of K+V blocks
    sems,  # DMA (2,): one per buffer half
    m_scr,
    l_scr,
    acc_scr,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    prep_ref=None,  # VMEM (2, Hkv, nblocks, bk) f32 prepared scales
    starts_ref=None,  # SMEM [B] first visible token per row (window layers)
):
    """The heads-batched analog of ``_run_block_loop``: one program per
    SEQUENCE, ``(Hkv, G, ·)`` batched MXU contractions (``_mh_contract``),
    chain-prefetched ``_MhBlockCopy`` DMAs. Shared by the read-only and
    fused mh kernels (``tail`` 0 / 1: the fused kernel folds the current
    token in from VMEM).

    A row's blocks are counted from the PAGE that holds its first visible
    token: entry ``j`` of block ``i`` is position ``base + i·bk + j``,
    read through table column ``base // page + i·ppb + j // page``, with
    ``base`` 0 on a layer that attends everything. So a window row reads
    at most ``page - 1`` keys it then masks, whatever the block size, and
    only a row's LAST block (the length bound, and pages past it that were
    never fetched) and its FIRST (the window start) can hold an invisible
    position: those take the masked contraction, every block between them
    the plain one.

    DELIBERATE duplication of ``_run_block_loop``'s machinery (parity
    pinned by tests/test_ops.py): the per-head grid survives as the
    fallback for configs whose all-heads block would blow VMEM, and
    merging a head axis into it would couple both paths' shapes. (The
    GQA group axis rides implicitly in ``q``'s shape.)"""
    bk = page * pages_per_block

    def span(bb):
        """``(base, tokens)`` of row ``bb``: its first position read from
        pool pages (page-aligned) and how many follow up to its last."""
        bb = jax.lax.clamp(0, bb, batch_size - 1)
        hbm = lengths_ref[bb] - tail
        if starts_ref is None:
            return 0, hbm
        base = starts_ref[bb] // page * page
        return base, hbm - base

    def block_copy(bb, ii, slot):
        first, tokens = span(bb)
        return _MhBlockCopy(
            kv_hbm, layer, kv_buf.at[slot], sems.at[slot], page_table_ref,
            breaks_ref,
            bb * pages_per_seq + first // page + ii * pages_per_block,
            pages_per_block,
            jnp.clip(pl.cdiv(tokens, page) - ii * pages_per_block,
                     0, pages_per_block),
        )

    base, tokens = span(b)
    last = pl.cdiv(tokens, bk) - 1

    def next_indices(i):
        """Grid-order successor of block ``i`` of program ``b``, skipping
        sequences with nothing to read from pool pages."""

        def advance_b():
            nb = jax.lax.fori_loop(
                b + 1,
                batch_size,
                lambda _, x: jnp.where(
                    jnp.logical_and(x < batch_size, span(x)[1] <= 0),
                    x + 1,
                    x,
                ),
                b + 1,
            )
            return (nb, 0)

        return jax.lax.cond(i <= last, lambda: (b, i), advance_b)

    def visible(pos):
        seen = pos < base + tokens
        if starts_ref is not None:
            seen = seen & (pos >= starts_ref[b])
        return seen

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
            block_copy(b, i, slot).start()

        @pl.when(nb < batch_size)
        def _prefetch_next():
            nslot = jnp.where(slot == 0, 1, 0)
            block_copy(nb, ni, nslot).start()
            buffer_index_ref[0] = nslot

        block_copy(b, i, slot).wait()
        contract = functools.partial(
            _mh_contract, i=i, first_pos=base + i * bk, kv_blk=kv_buf.at[slot],
            q=q, scale=scale, visible=visible, prep_ref=prep_ref,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
        )
        edge = i == last
        if starts_ref is not None:
            edge = edge | (i == 0)
        pl.when(edge)(lambda: contract(masked=True))
        pl.when(jnp.logical_not(edge))(lambda: contract(masked=False))
        return ()

    jax.lax.fori_loop(0, last + 1, body, ())


def _mh_tables(page_table, page: int, pages_per_block: int | None):
    """``(table [B, padded], ppb, padded, flat table, flat breaks)`` of a
    heads-batched call. ``breaks[b, j]`` counts the columns ``1..j`` of row
    ``b`` whose page id does not follow its left neighbour's, so columns
    ``[s, e]`` hold one ascending run of ids iff ``breaks[e] == breaks[s]``
    — for any ``s``: a window row's blocks start at the page of its first
    visible token, not on a block multiple. The flat arrays carry ``ppb``
    entries past the last row, where such a block's unused tail may
    point."""
    page_table, ppb, padded = _block_geometry(page_table, page, pages_per_block)
    pt = jnp.asarray(page_table, jnp.int32)
    step = (pt[:, 1:] != pt[:, :-1] + 1).astype(jnp.int32)
    breaks = jnp.pad(jnp.cumsum(step, axis=1), ((0, 0), (1, 0)))

    def flat(a):
        return jnp.pad(a.reshape(-1), (0, ppb))

    return pt, ppb, padded, flat(pt), flat(breaks)


def _operand_q(q, kv_dtype, quantized: bool, scale: float | None = None):
    """``(q [..., D], scale)`` as the MXU takes it against a block of the
    pool. A float pool at least as wide as ``q``: ``q`` in the pool's
    dtype, unscaled, and ``1/sqrt(D)`` (or the caller's ``scale``: the row
    is wider than the head) for the kernel to apply to its float32 scores
    — the MXU then contracts the pool's blocks as fetched. An int8 pool, or
    a ``q`` wider than the pool: float32 ``q`` scaled here (``scale`` None)
    against blocks upcast in VMEM, as these kernels always computed."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if _operand_dtype(q.dtype, kv_dtype, quantized) != kv_dtype:
        return q.astype(jnp.float32) * scale, None
    return q.astype(kv_dtype), scale


def _operand_dtype(q_dtype, kv_dtype, quantized: bool):
    """The dtype ``_operand_q`` gives ``q``, from the dtypes alone."""
    if quantized or jnp.promote_types(q_dtype, kv_dtype) != kv_dtype:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(kv_dtype)


def _mh_operands(q, kv_dtype, quantized: bool, group: int,
                 scale: float | None = None):
    """``(q [B, Hkv, G, D], scale)`` as the mh decode kernels take them
    (``_operand_q``)."""
    B, Hq, D = q.shape
    q, scale = _operand_q(q, kv_dtype, quantized, scale)
    q = q.reshape(B, Hq // group, group, D)
    # A group fills whole 8-row tiles (zero rows, dropped by _mh_unpad):
    # the registers hold 8 rows whatever G is.
    return jnp.pad(q, ((0, 0), (0, 0), (0, -group % 8), (0, 0))), scale


def _mh_unpad(out, q):
    """``[B, Hkv, Gp, D]`` float32 kernel output -> ``[B, Hq, D]`` in
    ``q``'s dtype."""
    B, Hq, D = q.shape
    G = Hq // out.shape[1]
    return out[:, :, :G].reshape(B, Hq, D).astype(q.dtype)


def _mh_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B]
    page_table_ref,  # SMEM [B * padded + ppb] flattened (_mh_tables)
    breaks_ref,  # SMEM, same shape (_mh_tables)
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1]
    init_flag_ref,  # SMEM [1]
    *refs,  # q_ref, kv_hbm[, prep], o_ref, scratch — unpacked by flag
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    scale: float | None,
    quantized: bool,
):
    """Heads-fused read-only pool attention: grid ``(B,)`` (see
    ``_mh_block_loop``; ``q_ref`` and ``scale`` from ``_mh_operands``)."""
    if quantized:
        (q_ref, kv_hbm, prep_ref, o_ref,
         m_scr, l_scr, acc_scr, kv_buf, sems) = refs
    else:
        q_ref, kv_hbm, o_ref, m_scr, l_scr, acc_scr, kv_buf, sems = refs
        prep_ref = None
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _program():
        _mh_block_loop(
            b=b, layer=layer, tail=0, q=q_ref[...], scale=scale,
            lengths_ref=lengths_ref, page_table_ref=page_table_ref,
            breaks_ref=breaks_ref,
            buffer_index_ref=buffer_index_ref, init_flag_ref=init_flag_ref,
            kv_hbm=kv_hbm, kv_buf=kv_buf, sems=sems,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
            page=page, pages_per_block=pages_per_block,
            pages_per_seq=pages_per_seq, batch_size=batch_size,
            prep_ref=prep_ref,
        )
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


class _MhRowWrite:
    """The current token's K/V row of every kv head into its pool page,
    through the ALIASED output (see ``_fused_kernel``): sublane tiling
    forbids a partial slice on the page axis, so the whole
    ``(2, Hkv, page, D)`` page is read, one row replaced, and written
    back (every other row byte-identical, so racing block reads are
    unaffected). The read of the NEXT program's page is started before
    this program's block loop and lands behind it — a program waits for a
    page that is already there — and the write-back lands behind the loop
    as well. Rows write distinct pages; an idle row (length 0) is neither
    read nor written."""

    def __init__(self, kv_out, layer, slots_ref, lengths_ref, row_scr, sems,
                 page: int, batch_size: int):
        self._kv, self._layer = kv_out, layer
        self._slots, self._lengths = slots_ref, lengths_ref
        self._scr, self._sems = row_scr, sems  # (2, 2, Hkv, page, D); DMA (3,)
        self._page, self._B = page, batch_size

    def _live(self, bb):
        return self._lengths[jax.lax.clamp(0, bb, self._B - 1)] > 0

    def _window(self, bb):
        slot = self._slots[jax.lax.clamp(0, bb, self._B - 1)]
        return self._kv.at[:, self._layer, :, slot // self._page]

    def _read(self, bb):
        half = jax.lax.rem(bb, 2)
        return pltpu.make_async_copy(
            self._window(bb), self._scr.at[half], self._sems.at[half]
        )

    def _write(self, b):
        return pltpu.make_async_copy(
            self._scr.at[jax.lax.rem(b, 2)], self._window(b), self._sems.at[2]
        )

    def start(self, b, k_new, v_new):
        """Program ``b``: fetch ahead, then replace row ``slot % page`` of
        this program's page by ``k_new`` / ``v_new`` ``(Hkv, 1, D)`` and
        start the write-back."""
        pl.when((b == 0) & self._live(0))(lambda: self._read(0).start())
        pl.when((b + 1 < self._B) & self._live(b + 1))(
            lambda: self._read(b + 1).start()
        )

        @pl.when(self._live(b))
        def _():
            self._read(b).wait()
            half = jax.lax.rem(b, 2)
            shape = self._scr.shape[2:]  # (Hkv, page, D)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = row == self._slots[b] % self._page
            for which, new in enumerate((k_new, v_new)):
                self._scr[half, which] = jnp.where(
                    mask, jnp.broadcast_to(new, shape), self._scr[half, which]
                )
            self._write(b).start()

    def finish(self, b):
        pl.when(self._live(b))(lambda: self._write(b).wait())


def _mh_fused_kernel(
    # scalar prefetch
    lengths_ref,  # SMEM [B] context length INCLUDING the current token
    page_table_ref,  # SMEM [B * padded + ppb] flattened (_mh_tables)
    breaks_ref,  # SMEM, same shape (_mh_tables)
    slots_ref,  # SMEM [B] pool slot receiving this token's K/V
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1]
    init_flag_ref,  # SMEM [1]
    *refs,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    scale: float | None,
    quantized: bool,
    windowed: bool = False,
):
    """Heads-fused decode step: the ``_fused_kernel`` contract (write the
    current token's K/V row through the aliased pool output, fold it in
    from VMEM) at grid ``(B,)`` — the page-row RMW also moves all heads
    and both of K and V per DMA (``_MhRowWrite``: one read + one write per
    SEQUENCE instead of two of each per (b, h)).
    Quantized pools receive the row already quantized (``k_new``/``v_new``
    int8, written verbatim) PLUS its dequantized twin (``k_deq``/``v_deq``
    = int8 · scale, computed by the wrapper) for the VMEM fold-in — so
    attention sees bit-exactly what any later pool read will see, with
    zero in-kernel scale handling. The scale POOL is updated by the
    wrapper with one XLA scatter. ``windowed``: one more scalar-prefetch
    array, the first visible token of each row (``_window_starts``)."""
    starts_ref = None
    if windowed:
        starts_ref, *refs = refs
    if quantized:
        (q_ref, k_new_ref, v_new_ref, k_deq_ref, v_deq_ref, kv_hbm, prep_ref,
         kv_out, o_ref,
         m_scr, l_scr, acc_scr, kv_buf, row_scr, sems, w_sems) = refs
    else:
        (q_ref, k_new_ref, v_new_ref, kv_hbm,
         kv_out, o_ref,
         m_scr, l_scr, acc_scr, kv_buf, row_scr, sems, w_sems) = refs
        k_deq_ref, v_deq_ref, prep_ref = k_new_ref, v_new_ref, None
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]

    k_cur = k_deq_ref[...].astype(jnp.float32)  # (Hkv, 1, D)
    v_cur = v_deq_ref[...].astype(jnp.float32)

    o_ref[...] = jnp.zeros_like(o_ref)
    row = _MhRowWrite(
        kv_out, layer, slots_ref, lengths_ref, row_scr, w_sems, page, batch_size
    )
    row.start(b, k_new_ref[...], v_new_ref[...])

    @pl.when(length > 0)
    def _program():
        q = q_ref[...]  # (Hkv, G, D)
        _mh_block_loop(
            b=b, layer=layer, tail=1, q=q, scale=scale,
            lengths_ref=lengths_ref, page_table_ref=page_table_ref,
            breaks_ref=breaks_ref,
            buffer_index_ref=buffer_index_ref, init_flag_ref=init_flag_ref,
            kv_hbm=kv_hbm, kv_buf=kv_buf, sems=sems,
            m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr,
            page=page, pages_per_block=pages_per_block,
            pages_per_seq=pages_per_seq, batch_size=batch_size,
            prep_ref=prep_ref, starts_ref=starts_ref,
        )
        # The current token's score on the VPU: one key a head, float32
        # products of the values the MXU would have been given.
        s_cur = jnp.sum(  # (Hkv, G, 1)
            q.astype(jnp.float32) * k_cur, axis=-1, keepdims=True
        )
        if scale is not None:
            s_cur = s_cur * scale
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s_cur)
        p_cur = jnp.exp(s_cur - m_new[:, :, :1])  # (Hkv, G, 1)
        corr = jnp.exp(m_prev - m_new)
        l_fin = l_scr[...] * corr + p_cur
        acc_fin = acc_scr[...] * corr + p_cur * v_cur
        o_ref[...] = (acc_fin / l_fin).astype(o_ref.dtype)

    row.finish(b)


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


def _per_head_pages_per_block(page: int, max_pages: int) -> int:
    """The per-head decode grids' default: ~256 tokens of one head per
    compute block."""
    return max(1, min(max_pages, -(-256 // page)))


def _block_geometry(page_table, page: int, pages_per_block: int | None):
    """(padded page table, ppb, padded max_pages): pad max_pages up to a
    block multiple (the pad entries index page 0, whose reads are masked
    by the length bound like every other table pad)."""
    max_pages = page_table.shape[1]
    if pages_per_block is None:
        # (The heads-batched wrappers never get here with None: they pass
        # ``_mh_pages_per_block``'s 512 tokens.)
        pages_per_block = _per_head_pages_per_block(page, max_pages)
    ppb = min(pages_per_block, max_pages)
    blocks = -(-max_pages // ppb)
    padded = blocks * ppb
    if padded != max_pages:
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - max_pages)))
    return page_table, ppb, padded


def _mh_pages_per_block(
    Hkv: int, page: int, D: int, dtype, max_pages: int
) -> int:
    """Pages per compute block of the heads-batched kernels when the
    caller names none: 512 tokens, halved until the double-buffered K+V
    blocks of all heads fit 4 MiB of VMEM, and never wider than the page
    table. A block costs the loop ~0.35 us whatever it holds (the chain
    scores -> max -> exp -> p·v does not overlap with the next block's,
    and a DMA pays its latency once), against 0.31 us per 128 tokens of
    transfer at Hkv 4 / D 128 bf16: at 128 tokens the overhead was the
    larger half, at 512 the loop runs at the speed of its copies (PERF.md
    §5). A large block wastes nothing at a row's end or a window's start:
    ``_MhBlockCopy`` moves only the pages a row still holds, and
    ``_mh_tables`` starts a window row at page granularity."""
    ppb = max(1, 512 // page)
    block = 2 * 2 * Hkv * page * D * jnp.dtype(dtype).itemsize
    while ppb > 1 and ppb * block > 4 * 2**20:
        ppb //= 2
    return max(1, min(ppb, max_pages))


def decode_block_tokens(
    Hkv: int, page: int, D: int, dtype, max_pages: int, quantized: bool = False
) -> int:
    """Tokens of one compute block of ``paged_decode_fused_kernel`` when
    its caller names no ``pages_per_block`` — what ``/debug/state``
    ``attn.decode_block_tokens`` shows: the heads-batched kernel's choice
    where ``_auto_fuse_heads`` takes that grid, the per-head default
    (``_block_geometry``) elsewhere."""
    if _auto_fuse_heads(Hkv, page, D, dtype, max_pages, None, quantized):
        return page * _mh_pages_per_block(Hkv, page, D, dtype, max_pages)
    return page * _per_head_pages_per_block(page, max_pages)


def _auto_fuse_heads(
    Hkv: int, page: int, D: int, dtype, max_pages: int,
    pages_per_block: int | None, quantized: bool,
) -> bool:
    """Default ``fuse_heads`` policy: heads-batched programs whenever the
    VMEM the mh wrapper would actually allocate — the double-buffered
    all-heads K+V blocks at the CALLER's ``pages_per_block``
    (``_mh_pages_per_block`` when unset: at most 4 MiB), plus the int8
    prepared-scales input block — stays within an 8 MB budget (Hkv 4,
    page 16, D 128 bf16 at 512 tokens: 2 MiB). The per-head grid remains
    for huge-Hkv/page/block configs."""
    if pages_per_block is None:
        pages_per_block = _mh_pages_per_block(Hkv, page, D, dtype, max_pages)
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
    """Heads-batched pool attention wrapper (see ``_mh_kernel``); block
    size from ``_mh_pages_per_block`` where the caller names none."""
    B, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    G = Hq // Hkv
    quantized = kv_scales is not None
    if pages_per_block is None:
        pages_per_block = _mh_pages_per_block(
            Hkv, page, D, kv_pages.dtype, page_table.shape[1]
        )
    page_table, ppb, padded, flat_table, breaks = _mh_tables(
        page_table, page, pages_per_block
    )

    q4, scale = _mh_operands(q, kv_pages.dtype, quantized, G)
    Gp = q4.shape[2]
    q_spec = pl.BlockSpec((None, Hkv, Gp, D), lambda b, *_: (b, 0, 0, 0))

    kernel = functools.partial(
        _mh_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        scale=scale,
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
        pltpu.VMEM((Hkv, Gp, D), jnp.float32),
        pltpu.VMEM((Hkv, Gp, D), jnp.float32),
        pltpu.VMEM((Hkv, Gp, D), jnp.float32),
        pltpu.VMEM((2, 2, Hkv, ppb, page, D), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
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
        flat_table,
        breaks,
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
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Gp, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="radixmesh_pool_attn_mh",
    )(*args)
    return _mh_unpad(out, q)


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
    kv_scales=None, window=None, scale: float | None = None,
):
    """Heads-batched fused decode wrapper (see ``_mh_fused_kernel``)."""
    B, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    G = Hq // Hkv
    quantized = kv_scales is not None
    if pages_per_block is None:
        pages_per_block = _mh_pages_per_block(
            Hkv, page, D, kv_pages.dtype, page_table.shape[1]
        )
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    starts = None if window is None else _window_starts(lengths, window)
    page_table, ppb, padded, flat_table, breaks = _mh_tables(
        page_table, page, pages_per_block
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

    q4, scale = _mh_operands(q, kv_pages.dtype, quantized, G, scale)
    Gp = q4.shape[2]
    q_spec = pl.BlockSpec((None, Hkv, Gp, D), lambda b, *_: (b, 0, 0, 0))
    kv_new_spec = pl.BlockSpec((None, Hkv, 1, D), lambda b, *_: (b, 0, 0, 0))

    kernel = functools.partial(
        _mh_fused_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        scale=scale,
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
            pltpu.VMEM((Hkv, Gp, D), jnp.float32),
            pltpu.VMEM((Hkv, Gp, D), jnp.float32),
            pltpu.VMEM((Hkv, Gp, D), jnp.float32),
            pltpu.VMEM((2, 2, Hkv, ppb, page, D), kv_pages.dtype),
            pltpu.VMEM((2, 2, Hkv, page, D), kv_pages.dtype),  # row RMW
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((3,)),  # row RMW: two reads, a write
        ],
    )
    args = [
        lengths,
        flat_table,
        breaks,
        jnp.asarray(slots, dtype=jnp.int32),
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32),
        *([] if starts is None else [starts]),
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
        if starts is not None:
            # The kernel counts a window row's blocks from the page of its
            # first visible token: its scale rows follow the same columns.
            cols = (starts // page)[:, None] + jnp.arange(padded)[None, :]
            page_table = jnp.take_along_axis(
                page_table, jnp.minimum(cols, padded - 1), axis=1
            )
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    kv_out, out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Gp, D), jnp.float32),
        ],
        input_output_aliases={kv_arg: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="radixmesh_decode_fused_mh",
    )(*args)
    attn = _mh_unpad(out, q)
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
    jax.jit,
    static_argnames=("pages_per_block", "interpret", "fuse_heads", "scale"),
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
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
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
            kv_scales=kv_scales, window=window, scale=scale,
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

    if scale is None:
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


def _across_lanes(col, n: int):
    """A lane-replicated ``[R, W]`` value (the running max) as the
    ``[R, n]`` that a block's scores subtract: whole copies of it side by
    side where ``W`` divides ``n`` — registers reused, where a broadcast
    of its first column writes each of them (PERF.md §6 PR 43: an eighth
    of the kernel's time at 896 x 512 scores) — else that broadcast."""
    w = col.shape[-1]
    if n % w:
        return col[:, :1]
    return col if n == w else pltpu.repeat(col, n // w, 1)


def _chunk_fold(q, k, v, scale, seen, seen_v, k_scale, v_scale,
                m_ref, l_ref, acc_ref):
    """One block of keys through one kv head's online softmax: ``q``
    ``[R, D]`` against ``k`` / ``v`` ``[n, D]``. q·k as ``_mh_contract``
    has it — the MXU sees ``q``'s dtype (the pool's own for a float pool,
    so a bf16 block is contracted as it was fetched; a product of two bf16
    values is exact in float32) and ``scale`` is applied to the float32
    scores (what that buys is VMEM — half the q, chunk K and chunk V tiles,
    so twice the queries a program — not MXU time: float32 operands
    measured the same, PERF.md §6 PR 43); scores, running max and sum,
    ``exp``, correction and accumulator are float32. ``p`` enters p·v as the float32 it is (NOT
    rounded to the operand dtype as the decode kernels round it: a chunk's
    logits are held to the dense prefill's at 2e-2,
    ``tests/test_long_context.py``, and a bf16 ``p`` moved them to 5e-2;
    on the chip the MXU makes its one bf16 pass over it either way). ``seen``
    (broadcastable to ``[R, n]``) says which keys a query sees; BOTH factors
    of p·v are zeroed at every invisible position (see ``_contig_flags``),
    ``v`` by ``seen_v`` ``[n, 1]`` — None for the chunk's own dense K/V,
    which hold activations wherever they are masked. ``k_scale`` /
    ``v_scale``: an int8 block's ``(1, n)`` prepared scale rows."""
    s = jax.lax.dot_general(  # [R, n]
        q, k.astype(q.dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        s = s * scale
    if k_scale is not None:
        s = s * k_scale
    s = jnp.where(seen, s, _MASK)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - _across_lanes(m_new, s.shape[-1]))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    if v_scale is not None:
        p = p * v_scale
    p = jnp.where(seen, p, 0.0)
    if seen_v is not None:
        v = jnp.where(seen_v, v, jnp.zeros_like(v))
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(  # [R, D]
        p, v.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _chunk_kernel(
    # scalar prefetch
    prior_ref,  # SMEM [B] pool-context tokens per row (page-part bound)
    kvlen_ref,  # SMEM [B] valid context incl. this chunk
    page_table_ref,  # SMEM [B * padded + ppb] flattened (_mh_tables)
    breaks_ref,  # SMEM, same shape: breaks of consecutiveness (_mh_tables)
    layer_ref,  # SMEM [1]
    buffer_index_ref,  # SMEM [1] — double-buffer slot, persists across programs
    init_flag_ref,  # SMEM [1] — 1 until the first block of the launch started
    *refs,
    page: int,
    pages_per_block: int,
    pages_per_seq: int,
    batch_size: int,
    head_groups: int,  # Hkv // heads a program
    q_blocks: int,  # C // Cblk
    c_block: int,  # Cblk — queries per program
    group: int,  # G — q heads per kv head
    scale: float | None,  # of the f32 scores; None: q arrives pre-scaled
    quantized: bool,
    windowed: bool = False,
):
    """Chunk-prefill attention program for one ``(b, head group, c-block)``:
    stream the row's PRIOR context from pool pages through the online
    softmax — K and V of the program's kv heads in one ``_MhBlockCopy`` a
    block, chain-prefetched across programs (``_block_chain``: the next
    program's first block is in flight behind this program's dense block)
    — then fold the current chunk in as one dense causal block from VMEM.
    ``q_ref`` is ``[heads, Cblk * G, D]``, row ``r`` = query ``r // G`` of
    the block, q head ``r % G`` of the kv head (``_operand_q``).

    Query positions are canonical (``prior + chunk offset`` — see the
    wrapper's contract), so masks derive from scalars: prior bound for the
    page part, intra-chunk causality + ``kvlen`` bound for the dense part.
    Every prior block takes the same masked fold (a plain fold for the
    blocks between a program's edges measured the same, PERF.md §6 PR 43).
    A program whose first query is at or past the row's last new token —
    the padding of the chunk's shape, a row that has run out, a row on the
    scratch slot — writes zeros and moves nothing. ``windowed``: one more
    scalar-prefetch value, the keys a query of this layer sees (its own
    among them; 0 = all). Keys further back are masked, and the blocks
    are counted from the page (an int8 pool: the block, whose prepared
    scale rows are block-aligned) that holds the first key the program's
    FIRST query still sees — earlier pages are not fetched."""
    window = None
    if windowed:
        window_ref, *refs = refs
        window = window_ref[0]
    if quantized:
        (q_ref, kc_ref, vc_ref, kv_hbm, prep_ref, o_ref,
         m_scr, l_scr, acc_scr, kv_buf, sems) = refs
    else:
        (q_ref, kc_ref, vc_ref, kv_hbm, o_ref,
         m_scr, l_scr, acc_scr, kv_buf, sems) = refs
        prep_ref = None
    b, hg, cb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer = layer_ref[0]
    heads, rows = q_ref.shape[0], q_ref.shape[1]
    bk = page * pages_per_block
    align = bk if quantized else page
    n_programs = batch_size * head_groups * q_blocks

    def span(g):
        """``(row, head group, base, tokens)`` of program ``g`` in grid
        order: the first position it reads from pool pages and how many
        follow up to ``prior`` — none for a program with no query."""
        bb, hh, cc = (g // (head_groups * q_blocks), g // q_blocks % head_groups,
                      g % q_blocks)
        prior, first_q = prior_ref[bb], cc * c_block
        base = 0
        if windowed:
            base = jnp.where(
                window > 0,
                jnp.maximum(prior + first_q - window + 1, 0) // align * align,
                0,
            )
        tokens = jnp.where(first_q < kvlen_ref[bb] - prior, prior - base, 0)
        return bb, hh, base, jnp.maximum(tokens, 0)

    def copy(g, i, slot):
        bb, hh, base, tokens = span(g)
        return _MhBlockCopy(
            kv_hbm, layer, kv_buf.at[slot], sems.at[slot], page_table_ref,
            breaks_ref,
            bb * pages_per_seq + base // page + i * pages_per_block,
            pages_per_block,
            jnp.clip(pl.cdiv(tokens, page) - i * pages_per_block,
                     0, pages_per_block),
            heads=None if head_groups == 1 else pl.ds(hh * heads, heads),
        )

    def each_head(fn):
        if heads == 1:
            fn(0)
        else:
            jax.lax.fori_loop(0, heads, lambda h, _: fn(h), None)

    prior = prior_ref[b]
    new = kvlen_ref[b] - prior  # the row's tokens in this chunk

    @pl.when(cb * c_block >= new)
    def _no_query():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(cb * c_block < new)
    def _program():
        g = (b * head_groups + hg) * q_blocks + cb
        _, _, base, tokens = span(g)
        fetch = _block_chain(
            g, n_programs, lambda x: x, lambda x: 0,
            lambda x: pl.cdiv(span(x)[3], bk), 1, copy,
            buffer_index_ref, init_flag_ref,
        )
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # Query row r of this program sits at prior + c_q[r].
        c_q = cb * c_block + (
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        )

        def body(i, _):
            slot = fetch(i)
            first_pos = base + i * bk
            # Canonical query positions sit at/after ``prior``, so the page
            # part needs only the prior bound (strictly causal already) —
            # and the window, a query's own.
            kv_pos = first_pos + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk), 1)
            seen = kv_pos < prior
            if windowed:
                seen = seen & (
                    (window <= 0) | (prior + c_q - kv_pos < window))
            seen_v = first_pos + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) < prior

            def head(h):
                ks = vs = None
                if quantized:
                    at = pl.ds(first_pos // bk, 1)
                    ks, vs = prep_ref[0, h, at, :], prep_ref[1, h, at, :]
                # (ppb, page, D) -> (bk, D): a relayout-free collapse.
                _chunk_fold(
                    q_ref[h], kv_buf[slot, 0, h].reshape(bk, -1),
                    kv_buf[slot, 1, h].reshape(bk, -1), scale, seen, seen_v,
                    ks, vs, m_scr.at[h], l_scr.at[h], acc_scr.at[h],
                )

            each_head(head)
            return ()

        jax.lax.fori_loop(0, pl.cdiv(tokens, bk), body, ())

        # Dense block: the chunk itself, causal in chunk coordinates. Key
        # c_k's absolute position is prior + c_k.
        c_k = jax.lax.broadcasted_iota(jnp.int32, (1, kc_ref.shape[1]), 1)
        ok = (c_k <= c_q) & (c_k < new)
        if windowed:
            ok = ok & ((window <= 0) | (c_q - c_k < window))

        def dense(h):
            m_h, l_h, acc_h = m_scr.at[h], l_scr.at[h], acc_scr.at[h]
            _chunk_fold(q_ref[h], kc_ref[h], vc_ref[h], scale, ok, None,
                        None, None, m_h, l_h, acc_h)
            l_fin = l_h[...]
            o_ref[h] = jnp.where(
                l_fin > 0, acc_h[...] / jnp.maximum(l_fin, 1e-30), 0.0
            ).astype(o_ref.dtype)

        each_head(dense)


# What the chunk kernel may hold in VMEM, by ``_chunk_geometry``'s own
# estimate: inside the 16 MiB a kernel gets without asking. It does NOT ask
# for more (``vmem_limit_bytes``): XLA keeps operands of the call in VMEM
# too — a 64-row chunk's q, K and V, 63 MB — and a kernel that had asked
# for 64 (or 32) MiB hung the chip inside that program, where alone, its
# operands in HBM, it ran (PERF.md §6 PR 43).
_CHUNK_VMEM_BUDGET = 14 * 2**20


def _chunk_geometry(
    chunk: int, group: int, Hkv: int, page: int, D: int, dtype, q_dtype,
    max_pages: int, pages_per_block: int | None, q_block: int | None,
    quantized: bool,
) -> tuple[int, int, int]:
    """``(heads a program, pages a block, queries a program)`` of the
    chunk kernel, from the shapes and the pool's dtype alone. Heads and
    block as the decode kernels choose them: all kv heads where their
    double-buffered K+V block fits (``_auto_fuse_heads``), 512 tokens
    halved until it does (``_mh_pages_per_block``: a block's K and V of
    all heads in one copy and one wait keep pace with the MXU at 512, PERF.md
    §6 PR 43) — else one head a program and a block. Queries: the largest
    power-of-two divisor of ``chunk`` whose rows (``Cblk * G``) stay
    within 1 024 and whose blocks, scratch and scores stay within
    ``_CHUNK_VMEM_BUDGET`` — q and the chunk's K/V counted in the operand
    dtype (``_operand_dtype``: float32 against an int8 pool, which also
    holds a block's upcast and the table's prepared scale rows)."""
    heads = Hkv if _auto_fuse_heads(
        Hkv, page, D, dtype, max_pages, pages_per_block, quantized) else 1
    if pages_per_block is None:
        pages_per_block = _mh_pages_per_block(heads, page, D, dtype, max_pages)
    ppb = min(pages_per_block, max_pages)
    if q_block is not None:
        return heads, ppb, q_block
    kv, qb = jnp.dtype(dtype).itemsize, jnp.dtype(q_dtype).itemsize
    operand = _operand_dtype(q_dtype, dtype, quantized)
    op, bk = operand.itemsize, ppb * page
    # the K+V double buffer; the chunk's own K and V (double-buffered)
    fixed = heads * D * (2 * 2 * bk * kv + 2 * 2 * chunk * op)
    if operand != jnp.dtype(dtype):  # a head's K and V block upcast for a fold
        fixed += 2 * bk * D * op
    if quantized:  # the prepared scale rows of the whole table, twice
        fixed += 2 * 2 * heads * pl.cdiv(max_pages, ppb) * bk * 4

    def vmem(cblk):
        rows = cblk * group
        # q and out blocks (double-buffered), m / l / acc, s and p of a fold
        return fixed + rows * (
            heads * D * (2 * op + 2 * qb + 3 * 4) + 3 * 4 * max(bk, chunk))

    cblk = 1
    while (chunk % (cblk * 2) == 0 and cblk * 2 * group <= 1024
           and vmem(cblk * 2) <= _CHUNK_VMEM_BUDGET):
        cblk *= 2
    return heads, ppb, cblk


def chunk_query_tokens(
    chunk: int, group: int, Hkv: int, page: int, D: int, dtype, q_dtype,
    max_pages: int, quantized: bool = False,
) -> int:
    """Queries of one program of ``paged_chunk_attention_kernel`` at these
    shapes (``_chunk_geometry``): a launch of ``B`` rows of ``chunk``
    tokens is ``B * chunk // this`` query blocks a layer, and a block
    whose first query is past its row's last new token does no work —
    what ``/debug/state`` ``attn.chunk_query_blocks*`` count."""
    return _chunk_geometry(chunk, group, Hkv, page, D, dtype, q_dtype,
                           max_pages, None, None, quantized)[2]


# The fewest query rows (tokens x the heads of a group) of a program at
# which a wider chunk was still seen to pay: half a tile of the MXU's rows.
# On a v5e at 64 rows Mellum2's [1, 2048] took 74.0 ms against two
# [1, 1024] (256 rows) at 76.0, and Laguna's 4 096-token prompt 107.3 ms in
# [1, 1024] against 130.6 in [1, 512] (128 rows); the next halving leaves
# the chunk's own K and V no room beside one query (PERF.md §6 PR 54).
_MIN_QUERY_ROWS = 64


def chunk_admits(
    chunk: int, group: int, Hkv: int, page: int, D: int, dtype, q_dtype,
    max_pages: int, quantized: bool = False,
) -> bool:
    """Whether ``paged_chunk_attention_kernel`` takes a chunk this wide at
    pace, from the shapes alone: it holds the chunk's own K and V
    double-buffered (``heads x D x 4 x chunk`` elements), and what that
    leaves of ``_CHUNK_VMEM_BUDGET`` must still hold the blocks of a
    program of ``_MIN_QUERY_ROWS`` query rows (``_chunk_geometry``; the
    planner's widest chunk, ``engine/prefill_plan.py::chunk_width``)."""
    cblk = chunk_query_tokens(
        chunk, group, Hkv, page, D, dtype, q_dtype, max_pages, quantized)
    return cblk == chunk or cblk * group >= _MIN_QUERY_ROWS


@functools.partial(
    jax.jit,
    static_argnames=("pages_per_block", "q_block", "interpret", "scale"),
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
    scale: float | None = None,  # of the logits; None = 1 / sqrt(D)
) -> jnp.ndarray:
    """Pallas chunk-prefill attention: SURVEY §7 hard part (a) for the
    PREFILL side (VERDICT round-3 next-step #3 "pool-page chunk
    attention"). The jnp oracle is ``ops/attention.py::attend_chunk_hybrid``
    — same online-softmax merge of prior pool pages + the dense causal
    chunk, but pages stream HBM→VMEM per (sequence, query-block) program
    — all kv heads of a block in one copy; per (sequence, kv head,
    query-block) where that does not fit VMEM (``_chunk_geometry``) —
    instead of gathering [B, Hkv, bk, D] copies through XLA.

    CONTRACT: query positions are canonical —
    ``q_positions == prior_lengths[:, None] + arange(C)`` (the only form
    the serving stack produces; both chunked prefill and the speculative
    verify chunk satisfy it) — so causal masks derive from
    ``prior_lengths``/``kv_lengths`` and the chunk offset alone, and the
    chunk's K/V arrive dense from the layer activations (``k_cur``
    already dequantized when the pool is int8, preserving the
    see-what-you-store invariant). A query at or past
    ``kv_lengths - prior_lengths`` is padding: its output is unspecified
    (zeros where its whole query block is padding).

    Returns ``[B, C, Hq, D]``.
    """
    B, C, Hq, D = q.shape
    _, _, Hkv, P, page, _ = kv_pages.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must divide by Hkv={Hkv}")
    G = Hq // Hkv
    quantized = kv_scales is not None
    heads, ppb, cblk = _chunk_geometry(
        C, G, Hkv, page, D, kv_pages.dtype, q.dtype, page_table.shape[1],
        pages_per_block, q_block, quantized,
    )
    if C % cblk:
        raise ValueError(f"q_block={cblk} must divide chunk C={C}")
    page_table, ppb, padded, flat_table, flat_breaks = _mh_tables(
        page_table, page, ppb)
    qo, scale = _operand_q(q, kv_pages.dtype, quantized, scale)
    # [B, Hkv, C * G, D]: kv-head-major, so each program's q block is one
    # contiguous [Cblk * G, D] tile a head. The chunk's own K/V enter the
    # products beside the pool's blocks, in q's operand dtype.
    qo = qo.reshape(B, C, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, C * G, D
    )
    kc = k_cur.transpose(0, 2, 1, 3).astype(qo.dtype)  # [B, Hkv, C, D]
    vc = v_cur.transpose(0, 2, 1, 3).astype(qo.dtype)
    q_spec = pl.BlockSpec(
        (None, heads, cblk * G, D), lambda b, h, cb, *_: (b, h, cb, 0)
    )
    kc_spec = pl.BlockSpec(
        (None, heads, C, D), lambda b, h, cb, *_: (b, h, 0, 0)
    )

    kernel = functools.partial(
        _chunk_kernel,
        page=page,
        pages_per_block=ppb,
        pages_per_seq=padded,
        batch_size=B,
        head_groups=Hkv // heads,
        q_blocks=C // cblk,
        c_block=cblk,
        group=G,
        scale=scale,
        quantized=quantized,
        windowed=window is not None,
    )
    in_specs = [q_spec, kc_spec, kc_spec, pl.BlockSpec(memory_space=pl.ANY)]
    if quantized:
        in_specs.append(
            pl.BlockSpec(
                (2, None, heads, padded // ppb, ppb * page),
                lambda b, h, cb, *_: (0, b, h, 0, 0),
            )
        )
    scratch = [
        pltpu.VMEM((heads, cblk * G, D), jnp.float32),
        pltpu.VMEM((heads, cblk * G, D), jnp.float32),
        pltpu.VMEM((heads, cblk * G, D), jnp.float32),
        pltpu.VMEM((2, 2, heads, ppb, page, D), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 + (window is not None),
        grid=(B, Hkv // heads, C // cblk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    args = [
        jnp.asarray(prior_lengths, dtype=jnp.int32),
        jnp.asarray(kv_lengths, dtype=jnp.int32),
        flat_table,
        flat_breaks,
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32),
        *([] if window is None
          else [jnp.asarray(window, dtype=jnp.int32).reshape(1)]),
        qo,
        kc,
        vc,
        kv_pages,
    ]
    if quantized:
        args.append(_prep_scales(kv_scales, layer, page_table, page, ppb))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="radixmesh_chunk_attn",
    )(*args)
    return out.reshape(B, Hkv, C, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, C, Hq, D
    )


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

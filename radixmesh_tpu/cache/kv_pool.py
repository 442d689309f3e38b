"""Paged KV pool: preallocated ``jax.Array`` pages in TPU HBM.

TPU-native replacement for the reference's KV storage, where ``torch``
tensors merely hold KV *indices* and the actual pool
(``token_to_kv_pool_allocator``) is an external SGLang object the reference
calls ``free()`` on (``radix_cache.py:104-107,188-199``). Here the pool is a
first-class component:

- One preallocated, donated ``jax.Array`` of shape
  ``[planes, layers, heads, num_slots, width]`` lives in HBM for the
  model's whole life. The row — what one token leaves in one layer — is
  what the model says it is (``ModelConfig.kv_row``): two planes (K and V
  stacked, head-major) of ``kv_heads`` rows of ``head_dim``, or ONE plane
  of one latent row shared by every head (MLA), whose head is also the
  values. Nothing below knows which: the allocator and the radix tree hold
  slot indices, the scatters and gathers index the slot axis — no allocation
  inside the serving loop, static shapes for XLA, and per-layer pages view
  as ``[kv_heads, num_pages, page, head_dim]`` by pure reshape for the
  Pallas paged-attention kernel.
- A host-side :class:`SlotAllocator` free-list hands out token-granularity
  slot indices; the radix tree stores those indices as its node values and
  returns them to the allocator on eviction.
- Writes/gathers are jitted scatter/gather ops; under ``tp`` sharding the
  ``kv_heads`` axis is sharded over the mesh so each chip holds its head
  shard of every page (see ``parallel/sharding.py``).

``page_size`` groups slots into contiguous pages for the Pallas
paged-attention kernel (``ops/paged_attention.py``): slot ``s`` lives in
page ``s // page_size`` at offset ``s % page_size``. The allocator always
hands out whole pages so a request's slots are page-contiguous.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SlotAllocator", "PagedKVPool", "ALL_LAYERS", "write_runs", "write_runs_all",
    "gather_run",
]


class SlotAllocator:
    """Host-side free-list allocator of KV token slots, page-granular.

    Pages (groups of ``page_size`` consecutive slots) are the allocation
    unit; ``alloc(n)`` returns slot indices covering ``ceil(n/page_size)``
    pages. Freeing accepts any subset of slots and returns a page to the
    free list once every slot in it is free.
    """

    def __init__(self, num_slots: int, page_size: int = 1):
        if num_slots % page_size != 0:
            raise ValueError("num_slots must be a multiple of page_size")
        self.num_slots = num_slots
        self.page_size = page_size
        self.num_pages = num_slots // page_size
        # LIFO free list of pages with zero allocated slots.
        self._free_pages: list[int] = list(range(self.num_pages - 1, -1, -1))
        # Per-slot allocation state (True = handed out and not yet freed) and
        # per-page count of allocated slots. A page re-enters the free list
        # exactly when its allocated count returns to zero, so the unused
        # tail slots of a partially-filled page are reclaimed with it.
        self._slot_allocated = np.zeros(num_slots, dtype=bool)
        self._page_alloc_count = np.zeros(self.num_pages, dtype=np.int32)

    @property
    def free_slots(self) -> int:
        return len(self._free_pages) * self.page_size

    def is_allocated(self, slots: np.ndarray) -> np.ndarray:
        """Per-slot allocation state (bool array). Out-of-range ids report
        False rather than raising — callers use this to filter foreign or
        stale indices before acting on them."""
        slots = np.asarray(slots, dtype=np.int64)
        ok = (slots >= 0) & (slots < self.num_slots)
        out = np.zeros(len(slots), dtype=bool)
        out[ok] = self._slot_allocated[slots[ok]]
        return out

    def alloc(self, n_tokens: int) -> np.ndarray | None:
        """Allocate slots for ``n_tokens`` tokens (whole pages); ``None`` if
        the pool can't satisfy the request (caller should evict and retry,
        mirroring the reference's evict-then-insert flow)."""
        if n_tokens <= 0:
            return np.empty(0, dtype=np.int32)
        n_pages = -(-n_tokens // self.page_size)
        if n_pages > len(self._free_pages):
            return None
        pages = [self._free_pages.pop() for _ in range(n_pages)]
        slots = (
            np.asarray(pages, dtype=np.int32)[:, None] * self.page_size
            + np.arange(self.page_size, dtype=np.int32)[None, :]
        ).reshape(-1)[:n_tokens]
        self._slot_allocated[slots] = True
        pg, counts = np.unique(slots // self.page_size, return_counts=True)
        self._page_alloc_count[pg] = counts.astype(np.int32)
        return slots

    def reserve(self, slots: np.ndarray) -> None:
        """Claim *specific* slots (checkpoint restore: a snapshot's tree
        nodes reference the slot ids they held when saved). Raises if any
        requested slot is already allocated — restore targets a fresh pool."""
        slots = np.asarray(slots, dtype=np.int32)
        if slots.size == 0:
            return
        if np.any(self._slot_allocated[slots]):
            raise ValueError("cannot reserve: slot(s) already allocated")
        self._slot_allocated[slots] = True
        pages, counts = np.unique(slots // self.page_size, return_counts=True)
        newly_used = pages[self._page_alloc_count[pages] == 0]
        self._page_alloc_count[pages] += counts.astype(np.int32)
        used = set(int(p) for p in newly_used)
        self._free_pages = [p for p in self._free_pages if p not in used]

    def free(self, slots: np.ndarray) -> None:
        slots = np.asarray(slots, dtype=np.int32)
        if slots.size == 0:
            return
        if len(np.unique(slots)) != len(slots) or not np.all(
            self._slot_allocated[slots]
        ):
            # Checked before any mutation so the allocator stays consistent.
            raise ValueError("double free of KV slots")
        self._slot_allocated[slots] = False
        pages, counts = np.unique(slots // self.page_size, return_counts=True)
        self._page_alloc_count[pages] -= counts.astype(np.int32)
        for p in pages[self._page_alloc_count[pages] == 0]:
            self._free_pages.append(int(p))


def _pad_to_bucket(slots: np.ndarray, arrays: list, token_axes: list):
    """Pad ``slots`` (and each array along its token axis) up to a
    power-of-two bucket by repeating the last slot/value — an idempotent
    duplicate write — so jitted scatters compile O(log max_n) variants
    instead of one per distinct length (bucket floor 8)."""
    n = len(slots)
    bucket = max(8, 1 << (n - 1).bit_length())
    if bucket == n:
        return slots, arrays
    pad = bucket - n
    slots = np.concatenate([slots, np.repeat(slots[-1:], pad)])
    padded = []
    for arr, ax in zip(arrays, token_axes):
        last = jax.lax.slice_in_dim(arr, arr.shape[ax] - 1, arr.shape[ax], axis=ax)
        padded.append(
            jnp.concatenate([arr, jnp.repeat(last, pad, axis=ax)], axis=ax)
        )
    return slots, padded


# ---------------------------------------------------------------------------
# Rows enter and leave the pool by whole pages.
#
# XLA:TPU will not scatter into, or gather from, the slot axis of
# ``[planes, L, heads, slots, width]`` in the pool's layout: it copies the
# WHOLE pool into a layout with the slot axis outside the head axis, works
# there and copies the whole pool back (two pool-sized copies a write, one
# a gather, whatever the number of rows). Whole pages of the page view
# ``[planes, L, heads, pages, page, width]`` move with neither: a page is
# one tile of the layout the pool already has. So a writer hands over RUNS
# — ``slots [B, C]``, each row the consecutive rows of one request, as the
# allocator and the tree hand them out — and the run's pages are read,
# overlaid with the new rows at their offsets, and written back whole.
# One page after the other, in a loop: one XLA scatter of the same pages
# takes up to 2.8 times as long on the chip (its update wants a page-major
# copy of the rows; PERF.md section 5, PR 34), and two copies of a page
# in it (the scratch page) would race.
# ---------------------------------------------------------------------------


def is_run(slots: np.ndarray, page_size: int) -> bool:
    """Host side: ``slots`` are consecutive rows of their pages — each
    next slot is the next offset of the same page, or offset 0 of another
    page — and no page is entered twice."""
    slots = np.asarray(slots)
    pg, off = slots // page_size, slots % page_size
    step = (off[1:] == off[:-1] + 1) & (pg[1:] == pg[:-1])
    turn = (off[1:] == 0) & (off[:-1] == page_size - 1)
    if not np.all(step | turn):
        return False
    heads = np.concatenate([pg[:1], pg[1:][turn]])
    return len(np.unique(heads)) == len(heads)


def _pages_of_run(page_size: int, rows: int) -> int:
    """The most pages a run of ``rows`` consecutive rows can touch."""
    return (rows + 2 * page_size - 2) // page_size


def _i32(x: int) -> np.int32:
    return np.int32(x)


def _cut(x: jax.Array, start: tuple, sizes: tuple) -> jax.Array:
    """``lax.dynamic_slice`` at indices known not to be negative (their
    normalisation is most of what tracing a slice costs)."""
    return jax.lax.dynamic_slice(x, start, sizes, allow_negative_indices=False)


def _slot_at(slots: jax.Array, b, j) -> jax.Array:
    """``slots[b, j]`` for traced ``b``, ``j`` (plain ``lax``: these few
    lines are traced into every program that writes the pool, and
    ``jnp`` indexing costs milliseconds a line there)."""
    return jax.lax.reshape(_cut(slots, (b, j), (1, 1)), ())


def _open_page(slots: jax.Array, b, k, page_size: int):
    """Run ``b`` of ``slots [B, C]`` in its own frame: local row ``i`` is
    offset ``i % page`` of the ``i // page``-th page the run touches and
    holds token ``i - first``, ``first`` being the offset the run starts
    at. Returns ``(first, slot)``: that offset and the slot of the token
    that opens local page ``k`` (clamped: a page past the run's end repeats
    the last token, whose page it then is not)."""
    lax = jax.lax
    first = lax.rem(_slot_at(slots, b, _i32(0)), _i32(page_size))
    opener = lax.clamp(
        _i32(0), lax.sub(lax.mul(k, _i32(page_size)), first),
        _i32(slots.shape[1] - 1))
    return first, _slot_at(slots, b, opener)


def _page_view(pool: jax.Array, axis: int, page_size: int) -> jax.Array:
    shape = pool.shape
    return pool.reshape(
        shape[:axis] + (shape[axis] // page_size, page_size) + shape[axis + 1:]
    )


ALL_LAYERS = (slice(None),) * 3  # `lead` of the rows: planes, layers, heads


def write_runs(
    pool: jax.Array,  # [*lead, slots, *tail]
    slots: jax.Array,  # [B, C] runs (see above), or [n] any slots
    rows: jax.Array,  # [*lead', B, C, *tail] / [*lead', n, *tail]
    page_size: int,
    lead: tuple = ALL_LAYERS,
) -> jax.Array:
    """``pool`` with ``rows`` at ``slots``, every other row as it was;
    traced inside the caller's jit. ``lead`` indexes the axes before the
    slot axis (all of them, or a range of layers). Runs go page by page —
    read, overlaid with the rows that hit it, written back, one after the
    other, so a page named twice (the scratch page) is no hazard.
    One-dimensional ``slots`` are scattered token by token, as any set of
    slots can be — at the price of the pool's relayout on the chip."""
    return write_runs_all([(pool, rows, lead)], slots, page_size)[0]


def write_runs_all(writes: list, slots: jax.Array, page_size: int) -> list:
    """:func:`write_runs` for several arrays that share the slot numbers
    (the rows and their index keys, an int8 pool and its scales), each a
    ``(pool, rows, lead)``: one pass over the pages serves them all. A
    token is written where its slot IS the row of the page its place in
    the run says; one that does not continue its run (padding: the scratch
    slot, a slot outside the pool) is written nowhere else."""
    ps = page_size
    if slots.ndim == 1 or ps == 1:  # a page of one row is a token
        return [pool.at[lead + (slots,)].set(rows)
                for pool, rows, lead in writes]
    lax = jax.lax
    B, C = slots.shape
    num_slots = writes[0][0].shape[len(writes[0][2])]
    P = _pages_of_run(ps, C)
    # Token j of a run sits at j + page, so the tokens of any page the run
    # touches, its first and last too, are one slice of the padded arrays.
    pad = (ps, P * ps - C, 0)
    tokens = lax.pad(slots, _i32(-1), [(0, 0, 0), pad])
    views, pieces = [], []
    for pool, rows, lead in writes:
        axis = len(lead)
        tail = pool.shape[axis + 1:]
        views.append(_page_view(pool, axis, ps))
        pieces.append((
            axis,
            lax.pad(rows, jnp.zeros((), rows.dtype),
                    [(0, 0, 0)] * (axis + 1) + [pad] + [(0, 0, 0)] * len(tail)),
            tuple(_i32(s.start or 0) for s in lead),
            rows.shape[:axis] + (1, ps) + tail,
            (_i32(0),) * len(tail),
        ))

    def one_page(n, views):
        b, k = lax.div(n, _i32(P)), lax.rem(n, _i32(P))
        first, slot = _open_page(slots, b, k, ps)
        held = lax.bitwise_and(lax.ge(slot, _i32(0)), lax.lt(slot, _i32(num_slots)))
        page = lax.clamp(
            _i32(0), lax.div(slot, _i32(ps)), _i32(num_slots // ps - 1))
        start = lax.sub(lax.mul(lax.add(k, _i32(1)), _i32(ps)), first)
        rows_of_page = lax.add(
            lax.broadcast(lax.mul(page, _i32(ps)), (1, ps)),
            lax.iota(jnp.int32, ps).reshape(1, ps))
        mine = lax.bitwise_and(
            lax.eq(_cut(tokens, (b, start), (1, ps)), rows_of_page),
            lax.broadcast(held, (1, ps)))
        out = []
        for view, (axis, padded, starts, slab, zeros) in zip(views, pieces):
            at = starts + (page, _i32(0)) + zeros
            new = _cut(padded, (_i32(0),) * axis + (b, start) + zeros, slab)
            cur = _cut(view, at, slab)
            keep = lax.broadcast_in_dim(mine, slab, (axis, axis + 1))
            out.append(lax.dynamic_update_slice(
                view, lax.select(keep, new, cur), at,
                allow_negative_indices=False))
        return out

    views = lax.fori_loop(_i32(0), _i32(B * P), one_page, views)
    return [v.reshape(w[0].shape) for v, w in zip(views, writes)]


def gather_run(pool: jax.Array, slots: jax.Array, page_size: int,
               axis: int = 3) -> jax.Array:
    """``pool[..., slots, ...]`` for ONE run ``slots [1, n]`` — its pages
    are fetched whole and the rows cut out of them — or, token by token,
    for any ``slots [n]``."""
    lead = (slice(None),) * axis
    if slots.ndim == 1:
        return pool[lead + (slots,)]
    ps = page_size
    n = slots.shape[1]
    P = _pages_of_run(ps, n)
    first = slots[0, 0] % ps
    opens = jnp.clip(jnp.arange(P) * ps - first, 0, n - 1)
    got = _page_view(pool, axis, ps)[lead + (slots[0, opens] // ps,)]
    got = got.reshape(got.shape[:axis] + (P * ps,) + got.shape[axis + 2:])
    return jax.lax.dynamic_slice_in_dim(got, first, n, axis=axis)


# The pool's jitted writers and readers. ``slots`` is ``[1, n]`` — ONE run,
# moved by pages — or ``[n]``, any slots, token by token (``write_runs``);
# the new rows carry the same axes in the slot axis's place.


def _at_tokens(rows: jax.Array, slots: jax.Array, axis: int = 3) -> jax.Array:
    """``rows [.., n, ..]`` shaped for ``slots``: a run's get its axis."""
    return jnp.expand_dims(rows, axis) if slots.ndim == 2 else rows


def _layers(layer0: int, new_kv: jax.Array) -> tuple:
    """``write_runs``'s ``lead`` for head-major ``new_kv [2, nL, H, ..]``
    that holds the layers from ``layer0`` on."""
    return (slice(None), slice(layer0, layer0 + new_kv.shape[1]), slice(None))


# ``layer0`` serves the staged disagg handoff (engine/disagg.py +
# cache/kv_transfer.py): a handoff packet staged per layer-block can land
# block-by-block, so the pool update for block 0 overlaps block 1's
# host→device transfer instead of waiting for the whole packet. It is
# static — one compile per (block shape, position), bounded by L / block
# variants.


@partial(jax.jit, donate_argnums=(0,), static_argnums=(3, 4))
def _scatter_kv(kv: jax.Array, slots: jax.Array, new_kv: jax.Array,
                page_size: int, layer0: int = 0) -> jax.Array:
    # kv: [2, L, H, S, D]; new_kv: head-major [2, nL, H, n, D]
    return write_runs(kv, slots, _at_tokens(new_kv, slots), page_size,
                      _layers(layer0, new_kv))


@partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _scatter_index(index: jax.Array, slots: jax.Array, keys: jax.Array,
                   page_size: int) -> jax.Array:
    # index: [L_full, S, Di]; keys: [L_full, n, Di]
    return write_runs(index, slots, _at_tokens(keys, slots, 1), page_size,
                      lead=(slice(None),))


@partial(jax.jit, donate_argnums=(0,))
def _scatter_state(state: jax.Array, slots: jax.Array, new: jax.Array) -> jax.Array:
    """``new`` [layers, n, rows, 128] to state slots ``slots`` [n]: whole
    slots on a leading axis, so nothing is relaid."""
    return state.at[:, slots].set(new.astype(state.dtype))


@partial(jax.jit, static_argnums=(2,))
def _gather_kv(kv: jax.Array, slots: jax.Array, page_size: int) -> jax.Array:
    # → [2, L, n, H, D] (token-major, for tests/debug)
    return gather_run(kv, slots, page_size).transpose(0, 1, 3, 2, 4)


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4, 5))
def _scatter_kv_quant(
    kv: jax.Array,  # int8 [2, L, H, S, D]
    kv_scale: jax.Array,  # f32 [2, L, H, S]
    slots: jax.Array,
    new_kv: jax.Array,  # float head-major [2, nL, H, n, D]
    page_size: int,
    layer0: int = 0,
):
    from radixmesh_tpu.ops.quant import quantize_kv

    q, scale = quantize_kv(new_kv, axis=-1)
    return _scatter_kv_raw(kv, kv_scale, slots, q, scale, page_size, layer0)


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(5, 6))
def _scatter_kv_raw(
    kv: jax.Array,  # int8 [2, L, H, S, D]
    kv_scale: jax.Array,  # f32 [2, L, H, S]
    slots: jax.Array,
    new_kv: jax.Array,  # int8 head-major [2, nL, H, n, D]
    new_scale: jax.Array,  # f32 head-major [2, nL, H, n]
    page_size: int,
    layer0: int = 0,
):
    lead = _layers(layer0, new_kv)
    return tuple(write_runs_all(
        [(kv, _at_tokens(new_kv, slots), lead),
         (kv_scale, _at_tokens(new_scale, slots), lead)],
        slots, page_size,
    ))


@partial(jax.jit, static_argnums=(3,))
def _gather_kv_dequant(
    kv: jax.Array, kv_scale: jax.Array, slots: jax.Array, page_size: int
) -> jax.Array:
    # → dequantized f32 [2, L, n, H, D] (token-major, for tests/debug and
    # the engine's dense-prefill cached-prefix gather)
    from radixmesh_tpu.ops.quant import dequantize_kv

    deq = dequantize_kv(
        gather_run(kv, slots, page_size), gather_run(kv_scale, slots, page_size)
    )
    return deq.transpose(0, 1, 3, 2, 4)


class PagedKVPool:
    """Preallocated paged KV storage for every layer of one model replica."""

    def __init__(
        self,
        num_slots: int,
        num_layers: int,
        num_kv_heads: int,
        head_dim: int,
        page_size: int = 1,
        dtype: Any = jnp.bfloat16,
        sharding: jax.sharding.Sharding | None = None,
        quant: str | None = None,
        planes: int = 2,
        index_shape: tuple[int, int, int] | None = None,
        state_shape: tuple[int, int, int, int] | None = None,
        state_dtype: Any = None,
        conv_state_shape: tuple[int, int, int, int] | None = None,
        window_shape: tuple[int, int, int, int, int] | None = None,
    ):
        if planes not in (1, 2):
            raise ValueError("a pool row has 1 plane (latent) or 2 (K and V)")
        if planes == 1 and quant is not None:
            raise ValueError("a one-plane (latent) pool has no quantized form")
        self.planes = planes
        self.num_slots = num_slots
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.page_size = page_size
        self.quant = quant
        if quant is not None:
            from radixmesh_tpu.ops.quant import KV_QUANT_DTYPES

            if quant not in KV_QUANT_DTYPES:
                raise ValueError(f"unknown kv quantization {quant!r}")
            # No page-size constraint: the round-5 kernels gather the page
            # table's scales in XLA (_prep_scales) instead of staging
            # 128-slot scale rows in-kernel, so any page size works.
            dtype = KV_QUANT_DTYPES[quant]
        self.dtype = dtype
        self.allocator = SlotAllocator(num_slots, page_size)
        self.used_peak = 0  # most slots ever taken at once (/debug/state)
        # Head-major layout [2, L, Hkv, slots, D]: per-layer pages view as
        # [Hkv, num_pages, page, D] by pure reshape (no copy), which is the
        # layout the Pallas paged-attention kernel DMAs (batch dims of its
        # MXU contractions must lead), and the natural axis to shard over
        # `tp` (each chip holds its head shard of every page).
        # Born sharded: a pool that only fits spread over the mesh must
        # never exist whole on device 0 first.
        self.kv = jnp.zeros(
            (planes, num_layers, num_kv_heads, num_slots, head_dim),
            dtype=dtype,
            device=sharding,
        )
        # A second kind of state beside the rows (``ModelConfig.
        # kv_index_shape``): the index keys of a model with learned sparse
        # attention, ``[full layers, slots, width]``, addressed by the
        # SAME slot numbers — one allocator, so a slot's row and its index
        # keys are taken, handed to the radix tree, served by a tree hit
        # and freed together; only the scatters are two.
        self.index = None
        # ``/debug/state`` ``kv.write`` (``note_runs``); read by no metric.
        self.write_stats = {"launches": 0, "pages": 0, "edge_pages": 0}
        if index_shape is not None:
            if index_shape[1] != num_slots or quant is not None:
                raise ValueError(
                    "the index plane has the pool's slots and no quantized form"
                )
            self.index = jnp.zeros(index_shape, dtype=dtype)
        # A third kind of state (``ModelConfig.state_shape``): what the
        # layers that cache NOTHING per token carry — ``[layers, slots,
        # rows, 128]``, one slot a snapshot (every such layer's state at
        # one position of one sequence). Its unit is not a token, so it
        # has an allocator of its own: a live row holds a working slot and
        # a boundary slot, a tree node may hold one (valid at its end).
        # Slot 0 is nobody's: launches send there what they do not want.
        self.state = None
        self.state_allocator = None
        self.state_used_peak = 0
        if state_shape is not None:
            if quant is not None:
                raise ValueError("state slots have no quantized form")
            self.state = jnp.zeros(state_shape, dtype=state_dtype or dtype)
            self.state_allocator = SlotAllocator(state_shape[1], 1)
            self.state_allocator.reserve(np.zeros(1, np.int32))
        # A model with selective state-space layers keeps the recurrent
        # part in ``state`` (float32) and the last inputs of its
        # convolutions here (``ModelConfig.conv_state_shape``, the model's
        # dtype): one allocator, one snapshot = one slot of both.
        self.state_conv = None
        if conv_state_shape is not None:
            if state_shape is None or conv_state_shape[1] != state_shape[1]:
                raise ValueError("the conv states share the state slots")
            self.state_conv = jnp.zeros(conv_state_shape, dtype=dtype)
        # A second GROUP of rows (``ModelConfig.window_pool_shape``): the
        # window layers' K / V of a model whose full and window layers do
        # not share an array, ``[planes, window layers, heads, slots,
        # width]``, wanted for the last ``sliding_window`` tokens of a row
        # only. Its pages are counted, not owned: a row of the batch holds
        # one count on every page of its window, a snapshot in the radix
        # tree one on every page of its tail, and a page returns to the
        # free list when the last count goes (``unref_window``). Page 0 is
        # nobody's: idle rows write there. The group goes with state slots
        # (a model with state-space layers) or without any (attention kinds
        # of two shapes: a snapshot there IS its tail, counts and no copy).
        self.window = None
        self.window_refs = None
        self.window_used_peak = 0
        if window_shape is not None:
            if quant is not None or window_shape[3] % page_size:
                raise ValueError(
                    "the window group has whole pages and no quantized form")
            self.window = jnp.zeros(window_shape, dtype=dtype)
            n_pages = window_shape[3] // page_size
            self.window_refs = np.zeros(n_pages, np.int32)
            self.window_refs[0] = 1
            self._window_free = list(range(n_pages - 1, 0, -1))
        # Per-(token, head) symmetric scales for quantized pools: value ≈
        # int8 * scale (ops/quant.py). Same [2, L, Hkv, slots] geometry as
        # the data minus head_dim — shards identically over `tp`, and the
        # per-layer pages view is again a pure reshape.
        self.kv_scale = None
        if quant is not None:
            scale_sharding = None
            if isinstance(sharding, jax.sharding.NamedSharding):
                # Scale sharding mirrors the data's head axis; the slot and
                # trailing axes are replicated the same way.
                spec = tuple(sharding.spec) + (None,) * 5
                scale_sharding = jax.sharding.NamedSharding(
                    sharding.mesh, jax.sharding.PartitionSpec(*spec[:4])
                )
            self.kv_scale = jnp.zeros(
                (2, num_layers, num_kv_heads, num_slots),
                jnp.float32,
                device=scale_sharding,
            )

    @property
    def num_pages(self) -> int:
        return self.allocator.num_pages

    @property
    def index_shape(self) -> tuple[int, int, int] | None:
        return None if self.index is None else tuple(self.index.shape)

    @property
    def index_plane_bytes(self) -> int:
        return 0 if self.index is None else self.index.size * self.index.itemsize

    @property
    def state_shape(self) -> tuple[int, int, int, int] | None:
        return None if self.state is None else tuple(self.state.shape)

    @property
    def state_slot_bytes(self) -> int:
        """Bytes of one snapshot (all its layers)."""
        return sum(a.size // a.shape[1] * a.itemsize
                   for a in (self.state, self.state_conv) if a is not None)

    # ---- the window group (host side: counted pages) ----

    @property
    def window_slots(self) -> int:
        return 0 if self.window is None else self.window.shape[3]

    @property
    def window_slots_used(self) -> int:
        """Slots of the window group's pages that somebody counts on (the
        scratch page is nobody's)."""
        if self.window is None:
            return 0
        return (len(self.window_refs) - 1 - len(self._window_free)) * self.page_size

    def alloc_window(self, n_pages: int) -> np.ndarray | None:
        """``n_pages`` pages of the window group, one count each (the
        caller's); None when fewer are free."""
        if n_pages > len(self._window_free):
            return None
        pages = np.asarray(
            [self._window_free.pop() for _ in range(n_pages)], np.int32)
        self.window_refs[pages] = 1
        self.window_used_peak = max(self.window_used_peak, self.window_slots_used)
        return pages

    def ref_window(self, pages) -> None:
        """One more count on each of ``pages`` (held already by someone)."""
        pages = np.asarray(pages, np.int32)
        if pages.size and self.window_refs[pages].min() < 1:
            raise ValueError("a count on a free window page")
        np.add.at(self.window_refs, pages, 1)

    def unref_window(self, pages) -> int:
        """One count less on each of ``pages``; those nobody counts on any
        more return to the free list. Returns how many did."""
        pages = np.asarray(pages, np.int32)
        if pages.size == 0:
            return 0
        np.add.at(self.window_refs, pages, -1)
        if self.window_refs[pages].min() < 0 or self.window_refs[0] < 1:
            raise ValueError("double free of window pages")
        freed = np.unique(pages[self.window_refs[pages] == 0])
        self._window_free.extend(int(p) for p in freed)
        return len(freed)

    def write_window(self, slots: np.ndarray, k: jax.Array, v: jax.Array) -> None:
        """Write the window layers' K / V ``[layers, n, heads, width]`` of
        ``n`` tokens at the window group's ``slots`` (a dense prefill's;
        the paged launches write their own), bucketed as :meth:`write`."""
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots) == 0:
            return
        sl, (new,) = self._as_written(
            slots, [jnp.stack([jnp.asarray(k), jnp.asarray(v)])], [2])
        self.window = _scatter_kv(
            self.window, sl, new.transpose(0, 1, 3, 2, 4).astype(self.dtype),
            self.page_size, 0)

    # ---- allocation (host side) ----

    def alloc(self, n_tokens: int) -> np.ndarray | None:
        got = self.allocator.alloc(n_tokens)
        if got is not None:
            self.used_peak = max(
                self.used_peak, self.num_slots - self.allocator.free_slots)
        return got

    def alloc_state(self) -> int | None:
        """One state slot, or None when every slot is taken."""
        got = self.state_allocator.alloc(1)
        if got is None:
            return None
        a = self.state_allocator
        self.state_used_peak = max(
            self.state_used_peak, a.num_slots - a.free_slots)
        return int(got[0])

    def free_state(self, slot: int) -> None:
        self.state_allocator.free(np.asarray([slot], np.int32))

    @property
    def state_slots_used(self) -> int:
        a = self.state_allocator
        return 0 if a is None else a.num_slots - a.free_slots

    def write_state(self, slots: np.ndarray, states) -> None:
        """Store ``states`` [layers, n, rows, 128] at state slots ``slots``
        [n] (a dense prefill's; the paged launches write their own); with
        conv states beside them, ``states`` is the pair of both."""
        slots = jnp.asarray(slots, jnp.int32)
        if self.state_conv is not None:
            states, conv = states
            self.state_conv = _scatter_state(self.state_conv, slots, conv)
        self.state = _scatter_state(self.state, slots, states)

    def free(self, slots: np.ndarray) -> None:
        self.allocator.free(slots)

    def reserve(self, slots: np.ndarray) -> None:
        self.allocator.reserve(slots)

    @property
    def free_slots(self) -> int:
        return self.allocator.free_slots

    def fill_free_fraction(self) -> float:
        """Free fraction of the pool, 0..1 — the fleet digest's
        ``pool_fill`` complement (``obs/fleet_plane.py``)."""
        if self.num_slots <= 0:
            return 1.0
        return self.free_slots / self.num_slots

    # ---- device ops ----

    def _as_written(self, slots: np.ndarray, arrays: list, token_axes: list):
        """``(slots, arrays)`` as the jitted writers take them: padded to
        the bucket (the pad repeats the last row at the last slot: in the
        run's frame it is that row again or the row of no page), and —
        where the slots are a run — as ``[1, n]``, to go by whole pages.
        Any other set of slots goes token by token."""
        run = is_run(slots, self.page_size)
        # A launch that goes token by token moves no page.
        self.note_runs(slots[:1], np.asarray([len(slots) if run else 0]))
        slots, arrays = _pad_to_bucket(slots, arrays, token_axes)
        # (The run's axis is added on the host: an eager op would compile.)
        return jnp.asarray(slots[None] if run else slots, jnp.int32), arrays

    def note_runs(self, first_slots: np.ndarray, counts: np.ndarray) -> None:
        """``/debug/state`` ``kv.write``: one launch wrote runs of
        ``counts`` rows from ``first_slots`` on. ``pages`` are the pages
        it wrote whole-page-wise, ``edge_pages`` those of them it was not
        given every row of (entered inside, or left before the end)."""
        ps = self.page_size
        counts = np.asarray(counts, dtype=np.int64)
        head = np.asarray(first_slots, dtype=np.int64)[counts > 0] % ps
        end = head + counts[counts > 0]
        pages = -(-end // ps)
        cut_head, cut_tail = head > 0, end % ps > 0
        edge = cut_head.astype(np.int64) + cut_tail - (
            cut_head & cut_tail & (pages == 1))
        self.write_stats["launches"] += 1
        self.write_stats["pages"] += int(pages.sum())
        self.write_stats["edge_pages"] += int(edge.sum())

    def write(
        self, slots: np.ndarray | jax.Array, k: jax.Array,
        v: jax.Array | None = None,
    ) -> None:
        """Write per-layer K/V for ``n`` tokens at ``slots``.

        ``k``/``v``: ``[L, n, kv_heads, head_dim]`` (a one-plane pool takes
        its rows as ``k`` and no ``v``). The pool array is
        donated through the scatter so HBM is updated in place. The token
        count is padded up to a power-of-two bucket so ``jax.jit`` compiles
        O(log max_n) scatter variants instead of one per distinct length.
        A run of slots (what the allocator and the tree hand out) goes by
        whole pages; every row of those pages that is not among ``slots``
        stays as it was.
        """
        rows = [k] if self.planes == 1 else [k, v]
        self.write_block(slots, jnp.stack([jnp.asarray(r) for r in rows]))

    def write_index(self, slots: np.ndarray | jax.Array, keys: jax.Array) -> None:
        """Write the index keys ``[L_full, n, width]`` of ``n`` tokens at
        ``slots`` (the slots their rows were written at), bucketed as
        :meth:`write`."""
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots) == 0:
            return
        sl, (keys,) = self._as_written(slots, [jnp.asarray(keys)], [1])
        self.index = _scatter_index(
            self.index, sl, keys.astype(self.dtype), self.page_size
        )

    def pages_for_layer(self, layer: int) -> tuple[jax.Array, jax.Array]:
        """(k_pages, v_pages), each ``[Hkv, num_pages, page, D]`` — a
        zero-copy view of this layer's pool, the kernel's input layout."""
        shape = (self.num_kv_heads, self.num_pages, self.page_size, self.head_dim)
        return self.kv[0, layer].reshape(shape), self.kv[-1, layer].reshape(shape)

    def gather_raw(self, slots: np.ndarray | jax.Array):
        """``(kv [2, L, n, H, D] in POOL dtype, scales [2, L, n, H] | None)``
        — the exact stored representation, for shipping across nodes
        (disaggregated handoff) without a dequantize→requantize round trip
        (which quadruples int8 wire bytes and drifts the values)."""
        sl = jnp.asarray(slots, dtype=jnp.int32)
        kv = self.kv[:, :, :, sl].transpose(0, 1, 3, 2, 4)
        if self.quant is None:
            return kv, None
        return kv, self.kv_scale[:, :, :, sl].transpose(0, 1, 3, 2)

    def write_raw(self, slots: np.ndarray, kv, scales) -> None:
        """Store already-quantized K/V verbatim (inverse of
        :meth:`gather_raw`; quantized pools only). ``kv`` token-major
        ``[2, L, n, H, D]`` int8, ``scales`` ``[2, L, n, H]``."""
        if self.quant is None:
            raise ValueError("write_raw targets quantized pools")
        self.write_block(slots, kv, 0, scales)

    def write_block(
        self,
        slots: np.ndarray,
        kv,
        layer0: int = 0,
        scales=None,
    ) -> None:
        """Store a token-major ``[2, nL, n, H, D]`` block covering layers
        ``[layer0, layer0 + nL)`` at ``slots`` — the staged-handoff write
        (``engine/disagg.py``): layer-blocked packets land block-by-block
        so early blocks' scatters overlap later blocks' transfers.

        Dtype dispatch: ``scales`` given → raw quantized store (quantized
        pools only); quantized pool without scales → quantize-on-store;
        plain pool → cast + store."""
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots) == 0:
            return
        if scales is not None and self.quant is None:
            raise ValueError("raw quantized blocks target quantized pools")
        arrays = [jnp.asarray(kv, self.dtype if scales is not None else None)]
        if scales is not None:
            arrays.append(jnp.asarray(scales, jnp.float32))
        sl, arrays = self._as_written(slots, arrays, [2] * len(arrays))
        new_kv = arrays[0].transpose(0, 1, 3, 2, 4)  # token- → head-major
        if scales is not None:
            self.kv, self.kv_scale = _scatter_kv_raw(
                self.kv, self.kv_scale, sl, new_kv,
                arrays[1].transpose(0, 1, 3, 2), self.page_size, layer0,
            )
        elif self.quant is not None:
            self.kv, self.kv_scale = _scatter_kv_quant(
                self.kv, self.kv_scale, sl, new_kv, self.page_size, layer0
            )
        else:
            self.kv = _scatter_kv(
                self.kv, sl, new_kv.astype(self.dtype), self.page_size, layer0
            )

    def gather(self, slots: np.ndarray | jax.Array) -> jax.Array:
        """Gather ``[2, L, n, kv_heads, head_dim]`` for the given slots,
        dequantized for quantized pools (debug/test path: no launch of
        the engine calls it — attention kernels read pages directly, a
        cached prefix included). A run of slots comes by whole pages."""
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots) and is_run(slots, self.page_size):
            slots = slots[None]
        sl = jnp.asarray(slots)
        if self.quant is not None:
            return _gather_kv_dequant(self.kv, self.kv_scale, sl, self.page_size)
        return _gather_kv(self.kv, sl, self.page_size)

    def page_table(self, slots: np.ndarray) -> np.ndarray:
        """Page ids covering a page-aligned run of slots — the block table
        the paged-attention kernel consumes."""
        slots = np.asarray(slots, dtype=np.int32)
        if slots.size == 0:
            return np.empty(0, dtype=np.int32)
        if self.page_size == 1:
            return slots
        heads = slots[:: self.page_size]
        if np.any(heads % self.page_size != 0):
            raise ValueError("slots are not page-aligned")
        return heads // self.page_size

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

__all__ = ["SlotAllocator", "PagedKVPool"]


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


@partial(jax.jit, donate_argnums=(0,))
def _scatter_kv(kv: jax.Array, slots: jax.Array, new_kv: jax.Array) -> jax.Array:
    # kv: [2, L, H, S, D]; slots: [n]; new_kv: [2, L, H, n, D]
    return kv.at[:, :, :, slots].set(new_kv)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_index(index: jax.Array, slots: jax.Array, keys: jax.Array) -> jax.Array:
    # index: [L_full, S, Di]; slots: [n]; keys: [L_full, n, Di]
    return index.at[:, slots].set(keys)


@jax.jit
def _gather_kv(kv: jax.Array, slots: jax.Array) -> jax.Array:
    # → [2, L, n, H, D] (token-major, for tests/debug)
    return kv[:, :, :, slots].transpose(0, 1, 3, 2, 4)


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter_kv_quant(
    kv: jax.Array,  # int8 [2, L, H, S, D]
    kv_scale: jax.Array,  # f32 [2, L, H, S]
    slots: jax.Array,  # [n]
    new_kv: jax.Array,  # [2, L, H, n, D] float
):
    from radixmesh_tpu.ops.quant import quantize_kv

    q, scale = quantize_kv(new_kv, axis=-1)
    return kv.at[:, :, :, slots].set(q), kv_scale.at[:, :, :, slots].set(scale)


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter_kv_raw(
    kv: jax.Array,  # int8 [2, L, H, S, D]
    kv_scale: jax.Array,  # f32 [2, L, H, S]
    slots: jax.Array,  # [n]
    new_kv: jax.Array,  # int8 token-major [2, L, n, H, D]
    new_scale: jax.Array,  # f32 [2, L, n, H]
):
    return (
        kv.at[:, :, :, slots].set(new_kv.transpose(0, 1, 3, 2, 4)),
        kv_scale.at[:, :, :, slots].set(new_scale.transpose(0, 1, 3, 2)),
    )


# Layer-ranged scatters for the staged disagg handoff (engine/disagg.py
# + cache/kv_transfer.py): a handoff packet staged per layer-block can
# land block-by-block, so the pool update for block 0 overlaps block 1's
# host→device transfer instead of waiting for the whole packet. ``layer0``
# is static — one compile per (block shape, position), bounded by
# L / block variants.


@partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _scatter_kv_layers(
    kv: jax.Array,  # [2, L, H, S, D]
    slots: jax.Array,  # [n]
    new_kv: jax.Array,  # head-major [2, nL, H, n, D]
    layer0: int,
) -> jax.Array:
    return kv.at[:, layer0 : layer0 + new_kv.shape[1], :, slots].set(new_kv)


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(5,))
def _scatter_kv_raw_layers(
    kv: jax.Array,  # int8 [2, L, H, S, D]
    kv_scale: jax.Array,  # f32 [2, L, H, S]
    slots: jax.Array,  # [n]
    new_kv: jax.Array,  # int8 head-major [2, nL, H, n, D]
    new_scale: jax.Array,  # f32 head-major [2, nL, H, n]
    layer0: int,
):
    nl = new_kv.shape[1]
    return (
        kv.at[:, layer0 : layer0 + nl, :, slots].set(new_kv),
        kv_scale.at[:, layer0 : layer0 + nl, :, slots].set(new_scale),
    )


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4,))
def _scatter_kv_quant_layers(
    kv: jax.Array,  # int8 [2, L, H, S, D]
    kv_scale: jax.Array,  # f32 [2, L, H, S]
    slots: jax.Array,  # [n]
    new_kv: jax.Array,  # float head-major [2, nL, H, n, D]
    layer0: int,
):
    from radixmesh_tpu.ops.quant import quantize_kv

    q, scale = quantize_kv(new_kv, axis=-1)
    nl = new_kv.shape[1]
    return (
        kv.at[:, layer0 : layer0 + nl, :, slots].set(q),
        kv_scale.at[:, layer0 : layer0 + nl, :, slots].set(scale),
    )


@jax.jit
def _gather_kv_dequant(
    kv: jax.Array, kv_scale: jax.Array, slots: jax.Array
) -> jax.Array:
    # → dequantized f32 [2, L, n, H, D] (token-major, for tests/debug and
    # the engine's dense-prefill cached-prefix gather)
    from radixmesh_tpu.ops.quant import dequantize_kv

    deq = dequantize_kv(kv[:, :, :, slots], kv_scale[:, :, :, slots])
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
        if index_shape is not None:
            if index_shape[1] != num_slots or quant is not None:
                raise ValueError(
                    "the index plane has the pool's slots and no quantized form"
                )
            self.index = jnp.zeros(index_shape, dtype=dtype)
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

    # ---- allocation (host side) ----

    def alloc(self, n_tokens: int) -> np.ndarray | None:
        return self.allocator.alloc(n_tokens)

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

    def write(
        self, slots: np.ndarray | jax.Array, k: jax.Array,
        v: jax.Array | None = None,
    ) -> None:
        """Write per-layer K/V for ``n`` tokens at ``slots``.

        ``k``/``v``: ``[L, n, kv_heads, head_dim]`` (a one-plane pool takes
        its rows as ``k`` and no ``v``). The pool array is
        donated through the scatter so HBM is updated in place. The token
        count is padded up to a power-of-two bucket (by repeating the last
        slot/value — an idempotent duplicate write) so ``jax.jit`` compiles
        O(log max_n) scatter variants instead of one per distinct length.
        """
        slots = np.asarray(slots, dtype=np.int32)
        n = len(slots)
        if n == 0:
            return
        rows = [k] if self.planes == 1 else [k, v]
        slots, rows = _pad_to_bucket(
            slots, [jnp.asarray(r) for r in rows], [1] * len(rows)
        )
        # [L, n, H, D] → head-major [L, H, n, D].
        new_kv = jnp.stack(rows).transpose(0, 1, 3, 2, 4)
        sl = jnp.asarray(slots, dtype=jnp.int32)
        if self.quant is not None:
            self.kv, self.kv_scale = _scatter_kv_quant(
                self.kv, self.kv_scale, sl, new_kv
            )
        else:
            self.kv = _scatter_kv(self.kv, sl, new_kv.astype(self.dtype))

    def write_index(self, slots: np.ndarray | jax.Array, keys: jax.Array) -> None:
        """Write the index keys ``[L_full, n, width]`` of ``n`` tokens at
        ``slots`` (the slots their rows were written at), bucketed as
        :meth:`write`."""
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots) == 0:
            return
        slots, (keys,) = _pad_to_bucket(slots, [jnp.asarray(keys)], [1])
        self.index = _scatter_index(
            self.index, jnp.asarray(slots, dtype=jnp.int32),
            keys.astype(self.dtype),
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
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots) == 0:
            return
        slots, (kv, scales) = _pad_to_bucket(
            slots,
            [jnp.asarray(kv, self.dtype), jnp.asarray(scales, jnp.float32)],
            [2, 2],
        )
        self.kv, self.kv_scale = _scatter_kv_raw(
            self.kv, self.kv_scale, jnp.asarray(slots, jnp.int32), kv, scales
        )

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

        Dtype dispatch mirrors the full-layer writers: ``scales`` given →
        raw quantized store (quantized pools only); quantized pool
        without scales → quantize-on-store; plain pool → cast + store.
        Full-layer blocks delegate to the existing writers so the common
        whole-packet path adds no new compile variants."""
        slots = np.asarray(slots, dtype=np.int32)
        n = len(slots)
        if n == 0:
            return
        nl = kv.shape[1]
        full = layer0 == 0 and nl == self.num_layers
        if full:
            if scales is not None:
                self.write_raw(slots, kv, scales)
            else:
                self.write(slots, *jnp.asarray(kv))
            return
        if scales is not None and self.quant is None:
            raise ValueError("raw quantized blocks target quantized pools")
        arrays = [jnp.asarray(kv, self.dtype if scales is not None else None)]
        axes = [2]
        if scales is not None:
            arrays.append(jnp.asarray(scales, jnp.float32))
            axes.append(2)
        slots, arrays = _pad_to_bucket(slots, arrays, axes)
        sl = jnp.asarray(slots, dtype=jnp.int32)
        new_kv = arrays[0].transpose(0, 1, 3, 2, 4)  # token- → head-major
        if scales is not None:
            self.kv, self.kv_scale = _scatter_kv_raw_layers(
                self.kv, self.kv_scale, sl, new_kv,
                arrays[1].transpose(0, 1, 3, 2), layer0,
            )
        elif self.quant is not None:
            self.kv, self.kv_scale = _scatter_kv_quant_layers(
                self.kv, self.kv_scale, sl, new_kv, layer0
            )
        else:
            self.kv = _scatter_kv_layers(
                self.kv, sl, new_kv.astype(self.dtype), layer0
            )

    def gather(self, slots: np.ndarray | jax.Array) -> jax.Array:
        """Gather ``[2, L, n, kv_heads, head_dim]`` for the given slots,
        dequantized for quantized pools (debug/test path and the dense-
        prefill cached-prefix gather; attention kernels read pages
        directly)."""
        sl = jnp.asarray(slots, dtype=jnp.int32)
        if self.quant is not None:
            return _gather_kv_dequant(self.kv, self.kv_scale, sl)
        return _gather_kv(self.kv, sl)

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

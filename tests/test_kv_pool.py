"""Tests for the paged KV pool + slot allocator (TPU-native replacement for
the reference's external ``token_to_kv_pool_allocator``, SURVEY §2)."""

import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.cache.kv_pool import PagedKVPool, SlotAllocator


class TestSlotAllocator:
    def test_alloc_free_roundtrip(self):
        a = SlotAllocator(16, page_size=1)
        s1 = a.alloc(5)
        assert s1 is not None and len(s1) == 5
        assert a.free_slots == 11
        a.free(s1)
        assert a.free_slots == 16

    def test_exhaustion_returns_none(self):
        a = SlotAllocator(4, page_size=1)
        assert a.alloc(4) is not None
        assert a.alloc(1) is None

    def test_unique_slots(self):
        a = SlotAllocator(64, page_size=1)
        s1, s2 = a.alloc(30), a.alloc(30)
        assert len(np.intersect1d(s1, s2)) == 0

    def test_page_granularity(self):
        a = SlotAllocator(32, page_size=4)
        s = a.alloc(6)  # rounds up to 2 pages = 8 slots, returns first 6
        assert len(s) == 6
        assert a.free_slots == 32 - 8
        # Slots are page-contiguous.
        assert s[0] % 4 == 0
        np.testing.assert_array_equal(s[:4] - s[0], np.arange(4))

    def test_partial_free_reclaims_page_when_complete(self):
        a = SlotAllocator(8, page_size=4)
        s = a.alloc(4)
        a.free(s[:2])
        assert a.free_slots == 4  # page not yet whole
        a.free(s[2:])
        assert a.free_slots == 8

    def test_partial_page_tail_slots_reclaimed(self):
        # alloc(6) with page_size=4 occupies 2 pages; freeing the 6 returned
        # slots must reclaim both pages (the 2 unused tail slots with them).
        a = SlotAllocator(8, page_size=4)
        s = a.alloc(6)
        assert a.free_slots == 0
        a.free(s)
        assert a.free_slots == 8

    def test_subset_double_free_detected(self):
        a = SlotAllocator(8, page_size=4)
        s = a.alloc(4)
        a.free(s[:2])
        with pytest.raises(ValueError):
            a.free(s[:2])  # re-freeing the same subset must not complete a page
        a.free(s[2:])
        assert a.free_slots == 8

    def test_double_free_raises(self):
        a = SlotAllocator(8, page_size=1)
        s = a.alloc(2)
        a.free(s)
        with pytest.raises(ValueError):
            a.free(s)

    def test_zero_alloc(self):
        a = SlotAllocator(8, page_size=1)
        assert len(a.alloc(0)) == 0


class TestPagedKVPool:
    def test_write_gather_roundtrip(self):
        pool = PagedKVPool(
            num_slots=32, num_layers=2, num_kv_heads=2, head_dim=4, dtype=jnp.float32
        )
        slots = pool.alloc(3)
        k = jnp.arange(2 * 3 * 2 * 4, dtype=jnp.float32).reshape(2, 3, 2, 4)
        v = -k
        pool.write(slots, k, v)
        got = pool.gather(slots)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(k))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(v))

    def test_writes_do_not_clobber_other_slots(self):
        pool = PagedKVPool(
            num_slots=16, num_layers=1, num_kv_heads=1, head_dim=2, dtype=jnp.float32
        )
        s1, s2 = pool.alloc(2), pool.alloc(2)
        ones = jnp.ones((1, 2, 1, 2))
        pool.write(s1, ones, ones)
        pool.write(s2, ones * 2, ones * 2)
        np.testing.assert_allclose(np.asarray(pool.gather(s1)[0]), np.asarray(ones))
        np.testing.assert_allclose(np.asarray(pool.gather(s2)[0]), np.asarray(ones) * 2)

    def test_page_table(self):
        pool = PagedKVPool(
            num_slots=32,
            num_layers=1,
            num_kv_heads=1,
            head_dim=2,
            page_size=4,
            dtype=jnp.float32,
        )
        slots = pool.alloc(8)
        table = pool.page_table(slots)
        assert len(table) == 2
        np.testing.assert_array_equal(table, slots[::4] // 4)

    def test_free_via_tree_eviction_callback(self):
        from radixmesh_tpu.cache.radix_tree import RadixTree

        pool = PagedKVPool(
            num_slots=8, num_layers=1, num_kv_heads=1, head_dim=2, dtype=jnp.float32
        )
        tree = RadixTree(on_free=pool.free)
        slots = pool.alloc(8)
        tree.insert(np.arange(8), slots)
        assert pool.free_slots == 0
        assert pool.alloc(1) is None
        tree.evict(8)
        assert pool.free_slots == 8


# ---------------------------------------------------------------------------
# Rows enter and leave the pool by whole pages (PR 34): bit for bit what a
# scatter token by token leaves, at every row of the pool.
# ---------------------------------------------------------------------------

L, H, D = 2, 2, 4


def _pool(page_size, planes=2, num_slots=128, index=False, quant=None, seed=0):
    """A pool whose every row already holds something (a neighbour's rows,
    a freed request's): what a write must leave alone."""
    pool = PagedKVPool(
        num_slots=num_slots, num_layers=L, num_kv_heads=1 if planes == 1 else H,
        head_dim=D, page_size=page_size, planes=planes, quant=quant,
        dtype=jnp.float32,
        index_shape=(1, num_slots, 3) if index else None,
    )
    rng = np.random.default_rng(seed)
    if quant is None:
        pool.kv = jnp.asarray(rng.standard_normal(pool.kv.shape), jnp.float32)
    if index:
        pool.index = jnp.asarray(
            rng.standard_normal(pool.index.shape), jnp.float32)
    return pool


def _rows(pool, n, seed=1):
    rng = np.random.default_rng(seed)
    shape = (L, n, pool.num_kv_heads, D)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return (k,) if pool.planes == 1 else (k, -k)


def _by_token(kv, slots, rows):
    """The per-token reference: ``kv[:, :, :, slot] = row``."""
    ref = np.asarray(kv).copy()
    for p, r in enumerate(rows):
        ref[p][:, :, slots] = np.asarray(r).transpose(0, 2, 1, 3)
    return ref


def _positions(pool, first, n):
    """Slots of ``n`` consecutive rows of one request, the first at offset
    ``first`` of a page, over pages the allocator hands out."""
    own = pool.alloc(first + n)
    return own[first:]


@pytest.mark.parametrize("page_size", [1, 16])
@pytest.mark.parametrize(
    "first,n",
    [(0, 32), (0, 20), (0, 37), (5, 20), (15, 1), (3, 2)],
    ids=["whole_pages", "part_filled_tail", "bucket_padded",
         "starts_inside_a_page", "one_row_at_a_page_end", "inside_one_page"],
)
@pytest.mark.parametrize("planes", [2, 1], ids=["kv", "latent"])
def test_write_by_pages_equals_write_by_tokens(page_size, first, n, planes):
    pool = _pool(page_size, planes)
    neighbour = pool.alloc(16)
    slots = _positions(pool, first, n)
    rows = _rows(pool, n)
    before = np.asarray(pool.kv)
    pool.write(slots, *rows)
    after = np.asarray(pool.kv)
    np.testing.assert_array_equal(after, _by_token(before, slots, rows))
    np.testing.assert_array_equal(
        after[:, :, :, neighbour], before[:, :, :, neighbour])
    got = np.asarray(pool.gather(slots))  # [planes, L, n, H, D]
    for p, r in enumerate(rows):
        np.testing.assert_array_equal(got[p], np.asarray(r))
    if page_size > 1:
        w = pool.write_stats
        pages = -(-(first + n) // page_size)
        assert (w["launches"], w["pages"]) == (1, pages)
        edge = {0} if first else set()
        if (first + n) % page_size:
            edge.add(pages - 1)
        assert w["edge_pages"] == len(edge)


def test_unwritten_tail_of_a_fresh_page_reads_zeros():
    """A request's own page, its tail not yet written: on a fresh pool the
    tail reads zeros, never what the bucket's padding held."""
    pool = PagedKVPool(num_slots=64, num_layers=L, num_kv_heads=H, head_dim=D,
                       page_size=16, dtype=jnp.float32)
    own = pool.alloc(32)
    pool.write(own[:21], *_rows(pool, 21))
    assert not np.asarray(pool.kv)[:, :, :, own[21:]].any()
    assert np.asarray(pool.kv)[:, :, :, own[:21]].all()


@pytest.mark.parametrize("page_size", [1, 16])
def test_a_write_inside_a_page_keeps_its_live_rows(page_size):
    """Two writes into one page (a prompt's tail, then the rows decoded
    after it): the second starts inside the page and the first's rows
    survive it."""
    pool = _pool(page_size)
    own = pool.alloc(48)
    a, b = _rows(pool, 21, seed=2), _rows(pool, 20, seed=3)
    pool.write(own[:21], *a)
    pool.write(own[21:41], *b)
    got = np.asarray(pool.gather(own[:41]))
    for p in range(2):
        np.testing.assert_array_equal(got[p][:, :21], np.asarray(a[p]))
        np.testing.assert_array_equal(got[p][:, 21:], np.asarray(b[p]))


@pytest.mark.parametrize("page_size", [1, 16])
def test_any_set_of_slots_still_goes_token_by_token(page_size):
    """Slots that are no run (reversed, scattered over pages) take the
    per-token scatter and gather, as before."""
    from radixmesh_tpu.cache.kv_pool import is_run

    pool = _pool(page_size)
    slots = pool.alloc(40)[::-3].copy()
    assert page_size == 1 or not is_run(slots, page_size)
    rows = _rows(pool, len(slots))
    before = np.asarray(pool.kv)
    pool.write(slots, *rows)
    np.testing.assert_array_equal(
        np.asarray(pool.kv), _by_token(before, slots, rows))
    np.testing.assert_array_equal(
        np.asarray(pool.gather(slots))[0], np.asarray(rows[0]))


@pytest.mark.parametrize("page_size", [1, 16])
@pytest.mark.parametrize("first,n", [(0, 20), (7, 30)])
def test_index_plane_by_pages_equals_by_tokens(page_size, first, n):
    pool = _pool(page_size, planes=1, index=True)
    slots = _positions(pool, first, n)
    keys = jnp.asarray(
        np.random.default_rng(4).standard_normal((1, n, 3)), jnp.float32)
    ref = np.asarray(pool.index).copy()
    ref[:, slots] = np.asarray(keys)
    pool.write_index(slots, keys)
    np.testing.assert_array_equal(np.asarray(pool.index), ref)


@pytest.mark.parametrize("page_size", [1, 16])
@pytest.mark.parametrize("layer0,n_layers", [(0, 2), (1, 1)])
def test_int8_pool_and_layer_blocks_by_pages(page_size, layer0, n_layers):
    """The quantizing writer, the raw one and a layer-ranged block: the
    same helper, the scale plane (no width axis) with the rows."""
    pool = _pool(page_size, quant="int8")
    twin = _pool(page_size, quant="int8")
    slots = _positions(pool, 5, 30)
    _positions(twin, 5, 30)
    k, v = _rows(pool, 30)
    block = jnp.stack([k, v])[:, layer0 : layer0 + n_layers]
    pool.write_block(slots, block, layer0)
    got = np.asarray(pool.gather(slots))[:, layer0 : layer0 + n_layers]
    np.testing.assert_allclose(got, np.asarray(block), atol=0.05)
    # The stored form lands verbatim in a second pool, token by token there.
    kv, scales = pool.gather_raw(slots)
    twin.write_block(slots[::-1].copy(), kv[:, :, ::-1], 0, scales[:, :, ::-1])
    np.testing.assert_array_equal(np.asarray(twin.kv), np.asarray(pool.kv))
    np.testing.assert_array_equal(
        np.asarray(twin.kv_scale), np.asarray(pool.kv_scale))


@pytest.mark.parametrize("page_size", [1, 16])
@pytest.mark.parametrize("chunk", [1, 16, 48])
def test_chunk_runs_land_where_their_slots_say(page_size, chunk):
    """``write_runs`` as ``prefill_chunk_paged`` calls it: a batch of runs
    ``[B, C]``, one starting inside a page that holds live rows, one
    ending inside its page, an idle row; every padding token names the
    scratch slot. Outside the scratch page the pool is what the per-token
    scatter of the real tokens leaves."""
    import jax

    from radixmesh_tpu.cache.kv_pool import write_runs

    pool = _pool(page_size, num_slots=256)
    scratch = int(pool.alloc(1)[0]) + page_size // 2
    runs = [(5, chunk), (0, max(chunk - 3, 1)), (0, 0), (11, min(chunk, 9))]
    slots = np.full((len(runs), chunk), scratch, np.int32)
    for b, (first, n) in enumerate(runs):
        slots[b, :n] = _positions(pool, first, n) if n else []
    rng = np.random.default_rng(5)
    rows = jnp.asarray(
        rng.standard_normal((2, L, H, len(runs), chunk, D)), jnp.float32)
    got = np.asarray(jax.jit(write_runs, static_argnums=(3,))(
        pool.kv, jnp.asarray(slots), rows, page_size))
    ref = np.asarray(pool.kv).copy()
    real = slots != scratch
    ref[:, :, :, slots[real]] = np.asarray(rows)[:, :, :, real]
    page = np.arange(pool.num_slots) // page_size == scratch // page_size
    np.testing.assert_array_equal(got[:, :, :, ~page], ref[:, :, :, ~page])


def test_kv_write_counts_pages_and_edge_pages():
    pool = PagedKVPool(num_slots=64, num_layers=1, num_kv_heads=1, head_dim=2,
                       page_size=16, dtype=jnp.float32)
    # whole pages; a tail; a head and a tail in one page; an idle row
    pool.note_runs(np.array([0, 16, 37, 9]), np.array([32, 20, 4, 0]))
    assert pool.write_stats == {"launches": 1, "pages": 5, "edge_pages": 2}
    pool.note_runs(np.array([5]), np.array([30]))  # head cut, tail cut
    assert pool.write_stats == {"launches": 2, "pages": 8, "edge_pages": 4}


@pytest.mark.parametrize("page_size", [4, 16])
def test_chunk_program_leaves_the_pool_the_per_token_scatter_left(
        page_size, monkeypatch):
    """``prefill_chunk_paged`` with its rows written by pages against the
    same program with the scatter it had before (``pool.at[.., slots]``):
    the same logits, and the same pool bit for bit at every row but the
    scratch page's — a run from inside a page with live rows, one that ends
    inside its page, an idle row."""
    import jax

    from radixmesh_tpu.models import llama

    cfg = llama.ModelConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ps, C, num_slots = page_size, 16, 512
    rng = np.random.default_rng(6)
    alloc = SlotAllocator(num_slots, ps)
    scratch = int(alloc.alloc(1)[0])
    # (tokens already in the pool, tokens of this chunk)
    rows = [(ps + 3, C), (2 * ps, C - 5), (0, 0)]
    B, maxp = len(rows), 8
    toks = rng.integers(1, cfg.vocab_size, (B, C)).astype(np.int32)
    poss = np.zeros((B, C), np.int32)
    slots = np.full((B, C), scratch, np.int32)
    pt = np.full((B, maxp), scratch // ps, np.int32)
    kvlen = np.zeros((B,), np.int32)
    for b, (prior, n) in enumerate(rows):
        own = alloc.alloc(prior + n) if prior + n else np.empty(0, np.int32)
        pt[b, : -(-len(own) // ps)] = own[::ps] // ps
        poss[b] = prior + np.arange(C)
        slots[b, :n] = own[prior:]
        kvlen[b] = prior + n
    pool = jnp.asarray(rng.standard_normal(
        (2, cfg.n_layers, cfg.n_kv_heads, num_slots, cfg.head_dim)), cfg.dtype)
    args = [jnp.asarray(x) for x in (toks, poss)] + [None] + [
        jnp.asarray(x) for x in (slots, pt, kvlen)]

    def run():
        jax.clear_caches()
        args[2] = pool + 0  # the program donates its pool
        logits, out = llama.prefill_chunk_paged(
            params, cfg, *args, page_size=ps, kv_block_pages=4)
        return np.asarray(logits, np.float32), np.asarray(out, np.float32)

    logits, by_pages = run()
    traced = []

    def by_token(writes, sl, page):
        traced.append(page)
        return [p.at[lead + (sl,)].set(r) for p, r, lead in writes]

    monkeypatch.setattr(llama, "write_runs_all", by_token)
    want_logits, by_tokens = run()
    jax.clear_caches()
    assert traced == [ps]
    np.testing.assert_array_equal(logits, want_logits)
    page = np.arange(num_slots) // ps == scratch // ps
    np.testing.assert_array_equal(by_pages[:, :, :, ~page], by_tokens[:, :, :, ~page])
    assert not np.array_equal(by_pages, np.asarray(pool, np.float32))


def test_engine_prefills_count_their_pages():
    """`/debug/state` `kv.write`: a dense prefill (`pool.write`) and a
    chunked one (`prefill_chunk_paged`) both say how many pages they moved
    and how many of those they were given only part of."""
    import jax

    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.engine.request import SamplingParams
    from radixmesh_tpu.models.llama import ModelConfig, init_params

    cfg = ModelConfig.tiny()
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), num_slots=512,
                 page_size=4, max_batch=2, max_seq_len=128,
                 long_prefill_threshold=16, prefill_chunk=16)
    greedy = SamplingParams(temperature=0.0, max_new_tokens=2)
    eng.generate([list(range(1, 11))], greedy)  # 10 tokens, dense
    assert eng.pool.write_stats == {"launches": 1, "pages": 3, "edge_pages": 1}
    assert eng._last_prefill_path == "dense"
    eng.generate([list(range(100, 138))], greedy)  # 38 tokens: 16 + 16 + 6
    assert eng._last_prefill_path == "chunk"
    assert eng.pool.write_stats == {"launches": 4, "pages": 13, "edge_pages": 2}


def test_a_window_group_without_state_slots_counts_its_pages():
    """The window group of a model that carries no state: pages under
    counts, a tail's count beside a row's, the scratch page nobody's."""
    from radixmesh_tpu.cache.kv_pool import PagedKVPool

    pool = PagedKVPool(num_slots=256, num_layers=2, num_kv_heads=2, head_dim=32,
                       page_size=16, dtype=jnp.float32,
                       window_shape=(2, 3, 2, 128, 32))
    assert pool.state is None and pool.state_allocator is None
    assert pool.window.shape == (2, 3, 2, 128, 32) and pool.window_slots == 128
    assert pool.window_slots_used == 0 and pool.window_refs[0] == 1
    row = pool.alloc_window(3)
    assert 0 not in row and pool.window_slots_used == 48
    tail = row[1:]
    pool.ref_window(tail)  # a snapshot's count on the same pages
    assert pool.unref_window(row) is not None  # the row leaves
    assert pool.window_slots_used == 32  # the tail still holds two
    pool.unref_window(tail)
    assert pool.window_slots_used == 0 and pool.window_refs.sum() == 1
    assert pool.window_used_peak == 48
    with pytest.raises(ValueError, match="double free"):
        pool.unref_window(tail)
    assert pool.alloc_window(8) is None  # seven pages beside the scratch page


@pytest.mark.parametrize("kind, state, conv, dtype, want", [
    # a linear-attention matrix a head (float32) and its convolutions' inputs
    ("kda", (3, 9, 64, 16), (3, 9, 5, 128), jnp.float32, 3 * (64 * 16 * 4 + 5 * 128 * 4)),
    ("kda_bf16_conv", (6, 5, 4096, 128), (6, 5, 288, 128), jnp.bfloat16,
     6 * (4096 * 128 * 4 + 288 * 128 * 2)),  # 13 025 280 B: published widths
    ("conv_only", (4, 9, 2, 128), None, jnp.bfloat16, 4 * 2 * 128 * 2),
])
def test_state_slots_are_counted_by_the_bytes_of_both_arrays(kind, state, conv,
                                                            dtype, want):
    """One snapshot = one slot of the float32 states AND of the conv states
    beside them, under one allocator; ``state_slot_bytes`` is what both
    hold of it, whatever the snapshot's size."""
    pool = PagedKVPool(
        num_slots=64, num_layers=1, num_kv_heads=1, head_dim=128, page_size=16,
        dtype=dtype, planes=1, state_shape=state,
        state_dtype=jnp.float32 if conv else dtype, conv_state_shape=conv)
    assert pool.state_slot_bytes == want
    assert pool.state_allocator.num_slots == state[1]
    first = pool.alloc_state()
    assert first == 1 and pool.state_slots_used == 2  # slot 0 is nobody's
    if conv is not None:
        assert pool.state.dtype == jnp.float32 and pool.state_conv.dtype == dtype
        if kind == "kda":
            pool.write_state(np.asarray([first]), (
                jnp.ones((state[0], 1, *state[2:])),
                jnp.full((conv[0], 1, *conv[2:]), 2.0)))
            assert float(pool.state[:, first].min()) == 1.0
            assert float(pool.state_conv[:, first].min()) == 2.0
            assert not np.asarray(pool.state[:, 2]).any()
    pool.free_state(first)
    assert pool.state_slots_used == 1

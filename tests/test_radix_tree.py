"""Unit tests for the host-side radix tree.

The reference has no unit tests (SURVEY §4); these cover the capability set
of ``radix_cache.py:87-436``: match/insert/split, paged keys, LRU eviction,
lock refs, size accounting, and the event journal.
"""

import numpy as np
import pytest

from radixmesh_tpu.cache.radix_tree import (
    AllBlocksCleared,
    BlockRemoved,
    BlockStored,
    MatchResult,
    RadixTree,
    match_len,
)

pytestmark = pytest.mark.quick


def ids(n, start=0):
    return np.arange(start, start + n, dtype=np.int32)


def make_tree(**kw):
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return RadixTree(time_fn=clock, **kw)


class TestMatchLen:
    def test_basic(self):
        assert match_len(ids(5), ids(5)) == 5
        assert match_len(ids(5), ids(3)) == 3
        assert match_len(np.array([1, 2, 9]), np.array([1, 2, 3])) == 2
        assert match_len(np.array([7]), np.array([1])) == 0
        assert match_len(ids(0), ids(5)) == 0


class TestInsertMatch:
    def test_empty_tree_match(self):
        tree = make_tree()
        res = tree.match_prefix([1, 2, 3])
        assert res.length == 0
        assert res.last_node is tree.root

    def test_insert_then_match_exact(self):
        tree = make_tree()
        key, val = [1, 2, 3], np.array([10, 11, 12], dtype=np.int32)
        assert tree.insert(key, val) == 0
        res = tree.match_prefix(key)
        assert res.length == 3
        np.testing.assert_array_equal(res.indices(), val)

    def test_match_partial_splits_node(self):
        tree = make_tree()
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        res = tree.match_prefix([1, 2, 99])
        assert res.length == 2
        np.testing.assert_array_equal(res.indices(), [10, 11])
        # The node was split: the matched node holds exactly [1, 2].
        np.testing.assert_array_equal(res.last_node.key, [1, 2])
        # Full key still reachable.
        res2 = tree.match_prefix([1, 2, 3, 4])
        assert res2.length == 4
        np.testing.assert_array_equal(res2.indices(), [10, 11, 12, 13])

    def test_readonly_match_does_not_split(self):
        tree = make_tree()
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        before = tree.total_size()
        res = tree.match_prefix([1, 2], split_partial=False)
        assert res.length == 2
        np.testing.assert_array_equal(res.indices(), [10, 11])
        assert tree.total_size() == before
        # Node count unchanged: root has a single 4-token child.
        assert len(tree.root.children) == 1
        only = next(iter(tree.root.children.values()))
        assert len(only.key) == 4
        # last_node anchors at the deepest FULLY matched node, so locking it
        # never protects tokens beyond the matched prefix.
        assert res.last_node is tree.root
        tree.inc_lock_ref(res.last_node)
        assert tree.protected_size() == 0
        assert tree.evict(100) == 4

    def test_insert_returns_existing_prefix_len(self):
        tree = make_tree()
        tree.insert([1, 2, 3], np.array([10, 11, 12], dtype=np.int32))
        got = tree.insert([1, 2, 3, 4, 5], np.array([10, 11, 12, 13, 14], dtype=np.int32))
        assert got == 3
        res = tree.match_prefix([1, 2, 3, 4, 5])
        assert res.length == 5

    def test_insert_idempotent(self):
        tree = make_tree()
        v = np.array([10, 11, 12], dtype=np.int32)
        tree.insert([1, 2, 3], v)
        assert tree.insert([1, 2, 3], v) == 3
        assert tree.total_size() == 3

    def test_branching(self):
        tree = make_tree()
        tree.insert([1, 2, 3], np.array([10, 11, 12], dtype=np.int32))
        tree.insert([1, 2, 7, 8], np.array([10, 11, 20, 21], dtype=np.int32))
        tree.insert([5, 6], np.array([30, 31], dtype=np.int32))
        assert tree.match_prefix([1, 2, 3]).length == 3
        np.testing.assert_array_equal(
            tree.match_prefix([1, 2, 7, 8]).indices(), [10, 11, 20, 21]
        )
        np.testing.assert_array_equal(tree.match_prefix([5, 6, 9]).indices(), [30, 31])
        assert tree.total_size() == 2 + 1 + 2 + 2  # [1,2],[3],[7,8],[5,6]

    def test_value_length_mismatch_raises(self):
        tree = make_tree()
        with pytest.raises(ValueError):
            tree.insert([1, 2, 3], np.array([1], dtype=np.int32))


class TestPaged:
    def test_paged_match_whole_pages_only(self):
        tree = make_tree(page_size=4)
        tree.insert(ids(8), ids(8, start=100))
        # 6-token query matches only the first full page (4 tokens).
        res = tree.match_prefix(ids(6))
        assert res.length == 4
        np.testing.assert_array_equal(res.indices(), ids(4, start=100))

    def test_paged_insert_truncates_partial_page(self):
        tree = make_tree(page_size=4)
        tree.insert(ids(6), ids(6, start=100))
        assert tree.total_size() == 4

    def test_paged_divergence_inside_page(self):
        tree = make_tree(page_size=2)
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        # Diverges at token 3 (inside second page) -> only first page matches.
        res = tree.match_prefix([1, 2, 3, 9])
        assert res.length == 2


class TestEviction:
    def test_evict_lru_and_free_callback(self):
        freed = []
        tree = make_tree(on_free=lambda idx: freed.append(np.array(idx)))
        tree.insert([1, 2], np.array([10, 11], dtype=np.int32))
        tree.insert([3, 4], np.array([20, 21], dtype=np.int32))
        tree.insert([5, 6], np.array([30, 31], dtype=np.int32))
        tree.match_prefix([1, 2])  # refresh [1,2] -> LRU is [3,4]
        n = tree.evict(2)
        assert n == 2
        assert tree.match_prefix([3, 4]).length == 0
        assert tree.match_prefix([1, 2]).length == 2
        np.testing.assert_array_equal(np.concatenate(freed), [20, 21])

    def test_evict_respects_lock(self):
        tree = make_tree()
        tree.insert([1, 2], np.array([10, 11], dtype=np.int32))
        res = tree.match_prefix([1, 2])
        tree.inc_lock_ref(res.last_node)
        assert tree.evict(10) == 0
        assert tree.match_prefix([1, 2]).length == 2
        tree.dec_lock_ref(res.last_node)
        assert tree.evict(10) == 2
        assert tree.match_prefix([1, 2]).length == 0

    def test_evict_cascades_to_parent(self):
        tree = make_tree()
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        tree.match_prefix([1, 2])  # split into [1,2] -> [3,4]
        assert tree.evict(4) == 4
        assert tree.total_size() == 0

    def test_size_accounting(self):
        tree = make_tree()
        tree.insert([1, 2, 3], np.array([10, 11, 12], dtype=np.int32))
        assert tree.evictable_size() == 3
        assert tree.protected_size() == 0
        res = tree.match_prefix([1, 2, 3])
        tree.inc_lock_ref(res.last_node)
        assert tree.evictable_size() == 0
        assert tree.protected_size() == 3
        tree.dec_lock_ref(res.last_node)
        assert tree.evictable_size() == 3
        assert tree.protected_size() == 0

    def test_lock_accounting_across_split(self):
        tree = make_tree()
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        res = tree.match_prefix([1, 2])  # splits; lock only the [1,2] node
        tree.inc_lock_ref(res.last_node)
        assert tree.protected_size() == 2
        assert tree.evictable_size() == 2
        # Only the unlocked tail can be evicted.
        assert tree.evict(100) == 2
        tree.dec_lock_ref(res.last_node)
        assert tree.evict(100) == 2


class TestEventsAndReset:
    def test_store_and_remove_events(self):
        tree = make_tree(enable_events=True)
        ev0 = tree.take_events()
        assert any(isinstance(e, AllBlocksCleared) for e in ev0)
        tree.insert([1, 2, 3], np.array([10, 11, 12], dtype=np.int32))
        (stored,) = [e for e in tree.take_events() if isinstance(e, BlockStored)]
        assert stored.token_ids == (1, 2, 3)
        assert stored.parent_block_hash is None
        tree.evict(3)
        (removed,) = [e for e in tree.take_events() if isinstance(e, BlockRemoved)]
        # Every per-page block hash is reported, not just the last one, so an
        # external observer mirroring the journal stays consistent.
        assert removed.block_hashes == stored.block_hashes

    def test_event_parent_chaining(self):
        tree = make_tree(enable_events=True)
        tree.insert([1, 2], np.array([10, 11], dtype=np.int32))
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        events = [e for e in tree.take_events() if isinstance(e, BlockStored)]
        assert len(events) == 2
        assert events[1].parent_block_hash == events[0].block_hashes[-1]

    def test_event_chaining_survives_split(self):
        tree = make_tree(enable_events=True)
        tree.insert([1, 2, 3, 4], np.array([10, 11, 12, 13], dtype=np.int32))
        (e0,) = [e for e in tree.take_events() if isinstance(e, BlockStored)]
        tree.insert([1, 2, 9, 9], np.array([10, 11, 30, 31], dtype=np.int32))
        (e1,) = [e for e in tree.take_events() if isinstance(e, BlockStored)]
        # The new [9,9] leaf chains off the hash of the stored [1,2] prefix.
        assert e1.parent_block_hash == e0.block_hashes[1]
        # Evicting everything removes every hash that was ever stored.
        tree.evict(100)
        removed = [
            h
            for e in tree.take_events()
            if isinstance(e, BlockRemoved)
            for h in e.block_hashes
        ]
        assert sorted(removed) == sorted(e0.block_hashes + e1.block_hashes)

    def test_reset(self):
        tree = make_tree()
        tree.insert([1, 2, 3], np.array([10, 11, 12], dtype=np.int32))
        tree.reset()
        assert tree.total_size() == 0
        assert tree.match_prefix([1, 2, 3]).length == 0
        assert tree.evictable_size() == 0

    def test_reset_returns_slots_to_pool(self):
        from radixmesh_tpu.cache.kv_pool import PagedKVPool
        import jax.numpy as jnp

        pool = PagedKVPool(
            num_slots=8, num_layers=1, num_kv_heads=1, head_dim=2, dtype=jnp.float32
        )
        tree = make_tree(on_free=pool.free)
        tree.insert(np.arange(8), pool.alloc(8))
        assert pool.free_slots == 0
        tree.reset()
        assert pool.free_slots == 8

    def test_all_values_flatten(self):
        tree = make_tree()
        tree.insert([1, 2], np.array([10, 11], dtype=np.int32))
        tree.insert([5], np.array([30], dtype=np.int32))
        assert sorted(tree.all_values_flatten().tolist()) == [10, 11, 30]


class TestFingerprint:
    """Order-independent tree fingerprint (the fleet convergence audit's
    foundation, ``obs/fleet_plane.py``): equal key SETS must fingerprint
    equal regardless of insert order or node-split structure; any
    divergent leaf must flip it."""

    def _random_ops(self, rng, n):
        chains = [
            rng.integers(0, 6, size=rng.integers(3, 10)).astype(np.int32)
            for _ in range(3)
        ]
        ops = []
        for _ in range(n):
            chain = chains[rng.integers(0, len(chains))]
            key = chain[: rng.integers(1, len(chain) + 1)].copy()
            if rng.random() < 0.4:
                key = np.concatenate(
                    [key, rng.integers(6, 12, size=rng.integers(1, 4)).astype(np.int32)]
                )
            ops.append(key)
        return ops

    def test_any_permutation_same_fingerprint(self):
        """Property: every permutation of the same insert sequence on two
        trees yields equal fingerprints (XOR commutes; chains are pure
        path functions)."""
        rng = np.random.default_rng(7)
        for trial in range(8):
            ops = self._random_ops(rng, 20)
            ref = make_tree()
            for key in ops:
                ref.insert(key, np.arange(len(key), dtype=np.int32))
            for _ in range(3):
                perm = [ops[i] for i in rng.permutation(len(ops))]
                t = make_tree()
                for key in perm:
                    t.insert(key, np.arange(len(key), dtype=np.int32))
                assert t.fingerprint == ref.fingerprint, f"trial {trial}"
            assert ref.fingerprint != 0

    def test_single_divergent_leaf_differs(self):
        rng = np.random.default_rng(11)
        ops = self._random_ops(rng, 15)
        a, b = make_tree(), make_tree()
        for key in ops:
            a.insert(key, np.arange(len(key), dtype=np.int32))
            b.insert(key, np.arange(len(key), dtype=np.int32))
        assert a.fingerprint == b.fingerprint
        b.insert(np.array([99, 98, 97], dtype=np.int32), ids(3))
        assert a.fingerprint != b.fingerprint

    def test_match_split_does_not_change_fingerprint(self):
        """match_prefix's in-place node splits change structure but not
        the key set — the fingerprint must be structure-blind."""
        t = make_tree()
        t.insert(ids(10), ids(10))
        before = t.fingerprint
        t.match_prefix(ids(4))  # splits the 10-node at 4
        assert t.fingerprint == before
        t.insert(ids(7), ids(7))  # fully-contained prefix: no new tokens
        assert t.fingerprint == before

    def test_evict_and_delete_remove_contribution(self):
        t = make_tree()
        t.insert(ids(8), ids(8))
        empty_after_insert = t.fingerprint
        t.insert(ids(8, start=100), ids(8))
        t.evict(8)  # LRU: the first insert goes
        assert t.fingerprint != empty_after_insert
        t.evict(8)  # the second goes too
        assert t.fingerprint == 0
        # Re-inserting the same keys restores the exact fingerprint.
        t.insert(ids(8), ids(8))
        assert t.fingerprint == empty_after_insert

    def test_reset_zeroes(self):
        t = make_tree()
        t.insert(ids(6), ids(6))
        assert t.fingerprint != 0
        t.reset()
        assert t.fingerprint == 0

    def test_paged_tree_fingerprints_compare(self):
        a, b = make_tree(page_size=4), make_tree(page_size=4)
        a.insert(ids(8), ids(8))
        b.insert(ids(8), ids(8))
        assert a.fingerprint == b.fingerprint
        b.insert(ids(8, start=50), ids(8))
        assert a.fingerprint != b.fingerprint

    def test_older_than_evicts_only_stale(self):
        """TTL-sweep mode: only nodes last touched before the cutoff go."""
        t = make_tree()  # injected clock ticks 1.0 per call
        t.insert(ids(4), ids(4))
        t.insert(ids(4, start=100), ids(4))
        # Touch the second key so it is fresher than the cutoff.
        t.match_prefix(ids(4, start=100))
        cutoff = t.root.children[100].last_access_time
        freed = t.evict(10**9, older_than=cutoff)
        assert freed == 4
        assert t.match_prefix(ids(4)).length == 0
        assert t.match_prefix(ids(4, start=100)).length == 4


class TestFingerprintBuckets:
    """Bucketed fingerprint vector (anti-entropy repair,
    ``cache/repair_plane.py``): the same order-independence and
    split-invariance properties as the scalar, per bucket — plus the
    repair plane's two derived contracts: the scalar is always the XOR
    of the buckets, and a divergent key is always reachable through the
    buckets it diverges."""

    def _random_ops(self, rng, n):
        chains = [
            rng.integers(0, 6, size=rng.integers(3, 10)).astype(np.int32)
            for _ in range(3)
        ]
        ops = []
        for _ in range(n):
            chain = chains[rng.integers(0, len(chains))]
            key = chain[: rng.integers(1, len(chain) + 1)].copy()
            if rng.random() < 0.4:
                key = np.concatenate(
                    [key, rng.integers(6, 12, size=rng.integers(1, 4)).astype(np.int32)]
                )
            ops.append(key)
        return ops

    def _xor_of(self, vec):
        out = 0
        for w in vec:
            out ^= int(w)
        return out

    def test_permutation_equality(self):
        rng = np.random.default_rng(31)
        for trial in range(6):
            ops = self._random_ops(rng, 20)
            ref = make_tree()
            for key in ops:
                ref.insert(key, np.arange(len(key), dtype=np.int32))
            for _ in range(3):
                t = make_tree()
                for i in rng.permutation(len(ops)):
                    t.insert(ops[i], np.arange(len(ops[i]), dtype=np.int32))
                assert (
                    t.fingerprint_buckets() == ref.fingerprint_buckets()
                ).all(), f"trial {trial}"
            assert self._xor_of(ref.fingerprint_buckets()) == ref.fingerprint

    def test_split_invariance(self):
        """Node splits repartition a chain array between two nodes; no
        bucket may move (the repair protocol compares vectors across
        replicas whose split structures differ)."""
        t = make_tree()
        t.insert(ids(12), ids(12))
        before = t.fingerprint_buckets()
        t.match_prefix(ids(5))  # splits the 12-node at 5
        assert (t.fingerprint_buckets() == before).all()
        # A replica that INSERTED the two spans separately (different
        # structure, same key set) must agree bucket-for-bucket.
        u = make_tree()
        u.insert(ids(5), ids(5))
        u.insert(ids(12), ids(12))
        assert (u.fingerprint_buckets() == before).all()

    def test_bucket_stability_under_eviction(self):
        """Evicting a key restores the exact pre-insert vector; an empty
        tree's vector is all-zero (XOR self-inverse, per bucket)."""
        t = make_tree()
        t.insert(ids(8), ids(8))
        only_first = t.fingerprint_buckets()
        t.insert(ids(6, start=200), ids(6))
        assert (t.fingerprint_buckets() != only_first).any()
        t.match_prefix(ids(6, start=200))  # freshen the second key
        t.evict(8, older_than=t.root.children[200].last_access_time)
        assert t.match_prefix(ids(8)).length == 0  # first key evicted
        # What remains must vector-match a fresh tree holding only the
        # surviving key (eviction removed EXACTLY the evictee's words).
        u = make_tree()
        u.insert(ids(6, start=200), ids(6))
        assert (t.fingerprint_buckets() == u.fingerprint_buckets()).all()
        # Evict everything: vector must return to zero.
        t.evict(10**9)
        assert (t.fingerprint_buckets() == 0).all()
        assert t.fingerprint == 0
        # Reinsert: bit-identical vector again.
        t.insert(ids(8), ids(8))
        assert (t.fingerprint_buckets() == only_first).all()

    def test_divergent_key_lands_in_diverged_buckets(self):
        """The repair-plane invariant: whatever key two trees disagree
        on, enumerating the DIVERGED buckets on the richer tree finds a
        node whose path covers that key."""
        rng = np.random.default_rng(5)
        for trial in range(5):
            ops = self._random_ops(rng, 15)
            a, b = make_tree(), make_tree()
            for key in ops:
                a.insert(key, np.arange(len(key), dtype=np.int32))
                b.insert(key, np.arange(len(key), dtype=np.int32))
            extra = rng.integers(50, 90, size=4).astype(np.int32)
            a.insert(extra, np.arange(4, dtype=np.int32))
            diff = [
                int(i)
                for i in np.nonzero(
                    a.fingerprint_buckets() != b.fingerprint_buckets()
                )[0]
            ]
            assert diff, f"trial {trial}: divergence invisible in buckets"
            touched = a.nodes_touching_buckets(diff)
            assert any(
                len(n.key) and n.key[-1] == extra[-1] for n in touched
            ), f"trial {trial}: divergent leaf not enumerated"

    def test_path_hash_stable_across_split_structure(self):
        """Key-summary identity must match across replicas regardless of
        node boundaries: the same full path hashes equal whether stored
        as one node or split."""
        a, b = make_tree(), make_tree()
        a.insert(ids(10), ids(10))
        b.insert(ids(10), ids(10))
        b.match_prefix(ids(4))  # split b's node
        ha = {a.path_hash(n) for n in a._all_nodes() if n is not a.root}
        hb = {b.path_hash(n) for n in b._all_nodes() if n is not b.root}
        # b's extra interior node adds a PREFIX hash; the full-leaf hash
        # must be present and equal in both.
        assert ha <= hb
        assert a.path_hash(max(
            (n for n in a._all_nodes() if n is not a.root),
            key=lambda n: len(n.chain),
        )) in hb


# ---------------------------------------------------------------------------
# a resume point without a state slot: a window tail and nothing else
# ---------------------------------------------------------------------------


def _tail_tree(**kw):
    refs = {}

    def free_tail(tail):
        for p in tail.tolist():
            refs[p] -= 1

    tree = RadixTree(page_size=4, on_free_tail=free_tail, **kw)

    def tail(*pages):
        for p in pages:
            refs[p] = refs.get(p, 0) + 1
        return np.asarray(pages, np.int32)

    return tree, refs, tail


def test_a_tail_alone_is_a_resume_point():
    tree, refs, tail = _tail_tree()
    assert tree.on_free_state is None
    key = ids(24)
    tree.insert(key, np.arange(100, 124, dtype=np.int32))
    t24 = tail(7, 8)
    assert tree.attach_state(key, None, t24)
    assert not tree.attach_state(key, None, tail(9))  # it has one
    refs[9] -= 1  # declined: the count stays the caller's, who returns it
    assert not tree.attach_state(key[:8], None, None)  # nothing to hold
    assert tree.attach_state(key[:8], None, tail(3))  # splits at 8
    m = tree.match_prefix(key)
    assert m.states == [(8, None), (24, None)] and m.tail_at(24) is t24
    assert list(m.tail_at(8)) == [3] and m.tail_at(16) is None
    # the deepest resume point at or before a limit; nothing: position 0
    assert m.state_at_or_before(23) == (8, None)
    assert m.state_at_or_before(24) == (24, None)
    assert m.state_at_or_before(7) == (0, None)
    node = m.last_node
    assert node.resumable and node.state is None


@pytest.mark.parametrize("how", ["evict_states", "evict", "reset", "pinned"])
def test_every_way_a_tail_goes_its_counts_return_to_zero(how):
    tree, refs, tail = _tail_tree()
    key = ids(24)
    tree.insert(key, np.arange(100, 124, dtype=np.int32))
    assert tree.attach_state(key, None, tail(7, 8))
    assert tree.attach_state(key[:8], None, tail(3, 7))  # page 7: two tails
    assert refs == {7: 2, 8: 1, 3: 1}
    if how == "evict_states":
        assert tree.evict_states(5) == 2
        assert tree.match_prefix(key).length == 24  # the rows stay
    elif how == "pinned":
        tree.inc_lock_ref(tree.match_prefix(key).last_node)  # a live row
        assert tree.evict_states(5) == 0  # plain: unlocked only
        # the superseded one first, although locked
        assert tree.evict_states(1, pinned=set()) == 1
        assert tree.match_prefix(key).states == [(24, None)]
        assert tree.evict_states(1, pinned=set()) == 1
    elif how == "evict":
        tree.evict(24)
        assert tree.match_prefix(key).length == 0
    else:
        tree.reset()
    assert tree.match_prefix(key).states == []
    assert set(refs.values()) == {0}
    assert tree.state_evictions == (2 if how in ("evict_states", "pinned") else 0)


def test_a_tree_without_either_callback_tracks_no_resume_point():
    tree = make_tree()
    tree.insert(ids(8), ids(8))
    assert tree.match_prefix(ids(8)).states == []
    assert not tree._tracks_states


def _state_tree():
    freed = []
    tree = RadixTree(page_size=4, on_free_state=freed.append)
    return tree, freed


@pytest.mark.parametrize("n, gone", [(1, [11]), (2, [11, 12]), (3, [11, 12, 21]),
                                     (4, [11, 12, 21, 13])])
def test_a_full_state_group_gives_up_snapshots_in_order(n, gone):
    """Pressure on snapshots of megabytes (``evict_states(pinned=...)``):
    first those a deeper snapshot of the same unbranched path supersedes (a
    session's earlier turns: slots 11, 12 under 13), oldest first; then a
    locked leaf's own (a live row's prompt: its release leaves a deeper
    one); then the rest, least recently used; never a pinned one."""
    tree, freed = _state_tree()
    session, other, live = ids(24), ids(16, start=100), ids(12, start=200)
    for key in (session, other, live):
        tree.insert(key, np.arange(len(key), dtype=np.int32))
    for pos, slot in ((8, 11), (16, 12), (24, 13)):
        assert tree.attach_state(session[:pos], slot)
    assert tree.attach_state(other, 31)  # what a waiting request resumes from
    assert tree.attach_state(live, 21)
    tree.inc_lock_ref(tree.match_prefix(live).last_node)
    tree.match_prefix(session)  # touched last: the least recently used is `other`
    assert tree.evict_states(n, pinned={31}) == n
    assert freed == gone
    assert tree.state_evictions == n
    # the rows stay whatever went: a later match resumes shallower
    assert tree.match_prefix(session).length == 24
    deepest = tree.match_prefix(session).state_at_or_before(24)
    assert deepest == ((24, 13) if n < 4 else (0, None))


def test_plain_pressure_leaves_locked_snapshots_alone():
    tree, freed = _state_tree()
    key = ids(16)
    tree.insert(key, np.arange(16, dtype=np.int32))
    assert tree.attach_state(key[:8], 5) and tree.attach_state(key, 6)
    tree.inc_lock_ref(tree.match_prefix(key).last_node)
    assert tree.evict_states(4) == 0 and freed == []  # LFM2's rule: unlocked only
    assert tree.evict_states(4, pinned={6}) == 1 and freed == [5]

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


# ---------------------------------------------------------------------------
# a publish that starts at the request's locked node (``insert_below`` +
# ``attach_state_at``) leaves the tree the root walks leave
# ---------------------------------------------------------------------------


def _attach_from_root(tree, key, slot, tail):
    """The oracle: ``attach_state`` as a walk of the whole key from the
    root (what the engine's publish did before it started at the lock)."""
    node = tree.root
    while len(key) > 0:
        child = node.children.get(tree._child_key(key))
        if child is None or child.value is None:
            return False
        m = tree._match(child.key, key)
        if m < len(key) and m < len(child.key) or m == 0:
            return False
        if m < len(child.key):
            child = tree._split_node(child, m)
        node = child
        key = key[m:]
    if node is tree.root or node.resumable or (slot is None and tail is None):
        return False
    node.state, node.tail = slot, tail
    return True


class _Row:
    def __init__(self, prompt):
        self.prompt = np.asarray(prompt, np.int32)
        self.out = []
        self.slots = self.own = np.empty(0, np.int32)
        self.lock, self.lock_end = None, 0
        self.prefix_len = self.canon_len = 0
        self.snaps = []

    def key(self, start, end):
        return np.concatenate(
            [self.prompt, np.asarray(self.out, np.int32)])[start:end]

    @property
    def kv_len(self):
        return len(self.prompt) + len(self.out)


class _Side:
    """One tree with the requests that publish into it: ``from_lock``
    False walks from the root (insert, match_prefix, attach by key), True
    starts at each request's lock."""

    def __init__(self, from_lock, page, states, tails, clock):
        self.from_lock, self.page = from_lock, page
        self.states, self.tails = states, tails
        self.freed = []  # every slot, state slot and tail page given back
        self.next_slot = self.next_state = self.next_page = 0
        self.tree = RadixTree(
            page_size=page, enable_events=True, time_fn=lambda: clock[0],
            on_free=lambda a: self.freed.append(("kv", tuple(sorted(a.tolist())))),
            on_free_state=(
                (lambda s: self.freed.append(("state", s))) if states else None),
            on_free_tail=(
                (lambda t: self.freed.append(("tail", tuple(t.tolist()))))
                if tails else None),
        )
        self.rows = {}

    def alloc(self, n):
        self.next_slot += n
        return np.arange(self.next_slot - n, self.next_slot, dtype=np.int32)

    def free(self, slots):
        self.freed.append(("kv", tuple(sorted(np.asarray(slots).tolist()))))

    def admit(self, rid, prompt, resume_below):
        """An admission as ``Engine._acquire_prompt_slots`` makes it: the
        match, the lock at its end, slots for what is computed — from the
        match, or with ``resume_below`` from that many pages above it (a
        model that resumes from a snapshot shallower than its match)."""
        row = self.rows[rid] = _Row(prompt)
        ps = self.page
        m = self.tree.match_prefix(row.prompt)
        matched = min(m.length, (len(row.prompt) - 1) // ps * ps)
        reuse = max(matched - resume_below * ps, 0)
        self.tree.inc_lock_ref(m.last_node)
        row.lock, row.lock_end = m.last_node, m.length
        row.prefix_len = row.canon_len = reuse
        row.own = self.alloc(len(row.prompt) - reuse)
        row.slots = np.concatenate([m.indices()[:reuse], row.own])
        if self.states or self.tails:
            have = {pos for pos, _ in m.states}
            for pos in sorted({len(row.prompt) // ps * ps, matched}):
                if pos > reuse and pos not in have:
                    row.snaps.append((pos, self._snap()))

    def _snap(self):
        slot = tail = None
        if self.states:
            self.next_state += 1
            slot = self.next_state
        if self.tails:
            self.next_page += 2
            tail = np.asarray([self.next_page - 1, self.next_page], np.int32)
        return slot, tail

    def extend(self, rid, tokens):
        row = self.rows[rid]
        new = self.alloc(len(tokens))
        row.out += list(tokens)
        row.slots = np.concatenate([row.slots, new])
        row.own = np.concatenate([row.own, new])

    def publish(self, rid):
        row = self.rows[rid]
        tree, ps, key_len = self.tree, self.page, row.kv_len
        aligned = key_len - key_len % ps
        if self.from_lock:
            base = row.lock_end
            lo = min(row.canon_len, base)
            present, new_lock, canon = tree.insert_below(
                row.lock, base, row.key(base, aligned),
                row.slots[base:aligned].copy(), canon_from=lo)
            matched = base + present
            assert len(canon) == aligned - lo
        else:
            lo = 0
            key = row.key(0, key_len)
            matched = tree.insert(key, row.slots[:key_len].copy())
            m2 = tree.match_prefix(key)
            new_lock, canon = m2.last_node, m2.indices()
            assert len(canon) == aligned
        if matched > row.prefix_len:
            old = row.slots[lo:aligned]
            dup = old[old != canon]
            if dup.size:
                old[:] = canon
                row.own = np.setdiff1d(row.own, dup)
                self.free(dup)
        row.own = np.setdiff1d(row.own, row.slots[matched:aligned])
        if new_lock is not row.lock:
            tree.inc_lock_ref(new_lock)
            tree.dec_lock_ref(row.lock)
            row.lock = new_lock
        row.lock_end = row.canon_len = aligned
        keep = []
        for pos, (slot, tail) in row.snaps:
            if pos > aligned:
                keep.append((pos, (slot, tail)))
            elif not (
                tree.attach_state_at(new_lock, aligned, pos, slot, tail)
                if self.from_lock else
                _attach_from_root(tree, row.key(0, pos), slot, tail)
            ):
                self.freed.append(("declined", pos, slot))
        row.snaps = keep

    def release(self, rid):
        row = self.rows[rid]
        cut = row.kv_len // self.page * self.page
        if (self.states or self.tails) and cut > len(row.prompt):
            row.snaps.append((cut, self._snap()))  # the boundary's state
        self.publish(rid)
        if row.own.size:
            self.free(row.own)
        self.tree.dec_lock_ref(row.lock)
        del self.rows[rid]

    def evict(self, n, to_host):
        def writeback(node):  # the host tier takes it: the node stays
            node.host_value = np.asarray(node.value, np.int32) + 1_000_000
            return True

        self.tree._evict_impl(n, writeback=writeback if to_host else None)

    def dump(self):
        def rec(n):
            return (
                n.key.tolist(),
                None if n.value is None else np.asarray(n.value).tolist(),
                None if n.host_value is None else n.host_value.tolist(),
                n.lock_ref, n.hit_count, n.last_access_time, n.state,
                None if n.tail is None else n.tail.tolist(),
                n.block_hashes, n.chain.tolist(),
                [(k, rec(c)) for k, c in n.children.items()],  # in dict order
            )

        t = self.tree
        rows = {
            rid: (r.slots.tolist(), sorted(r.own.tolist()), r.lock_end,
                  t.path_hash(r.lock), r.lock.lock_ref, r.snaps and
                  [(p, s, None if tl is None else tl.tolist())
                   for p, (s, tl) in r.snaps])
            for rid, r in self.rows.items()
        }
        return {
            "tree": rec(t.root), "evictable": t.evictable_size_,
            "protected": t.protected_size_, "total": t.total_size(),
            "fingerprint": t.fingerprint_, "buckets": t.fp_buckets_.tolist(),
            "events": list(t._events), "freed": list(self.freed),
            "state_evictions": t.state_evictions, "rows": rows,
        }


class _Twins:
    """The same operations on a root-walking side and a from-the-lock
    side, compared after every one of them."""

    def __init__(self, page, states=False, tails=False):
        self.clock = [0.0]
        self.sides = [_Side(f, page, states, tails, self.clock) for f in (False, True)]
        self.page = page

    def __getattr__(self, op):
        def run(*args):
            self.clock[0] += 1.0
            for side in self.sides:
                getattr(side, op)(*args)
            a, b = (side.dump() for side in self.sides)
            for part in a:
                assert a[part] == b[part], (op, args, part)
            return self.sides[1]

        return run

    def match(self, key):
        """Another request's admission walk (it splits where it ends)."""
        self.clock[0] += 1.0
        lens = [s.tree.match_prefix(np.asarray(key, np.int32)).length
                for s in self.sides]
        assert lens[0] == lens[1]


_PAGES = [1, 16, 128]
_KINDS = {"rows": {}, "states": {"states": True}, "tails": {"tails": True},
          "states_and_tails": {"states": True, "tails": True}}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", _KINDS)
@pytest.mark.parametrize("page", _PAGES)
def test_a_publish_from_the_lock_leaves_the_tree_the_root_walk_leaves(
        page, kind, seed):
    """Seeded sessions on shared documents: admissions (some resuming
    above their match), replies, mid-reply publishes, releases, other
    requests' walks, evictions to nothing and to the host tier — the twin
    trees, their requests' slots and everything given back stay equal."""
    rng = np.random.default_rng(1000 * page + seed)
    tw = _Twins(page, **_KINDS[kind])

    def some(n, choice):  # one of a few spans: requests meet on them
        g = np.random.default_rng(choice)
        return g.integers(0, 50, int(g.integers(1, n + 1)))

    docs = [some(4 * page, 100 + i) for i in range(2)]
    finished = []  # whole sequences of released requests: a next turn's stem
    live, waiting, rid = [], set(), 0  # waiting: admitted, prompt not yet published
    for _ in range(80):
        op = rng.choice(["admit", "extend", "publish", "release", "match", "evict"],
                        p=[0.3, 0.3, 0.1, 0.15, 0.1, 0.05])
        if op == "admit" and len(live) < 5:
            stems = docs + finished[-2:]
            stem = stems[int(rng.integers(len(stems)))]
            cut = len(stem) - int(rng.integers(2)) * min(page, len(stem) - 1)
            prompt = np.concatenate(
                [stem[:cut], some(2 * page + 1, int(rng.integers(2)))])
            resume_below = int(rng.integers(0, 3)) if kind != "rows" else 0
            tw.admit(rid, prompt, resume_below)
            waiting.add(rid)  # a wave installs its members after all are admitted
            live.append(rid)
            rid += 1
        elif op in ("extend", "publish", "release") and live:
            r = live[int(rng.integers(len(live)))]
            if r in waiting:
                tw.publish(r)  # the prompt's install
                waiting.discard(r)
            elif op == "extend":
                tw.extend(r, some(page + 3, 200 + int(rng.integers(2))).tolist())
            elif op == "publish":
                tw.publish(r)
            else:
                row = tw.sides[1].rows[r]
                finished.append(row.key(0, row.kv_len))
                tw.release(r)
                live.remove(r)
        elif op == "match":
            stem = (docs + finished)[int(rng.integers(len(docs) + len(finished)))]
            tw.match(stem[: int(rng.integers(0, len(stem) + 1))])
        elif op == "evict":
            tw.evict(int(rng.integers(1, 4 * page)), bool(rng.integers(2)))
    for r in live:
        tw.release(r)
    side = tw.sides[1]
    assert side.tree.protected_size() == 0 and not side.rows
    # every slot handed out is in the tree or was given back, exactly once
    back = [s for kind_, *rest in side.freed if kind_ == "kv" for s in rest[0]]
    held = side.tree.all_values_flatten().tolist()
    assert sorted(back + held) == list(range(side.next_slot))


@pytest.mark.parametrize("page", _PAGES)
def test_the_locked_node_split_by_another_request_keeps_its_end(page):
    tw = _Twins(page, states=True)
    doc = ids(4 * page)
    tw.admit(0, np.concatenate([doc, ids(page + 1, start=500)]), 0)
    tw.publish(0)
    tw.release(0)
    tw.admit(1, np.concatenate([doc, ids(page + 1, start=500), ids(3, start=900)]), 0)
    row = tw.sides[1].rows[1]
    lock, end = row.lock, row.lock_end
    assert end == (5 * page + 1) // page * page and len(lock.key) == end
    tw.match(doc[: 2 * page])  # splits the locked node above its end
    tw.match(np.concatenate([doc[: 3 * page], ids(page, start=700)]))  # and again
    assert row.lock is lock and len(lock.key) == end - 3 * page  # the lower half
    tw.publish(1)
    tw.extend(1, [7] * (page + 2))
    tw.release(1)
    m = tw.sides[1].tree.match_prefix(np.concatenate([doc, ids(page + 1, start=500)]))
    assert m.length == end


@pytest.mark.parametrize("page", _PAGES)
def test_a_publish_canonicalises_from_a_prefix_above_its_lock(page):
    """A resuming model computes again from a snapshot shallower than its
    match: the slots it wrote over ``[prefix_len, lock's end)`` are
    duplicates of the tree's, found by climbing the lock's parents."""
    tw = _Twins(page, states=True, tails=True)
    doc = ids(6 * page)
    tw.admit(0, doc, 0)
    tw.publish(0)
    tw.release(0)
    tw.match(doc[: 2 * page])
    tw.match(doc[: 4 * page])  # three nodes above the next lock's end
    tw.admit(1, np.concatenate([doc, ids(5, start=800)]), 3)
    row = tw.sides[1].rows[1]
    assert row.prefix_len == 3 * page and row.lock_end == 6 * page
    before = row.slots[3 * page: 6 * page].copy()
    side = tw.publish(1)
    assert side.freed[-1] == ("kv", tuple(before.tolist()))  # exactly those
    assert row.slots[: 6 * page].tolist() == list(range(6 * page))
    assert row.canon_len == row.lock_end == (6 * page + 5) // page * page
    tw.extend(1, [3] * (2 * page))
    tw.release(1)


@pytest.mark.parametrize("page", _PAGES)
def test_a_key_that_ends_inside_a_node_splits_it_once(page):
    tw = _Twins(page)
    long = ids(5 * page)
    tw.admit(0, long, 0)
    tw.publish(0)
    tw.release(0)
    tw.evict(10 * page, False)  # nothing held: the tree is empty again
    tw.admit(1, long[: 2 * page + 1], 0)  # cold: locked at the root
    tw.admit(2, long, 0)
    tw.publish(2)
    tw.release(2)  # the whole of it, one node
    side = tw.publish(1)  # ends inside that node
    row = side.rows[1]
    end = (2 * page + 1) // page * page
    assert row.lock_end == end and len(row.lock.key) == end
    assert len(next(iter(row.lock.children.values())).key) == 5 * page - end
    assert side.freed[-1][0] == "kv" and len(side.freed[-1][1]) == end
    tw.release(1)


@pytest.mark.parametrize("page", _PAGES)
def test_a_duplicate_published_first_frees_exactly_the_differing_slots(page):
    tw = _Twins(page)
    doc, q = ids(2 * page), ids(page, start=300)
    tw.admit(0, doc, 0)
    tw.publish(0)
    tw.release(0)
    for rid in (1, 2):  # two requests ask the same: neither sees the other
        tw.admit(rid, np.concatenate([doc, q, [9]]), 0)
    tw.publish(1)

    def al(n):
        return n // page * page

    end = al(3 * page + 1)
    mine = tw.sides[1].rows[2].slots[2 * page: end].copy()
    theirs = tw.sides[1].rows[1].slots[2 * page: end].copy()
    side = tw.publish(2)
    assert side.freed[-1] == ("kv", tuple(mine.tolist()))
    assert side.rows[2].slots[:end].tolist() == [
        *range(2 * page), *theirs.tolist()]
    for rid in (1, 2):  # replies that agree for a page, then differ
        tw.extend(rid, [5] * page + [rid] * page)
    tw.release(1)
    n = len(tw.sides[1].freed)
    side = tw.release(2)
    own = side.freed[n:]
    # what agrees and the residue went back, what differs stays
    agree, whole = al(4 * page + 1), al(5 * page + 1)
    assert sum(len(f[1]) for f in own if f[0] == "kv") == (
        agree - end + 5 * page + 1 - whole)
    assert side.tree.total_size() == whole + whole - agree


@pytest.mark.parametrize("page", _PAGES)
def test_a_host_resident_node_below_the_lock_adopts_the_span(page):
    tw = _Twins(page, tails=True)
    doc = ids(2 * page)
    reply = ids(2 * page, start=400)
    tw.admit(0, doc, 0)
    tw.publish(0)
    tw.extend(0, reply.tolist())
    tw.release(0)
    tw.admit(1, np.concatenate([doc, [1]]), 0)  # holds the document
    tw.publish(1)
    tw.evict(2 * page, True)  # the reply goes to the host tier, its node stays
    side = tw.sides[1]

    def hosted():
        return [n for n in side.tree._all_nodes() if n.host_value is not None]

    (below,) = hosted()
    assert below.value is None and len(below.key) == 2 * page
    tw.admit(2, np.concatenate([doc, reply[:page], [2]]), 0)
    assert side.rows[2].lock_end == 2 * page  # the device-resident prefix
    n_free = len(side.freed)
    tw.publish(2)  # computes the reply's first page again: adopted, not a duplicate
    assert len(side.freed) == n_free
    node, below = hosted()  # split where the prompt left it
    assert node.value is not None and below.value is None
    assert len(node.key) == page
    assert side.rows[2].own.size == (3 * page + 1) % page
    tw.release(2)
    tw.release(1)


def test_insert_below_refuses_a_depth_that_is_not_the_nodes_end():
    tree = make_tree(page_size=4)
    tree.insert(ids(8), ids(8))
    node = tree.match_prefix(ids(8)).last_node
    with pytest.raises(ValueError, match="does not end at depth"):
        tree.insert_below(node, 4, ids(4, start=8), ids(4))
    with pytest.raises(ValueError, match="does not end at depth"):
        tree.insert_below(node, 8, ids(4, start=8), ids(4), canon_from=12)
    with pytest.raises(ValueError, match="value length"):
        tree.insert_below(node, 8, ids(4, start=8), ids(3))
    # nothing below: the node itself, and its own slots from where asked
    present, last, canon = tree.insert_below(node, 8, ids(3, start=8), ids(3),
                                             canon_from=2)
    assert (present, last) == (0, node) and canon.tolist() == list(range(2, 8))


def test_attach_state_at_climbs_to_the_node_that_ends_there():
    tree, freed = _state_tree()
    key = ids(24)
    tree.insert(key[:8], ids(8))
    tree.insert(key, ids(24))
    deepest = tree.match_prefix(key).last_node
    assert not tree.attach_state_at(deepest, 24, 28, 9)  # below the node's end
    assert not tree.attach_state_at(deepest, 24, 0, 9)  # the root takes none
    assert tree.attach_state_at(deepest, 24, 8, 5)  # the parent ends there
    assert tree.attach_state_at(deepest, 24, 16, 6)  # splits the node at 16
    assert tree.attach_state_at(deepest, 24, 24, 7)
    assert not tree.attach_state_at(deepest, 24, 16, 8)  # it has one
    assert tree.match_prefix(key).states == [(8, 5), (16, 6), (24, 7)]
    assert len(deepest.key) == 8 and freed == []

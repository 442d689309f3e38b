"""Durable KV spill tier (``cache/kv_tier.py``): extent-file crash
discipline (commit-by-rename, checksum-verified reads, torn tails and
bit flips dropped — never served), the three-tier radix walk, write-
behind destage + demote-over-drop eviction, cold-cell resurrection,
and byte-identical resume after a whole-cell kill. Every scratch dir is
a pytest ``tmp_path`` (nothing lands in the repo tree)."""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from radixmesh_tpu.cache.kv_tier import (
    EXTENT_SCHEMA_VERSION,
    DiskKVTier,
    ExtentRef,
    node_heat,
)
from radixmesh_tpu.cache.radix_tree import RadixTree, TreeNode
from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import RequestState, SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params

PAGE = 4


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def make_engine(tiny, tier_dir, **kw):
    cfg, params = tiny
    kw.setdefault("num_slots", 1024)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 3)
    kw.setdefault("host_cache_slots", 512)
    kw.setdefault("kv_tier_watermark", 0.0)
    kw.setdefault("kv_tier_destage_budget", 64)
    kw.setdefault("kv_tier_destage_interval_s", 0.0)
    kw.setdefault("kv_transfer_chunk_tokens", 32)
    return Engine(cfg, params, kv_tier_dir=str(tier_dir), **kw)


def settle(eng, timeout=15.0):
    """Pump until every spill committed (the destager's engine half)."""
    plane = eng.kv_transfer
    plane.wait_host_ready()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        eng.step()
        if plane.spills_idle():
            return
        plane.wait_progress(0.01)
    raise AssertionError("spills never settled")


def spill_everything(eng, prompts, sampling):
    """Serve, push device -> host, destage host -> disk, commit."""
    for p in prompts:
        eng.generate([list(p)], sampling)
    eng.tree.evict(1 << 20)
    eng.kv_transfer.wait_host_ready()
    eng.tree.destage_cold(force=True, budget=1 << 20)
    settle(eng)


# ---------------------------------------------------------------------------
# extent format: commit discipline + corruption property tests
# ---------------------------------------------------------------------------


@pytest.mark.quick
class TestExtentFormat:
    def _tier(self, tmp_path, **kw):
        kw.setdefault("page_size", PAGE)
        return DiskKVTier(str(tmp_path / "tier"), name="fmt", **kw)

    def _payload(self, n=8, seed=0):
        rng = np.random.default_rng(seed)
        prefix = rng.integers(1, 100, size=12).astype(np.int32)
        seg = rng.integers(1, 100, size=n).astype(np.int32)
        kv = rng.standard_normal((2, 2, n, 2, 4)).astype(np.float32)
        return prefix, seg, kv

    def test_write_read_roundtrip(self, tmp_path):
        tier = self._tier(tmp_path)
        prefix, seg, kv = self._payload()
        ref = tier.write_extent(prefix, seg, kv, None)
        assert ref is not None and len(ref) == len(seg)
        got, scales = tier.read_extent(ref)
        assert scales is None
        np.testing.assert_array_equal(got, kv)

    def test_quant_scales_roundtrip(self, tmp_path):
        tier = self._tier(tmp_path)
        prefix, seg, _ = self._payload()
        rng = np.random.default_rng(1)
        kv = rng.integers(-128, 127, size=(2, 2, 8, 2, 4)).astype(np.int8)
        scales = rng.standard_normal((2, 2, 8, 2)).astype(np.float32)
        ref = tier.write_extent(prefix, seg, kv, scales)
        got, got_s = tier.read_extent(ref)
        np.testing.assert_array_equal(got, kv)
        np.testing.assert_array_equal(got_s, scales)

    def test_same_path_respill_replaces_not_duplicates(self, tmp_path):
        tier = self._tier(tmp_path)
        prefix, seg, kv = self._payload()
        tier.write_extent(prefix, seg, kv, None)
        tier.write_extent(prefix, seg, kv * 2, None)
        assert tier.extents == 1

    def test_truncation_anywhere_is_detected_never_served(self, tmp_path):
        """Property: a committed extent truncated at ANY offset reads as
        None (counted), and the file is dropped — the torn-tail rule."""
        rng = np.random.default_rng(2)
        for trial in range(12):
            tier = self._tier(tmp_path / f"t{trial}")
            prefix, seg, kv = self._payload(seed=trial)
            ref = tier.write_extent(prefix, seg, kv, None)
            size = os.path.getsize(ref.path)
            cut = int(rng.integers(0, size))
            with open(ref.path, "r+b") as fh:
                fh.truncate(cut)
            assert tier.read_extent(ref) is None
            assert not os.path.exists(ref.path)

    def test_bitflip_anywhere_is_detected_never_served(self, tmp_path):
        """Property: one flipped bit at ANY byte offset — preamble,
        header, tokens, or KV payload — fails verification."""
        rng = np.random.default_rng(3)
        for trial in range(16):
            tier = self._tier(tmp_path / f"b{trial}")
            prefix, seg, kv = self._payload(seed=100 + trial)
            ref = tier.write_extent(prefix, seg, kv, None)
            size = os.path.getsize(ref.path)
            off = int(rng.integers(0, size))
            with open(ref.path, "r+b") as fh:
                fh.seek(off)
                b = fh.read(1)
                fh.seek(off)
                fh.write(bytes([b[0] ^ (1 << int(rng.integers(0, 8)))]))
            assert tier.read_extent(ref) is None

    def test_future_schema_refused(self, tmp_path):
        tier = self._tier(tmp_path)
        prefix, seg, kv = self._payload()
        ref = tier.write_extent(prefix, seg, kv, None)
        with open(ref.path, "r+b") as fh:
            raw = bytearray(fh.read())
            # Preamble: magic(4) schema(H at offset 4).
            raw[4:6] = (EXTENT_SCHEMA_VERSION + 1).to_bytes(2, "little")
            fh.seek(0)
            fh.write(bytes(raw))
        assert tier.read_extent(ref) is None

    def test_crash_mid_spill_leaves_committed_extents_readable(self, tmp_path):
        """kill -9 mid-write = a leftover temp file; the rename is the
        commit point, so every committed extent scans clean and the
        torn temp is removed, never grafted."""
        tier = self._tier(tmp_path)
        prefix, seg, kv = self._payload()
        tier.write_extent(prefix, seg, kv, None)
        torn = os.path.join(tier.dir, "ext-dead.kv.tmp.12345")
        with open(torn, "wb") as fh:
            fh.write(b"half-written garbage")
        tier2 = DiskKVTier(tier.dir, page_size=PAGE, name="fmt2")
        metas = tier2.scan()
        assert len(metas) == 1
        np.testing.assert_array_equal(metas[0].seg_tokens, seg)
        assert not os.path.exists(torn)
        got, _ = tier2.read_extent(metas[0].ref)
        np.testing.assert_array_equal(got, kv)

    def test_capacity_drops_oldest_and_counts(self, tmp_path):
        tier = self._tier(tmp_path, capacity_bytes=1)
        p1 = self._payload(seed=10)
        p2 = self._payload(seed=11)
        tier.write_extent(*p1, None)
        tier.write_extent(*p2, None)
        # Over a 1-byte budget only the newest (protected) write stays.
        assert tier.extents == 1
        assert any(m[2] == "drop" for m in tier.recent_moves)

    def test_retire_is_in_memory_until_drained(self, tmp_path):
        tier = self._tier(tmp_path)
        prefix, seg, kv = self._payload()
        ref = tier.write_extent(prefix, seg, kv, None)
        tier.retire(ref)
        assert os.path.exists(ref.path)  # engine-thread safe: no unlink
        assert tier.drain_retired() == 1
        assert not os.path.exists(ref.path)

    def test_node_heat_decays(self):
        n = TreeNode()
        n.hit_count = 8
        n.last_access_time = 100.0
        assert node_heat(n, 100.0, half_life_s=10.0) == pytest.approx(8.0)
        assert node_heat(n, 110.0, half_life_s=10.0) == pytest.approx(4.0)
        assert node_heat(n, 200.0, half_life_s=10.0) < 0.01


# ---------------------------------------------------------------------------
# three-tier radix walk
# ---------------------------------------------------------------------------


def _ref(n):
    return ExtentRef(path=f"/fake/{n}", n_seg=n, nbytes=1, shard=0)


@pytest.mark.quick
class TestTierWalk:
    def _tree(self):
        t = RadixTree(page_size=1)
        return t

    def test_disk_extension_returned_in_order(self):
        t = self._tree()
        t.insert([1, 2, 3, 4, 5, 6], np.arange(6, dtype=np.int32))
        # device prefix [1,2] -> host [3,4] -> disk [5,6]
        node = t.root.children[1]
        a = t._split_node(node, 2)
        mid = a.children[self._ck(t, a)]
        b = t._split_node(mid, 2)
        leaf = b.children[self._ck(t, b)]
        mid_n = b
        mid_n.host_value = np.asarray(mid_n.value)
        mid_n.value = None
        leaf.disk_value = _ref(2)
        leaf.value = None
        m = t.match_prefix([1, 2, 3, 4, 5, 6])
        assert m.length == 2
        assert m.host_length == 2
        assert m.disk_length == 2
        assert [n is leaf for n in m.disk_nodes] == [True]
        assert m.restorable_nodes() == [mid_n, leaf]

    @staticmethod
    def _ck(tree, node):
        (k,) = node.children.keys()
        return k

    def test_host_below_disk_breaks_the_walk(self):
        t = self._tree()
        t.insert([1, 2, 3, 4], np.arange(4, dtype=np.int32))
        node = t.root.children[1]
        a = t._split_node(node, 2)
        deep = a.children[self._ck(t, a)]
        a.value = None
        a.disk_value = _ref(2)  # disk-resident interior
        deep.host_value = np.asarray(deep.value)
        deep.value = None  # host below disk: not prefix-closed
        m = t.match_prefix([1, 2, 3, 4])
        assert m.disk_length == 2 and m.host_length == 0

    def test_partial_disk_match_never_splits(self):
        t = self._tree()
        t.insert([1, 2, 3, 4], np.arange(4, dtype=np.int32))
        node = t.root.children[1]
        node.disk_value = _ref(4)
        node.value = None
        n_before = sum(1 for _ in t._all_nodes())
        m = t.match_prefix([1, 2, 9, 9])  # diverges mid-extent
        assert m.disk_length == 0
        assert sum(1 for _ in t._all_nodes()) == n_before

    def test_split_detaches_extent_via_hook(self):
        t = self._tree()
        retired = []
        t.on_disk_detach = retired.append
        t.insert([1, 2, 3, 4], np.arange(4, dtype=np.int32))
        node = t.root.children[1]
        ref = _ref(4)
        node.disk_value = ref
        t._split_node(node, 2)
        assert retired == [ref]
        assert node.disk_value is None

    def test_remove_node_retires_extents(self):
        t = self._tree()
        retired = []
        t.on_disk_detach = retired.append
        t.insert([1, 2, 3, 4], np.arange(4, dtype=np.int32))
        node = t.root.children[1]
        node.disk_value = _ref(4)
        node.value = None
        t._remove_node(node, [])
        assert len(retired) == 1

    def test_reset_retires_extents(self):
        t = self._tree()
        retired = []
        t.on_disk_detach = retired.append
        t.insert([1, 2, 3, 4], np.arange(4, dtype=np.int32))
        t.root.children[1].disk_value = _ref(4)
        t.reset()
        assert len(retired) == 1


# ---------------------------------------------------------------------------
# engine integration: spill / demote / restore / resurrect / resume
# ---------------------------------------------------------------------------


class TestEngineTier:
    def _prompts(self, cfg, n, tokens, seed=0):
        rng = np.random.default_rng(seed)
        return [
            rng.integers(1, cfg.vocab_size - 1, size=tokens).astype(np.int32)
            for _ in range(n)
        ]

    def test_tier_requires_host_cache(self, tiny, tmp_path):
        cfg, params = tiny
        with pytest.raises(ValueError, match="host tier"):
            Engine(cfg, params, kv_tier_dir=str(tmp_path / "d"),
                   host_cache_slots=0)

    def test_tier_auto_arms_the_plane(self, tiny, tmp_path):
        eng = make_engine(tiny, tmp_path / "arm", kv_transfer_async=False)
        assert eng.kv_transfer is not None
        assert eng.tree.disk is not None
        eng.kv_transfer.close()

    def test_spill_kill_resurrect_serves_from_disk(self, tiny, tmp_path):
        cfg, params = tiny
        d = tmp_path / "cell"
        prompts = self._prompts(cfg, 3, 96)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, d)
        spill_everything(eng, prompts, samp)
        assert eng._kv_tier.extents >= 3
        eng.kv_transfer.close()  # the whole cell dies: no flush
        del eng

        eng2 = make_engine(tiny, d)
        assert eng2.resurrected["grafted_nodes"] >= 3
        m = eng2.tree.match_prefix(prompts[0])
        assert m.disk_length > 0 and m.length == 0 and m.host_length == 0
        c0 = eng2.stats.cached_tokens
        eng2.generate([list(prompts[0])], samp)
        assert eng2.stats.cached_tokens - c0 > 0  # served from disk
        eng2.kv_transfer.close()

    def test_corrupt_extent_degrades_to_shorter_verified_prefix(
        self, tiny, tmp_path
    ):
        cfg, params = tiny
        d = tmp_path / "corrupt"
        prompts = self._prompts(cfg, 2, 96, seed=7)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, d)
        spill_everything(eng, prompts, samp)
        eng.kv_transfer.close()
        del eng
        files = sorted(glob.glob(str(d / "ext-*.kv")))
        with open(files[0], "r+b") as fh:
            fh.seek(os.path.getsize(files[0]) // 2)
            b = fh.read(1)
            fh.seek(-1, 1)
            fh.write(bytes([b[0] ^ 0xFF]))
        eng2 = make_engine(tiny, d)
        # The corrupt extent was dropped at scan; the survivor grafted.
        corrupt = sum(
            int(m.value) for m in eng2._kv_tier._m_corrupt_by.values()
        )
        assert corrupt >= 1
        # Both prompts still SERVE (one recomputes, one hits disk) and
        # nothing raises — corrupt KV never reaches the pool.
        for p in prompts:
            eng2.generate([list(p)], samp)
        eng2.kv_transfer.close()

    def test_eviction_prefers_demote_over_drop(self, tiny, tmp_path):
        """A disk-backed host copy frees its arena slots WITHOUT the
        node dying; an unbacked one dies — demote-over-drop."""
        cfg, params = tiny
        prompts = self._prompts(cfg, 2, 64, seed=3)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, tmp_path / "demote")
        spill_everything(eng, prompts, samp)
        dropped0 = eng.tree._m_host_evicted.value
        freed = eng.tree._evict_host(1 << 20)
        assert freed > 0
        # Demotes, not drops: the host-evicted (died) counter is flat
        # and every prefix still matches through its extent.
        assert eng.tree._m_host_evicted.value == dropped0
        for p in prompts:
            m = eng.tree.match_prefix(p)
            assert m.disk_length > 0
        eng.kv_transfer.close()

    def test_destage_min_heat_lets_cold_nodes_die(self, tiny, tmp_path):
        cfg, params = tiny
        prompts = self._prompts(cfg, 2, 64, seed=4)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(
            tiny, tmp_path / "cold", kv_tier_min_heat=1e9,
        )
        for p in prompts:
            eng.generate([list(p)], samp)
        eng.tree.evict(1 << 20)
        eng.kv_transfer.wait_host_ready()
        # Non-forced destage respects the heat floor: nothing qualifies.
        assert eng.tree.destage_cold(
            watermark=0.0, min_heat=1e9, budget=64
        ) == 0
        # The drain path is forced: durability wins over heat.
        assert eng.tree.destage_cold(force=True, budget=64) > 0
        eng.kv_transfer.close()

    def test_parked_disk_restore_while_decode_steps(self, tiny, tmp_path):
        cfg, params = tiny
        prompts = self._prompts(cfg, 2, 96, seed=5)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, tmp_path / "park")
        spill_everything(eng, prompts, samp)
        eng.tree._evict_host(1 << 20)  # disk-only residency
        bg = eng.add_request(
            list(self._prompts(cfg, 1, 32, seed=6)[0]),
            SamplingParams(temperature=0.0, max_new_tokens=32),
        )
        eng.step()
        req = eng.add_request(list(prompts[0]), samp)
        parked = False
        decode_during = 0
        for _ in range(5000):
            before = eng.stats.decode_steps
            eng.step()
            if req.state is RequestState.RESTORING:
                parked = True
            if eng._restoring:
                decode_during += eng.stats.decode_steps - before
            if req.state is RequestState.FINISHED:
                break
        assert req.state is RequestState.FINISHED
        assert parked, "disk restores must park, never run inline"
        assert decode_during > 0, "decode blocked on a disk restore"
        if bg.state is not RequestState.FINISHED:
            eng.cancel(bg.rid)
        eng.kv_transfer.close()

    def test_prefetch_hint_restores_from_disk_ahead_of_request(
        self, tiny, tmp_path
    ):
        cfg, params = tiny
        prompts = self._prompts(cfg, 1, 64, seed=8)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, tmp_path / "hint")
        spill_everything(eng, prompts, samp)
        eng.tree._evict_host(1 << 20)
        assert eng.tree.match_prefix(prompts[0]).disk_length > 0
        eng.kv_transfer.note_hint(prompts[0])
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            eng.step()
            if eng.tree.match_prefix(prompts[0]).length > 0:
                break
            eng.kv_transfer.wait_progress(0.01)
        m = eng.tree.match_prefix(prompts[0])
        assert m.length > 0, "hint never promoted the disk prefix"
        # The disk copy is retained: re-demotion stays free.
        node = m.last_node
        assert node.disk_value is not None
        eng.kv_transfer.close()

    def test_drain_flush_disk_commits_everything(self, tiny, tmp_path):
        cfg, params = tiny
        d = tmp_path / "drain"
        prompts = self._prompts(cfg, 2, 64, seed=9)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, d)
        for p in prompts:
            eng.generate([list(p)], samp)
        eng.drain_flush_hot()
        eng.kv_transfer.wait_host_ready()
        spilled, committed = eng.drain_flush_disk()
        assert spilled > 0 and committed is True
        assert eng._kv_tier.extents >= 2
        eng.kv_transfer.close()
        del eng
        eng2 = make_engine(tiny, d)
        assert eng2.resurrected["grafted_nodes"] >= 2
        eng2.kv_transfer.close()

    def test_cold_restart_resume_byte_identical(self, tiny, tmp_path):
        """The PR 7 seeded-replay contract composed with the tier: a
        stream interrupted by a whole-cell kill resumes byte-identical
        on a cell rebuilt from the extent directory alone."""
        cfg, params = tiny
        d = tmp_path / "resume"
        rng = np.random.default_rng(11)
        prompt = list(
            rng.integers(1, cfg.vocab_size - 1, size=96).astype(np.int32)
        )
        samp = SamplingParams(
            temperature=0.9, top_p=0.95, seed=4242, max_new_tokens=8
        )
        # Deterministic expectation on a pristine engine.
        ref = make_engine(tiny, tmp_path / "ref")
        r = ref.add_request(prompt, samp)
        while ref.has_work():
            ref.step()
        expected = list(r.generated)
        ref.kv_transfer.close()

        eng = make_engine(tiny, d)
        spill_everything(eng, [np.asarray(prompt)], samp)
        req = eng.add_request(prompt, samp)
        while len(req.generated) < 3:
            eng.step()
        delivered = list(req.generated)
        eng.kv_transfer.close()  # mid-decode whole-cell kill
        del eng

        eng2 = make_engine(tiny, d)
        c0 = eng2.stats.cached_tokens
        resumed = eng2.add_request(prompt, samp, resume_tokens=delivered)
        while eng2.has_work():
            eng2.step()
        assert delivered + list(resumed.generated) == expected
        assert eng2.stats.cached_tokens - c0 > 0  # replay hit disk KV
        eng2.kv_transfer.close()


# ---------------------------------------------------------------------------
# doctor: tier_thrash (live seam + postmortem) — satellite 3's tests
# ---------------------------------------------------------------------------


@pytest.mark.quick
class TestTierThrashRule:
    def _doctor_with_moves(self, moves, now=1000.0):
        from radixmesh_tpu.obs.doctor import MeshDoctor

        class FakeTier:
            recent_moves = moves

        class FakeEng:
            _kv_tier = FakeTier()
            _restoring = ()
            kv_transfer = None

            def telemetry(self):
                return {}

            def spec_report(self):
                return {}

        return MeshDoctor(engine=FakeEng(), now=lambda: now)

    def test_fires_on_sustained_flapping(self):
        moves = []
        for i in range(4):
            moves.append((990.0 + i, 7, "demote"))
            moves.append((990.5 + i, 7, "promote"))
        report = self._doctor_with_moves(moves).diagnose()
        (f,) = [x for x in report["findings"] if x["rule"] == "tier_thrash"]
        assert f["evidence"]["shard"] == 7
        assert f["evidence"]["cycles"] >= 3
        assert f["evidence"]["source"] == "live"

    def test_quiet_below_cycle_floor_and_outside_window(self):
        moves = [
            (990.0, 7, "demote"), (990.5, 7, "promote"),  # one cycle
            (100.0, 9, "demote"), (100.5, 9, "promote"),  # ancient
            (101.0, 9, "demote"), (101.5, 9, "promote"),
            (102.0, 9, "demote"), (102.5, 9, "promote"),
        ]
        report = self._doctor_with_moves(moves).diagnose()
        assert not [
            x for x in report["findings"] if x["rule"] == "tier_thrash"
        ]
        assert "tier_thrash" in report["rules_checked"]

    def test_one_way_demotion_is_not_thrash(self):
        moves = [(990.0 + i, 7, "demote") for i in range(10)]
        report = self._doctor_with_moves(moves).diagnose()
        assert not [
            x for x in report["findings"] if x["rule"] == "tier_thrash"
        ]

    def test_postmortem_variant_from_recorded_counters(self):
        from radixmesh_tpu.obs.doctor import postmortem_report

        pts_d, pts_p = [], []
        for i in range(4):
            pts_d.append([2 * i, 10.0 + i, float(i + 1)])
            pts_p.append([2 * i + 1, 10.5 + i, float(i + 1)])
        dump = {
            "node": "n0",
            "unclean": False,
            "interval_s": 1.0,
            "series": {
                'radixmesh_kv_tier_moves_total{dir="demote",shard="5",tier="e"}': pts_d,
                'radixmesh_kv_tier_moves_total{dir="promote",shard="5",tier="e"}': pts_p,
            },
            "last_t": 14.0,
            "last_seq": 7,
        }
        report = postmortem_report(dump)
        (f,) = [x for x in report["findings"] if x["rule"] == "tier_thrash"]
        assert f["evidence"]["shard"] == 5
        assert f["evidence"]["cycles"] >= 3
        assert "tier_thrash" in report["rules_checked"]

    def test_evidence_fields_pinned(self):
        from radixmesh_tpu.obs.doctor import (
            POSTMORTEM_EVIDENCE_FIELDS,
            RULE_EVIDENCE_FIELDS,
            RULES,
            POSTMORTEM_RULES,
        )

        assert "tier_thrash" in RULES
        assert "tier_thrash" in POSTMORTEM_RULES
        assert "shard" in RULE_EVIDENCE_FIELDS["tier_thrash"]
        assert "cycles" in POSTMORTEM_EVIDENCE_FIELDS["tier_thrash"]


# ---------------------------------------------------------------------------
# live acceptance: the TIER artifact's data source end to end
# ---------------------------------------------------------------------------


class TestTierWorkloadAcceptance:
    def test_run_tier_workload_gates_green(self):
        """One reduced-size live run of the whole acceptance workload:
        every validate_tier gate must hold on fresh data."""
        from radixmesh_tpu.workload import (
            build_tier_report,
            run_tier_workload,
            validate_tier,
        )

        # Spills stage THROUGH the host arena, so one prefix must fit
        # it (prefix_tokens < host_slots) while the whole set exceeds
        # it 10x.
        res = run_tier_workload(
            n_prefixes=14, prefix_tokens=192, host_slots=256,
            n_streams=3, seed=1,
        )
        assert res["capacity"]["working_set_ratio"] >= 10
        assert (
            res["capacity"]["tier_hit_rate"]
            > res["capacity"]["baseline_hit_rate"]
        )
        assert res["restore_overlap"]["overlap_ok"]
        cs = res["cold_start"]
        assert cs["failed"] == 0
        assert cs["resumed"] == cs["interrupted"] > 0
        assert cs["byte_identical"] is True
        assert cs["disk_hit_tokens"] > 0
        assert cs["corrupt_detected"] >= 2 and cs["corrupt_served"] == 0
        report = build_tier_report(
            res, meshcheck={"files": [], "findings": 0, "clean": True}
        )
        assert validate_tier(report) == []


# ---------------------------------------------------------------------------
# review-hardening regressions (PR 15 code review)
# ---------------------------------------------------------------------------


@pytest.mark.quick
class TestReviewHardening:
    def test_stale_retired_ref_never_deletes_live_extent(self, tmp_path):
        """A retired ref whose path was since RE-committed (boundary-
        changed re-spill maps a NEW ref at the same name) must not
        delete the live extent or skew the books — _unlink is identity-
        guarded, not path-keyed."""
        tier = DiskKVTier(str(tmp_path / "t"), page_size=PAGE, name="stale")
        rng = np.random.default_rng(0)
        prefix = rng.integers(1, 100, size=8).astype(np.int32)
        seg = rng.integers(1, 100, size=8).astype(np.int32)
        kv = rng.standard_normal((2, 2, 8, 2, 4)).astype(np.float32)
        ref1 = tier.write_extent(prefix, seg, kv, None)
        tier.retire(ref1)  # the node split: old ref queued for unlink
        ref2 = tier.write_extent(prefix, seg, kv * 2, None)  # re-spill
        assert ref2.path == ref1.path
        tier.drain_retired()  # must be a no-op for the stale ref
        assert tier.has(ref2)
        assert os.path.exists(ref2.path)
        got, _ = tier.read_extent(ref2)
        np.testing.assert_array_equal(got, kv * 2)
        assert tier.resident_bytes == ref2.nbytes

    def test_transient_restore_failure_keeps_extent_attached(
        self, tiny, tmp_path
    ):
        """A restore unit that fails for a TRANSIENT reason (the extent
        file is intact) must leave node.disk_value attached for the
        next attempt; only a verification failure (file dropped by the
        tier) clears the ref."""
        from radixmesh_tpu.cache.kv_transfer import _RestoreUnit

        cfg, params = tiny
        rng = np.random.default_rng(1)
        p = rng.integers(1, cfg.vocab_size - 1, size=64).astype(np.int32)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng = make_engine(tiny, tmp_path / "transient")
        spill_everything(eng, [p], samp)
        eng.tree._evict_host(1 << 20)
        m = eng.tree.match_prefix(p)
        node = m.disk_nodes[0]
        ref = node.disk_value
        plane = eng.kv_transfer

        def failed_unit():
            dev = eng.pool.alloc(len(ref))
            u = _RestoreUnit(
                node, np.empty(0, dtype=np.int32), dev[: len(ref)],
                extent=ref, n_tokens=len(ref), failed=True,
            )
            return u

        # Transient: the tier still holds the extent -> ref retained.
        plane._apply_unit(eng.tree, failed_unit())
        assert node.disk_value is ref
        assert eng._kv_tier.has(ref)
        # Verification failure: the tier dropped the file -> ref cleared.
        eng._kv_tier._unlink(ref)
        plane._apply_unit(eng.tree, failed_unit())
        assert node.disk_value is None
        eng.kv_transfer.close()

    def test_advertised_value_never_pool_freed(self):
        """The resurrection re-announce publishes placeholder indices:
        _free_local must never release them (they alias live pool
        slots), while a normal same-rank PrefillValue still frees."""
        from radixmesh_tpu.cache.kv_pool import PagedKVPool
        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.cache.mesh_values import (
            AdvertisedValue,
            PrefillValue,
        )
        from radixmesh_tpu.config import MeshConfig

        pool = PagedKVPool(
            num_slots=32, num_layers=1, num_kv_heads=1, head_dim=2,
            page_size=1,
        )
        mesh = MeshCache(
            MeshConfig(
                prefill_nodes=["a0"], decode_nodes=[], router_nodes=[],
                local_addr="a0", protocol="inproc",
            ),
            pool=pool,
        )
        taken = pool.alloc(8)
        free0 = pool.free_slots
        mesh._free_local(AdvertisedValue(taken, mesh.rank))
        assert pool.free_slots == free0  # advertisement: not freed
        mesh._free_local(PrefillValue(taken, mesh.rank))
        assert pool.free_slots == free0 + 8  # real publish: freed
        mesh.close()

    def test_postmortem_counter_baseline_not_an_event_burst(self):
        """A late-started/pruned history ring's first retained counter
        point carries the cumulative pre-window total — it is the
        BASELINE, not hundreds of moves at one instant, so a flat
        series must not fire tier_thrash."""
        from radixmesh_tpu.obs.doctor import postmortem_report

        dump = {
            "node": "n0", "unclean": False, "interval_s": 1.0,
            "series": {
                'radixmesh_kv_tier_moves_total{dir="demote",shard="3",tier="e"}':
                    [[0, 10.0, 500.0]],
                'radixmesh_kv_tier_moves_total{dir="promote",shard="3",tier="e"}':
                    [[1, 10.0, 500.0]],
            },
            "last_t": 10.0, "last_seq": 1,
        }
        report = postmortem_report(dump)
        assert not [
            f for f in report["findings"] if f["rule"] == "tier_thrash"
        ]


@pytest.mark.quick
class TestReviewHardeningRound2:
    def _mesh(self):
        from radixmesh_tpu.cache.kv_pool import PagedKVPool
        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.config import MeshConfig

        pool = PagedKVPool(
            num_slots=32, num_layers=1, num_kv_heads=1, head_dim=2,
            page_size=1,
        )
        return MeshCache(
            MeshConfig(
                prefill_nodes=["a0"], decode_nodes=[], router_nodes=[],
                local_addr="a0", protocol="inproc",
            ),
            pool=pool,
        ), pool

    def test_advertised_value_never_enters_dup_ledger(self):
        """Conflict resolution recording an AdvertisedValue loser must
        not claim its placeholder ids (they alias live pool slots; a
        later _pending_free would free them under real data)."""
        from radixmesh_tpu.cache.mesh_values import AdvertisedValue
        from radixmesh_tpu.cache.mesh_cache import NodeKey

        mesh, pool = self._mesh()
        live = pool.alloc(8)  # live KV occupying slots 0..7
        adv = AdvertisedValue(np.arange(8, dtype=np.int32), mesh.rank)
        mesh._claim(NodeKey(np.arange(8, dtype=np.int32), mesh.rank), adv)
        assert not mesh._dup_pending
        mesh.close()

    def test_real_publish_upgrades_the_advertisement(self):
        """The origin's true publish after resurrection must REPLACE the
        placeholder value in the mesh tree (asymmetric eq + the upgrade
        branch in _resolve_conflict) — local_prefix_indices then maps
        to real slots, not arange ids; and a late advertisement never
        displaces real KV."""
        from radixmesh_tpu.cache.mesh_values import (
            AdvertisedValue,
            PrefillValue,
        )

        mesh, pool = self._mesh()
        key = np.arange(10, 18, dtype=np.int32)
        mesh.insert(key, np.arange(8, dtype=np.int32), advertise=True)
        node = mesh.tree.root.children[10]
        assert isinstance(node.value, AdvertisedValue)
        real = pool.alloc(8)
        conflicts0 = mesh._m_conflicts.value
        mesh.insert(key, real)  # the post-restore real publish
        node = mesh.tree.root.children[10]
        assert type(node.value) is PrefillValue
        np.testing.assert_array_equal(node.value.indices, real[:8])
        assert mesh._m_conflicts.value == conflicts0  # upgrade, not conflict
        assert not mesh._dup_pending
        # Reverse direction: a late advertisement must not displace it.
        mesh.insert(key, np.arange(8, dtype=np.int32), advertise=True)
        node = mesh.tree.root.children[10]
        assert type(node.value) is PrefillValue
        mesh.close()

    def test_poison_retired_when_spill_drop_frees_slots(self, tiny, tmp_path):
        """The spill 'poisoned' commit path frees arena slots — their
        poison entries must retire with them, or the next tenant's
        valid host copy gets condemned."""
        eng = make_engine(tiny, tmp_path / "poison")
        plane = eng.kv_transfer
        rng = np.random.default_rng(2)
        p = rng.integers(1, 100, size=64).astype(np.int32)
        samp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng.generate([list(p)], samp)
        eng.tree.evict(1 << 20)
        plane.wait_host_ready()
        m = eng.tree.match_prefix(p)
        node = m.host_nodes[0]
        slots = np.asarray(node.host_value, dtype=np.int32)
        with plane._lock:
            plane._poisoned_host.update(int(s) for s in slots)
        with plane._lock:
            plane._spilled.append((node, slots.copy(), None, "poisoned"))
        plane.pump(eng.tree)
        assert node.host_value is None  # garbage copy dropped
        with plane._lock:
            assert not (
                plane._poisoned_host & {int(s) for s in slots}
            ), "freed slots left poisoned: the next tenant would be condemned"
        plane.close()

    def test_capacity_purge_single_snapshot(self, tmp_path):
        """A deep purge sheds everything over budget in one pass and
        keeps the books exact (the O(extents^2) stat loop rewrite)."""
        tier = DiskKVTier(
            str(tmp_path / "t"), page_size=PAGE, name="purge",
            capacity_bytes=1,
        )
        rng = np.random.default_rng(3)
        for i in range(6):
            prefix = rng.integers(1, 100, size=4).astype(np.int32)
            seg = rng.integers(1, 100, size=8).astype(np.int32)
            kv = rng.standard_normal((2, 2, 8, 2, 4)).astype(np.float32)
            tier.write_extent(prefix, seg, kv, None)
        assert tier.extents == 1  # only the protected newest survives
        assert tier.resident_bytes > 0
        drops = sum(1 for m in tier.recent_moves if m[2] == "drop")
        assert drops == 5

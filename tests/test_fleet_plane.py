"""Fleet telemetry plane (``obs/fleet_plane.py``): digest wire shape +
size lint, FleetView fold/convergence/health detectors, throttled fault
logging, and the ISSUE-3 acceptance scenario on a live 3-ring-node
in-proc mesh — fingerprints converge after replication quiesces,
``convergence_age_seconds`` rises under an injected partition and
returns to ~0 after heal, a health-aware router stops selecting a node
whose stall watchdog fires, and digest overhead stays at one oplog
frame per interval per node."""

import time

import numpy as np
import pytest

from radixmesh_tpu.cache.mesh_cache import MeshCache
from radixmesh_tpu.cache.mesh_values import PrefillValue
from radixmesh_tpu.cache.oplog import OplogType
from radixmesh_tpu.comm.inproc import InprocHub
from radixmesh_tpu.config import MeshConfig, NodeRole
from radixmesh_tpu.obs.fleet_plane import (
    DIGEST_BYTE_BUDGET,
    EVICTION_CAUSES,
    FleetConfig,
    FleetPlane,
    FleetView,
    NodeDigest,
)
from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter
from radixmesh_tpu.utils.logging import reset_throttle, throttled

pytestmark = pytest.mark.quick


def wait_for(pred, timeout=15.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def digest(rank=0, seq=1, ts=None, fingerprint=1, **kw):
    base = dict(
        rank=rank,
        role="prefill",
        seq=seq,
        ts=time.time() if ts is None else ts,
        epoch=0,
        fingerprint=fingerprint,
        tree_tokens=100,
        cache_hit_rate=0.5,
        pool_fill=0.3,
        host_fill=0.0,
        batch_occupancy=0.0,
        decode_ewma_s=0.01,
        waiting=0,
        decode_steps=0,
        replication_lag_s=0.0,
        slo_tier=0,
        evictions=(0, 0, 0, 0),
    )
    base.update(kw)
    return NodeDigest(**base)


class TestNodeDigestWire:
    def test_roundtrip_every_field(self):
        d = digest(
            rank=3, seq=42, epoch=7, fingerprint=(1 << 63) + 12345,
            tree_tokens=999, cache_hit_rate=0.75, pool_fill=0.9,
            host_fill=0.1, batch_occupancy=1.0, decode_ewma_s=0.025,
            waiting=5, decode_steps=123456, replication_lag_s=0.5,
            slo_tier=2, evictions=(10, 20, 30, 40), role="decode",
            interval_s=7.5,
        )
        d2 = NodeDigest.decode(d.encode())
        for f in (
            "rank", "role", "seq", "epoch", "fingerprint", "tree_tokens",
            "waiting", "decode_steps", "slo_tier", "evictions",
        ):
            assert getattr(d2, f) == getattr(d, f), f
        for f in (
            "ts", "cache_hit_rate", "pool_fill", "host_fill",
            "batch_occupancy", "decode_ewma_s", "replication_lag_s",
            "interval_s",
        ):
            assert getattr(d2, f) == pytest.approx(getattr(d, f), rel=1e-6), f

    def test_size_lint_under_pinned_budget(self):
        """CI satellite: the serialized digest stays under the byte
        budget so ring piggybacking stays one cheap frame. Extremes
        (huge counters) must not grow it — the layout is fixed."""
        worst = digest(
            rank=2**30, seq=2**60, epoch=2**30, fingerprint=(1 << 64) - 1,
            tree_tokens=2**60, decode_steps=2**60, waiting=2**30,
            evictions=(2**60, 2**60, 2**60, 2**60),
        )
        assert worst.encoded_size() <= DIGEST_BYTE_BUDGET
        assert digest().encoded_size() == worst.encoded_size()

    def test_malformed_rejected(self):
        with pytest.raises(ValueError):
            NodeDigest.decode(np.zeros(2, dtype=np.int32))
        bad = digest().encode().copy()
        bad[0] ^= 0xFF  # corrupt the magic byte
        with pytest.raises(ValueError):
            NodeDigest.decode(bad)

    def test_as_dict_names_eviction_causes(self):
        d = digest(evictions=(1, 2, 3, 4)).as_dict()
        assert d["evictions"] == dict(zip(EVICTION_CAUSES, (1, 2, 3, 4)))


class TestFleetView:
    def test_fold_newest_wins_and_idempotent(self):
        v = FleetView()
        t = time.time()
        assert v.fold(digest(seq=2, ts=t))
        assert not v.fold(digest(seq=1, ts=t - 1))  # stale
        assert not v.fold(digest(seq=2, ts=t))  # exact ring re-delivery
        assert v.fold(digest(seq=3, ts=t + 1))
        assert v.digests()[0].seq == 3

    def test_restarted_node_seq_reset_is_accepted(self):
        """A reboot resets the digest seq counter to 1; the fold order is
        wall-clock-first exactly so those fresh digests are NOT rejected
        (seq-first would read the healthy rebooted node as stale/sick
        until seq caught up to its pre-crash value)."""
        v = FleetView()
        t = time.time()
        assert v.fold(digest(seq=720, ts=t))  # an hour of uptime
        assert v.fold(digest(seq=1, ts=t + 5))  # post-reboot
        assert v.digests()[0].seq == 1

    def test_retain_prunes_departed_ranks(self):
        clock = [100.0]
        v = FleetView(now=lambda: clock[0])
        v.fold(digest(rank=0, fingerprint=1, ts=clock[0]))
        v.fold(digest(rank=1, fingerprint=2, ts=clock[0]))  # diverged pair
        assert not v.convergence()["converged"]
        v.retain({0})  # rank 1 left the membership view
        assert set(v.digests()) == {0}
        conv = v.convergence()
        assert conv["converged"] and conv["pairs"] == {}
        assert v.health_score(1) == 1.0  # unknown again, not stale-red

    def test_convergence_age_rises_and_clears(self):
        clock = [1000.0]
        v = FleetView(now=lambda: clock[0])
        v.fold(digest(rank=0, fingerprint=7, ts=clock[0]))
        v.fold(digest(rank=1, fingerprint=7, ts=clock[0]))
        assert v.convergence()["converged"]
        clock[0] += 1.0
        v.fold(digest(rank=1, seq=2, fingerprint=8, ts=clock[0]))
        clock[0] += 2.5
        conv = v.convergence()
        assert not conv["converged"]
        assert conv["pairs"]["0-1"] == pytest.approx(2.5)
        # Heal: rank 0 catches up to the same fingerprint.
        v.fold(digest(rank=0, seq=2, fingerprint=8, ts=clock[0]))
        conv = v.convergence()
        assert conv["converged"] and conv["pairs"]["0-1"] == 0.0

    def test_stall_watchdog(self):
        v = FleetView()
        t = time.time()
        v.fold(digest(seq=1, ts=t, batch_occupancy=0.5, decode_steps=10))
        assert v.health_score(0) == 1.0
        # Batch still busy, decode counter frozen → stall → score 0.
        v.fold(digest(seq=2, ts=t + 1, batch_occupancy=0.5, decode_steps=10))
        h = v.health()[0]
        assert h["score"] == 0.0 and "stall" in h["reasons"]
        # Progress resumes → healthy again.
        v.fold(digest(seq=3, ts=t + 2, batch_occupancy=0.5, decode_steps=11))
        assert v.health_score(0) == 1.0

    def test_idle_engine_is_not_a_stall(self):
        v = FleetView()
        t = time.time()
        v.fold(digest(seq=1, ts=t, batch_occupancy=0.0, decode_steps=10))
        v.fold(digest(seq=2, ts=t + 1, batch_occupancy=0.0, decode_steps=10))
        assert v.health_score(0) == 1.0

    def test_replication_lag_and_eviction_storm_detectors(self):
        cfg = FleetConfig(lag_threshold_s=1.0, eviction_storm_tokens_per_s=100.0)
        v = FleetView(cfg=cfg)
        t = time.time()
        v.fold(digest(seq=1, ts=t))
        # Lag over threshold caps the score at 0.3.
        v.fold(digest(seq=2, ts=t + 1, replication_lag_s=5.0))
        h = v.health()[0]
        assert h["score"] == 0.3 and "replication_lag" in h["reasons"]
        # Pressure evictions (capacity+preempt) at 1000 tok/s → storm.
        v.fold(digest(seq=3, ts=t + 2, evictions=(500, 0, 500, 0)))
        h = v.health()[0]
        assert h["score"] == 0.6 and h["reasons"] == ["eviction_storm"]
        # Policy evictions (ttl/mesh_trim) alone never read as a storm.
        v.fold(digest(seq=4, ts=t + 3, evictions=(500, 10**6, 500, 10**6)))
        assert v.health_score(0) == 1.0

    def test_stale_digest_decays(self):
        clock = [5000.0]
        v = FleetView(cfg=FleetConfig(interval_s=1.0), now=lambda: clock[0])
        v.fold(digest(ts=clock[0]))
        assert v.health_score(0) == 1.0
        clock[0] += 10.0  # > 3 intervals with no digest
        h = v.health()[0]
        assert h["score"] == 0.2 and "stale_digest" in h["reasons"]

    def test_unknown_rank_scores_healthy(self):
        assert FleetView().health_score(42) == 1.0


class TestThrottledLogging:
    def setup_method(self):
        reset_throttle()

    def teardown_method(self):
        reset_throttle()

    def test_once_per_interval_per_key(self):
        assert throttled("k", 10.0, now=0.0)
        assert not throttled("k", 10.0, now=5.0)
        assert not throttled("k", 10.0, now=9.99)
        assert throttled("k", 10.0, now=10.0)
        # Independent keys don't interfere.
        assert throttled(("k", 2), 10.0, now=0.0)

    def test_mesh_warning_sites_use_throttle(self):
        """The repeated-fault log sites (successor death, fan-out
        failure, transmit failure, rejoin) all pass through throttled()
        — grep-level regression guard so a refactor can't silently
        reintroduce per-cycle flooding."""
        import inspect

        from radixmesh_tpu.cache import mesh_cache

        src = inspect.getsource(mesh_cache)
        for anchor in (
            '("succ_dead"', '("router_down"', '("tx_fail"', '("rejoin"',
        ):
            assert anchor in src, f"throttle anchor {anchor} missing"


class FrozenStats:
    """Engine stand-in whose decode counter can be frozen (stall)."""

    def __init__(self):
        self.healthy = True
        self._steps = 0

    def telemetry(self):
        if self.healthy:
            self._steps += 1
        return {
            "batch_occupancy": 1.0,
            "waiting": 1,
            "decode_steps": self._steps,
            "decode_ewma_s": 0.02,
            "cache_hit_rate": 0.4,
            "pool_fill": 0.5,
            "host_fill": 0.0,
            "evictions": {"capacity": 0},
        }


class FleetCluster:
    """2 prefill + 1 decode ring + router over the inproc hub, each ring
    node with a FleetPlane (node p1 wired to a freezable stats source)."""

    def __init__(self, interval=0.05):
        InprocHub.reset_default()
        prefill, decode, router = ["p0", "p1"], ["d0"], ["r0"]
        self.nodes: list[MeshCache] = []
        for addr in prefill + decode + router:
            cfg = MeshConfig(
                prefill_nodes=prefill,
                decode_nodes=decode,
                router_nodes=router,
                local_addr=addr,
                protocol="inproc",
                tick_interval_s=0.05,
                gc_interval_s=30.0,
            )
            self.nodes.append(MeshCache(cfg, pool=None).start())
        for n in self.nodes:
            assert n.wait_ready(timeout=10), f"node {n.rank} never ready"
        self.ring = [n for n in self.nodes if n.role is not NodeRole.ROUTER]
        self.router_mesh = self.nodes[-1]
        self.stats = FrozenStats()
        self.planes = [
            FleetPlane(
                n,
                engine=self.stats if i == 1 else None,
                interval_s=interval,
            )
            for i, n in enumerate(self.ring)
        ]

    def publish_all(self):
        for p in self.planes:
            p.publish_once()

    def fingerprints(self):
        return [n.tree.fingerprint_ for n in self.nodes]

    def close(self):
        for p in self.planes:
            p.close()
        for n in self.nodes:
            n.close()
        InprocHub.reset_default()


@pytest.fixture
def cluster():
    c = FleetCluster()
    yield c
    c.close()


class TestFleetMeshIntegration:
    def test_digests_reach_every_node_and_router(self, cluster):
        cluster.publish_all()
        assert wait_for(
            lambda: all(len(n.fleet.digests()) == 3 for n in cluster.nodes)
        ), [len(n.fleet.digests()) for n in cluster.nodes]
        # The router's copy carries the origin's engine telemetry.
        d = cluster.router_mesh.fleet.digests()[cluster.ring[1].rank]
        assert d.batch_occupancy == 1.0 and d.role == "prefill"

    def test_fingerprints_converge_after_quiesce(self, cluster):
        rng = np.random.default_rng(0)
        for i in range(30):
            writer = cluster.ring[i % 3]
            key = rng.integers(0, 256, size=12).astype(np.int32)
            writer.insert(key, np.arange(12, dtype=np.int32))
        assert wait_for(
            lambda: len(set(cluster.fingerprints())) == 1
        ), [hex(f) for f in cluster.fingerprints()]
        cluster.publish_all()
        assert wait_for(
            lambda: cluster.router_mesh.fleet.convergence()["converged"]
        )

    def test_partition_raises_age_and_heal_clears_it(self, cluster):
        """The acceptance-criteria scenario: a key applied to ONE replica
        only (partition stand-in) makes convergence_age rise on the
        router's audit; replicating it for real brings the age back ~0."""
        rogue = cluster.ring[0]
        key = np.arange(500, 516, dtype=np.int32)
        idx = np.arange(16, dtype=np.int32)
        with rogue._lock:
            rogue._mesh_insert(key, PrefillValue(idx, rogue.rank))
        cluster.publish_all()
        assert wait_for(
            lambda: not cluster.router_mesh.fleet.convergence()["converged"]
        )
        time.sleep(0.15)
        cluster.publish_all()
        age = cluster.router_mesh.fleet.convergence()["max_convergence_age_s"]
        assert age >= 0.1, age
        rogue.insert(key, idx)  # heal: replicate the divergent key
        def _healed():
            cluster.publish_all()
            return cluster.router_mesh.fleet.convergence()["converged"]
        assert wait_for(_healed)
        assert (
            cluster.router_mesh.fleet.convergence()["max_convergence_age_s"]
            == 0.0
        )

    def test_stall_demotes_node_in_health_aware_router(self, cluster):
        router = CacheAwareRouter(
            cluster.router_mesh,
            cluster.router_mesh.cfg,
            health_aware=True,
        )
        router.finish_warm_up()
        sick = cluster.ring[1]
        sick_addr = sick.cfg.addr_of_rank(sick.rank)
        rng = np.random.default_rng(1)
        keys = [rng.integers(0, 999, size=8).astype(np.int32) for _ in range(48)]
        # Healthy: the hash ring spreads misses over BOTH prefill nodes.
        cluster.publish_all()
        healthy_targets = {router.cache_aware_route(k).prefill_addr for k in keys}
        assert sick_addr in healthy_targets
        # Freeze decode with a busy batch → stall → score 0 → demoted.
        cluster.stats.healthy = False
        def _scored_sick():
            cluster.planes[1].publish_once()
            return (
                cluster.router_mesh.fleet.health_score(sick.rank) < 0.5
            )
        assert wait_for(_scored_sick)
        sick_targets = {router.cache_aware_route(k).prefill_addr for k in keys}
        assert sick_addr not in sick_targets
        assert sick_targets  # traffic still routes somewhere
        # A cache HIT pointing at the sick node sheds to a healthy peer.
        hot = np.arange(700, 716, dtype=np.int32)
        sick.insert(hot, np.arange(16, dtype=np.int32))
        assert wait_for(
            lambda: cluster.router_mesh.match_prefix(hot).prefill_rank
            == sick.rank
        )
        res = router.cache_aware_route(hot)
        assert res.prefill_addr != sick_addr and not res.prefill_cache_hit
        # Recovery: decode progresses again → score 1.0 → selectable.
        cluster.stats.healthy = True
        def _recovered():
            cluster.planes[1].publish_once()
            return (
                cluster.router_mesh.fleet.health_score(sick.rank) >= 0.5
            )
        assert wait_for(_recovered)
        assert sick_addr in {
            router.cache_aware_route(k).prefill_addr for k in keys
        }

    def test_digest_overhead_one_frame_per_publish(self, cluster):
        """Acceptance bound: digest overhead ≤ 1 oplog frame per interval
        per node — each origination is one DIGEST frame, received exactly
        once per node per lap (counted at the router via fan-out)."""
        rounds = 6
        for _ in range(rounds):
            cluster.publish_all()
        total = sum(p.published for p in cluster.planes)
        assert total == rounds * len(cluster.ring)
        assert wait_for(
            lambda: cluster.router_mesh._m_received[OplogType.DIGEST].value
            >= total
        )
        time.sleep(0.1)  # no straggler frames beyond one per publish
        assert (
            cluster.router_mesh._m_received[OplogType.DIGEST].value == total
        )

    def test_digester_thread_runs_on_interval_and_stops(self, cluster):
        plane = cluster.planes[0]
        t0 = time.monotonic()
        plane.start()
        try:
            assert wait_for(lambda: plane.published >= 2, timeout=5.0)
        finally:
            plane.close()
        elapsed = time.monotonic() - t0
        count = plane.published
        # ≤ one origination per interval (+1 for the immediate first
        # tick) — the piggyback budget, enforced at the thread cadence.
        assert count <= elapsed / plane.cfg.interval_s + 2
        time.sleep(0.2)
        assert plane.published == count  # closed: no more publishes


class TestMeshTtlSweep:
    def test_ttl_expires_stale_replica_entries(self):
        InprocHub.reset_default()
        try:
            prefill, decode, router = ["p0"], ["d0"], ["r0"]
            nodes = []
            for addr in prefill + decode + router:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.05,
                    gc_interval_s=30.0,
                    mesh_ttl_s=0.2,
                )
                nodes.append(MeshCache(cfg, pool=None).start())
            for n in nodes:
                assert n.wait_ready(timeout=10)
            p0 = nodes[0]
            p0.insert(list(range(16)), np.arange(16, dtype=np.int32))
            assert p0.tree.match_prefix(np.arange(16, dtype=np.int32)).length == 16
            # Poll WITHOUT walking the tree — match_prefix refreshes
            # last_access_time, which would keep the entry forever-fresh.
            # Whichever node's sweep comes first expires it (on a loaded
            # machine a replica's tick can beat p0's, and p0 then drops
            # the entry by the replicated DELETE, not by its own sweep).
            assert wait_for(
                lambda: sum(n._m_evicted["ttl"].value for n in nodes) >= 16,
                timeout=10.0,
            ), "TTL sweep never expired the entry"
            assert p0.tree.evictable_size_ == 0
            # Expiry REPLICATES (DELETE lap): every replica drops the
            # entry, so fingerprints stay converged instead of the
            # audit reading policy expiry as permanent divergence.
            assert wait_for(
                lambda: all(n.tree.evictable_size_ == 0 for n in nodes)
            ), [n.tree.evictable_size_ for n in nodes]
            assert len({n.tree.fingerprint_ for n in nodes}) == 1
        finally:
            for n in nodes:
                n.close()
            InprocHub.reset_default()


class TestShardHeatFold:
    """PR 9 leg (b): the FleetView folds per-shard decayed loads from
    the SHARD_SUMMARY heat trailer into the cluster heat map + skew
    score the future rebalancer consumes."""

    def test_max_over_reporters_not_sum(self):
        """Co-owners see the SAME inserts: a fleet load that summed
        reporters would count one insert RF times."""
        v = FleetView()
        v.fold_shard_heat(0, {7: 10.0, 9: 1.0})
        v.fold_shard_heat(1, {7: 8.0, 9: 2.0})
        heat = v.shard_heat()
        assert heat["shards"]["7"] == 10.0
        assert heat["shards"]["9"] == 2.0
        assert heat["hot_shard"] == 7
        assert heat["reporters"] == 2
        # skew = max / mean = 10 / 6
        assert heat["skew_score"] == pytest.approx(10.0 / 6.0, abs=1e-3)

    def test_whole_summary_swap_and_empty_fold_clears(self):
        v = FleetView()
        v.fold_shard_heat(3, {1: 5.0, 2: 5.0})
        v.fold_shard_heat(3, {2: 1.0})  # ownership changed: shard 1 gone
        assert v.shard_heat()["shards"] == {"2": 1.0}
        v.fold_shard_heat(3, {})  # cold owner: cleared, not unknown
        assert v.shard_heat()["reporters"] == 0
        assert v.shard_heat()["hot_shard"] is None
        assert v.shard_heat()["skew_score"] == 0.0

    def test_forget_and_retain_drop_heat(self):
        v = FleetView()
        v.fold_shard_heat(4, {1: 3.0})
        v.fold_shard_heat(5, {2: 4.0})
        v.forget(4)
        assert "1" not in v.shard_heat()["shards"]
        v.retain([])
        assert v.shard_heat()["reporters"] == 0

    def test_snapshot_includes_heat_only_when_reported(self):
        v = FleetView()
        assert "shard_heat" not in v.snapshot()
        v.fold_shard_heat(0, {3: 2.0})
        assert v.snapshot()["shard_heat"]["hot_shard"] == 3


class TestClockOffsets:
    """PR 9 leg (a): per-rank wall-clock skew estimates derived from the
    digest timestamps every node already gossips — the stitcher's
    clock-offset correction input."""

    def _digest(self, rank, seq, ts):
        return NodeDigest(
            rank=rank, role="prefill", seq=seq, ts=ts, epoch=0,
            fingerprint=1, tree_tokens=0, cache_hit_rate=0.0,
            pool_fill=0.0, host_fill=0.0, batch_occupancy=0.0,
            decode_ewma_s=0.0, waiting=0, decode_steps=0,
        )

    def test_min_tracked_skew(self):
        clock = {"t": 100.0}
        v = FleetView(now=lambda: clock["t"])
        # First fold: digest stamped 2s behind the local clock.
        v.fold(self._digest(1, 1, ts=98.0))
        assert v.clock_offsets()[1] == pytest.approx(2.0)
        # A faster delivery tightens the estimate; a slower one never
        # loosens it (min-tracking bounds the transit inflation).
        clock["t"] = 101.0
        v.fold(self._digest(1, 2, ts=100.5))
        assert v.clock_offsets()[1] == pytest.approx(0.5)
        clock["t"] = 110.0
        v.fold(self._digest(1, 3, ts=105.0))
        assert v.clock_offsets()[1] == pytest.approx(0.5)

    def test_forget_drops_the_estimate(self):
        v = FleetView(now=lambda: 10.0)
        v.fold(self._digest(2, 1, ts=9.0))
        assert 2 in v.clock_offsets()
        v.forget(2)
        assert v.clock_offsets() == {}

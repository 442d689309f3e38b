"""Anti-entropy repair plane (``cache/repair_plane.py``): payload wire
round-trips, storm-control invariants, the repair session protocol over
a live inproc mesh, and the chaos acceptance scenario.

All timing is deadline-bounded polling (wait_for), never a bare sleep
asserting a duration; all randomness is seeded.

``quick`` marks only the sub-second protocol/unit tests; the
live-cluster session tests and the chaos acceptance scenario cost a few
seconds each (startup barriers + convergence waits) and ride tier-1
without inflating the ~1-minute quick gate."""

import time

import numpy as np
import pytest

from radixmesh_tpu.cache.mesh_cache import MeshCache
from radixmesh_tpu.cache.mesh_values import PrefillValue
from radixmesh_tpu.cache.radix_tree import FP_BUCKETS
from radixmesh_tpu.cache.repair_plane import (
    RepairConfig,
    RepairPlane,
    decode_probe,
    decode_summary,
    encode_probe,
    encode_summary,
)
from radixmesh_tpu.comm.inproc import InprocHub
from radixmesh_tpu.config import MeshConfig, NodeRole
from radixmesh_tpu.obs.fleet_plane import FleetPlane


@pytest.fixture(autouse=True)
def fresh_hub():
    InprocHub.reset_default()
    yield
    InprocHub.reset_default()


def wait_for(pred, timeout=15.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


@pytest.mark.quick
class TestPayloadWire:
    def test_probe_round_trip(self):
        rng = np.random.default_rng(0)
        vec = rng.integers(0, 1 << 63, size=FP_BUCKETS).astype("<u8")
        assert (decode_probe(encode_probe(vec)) == vec).all()

    def test_probe_size_within_frame_budget(self):
        """The PROBE payload is the bucket vector + a 4-byte header —
        the ISSUE's ≤ 512 B extra contract, pinned."""
        vec = np.zeros(FP_BUCKETS, dtype="<u8")
        assert encode_probe(vec).nbytes <= 512 + 8

    def test_summary_round_trip(self):
        rng = np.random.default_rng(1)
        vec = rng.integers(0, 1 << 63, size=FP_BUCKETS).astype("<u8")
        buckets = [3, 17, 63]
        hashes = [5, (1 << 64) - 1, 1 << 63]
        for reply in (False, True):
            v, b, h, r = decode_summary(
                encode_summary(vec, buckets, hashes, reply=reply)
            )
            assert (v == vec).all()
            assert b == buckets
            assert h == {x & ((1 << 64) - 1) for x in hashes}
            assert r is reply

    def test_empty_summary_round_trip(self):
        vec = np.zeros(FP_BUCKETS, dtype="<u8")
        v, b, h, r = decode_summary(encode_summary(vec, [], [], reply=False))
        assert b == [] and h == set() and r is False

    def test_malformed_payloads_raise(self):
        with pytest.raises(ValueError):
            decode_probe(np.zeros(3, dtype=np.int32))
        with pytest.raises(ValueError):
            decode_summary(np.zeros(1, dtype=np.int32))
        bad = encode_probe(np.zeros(FP_BUCKETS, dtype="<u8"))
        bad = bad.copy()
        bad[0] = 0  # clobber the magic
        with pytest.raises(ValueError):
            decode_probe(bad)


def make_cluster(n_prefill=2, repair_cfg=None, tick=0.05, digest=0.1):
    """Ring + router, fleet planes gossiping, repair planes UNstarted
    (tests drive scan_once / the worker explicitly or via .start())."""
    prefill = [f"rp{i}" for i in range(n_prefill)]
    decode, router = ["rd0"], ["rr0"]
    nodes = []
    for addr in prefill + decode + router:
        cfg = MeshConfig(
            prefill_nodes=prefill, decode_nodes=decode, router_nodes=router,
            local_addr=addr, protocol="inproc", tick_interval_s=tick,
            gc_interval_s=60.0, failure_timeout_s=60.0,
        )
        nodes.append(MeshCache(cfg, pool=None).start())
    for n in nodes:
        assert n.wait_ready(timeout=10)
    ring = [n for n in nodes if n.role is not NodeRole.ROUTER]
    planes = [FleetPlane(n, interval_s=digest) for n in ring]
    cfg = repair_cfg or RepairConfig(
        interval_s=0.05, age_threshold_s=0.2, backoff_base_s=0.2,
        backoff_max_s=2.0,
    )
    repairs = [RepairPlane(n, cfg, seed=0) for n in nodes]
    return nodes, ring, nodes[-1], planes, repairs


def close_all(nodes, planes, repairs):
    for r in repairs:
        r.close()
    for p in planes:
        p.close()
    for n in nodes:
        n.close()


class TestRepairSession:
    def test_dropped_insert_heals_everywhere(self):
        """One replica silently misses an INSERT (applied locally on the
        writer only — the dropped-frame stand-in); repair re-replicates
        it to every replica including the router."""
        nodes, ring, router, planes, repairs = make_cluster()
        try:
            for r in repairs:
                r.start()
            # Normal replicated state first.
            ring[0].insert(
                np.array([1, 2, 3], np.int32), np.arange(3, dtype=np.int32)
            )
            assert wait_for(
                lambda: len({n.tree.fingerprint_ for n in nodes}) == 1
            )
            # The "dropped frame": local-only apply on ring[1].
            key = np.array([40, 41, 42, 43], np.int32)
            with ring[1]._lock:
                ring[1]._mesh_insert(
                    key, PrefillValue(np.arange(4, dtype=np.int32), ring[1].rank)
                )
            assert len({n.tree.fingerprint_ for n in nodes}) > 1

            def converged():
                for p in planes:
                    p.publish_once()
                return len({n.tree.fingerprint_ for n in nodes}) == 1

            assert wait_for(converged), "repair never converged the fleet"
            # Every replica (router too) now matches the key.
            for n in nodes:
                res = n.tree.match_prefix(key, split_partial=False)
                assert res.length == len(key), f"rank {n.rank} missing the key"
            assert sum(r.stats()["keys_pushed"] for r in repairs) >= 1
        finally:
            close_all(nodes, planes, repairs)

    def test_dropped_delete_heals_by_resurrection(self):
        """A DELETE applied everywhere except one replica: repair
        converges the fleet (to the union — resurrection is the
        documented tombstone-free heal direction)."""
        nodes, ring, router, planes, repairs = make_cluster()
        try:
            key = np.array([7, 8, 9], np.int32)
            ring[0].insert(key, np.arange(3, dtype=np.int32))
            assert wait_for(
                lambda: len({n.tree.fingerprint_ for n in nodes}) == 1
            )
            # Everyone but ring[1] applies the delete (the frame "to"
            # ring[1] was dropped).
            for n in nodes:
                if n is ring[1]:
                    continue
                with n._lock:
                    assert n._apply_delete(key)
            assert len({n.tree.fingerprint_ for n in nodes}) > 1
            for r in repairs:
                r.start()

            def converged():
                for p in planes:
                    p.publish_once()
                return len({n.tree.fingerprint_ for n in nodes}) == 1

            assert wait_for(converged), "dropped DELETE never healed"
            # Union semantics: the survivor re-replicated the key.
            for n in nodes:
                assert (
                    n.tree.match_prefix(key, split_partial=False).length
                    == len(key)
                )
        finally:
            close_all(nodes, planes, repairs)

    def test_rank_conflict_winner_survives_repair(self):
        """Repair re-pushes must flow through the SAME conflict rules as
        live replication: after healing a divergence that involves a
        multi-writer conflict, every replica still attributes each
        position to the lowest writing rank."""
        nodes, ring, router, planes, repairs = make_cluster()
        try:
            key = np.array([5, 6, 7], np.int32)
            # Both prefills write the same key (rank 0 must win).
            ring[0].insert(key, np.arange(3, dtype=np.int32))
            ring[1].insert(key, 100 + np.arange(3, dtype=np.int32))
            assert wait_for(
                lambda: len({n.tree.fingerprint_ for n in nodes}) == 1
            )
            # ring[2] (decode) additionally misses an unrelated key.
            lost = np.array([70, 71], np.int32)
            for n in ring[:2]:
                with n._lock:
                    n._mesh_insert(
                        lost.copy(),
                        PrefillValue(np.arange(2, dtype=np.int32), ring[0].rank),
                    )
            for r in repairs:
                r.start()

            def converged():
                for p in planes:
                    p.publish_once()
                return len({n.tree.fingerprint_ for n in nodes}) == 1

            assert wait_for(converged)
            for n in ring:
                res = n.tree.match_prefix(key, split_partial=False)
                assert res.length == len(key)
                assert all(v.rank == ring[0].rank for v in res.values), (
                    f"rank {n.rank}: conflict winner changed post-repair"
                )
        finally:
            close_all(nodes, planes, repairs)

    def test_router_pulls_without_pushing(self):
        """An asymmetric divergence where the ROUTER is the stale side:
        it initiates (probe), the peer pushes over the ring, and the
        router's replica heals — while the router itself never
        originates ring traffic (its mesh send counter stays put)."""
        nodes, ring, router, planes, repairs = make_cluster()
        try:
            key = np.array([90, 91, 92], np.int32)
            # Apply on every RING node locally; the router never saw it
            # (a dropped master→router fan-out frame).
            for n in ring:
                with n._lock:
                    n._mesh_insert(
                        key.copy(),
                        PrefillValue(np.arange(3, dtype=np.int32), ring[0].rank),
                    )
            sent_before = int(router._m_sent.value)
            for r in repairs:
                r.start()

            def converged():
                for p in planes:
                    p.publish_once()
                return len({n.tree.fingerprint_ for n in nodes}) == 1

            assert wait_for(converged), "router replica never healed"
            assert (
                router.tree.match_prefix(key, split_partial=False).length
                == len(key)
            )
            assert int(router._m_sent.value) == sent_before, (
                "router originated ring traffic during repair"
            )
            # The router pushed no keys (it holds no indices).
            router_repair = repairs[-1]
            assert router_repair.stats()["keys_pushed"] == 0
        finally:
            close_all(nodes, planes, repairs)


class TestStormControl:
    def test_backoff_grows_between_rounds(self):
        """An unhealable divergence (peer never answers — its repair
        inbox is detached) must back off exponentially, not probe-storm."""
        nodes, ring, router, planes, repairs = make_cluster(
            repair_cfg=RepairConfig(
                interval_s=0.05, age_threshold_s=0.0, backoff_base_s=0.1,
                backoff_max_s=5.0, jitter_frac=0.0,
            )
        )
        try:
            # Diverge ring[0] from everyone; nobody else runs a plane,
            # so probes go unanswered and the episode never heals.
            with ring[0]._lock:
                ring[0]._mesh_insert(
                    np.array([3, 1], np.int32),
                    PrefillValue(np.arange(2, dtype=np.int32), ring[0].rank),
                )
            for p in planes:
                p.publish_once()
            plane = repairs[0]
            assert wait_for(
                lambda: len(ring[0].fleet.digests()) == len(ring)
            ), "digest fan-in never completed"
            sent = []
            for _ in range(4):
                plane.scan_once()
                sent.append(plane.stats()["probes_sent"])
                # Two immediate rescans: rate limit must hold them.
                plane.scan_once()
                plane.scan_once()
                assert plane.stats()["probes_sent"] == sent[-1]
                st = next(iter(plane._peers.values()))
                time.sleep(max(0.0, st["next_probe_at"] - time.monotonic()))
            # One probe per backoff window, and the window doubled.
            st = next(iter(plane._peers.values()))
            assert st["backoff_s"] >= 0.1 * (2 ** 3)
        finally:
            close_all(nodes, planes, repairs)

    @pytest.mark.quick
    def test_key_budget_bounds_push(self):
        """A summary exchange re-replicates at most key_budget entries
        per session."""
        prefill = ["kb0", "kb1"]
        cfgs = [
            MeshConfig(prefill_nodes=prefill, decode_nodes=["kbd"],
                       router_nodes=[], local_addr=a, protocol="inproc")
            for a in prefill
        ]
        a, b = MeshCache(cfgs[0]), MeshCache(cfgs[1])
        rng = np.random.default_rng(0)
        with a._lock:
            for _ in range(30):
                key = rng.integers(0, 500, size=6).astype(np.int32)
                a._mesh_insert(
                    key, PrefillValue(np.arange(6, dtype=np.int32), 0)
                )
        diff = [
            int(i)
            for i in np.nonzero(
                a.tree.fp_buckets_ != b.tree.fp_buckets_
            )[0]
        ]
        keys, oplogs = a.repair_push_keys(diff, set(), budget=5)
        assert keys == 5
        assert oplogs >= 5

    def test_quiescence_zero_traffic_when_converged(self):
        nodes, ring, router, planes, repairs = make_cluster()
        try:
            ring[0].insert(
                np.array([1, 2], np.int32), np.arange(2, dtype=np.int32)
            )
            assert wait_for(
                lambda: len({n.tree.fingerprint_ for n in nodes}) == 1
            )
            for p in planes:
                p.publish_once()
            # Everyone's view holds equal fingerprints: scans must send
            # nothing, ever.
            assert wait_for(
                lambda: all(
                    len(n.fleet.digests()) == len(ring) for n in nodes
                )
            )
            for r in repairs:
                for _ in range(5):
                    assert r.scan_once() == 0
                assert r.stats()["probes_sent"] == 0
        finally:
            close_all(nodes, planes, repairs)

    def test_data_loss_arms_early_probe(self):
        """The dropped-frame recovery hook: a data-kind loss waives the
        age threshold so the next scan probes immediately."""
        nodes, ring, router, planes, repairs = make_cluster(
            repair_cfg=RepairConfig(
                interval_s=10.0, age_threshold_s=60.0,  # would never fire
                backoff_base_s=0.1, backoff_max_s=1.0,
            )
        )
        try:
            with ring[0]._lock:
                ring[0]._mesh_insert(
                    np.array([9, 9, 9], np.int32),
                    PrefillValue(np.arange(3, dtype=np.int32), ring[0].rank),
                )
            for p in planes:
                p.publish_once()
            plane = repairs[0]
            assert wait_for(
                lambda: len(ring[0].fleet.digests()) == len(ring)
            )
            assert plane.scan_once() == 0  # threshold holds
            from radixmesh_tpu.cache.oplog import OplogType

            plane.note_loss("transmit", int(OplogType.INSERT))
            assert plane.scan_once() > 0  # early probe fired
            # Control-kind losses must NOT waive the threshold.
            plane2 = repairs[1]
            with ring[1]._lock:
                ring[1]._mesh_insert(
                    np.array([8, 8], np.int32),
                    PrefillValue(np.arange(2, dtype=np.int32), ring[1].rank),
                )
            for p in planes:
                p.publish_once()
            assert wait_for(
                lambda: len(ring[1].fleet.digests()) == len(ring)
            )
            plane2._early_until = 0.0
            plane2.note_loss("transmit", int(OplogType.TICK))
            assert plane2.scan_once() == 0
        finally:
            close_all(nodes, planes, repairs)


class TestDrainDuringSession:
    def test_peer_drain_aborts_session_cleanly(self):
        """Drain-under-chaos edge case (PR 6): a node drains while it is
        the PEER of an open repair session. The initiator's session must
        abort cleanly — the LEAVE drops the peer from its fleet view, the
        next scan prunes the peer state (no wedged budget, no probes at a
        ghost), and repair with the remaining fleet is unaffected."""
        from radixmesh_tpu.policy.lifecycle import LifecycleConfig, LifecyclePlane

        nodes, ring, router, planes, repairs = make_cluster(
            repair_cfg=RepairConfig(
                interval_s=10.0,  # scans driven by hand
                age_threshold_s=0.0, backoff_base_s=0.5, backoff_max_s=5.0,
                jitter_frac=0.0,
            )
        )
        lc = None
        try:
            # Diverge ring[0] from everyone; ring[1] (the future drainer)
            # never answers — its repair plane stays unstarted, so the
            # session against it hangs open mid-exchange.
            with ring[0]._lock:
                ring[0]._mesh_insert(
                    np.array([6, 6, 6], np.int32),
                    PrefillValue(np.arange(3, dtype=np.int32), ring[0].rank),
                )
            for p in planes:
                p.publish_once()
            plane = repairs[0]
            assert wait_for(
                lambda: len(ring[0].fleet.digests()) == len(ring)
            )
            assert plane.scan_once() > 0  # probes out, incl. to ring[1]
            assert ring[1].rank in plane._peers
            st_before = dict(plane._peers[ring[1].rank])
            assert st_before["rounds"] >= 1
            # ring[1] drains mid-session (its own plane closes first —
            # drain quiesces repair before LEAVE).
            lc = LifecyclePlane(
                ring[1], repair=repairs[1], fleet_plane=planes[1],
                cfg=LifecycleConfig(leave_retries=2, leave_confirm_s=0.1),
            )
            lc.drain(deadline_s=1.0)
            survivors = [n for n in nodes if n is not ring[1]]
            assert wait_for(
                lambda: all(
                    not n.view.contains(ring[1].rank) for n in survivors
                )
            )
            # The initiator's next scan prunes the departed peer: no
            # wedged session state, no further probes at it.
            sent_before = plane.stats()["probes_sent"]
            assert wait_for(
                lambda: (plane.scan_once(), ring[1].rank not in plane._peers)[1]
            ), "session state against the drained peer never pruned"
            for _ in range(3):
                plane.scan_once()
            assert ring[1].rank not in plane._peers
            assert ring[1].rank not in plane.stats()["diverged_peers"]
            # Probes may still flow to OTHER diverged peers — just never
            # to the drained one (its channel would be a ghost).
            assert plane.stats()["probes_sent"] >= sent_before
        finally:
            if lc is not None:
                lc.close()
            close_all(nodes, planes, repairs)


class TestChaosAcceptance:
    def test_chaos_scenario_converges_and_quiesces(self):
        """The acceptance criterion at test scale: seeded 20% loss + a
        partition of one prefill → divergence detected → repair
        converges P, D, AND router within the round budget — with
        requests served throughout and zero repair traffic once
        converged — then the PR 6 membership phases: a graceful drain
        under re-opened loss (zero failed, requeued-and-served, no
        failure detection) and a cold rejoin during a fresh partition
        (bootstrap within budget, router withholds hits until
        convergence) — then the PR 7 crash phase: an unclean decode-node
        kill mid-stream (zero lost requests, byte-identical resumes,
        resurrection ≥ 0.8 cache hit, budget-bounded recovery hops)."""
        from radixmesh_tpu.workload import (
            build_chaos_report,
            run_chaos_workload,
            validate_chaos,
        )

        # 60 requests paced through a 1.5 s fault window put ~200
        # seeded-droppable data frames on the wire — enough that the
        # seed-0 drop stream always loses INSERT frames (verified; a
        # smaller window can thread the needle and lose only control
        # frames, which heal by queueing).
        res = run_chaos_workload(
            partition_s=1.2,
            partition_delay_s=0.3,
            n_requests=60,
            quiesce_window_s=0.8,
            timeout_s=45.0,
            join_partition_s=1.0,
            drain_requests=25,
            crash_streams=8,
            crash_tokens=16,
        )
        report = build_chaos_report(res)
        assert validate_chaos(report) == []
        assert res["divergence"]["detected"]
        assert res["repair"]["converged"]
        assert res["repair"]["within_round_budget"]
        assert res["quiescence"]["quiet"]
        assert res["served"]["ok_rate_during_fault"] >= 0.9
        # Membership-lifecycle gates (validate_chaos enforces them too;
        # asserted directly so a failure names the exact phase).
        drain = res["drain"]
        assert drain["performed"] and drain["zero_failed"]
        assert drain["left_without_failure_detection"]
        assert drain["requeued_served"] == drain["requeued"]
        assert drain["writeback_flushed"]
        join = res["join"]
        assert join["performed"] and join["converged_with_donor"]
        assert join["within_round_budget"]
        assert join["hits_to_bootstrapping"] == 0
        assert join["withheld_hits"] > 0
        assert join["fleet_converged_after_join"]
        # Request-recovery gates (PR 7, server/recovery.py): the unclean
        # kill loses nothing, resumes byte-identically from the
        # replicated cache, and stays inside the deadline budget.
        crash = res["crash"]
        assert crash["performed"] and crash["failed"] == 0
        assert crash["interrupted"] > 0
        assert crash["resumed"] == crash["interrupted"]
        assert crash["prefix_identical"]
        assert crash["resurrection_hit_ratio"] >= 0.8
        assert crash["budget"]["within_one_backoff"]
        assert crash["hedge"]["first_writer_wins"]
        assert crash["hedge"]["loser_cancelled"]

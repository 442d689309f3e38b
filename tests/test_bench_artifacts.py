"""Bench artifact contracts (no measuring, no jax): the pinned artifact
schemas — 'artifacts must stay machine-comparable across rounds'."""

import json

import pytest

import bench

pytestmark = pytest.mark.quick


def _run_section(page_size: int) -> dict:
    return {
        "metric": "ring_insert_throughput",
        "value": 2800.0,
        "unit": "inserts/s (ingested+converged, 5 writers, 6 procs)",
        "transport": "native-cpp-tcp",
        "topology": "3 prefill + 2 decode + 1 router (localhost)",
        "inserts_per_writer": 400,
        "key_len_tokens": 256,
        "page_size": page_size,
        "wire_bytes_per_insert": 864 if page_size > 1 else 1584,
        "ingest_s_max": 0.2,
        "converge_s_max": 0.7,
        "oplog_applies_per_s": 14000.0,
        "lap_latency": {"p50_ms": 1.0, "p99_ms": 2.0, "mean_ms": 1.1, "n": 200},
        "route": {"routes_per_s": 12000.0, "p50_ms": 0.08, "p99_ms": 0.14,
                  "mean_ms": 0.08, "n": 5000},
        "wall_s": 16.0,
    }


def _full_report() -> dict:
    paged = _run_section(16)
    token = _run_section(1)
    return {
        "schema_version": bench.RINGBENCH_SCHEMA_VERSION,
        "metric": "ring_insert_throughput",
        "value": paged["value"],
        "unit": paged["unit"],
        "workload": "256-token keys, 400/writer",
        "page_granular": paged,
        "token_granular_baseline": token,
        "bytes_per_insert_ratio": 1.833,
        "inserts_per_s_ratio": 1.3,
        "lap_latency": paged["lap_latency"],
        "round3_wire_bytes_per_insert": bench.RINGBENCH_ROUND3_WIRE_BYTES,
        "vs_round3_wire": 2.421,
    }


class TestRingbenchSchema:
    def test_complete_report_validates(self):
        assert bench.validate_ringbench(_full_report()) == []

    def test_missing_fields_are_named(self):
        report = _full_report()
        del report["lap_latency"]  # the field r04 lacked
        del report["bytes_per_insert_ratio"]  # the field r05 lacked
        del report["page_granular"]["lap_latency"]["p99_ms"]
        missing = bench.validate_ringbench(report)
        assert "lap_latency" in missing
        assert "bytes_per_insert_ratio" in missing
        assert "page_granular.lap_latency.p99_ms" in missing

    def test_run_paired_shape_matches_schema(self):
        """The emitter and the validator agree: a synthetic paired report
        built the way scripts/ringbench.py builds one passes."""
        import importlib.util, os, sys

        spec = importlib.util.spec_from_file_location(
            "_ringbench_schema_check",
            os.path.join(os.path.dirname(bench.__file__) or ".",
                         "scripts", "ringbench.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        # Patch the heavy 6-process run with canned sections; run_paired's
        # assembly logic is what the schema pins.
        sections = iter([_run_section(16), _run_section(1)])
        mod.run = lambda *a, **k: next(sections)
        report = mod.run_paired(400, 200, 5000)
        assert report["schema_version"] == bench.RINGBENCH_SCHEMA_VERSION
        assert "schema_violation" not in report
        assert bench.validate_ringbench(report) == []
        assert report["vs_round3_wire"] == pytest.approx(2092 / 864, abs=1e-3)


class TestTraceArtifactSchema:
    """The flight-recorder trace artifact (TRACE_r{N}.json / workload
    trace_path emissions) stays machine-loadable: valid JSON object, a
    traceEvents list, numeric ts/dur, monotonic ts within each lane."""

    def _trace(self) -> dict:
        from radixmesh_tpu.obs.trace_plane import FlightRecorder

        rec = FlightRecorder(capacity=256, sample=1.0)
        ctx = rec.trace("req:1")
        ctx.add("admission_wait", 1.0, 0.01)
        ctx.add("prefill_wave", 1.01, 0.2, wave_rows=2)
        ctx.add("decode_chunk", 1.21, 0.05, k_steps=8)
        ctx.add("publish", 1.26, 0.002)
        rec.event("ring:decode@1", "replication_lag", 1.27, 0.003,
                  origin_rank=0)
        return json.loads(json.dumps(rec.chrome_trace()))

    def test_recorder_export_validates(self):
        assert bench.validate_trace(self._trace()) == []

    def test_violations_are_named(self):
        obj = self._trace()
        xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        xs[0]["ts"] = -5.0           # negative timestamp
        xs[1]["dur"] = "fast"        # non-numeric duration
        del xs[2]["tid"]             # no lane
        problems = "\n".join(bench.validate_trace(obj))
        assert "ts invalid" in problems
        assert "dur invalid" in problems
        assert "tid missing" in problems

    def test_ts_regression_within_lane_is_flagged(self):
        obj = self._trace()
        xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        same_lane = [e for e in xs if e["tid"] == xs[0]["tid"]]
        assert len(same_lane) >= 2
        same_lane[-1]["ts"] = 0.0  # jump backwards in its lane
        assert any(
            "regresses within tid" in p for p in bench.validate_trace(obj)
        )

    def test_not_an_object_and_missing_events(self):
        assert bench.validate_trace([1, 2]) == ["artifact is not a JSON object"]
        assert bench.validate_trace({"x": 1}) == [
            "traceEvents missing or not a list"
        ]


class TestFleetArtifactSchema:
    """The FLEET artifact (fleet telemetry plane, PR 3) stays machine-
    comparable across rounds: pinned top/section fields, the digest byte
    budget respected, and the one-frame-per-publish piggyback contract."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.FLEET_SCHEMA_VERSION,
            "metric": "fleet_digest_fan_in_p50_s",
            "value": 0.006,
            "unit": "s (one digest round visible on every node incl. router)",
            "workload": "120 inserts over 3 writers + injected divergence "
                        "+ injected stall (inproc ring)",
            "nodes": 4,
            "topology": "2 prefill + 1 decode + 1 router (inproc)",
            "digest_interval_s": 0.1,
            "digest_bytes": 112,
            "digest_byte_budget": 160,
            "fan_in": {"rounds": 5, "p50_s": 0.006, "max_s": 0.009},
            "convergence": {
                "inserts": 120, "writers": 3, "churn_s": 0.05,
                "max_age_during_churn_s": 0.02,
                "quiesce_to_converged_s": 0.1, "converged": True,
                "injected_divergence_detected": True,
                "age_while_diverged_s": 0.16, "healed": True, "heal_s": 0.3,
            },
            "stall_reaction": {
                "injected": True, "detected": True, "reaction_s": 0.05,
                "score_after": 0.0, "threshold": 0.5,
            },
            "health_aware_demotion": True,
            "digests_published": 54,
            "digest_frames_per_publish": 0.98,
            "wall_s": 0.5,
        }

    def test_complete_report_validates(self):
        assert bench.validate_fleet(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["health_aware_demotion"]
        del report["convergence"]["heal_s"]
        del report["stall_reaction"]["reaction_s"]
        missing = bench.validate_fleet(report)
        assert "health_aware_demotion" in missing
        assert "convergence.heal_s" in missing
        assert "stall_reaction.reaction_s" in missing

    def test_budget_and_frame_contracts_enforced(self):
        report = self._report()
        report["digest_bytes"] = 900  # over the pinned budget
        report["digest_frames_per_publish"] = 1.4  # piggyback broken
        problems = "\n".join(bench.validate_fleet(report))
        assert "exceeds digest_byte_budget" in problems
        assert "piggyback contract" in problems
        assert bench.validate_fleet([1]) == ["artifact is not a JSON object"]

    def test_emitter_output_matches_schema(self):
        """The workload's real output assembled by build_fleet_report
        passes the validator — emitter and schema cannot drift."""
        res = {
            "nodes": 4,
            "topology": "2 prefill + 1 decode + 1 router (inproc)",
            "digest_interval_s": 0.1,
            "digest_bytes": 112,
            "fan_in": self._report()["fan_in"],
            "convergence": self._report()["convergence"],
            "stall_reaction": self._report()["stall_reaction"],
            "health_aware_demotion": True,
            "digests_published": 54,
            "digest_frames_per_publish": 0.98,
            "wall_s": 0.5,
        }
        report = bench.build_fleet_report(res)
        assert bench.validate_fleet(report) == []
        from radixmesh_tpu.obs.fleet_plane import DIGEST_BYTE_BUDGET

        assert report["digest_byte_budget"] == DIGEST_BYTE_BUDGET


class TestKvflowArtifactSchema:
    """The KVFLOW artifact (async KV-movement plane, PR 4) stays
    machine-comparable across rounds: pinned top/section fields plus the
    two deterministic structural contracts — write-back gathers fused to
    at most one per sweep, and decode progress while a restore is in
    flight strictly above the synchronous path's zero."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.KVFLOW_SCHEMA_VERSION,
            "metric": "kv_restore_overlapped_ttft_ratio",
            "value": 0.94,
            "unit": "overlapped/sync mean TTFT of a host-tier restore burst",
            "workload": "4 host-tier restore requests x 3 interleaved trials",
            "restore": {
                "requests": 4, "repeats": 3,
                "sync_ttft_s": 0.236, "overlapped_ttft_s": 0.222,
                "overlap_ratio": 0.94, "overlap_wins": True,
                "sync_ttft_trials_s": [0.23, 0.22, 0.25],
                "overlapped_ttft_trials_s": [0.19, 0.23, 0.24],
                "sync_restore_ttft_s": 0.7, "overlapped_restore_ttft_s": 0.95,
                "sync_fresh_ttft_s": 0.8, "overlapped_fresh_ttft_s": 0.15,
                "restored_tokens": 3072, "parked_requests": 4,
                "decode_steps_during_restore": 1,
                "sync_decode_steps_during_restore": 0,
                "max_decode_gap_s": 0.29, "sync_max_decode_gap_s": 0.33,
            },
            "writeback": {
                "tokens_written_back": 3072, "sweeps": 1, "gathers": 1,
                "gathers_per_sweep": 1.0, "sync_gathers_per_sweep": 1.0,
                "evict_stall_s": 0.003, "sync_evict_stall_s": 0.04,
            },
            "prefetch": {
                "hints_sent": 8, "hints_joined": 4, "hit_ahead_rate": 1.0,
            },
            "chunk_tokens": 512,
            "ttft_chunk_tokens": 1536,
            "page_size": 4,
            "wall_s": 18.9,
        }

    def test_complete_report_validates(self):
        assert bench.validate_kvflow(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["chunk_tokens"]
        del report["restore"]["overlap_wins"]
        del report["prefetch"]["hit_ahead_rate"]
        missing = bench.validate_kvflow(report)
        assert "chunk_tokens" in missing
        assert "restore.overlap_wins" in missing
        assert "prefetch.hit_ahead_rate" in missing

    def test_structural_contracts_enforced(self):
        report = self._report()
        report["writeback"]["gathers_per_sweep"] = 3.0  # unfused
        report["restore"]["decode_steps_during_restore"] = 0  # blocked
        problems = "\n".join(bench.validate_kvflow(report))
        assert "fused-gather contract" in problems
        assert "decode-never-blocks contract" in problems
        assert bench.validate_kvflow([1]) == ["artifact is not a JSON object"]

    def test_build_report_matches_schema(self):
        """build_kvflow_report over a workload-shaped result passes the
        validator — emitter and schema cannot drift."""
        res = self._report()
        for k in ("schema_version", "metric", "value", "unit", "workload"):
            res.pop(k)
        assert bench.validate_kvflow(bench.build_kvflow_report(res)) == []

    def test_checked_in_artifact_validates(self):
        """The round artifact shipped with this PR passes its own
        schema (guards hand-edits and emitter drift alike)."""
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "KVFLOW_r*.json")))
        assert paths, "no KVFLOW artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_kvflow(report) == []


class TestChaosArtifactSchema:
    """The CHAOS artifact (self-healing mesh, PR 5) stays machine-
    comparable across rounds: pinned top/section fields plus the three
    structural acceptance gates — converged, within the repair-round
    budget, quiescent after convergence."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.CHAOS_SCHEMA_VERSION,
            "metric": "chaos_heal_converge_s",
            "value": 0.2,
            "unit": "s from fault-window close to ALL replicas pairwise "
                    "fingerprint-equal via anti-entropy repair",
            "workload": "20% seeded frame loss + 10s partition of cp1",
            "nodes": 4,
            "topology": "2 prefill + 1 decode + 1 router (inproc)",
            "round_budget": 8,
            "fault_plan": {
                "seed": 0, "drop_p": 0.2, "drop_window_s": 11.0,
                "partition_s": 10.0, "partitioned_node": "cp1",
                "frames_dropped": 88, "frames_delivered": 3486,
            },
            "served": {
                "attempted": 150, "ok": 150, "ok_rate_during_fault": 1.0,
            },
            "divergence": {
                "detected": True, "peak_diverged_pairs": 3,
                "max_age_s": 10.7,
            },
            "repair": {
                "converged": True, "converge_s": 0.2,
                "max_episode_rounds": 6, "within_round_budget": True,
                "probes_sent": 34, "summaries_sent": 52,
                "keys_pushed": 328, "oplogs_reemitted": 328, "heals": 12,
            },
            "quiescence": {
                "window_s": 2.0, "traffic_before": 86,
                "traffic_after": 86, "quiet": True,
            },
            "drain": {
                "performed": True, "node": "cp2", "drop_p": 0.2,
                "requeued": 6, "requeued_served": 6,
                "attempted_during_drain": 40, "ok_during_drain": 40,
                "zero_failed": True,
                "left_without_failure_detection": True,
                "left_cause_transitions": 1,
                "writeback_tokens": 1150, "writeback_flushed": True,
                "drain_s": 0.6,
            },
            "join": {
                "performed": True, "joiner": "cp2", "donor_rank": 0,
                "partition_active_at_join": True, "partition_s": 1.5,
                "partitioned_node": "cp1",
                "bootstrap_converge_s": 1.8, "bootstrap_rounds": 2,
                "round_budget": 16, "within_round_budget": True,
                "converged_with_donor": True, "withheld_hits": 30,
                "hits_to_bootstrapping": 0, "post_bootstrap_hits": 6,
                "fleet_converged_after_join": True, "join_s": 2.0,
            },
            "crash": {
                "performed": True, "node": "cd0", "drop_p": 0.2,
                "streams": 12, "tokens_per_stream": 24,
                "killed_at_token": 12, "interrupted": 10, "resumed": 10,
                "failed": 0, "prefix_identical": True,
                "replayed_tokens": 280, "replayed_cached_tokens": 268,
                "resurrection_hit_ratio": 0.957, "retries": 10,
                "resurrections": 10, "failover_routes": 10,
                "detection": {
                    "trigger": "hop_timeout", "hop_timeout_s": 0.4,
                    "detect_s": 0.4,
                },
                "budget": {
                    "deadline_s": 20.0, "max_overrun_s": 0.0,
                    "max_backoff_s": 0.06, "within_one_backoff": True,
                },
                "hedge": {
                    "fired": True, "winner": "cp1",
                    "first_writer_wins": True, "loser_cancelled": True,
                },
                "crash_s": 9.2,
            },
            "rebalance": {
                "performed": True, "skew_before": 20.3, "skew_after": 14.6,
                "skew_dropped": True, "moves": 4,
                "max_moves_per_round": 4, "moves_bounded": True,
                "boosted_shards": [19, 42, 37, 58], "hot_shard": 19,
                "attempted_mid_move": 175, "ok_mid_move": 175,
                "failed_mid_move": 0, "overrides_version": 1,
                "overrides_converged": True, "handoff_entries": 8,
                "requests_wave1": 155, "rebalance_s": 6.0,
            },
            "router_kill": {
                "performed": True, "routers": 2, "killed": "cr0",
                "survivor": "cr1", "streams": 10, "inflight_at_kill": 10,
                "completed": 10, "failed": 0, "failovers": 1, "hedges": 1,
                "survivor_served": True, "router_kill_s": 0.4,
            },
            "wall_s": 14.7,
        }

    def test_complete_report_validates(self):
        assert bench.validate_chaos(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["round_budget"]
        del report["repair"]["converge_s"]
        del report["quiescence"]["quiet"]
        del report["drain"]["writeback_tokens"]
        del report["join"]["bootstrap_rounds"]
        del report["crash"]["resurrection_hit_ratio"]
        missing = bench.validate_chaos(report)
        assert "round_budget" in missing
        assert "repair.converge_s" in missing
        assert "quiescence.quiet" in missing
        assert "drain.writeback_tokens" in missing
        assert "join.bootstrap_rounds" in missing
        assert "crash.resurrection_hit_ratio" in missing

    def test_acceptance_gates_enforced(self):
        report = self._report()
        report["repair"]["converged"] = False
        report["repair"]["within_round_budget"] = False
        report["divergence"]["detected"] = False
        report["quiescence"]["quiet"] = False
        problems = "\n".join(bench.validate_chaos(report))
        assert "never healed" in problems
        assert "exceeded round_budget" in problems
        assert "injected nothing" in problems
        assert "kept flowing" in problems
        assert bench.validate_chaos(7) == ["artifact is not a JSON object"]

    def test_lifecycle_gates_enforced(self):
        """The PR 6 membership gates: a drain that failed requests or
        tripped failure detection, or a join the router kept hit-routing
        to (or that never converged), must be named violations."""
        report = self._report()
        report["drain"]["zero_failed"] = False
        report["drain"]["left_without_failure_detection"] = False
        report["drain"]["writeback_flushed"] = False
        report["drain"]["requeued_served"] = 3
        report["join"]["converged_with_donor"] = False
        report["join"]["within_round_budget"] = False
        report["join"]["hits_to_bootstrapping"] = 4
        report["join"]["withheld_hits"] = 0
        problems = "\n".join(bench.validate_chaos(report))
        assert "requests failed during the graceful drain" in problems
        assert "requeued but not all served" in problems
        assert "tripped failure detection" in problems
        assert "not written back" in problems
        assert "never converged with its donor" in problems
        assert "over the budget" in problems
        assert "routed cache hits to a BOOTSTRAPPING node" in problems
        assert "never withheld a hit" in problems

    def test_crash_gates_enforced(self):
        """The PR 7 request-recovery gates: a kill that lost requests,
        a resume that corrupted the delivered prefix, a replay the cache
        didn't serve, a budget overrun past one backoff, or a hedge that
        broke first-writer-wins must all be named violations."""
        report = self._report()
        report["crash"]["failed"] = 2
        report["crash"]["resumed"] = 8
        report["crash"]["prefix_identical"] = False
        report["crash"]["resurrection_hit_ratio"] = 0.5
        report["crash"]["budget"]["within_one_backoff"] = False
        report["crash"]["hedge"]["first_writer_wins"] = False
        report["crash"]["hedge"]["loser_cancelled"] = False
        problems = "\n".join(bench.validate_chaos(report))
        assert "LOST to the unclean kill" in problems
        assert "not all resurrected" in problems
        assert "prefix not byte-identical" in problems
        assert "below 0.8" in problems
        assert "more than one retry backoff" in problems
        assert "first successful writer did not win" in problems
        assert "loser was not cancelled" in problems

    def test_crash_must_interrupt_something(self):
        """A kill that interrupted zero live streams proves nothing —
        the gate refuses vacuous passes."""
        report = self._report()
        report["crash"]["interrupted"] = 0
        report["crash"]["resumed"] = 0
        problems = "\n".join(bench.validate_chaos(report))
        assert "interrupted zero live streams" in problems

    def test_v1_artifact_without_lifecycle_sections_stays_valid(self):
        """CHAOS_r06 predates the join/drain sections: v1 artifacts must
        keep validating (version bumps add, never break)."""
        report = self._report()
        del report["drain"]
        del report["join"]
        del report["crash"]
        report["schema_version"] = 1
        assert bench.validate_chaos(report) == []

    def test_v2_artifact_without_crash_section_stays_valid(self):
        """CHAOS_r07 predates the crash section: v2 artifacts must keep
        validating with the join/drain sections but no crash."""
        report = self._report()
        del report["crash"]
        del report["rebalance"]
        del report["router_kill"]
        report["schema_version"] = 2
        assert bench.validate_chaos(report) == []

    def test_v3_artifact_without_robustness_sections_stays_valid(self):
        """CHAOS_r08 predates the rebalance/router_kill sections (PR 14):
        v3 artifacts must keep validating without them."""
        report = self._report()
        del report["rebalance"]
        del report["router_kill"]
        report["schema_version"] = 3
        assert bench.validate_chaos(report) == []

    def test_skipped_phase_is_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["drain"] = {"performed": False}
        report["join"] = {"performed": False}
        report["crash"] = {"performed": False}
        report["rebalance"] = {"performed": False}
        report["router_kill"] = {"performed": False}
        assert bench.validate_chaos(report) == []

    def test_rebalance_gates_enforced(self):
        """The PR 14 robustness-loop gates: a storm whose skew did not
        strictly drop, requests failed mid-move, unbounded or zero
        movement, or a fleet that never converged on the override
        version must all be named violations."""
        report = self._report()
        report["rebalance"]["skew_after"] = report["rebalance"][
            "skew_before"
        ]
        report["rebalance"]["failed_mid_move"] = 3
        report["rebalance"]["moves"] = 0
        report["rebalance"]["moves_bounded"] = False
        report["rebalance"]["overrides_converged"] = False
        problems = "\n".join(bench.validate_chaos(report))
        assert "did not strictly drop" in problems
        assert "failed mid-move" in problems
        assert "zero adopted moves" in problems
        assert "exceeded the per-round bound" in problems
        assert "never converged on the decider's override version" in problems

    def test_router_kill_gates_enforced(self):
        report = self._report()
        report["router_kill"]["routers"] = 1
        report["router_kill"]["failed"] = 1
        report["router_kill"]["completed"] = 8
        report["router_kill"]["inflight_at_kill"] = 0
        report["router_kill"]["failovers"] = 0
        report["router_kill"]["survivor_served"] = False
        problems = "\n".join(bench.validate_chaos(report))
        assert "needs N >= 2" in problems
        assert "LOST to the router kill" in problems
        assert "did not all complete" in problems
        assert "interrupted zero in-flight streams" in problems
        assert "never failed over" in problems
        assert "served no post-kill routes" in problems

    def test_build_report_matches_schema(self):
        res = {
            k: self._report()[k]
            for k in (
                "nodes", "topology", "round_budget", "fault_plan", "served",
                "divergence", "repair", "quiescence", "drain", "join",
                "crash", "rebalance", "router_kill", "wall_s",
            )
        }
        report = bench.build_chaos_report(res)
        assert bench.validate_chaos(report) == []
        assert report["value"] == res["repair"]["converge_s"]

    def test_checked_in_artifact_validates(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "CHAOS_r*.json")))
        assert paths, "no CHAOS artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_chaos(report) == []


class TestRingscaleArtifactSchema:
    """RINGSCALE v2 (scripts/ringscale.py + prefix-ownership sharding,
    cache/sharding.py): per-row rf/mode fields, the bytes-per-insert
    FLATNESS gate for sharded rows, the owner-propagation gate, and v1
    (pre-sharding, full-replica-only) artifacts staying valid."""

    @staticmethod
    def _row(n, rf, mode="sim", bytes_=None, p99=None):
        return {
            "n_nodes": n,
            "topology": "ring",
            "rf": rf,
            "mode": mode,
            "hop_delay_ms": 1.0,
            "frame_bytes": 252,
            "frames_per_insert": rf if rf else n,
            "measured_frames_per_insert": float(rf if rf else n),
            "ring_bytes_per_insert": (
                bytes_ if bytes_ is not None else 252 * (rf if rf else n)
            ),
            "prop_p50_ms": p99 if p99 is not None else 1.0,
            "prop_p99_ms": p99 if p99 is not None else 1.0,
        }

    def _report(self, rows):
        return {
            "schema_version": 2,
            "metric": "ring_scale_sweep",
            "mode": "mixed:live+sim",
            "sizes": sorted({r["n_nodes"] for r in rows}),
            "hop_delays_ms": [1.0],
            "rfs": sorted({r["rf"] for r in rows}),
            "results": rows,
            "bytes_per_insert_growth": {},
        }

    def test_complete_report_validates(self):
        rows = [
            self._row(12, 0, p99=11.0),
            self._row(200, 0, p99=199.0),
            self._row(12, 3, p99=1.0),
            self._row(200, 3, p99=1.0),
        ]
        assert bench.validate_ringscale(self._report(rows)) == []

    def test_missing_row_fields_are_named(self):
        rows = [self._row(12, 3)]
        del rows[0]["ring_bytes_per_insert"]
        problems = bench.validate_ringscale(self._report(rows))
        assert "results[0].ring_bytes_per_insert" in problems

    def test_flatness_gate_enforced(self):
        """Sharded bytes-per-insert growing with N is exactly the O(N)
        wall the plane exists to break — the gate must catch it."""
        rows = [
            self._row(12, 3, bytes_=756),
            self._row(200, 3, bytes_=7560),  # 10x growth: the wall is back
        ]
        problems = bench.validate_ringscale(self._report(rows))
        assert any("flatness" in p for p in problems), problems
        # Within 1.5x passes.
        rows = [self._row(12, 3, bytes_=700), self._row(200, 3, bytes_=756)]
        assert bench.validate_ringscale(self._report(rows)) == []

    def test_propagation_gate_enforced(self):
        """Owner-propagation p99 must not exceed the full-replica ring
        at the smallest size (same delay + mode)."""
        rows = [
            self._row(12, 0, mode="threads+tcp-py", p99=10.0),
            self._row(12, 3, mode="threads+tcp-py", p99=50.0),
        ]
        problems = bench.validate_ringscale(self._report(rows))
        assert any("propagation" in p for p in problems), problems
        # Sim rows are not compared against live rows.
        rows = [
            self._row(12, 0, mode="threads+tcp-py", p99=10.0),
            self._row(200, 3, mode="sim", p99=50.0),
        ]
        assert bench.validate_ringscale(self._report(rows)) == []

    def test_v1_artifact_stays_valid(self):
        """Pre-sharding artifacts (no schema_version; full-replica rows
        without rf/mode fields) keep validating as-is."""
        v1 = {
            "metric": "ring_scale_sweep",
            "mode": "procs+native",
            "sizes": [12, 25],
            "results": [
                {"n_nodes": 12, "topology": "ring",
                 "ring_bytes_per_insert": 3024},
            ],
        }
        assert bench.validate_ringscale(v1) == []
        assert bench.validate_ringscale({"metric": "other"}) != []

    def test_checked_in_artifacts_validate(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "RINGSCALE_r*.json")))
        assert paths, "no RINGSCALE artifact checked in"
        for path in paths:
            with open(path) as fh:
                report = json.load(fh)
            assert bench.validate_ringscale(report) == [], path
        # The newest artifact must be current-schema and actually
        # demonstrate the flat sharded curve at the 200-node ceiling;
        # from v3 on it must also carry the owner-propagation-under-
        # overrides row (the PR 14 deferral, measured in PR 15).
        with open(paths[-1]) as fh:
            newest = json.load(fh)
        assert newest.get("schema_version") >= 2
        sharded = [
            r for r in newest["results"] if int(r.get("rf", 0)) > 0
        ]
        assert any(r["n_nodes"] >= 200 for r in sharded)
        if newest.get("schema_version") >= 3:
            ov = [
                r for r in newest["results"] if r.get("overrides_active")
            ]
            assert ov and all(int(r.get("rf", 0)) > 0 for r in ov)
            assert any(r["n_nodes"] >= 200 for r in ov)


class TestObsArtifactSchema:
    """OBS v1 (PR 9, mesh-wide observability): the stitched-trace gate
    (interrupted request on >= OBS_MIN_NODE_TRACKS node tracks under one
    trace id, replication edges visible, zero lost streams), the heat
    gate (zipf hot shard detected with the correct owner set, skew above
    the floor), the step-attribution gate (per-wave MFU + pad fraction
    for prefill AND decode), and the wire gate (traceless frames
    bit-for-bit pre-PR-9)."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.OBS_SCHEMA_VERSION,
            "metric": "obs_stitched_node_tracks",
            "value": 6,
            "unit": "node tracks under a single 64-bit trace id",
            "workload": "traced crash drill + zipf heat + tiny-engine burst",
            "nodes": 7,
            "topology": "4 prefill + 2 decode + 1 router (inproc)",
            "replication_factor": 3,
            "stitch": {
                "performed": True, "node": "od0", "streams": 8,
                "tokens_per_stream": 20, "interrupted": 6, "resumed": 6,
                "failed": 0, "trace_id": "0x3da6417a0df7ba6d",
                "node_tracks": 6,
                "nodes_on_track": [
                    "decode@4", "decode@5", "obs-edge",
                    "prefill@0", "prefill@2", "prefill@3",
                ],
                "replication_edges": 37, "publish_edges": 20,
                "span_count": 2544, "stitched_events": 2561,
            },
            "heat": {
                "performed": True, "inserts": 394, "distinct_keys": 64,
                "zipf_alpha": 1.4, "skew_score": 16.3,
                "hot_shard": 7, "expected_hot_shard": 7,
                "hot_owners": [0, 1, 2, 4, 5],
                "expected_hot_owners": [0, 1, 2, 4, 5],
                "owner_set_correct": True, "reporters": 6,
            },
            "steps": {
                "performed": True, "n_params": 426624, "peak_tflops": 1.0,
                "prefill": {
                    "waves": 3, "real_tokens": 19, "padded_tokens": 32,
                    "mfu": 1.0e-05, "pad_fraction": 0.40625,
                },
                "decode": {
                    "waves": 30, "real_tokens": 45, "padded_tokens": 60,
                    "mfu": 1.9e-05, "pad_fraction": 0.25,
                },
            },
            "wire": {
                "rf0_traceless_unchanged": True,
                "trace_trailer_roundtrip": True,
                "trailer_bytes": 8,
            },
            "wall_s": 10.7,
        }

    def test_complete_report_validates(self):
        assert bench.validate_obs(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["replication_factor"]
        del report["stitch"]["trace_id"]
        del report["heat"]["skew_score"]
        del report["steps"]["prefill"]["mfu"]
        del report["wire"]["trailer_bytes"]
        missing = bench.validate_obs(report)
        assert "replication_factor" in missing
        assert "stitch.trace_id" in missing
        assert any("skew_score" in m for m in missing)
        assert "steps.prefill.mfu" in missing
        assert "wire.trailer_bytes" in missing
        assert bench.validate_obs(7) == ["artifact is not a JSON object"]

    def test_stitch_gates_enforced(self):
        report = self._report()
        report["stitch"]["node_tracks"] = bench.OBS_MIN_NODE_TRACKS - 1
        report["stitch"]["failed"] = 2
        report["stitch"]["resumed"] = 3
        report["stitch"]["replication_edges"] = 0
        problems = "\n".join(bench.validate_obs(report))
        assert "did not stitch" in problems
        assert "LOST" in problems
        assert "not all resurrected" in problems
        assert "no replication edges" in problems

    def test_heat_gates_enforced(self):
        report = self._report()
        report["heat"]["skew_score"] = bench.OBS_MIN_SKEW_SCORE - 0.5
        report["heat"]["hot_shard"] = 9
        report["heat"]["owner_set_correct"] = False
        report["heat"]["reporters"] = 0
        problems = "\n".join(bench.validate_obs(report))
        assert "skew score" in problems
        assert "ground truth" in problems
        assert "owner set was not correctly named" in problems
        assert "zero heat reporters" in problems

    def test_step_and_wire_gates_enforced(self):
        report = self._report()
        report["steps"]["decode"]["waves"] = 0
        report["steps"]["decode"]["mfu"] = 0.0
        report["steps"]["prefill"]["pad_fraction"] = 1.5
        report["wire"]["rf0_traceless_unchanged"] = False
        problems = "\n".join(bench.validate_obs(report))
        assert "zero decode waves" in problems
        assert "decode MFU" in problems
        assert "pad fraction" in problems
        assert "bit-for-bit" in problems

    def test_skipped_legs_are_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["stitch"] = {"performed": False}
        report["heat"] = {"performed": False}
        report["steps"] = {"performed": False}
        assert bench.validate_obs(report) == []

    def test_build_report_matches_schema(self):
        res = {
            k: self._report()[k]
            for k in (
                "nodes", "topology", "replication_factor", "stitch",
                "heat", "steps", "wire", "wall_s",
            )
        }
        report = bench.build_obs_report(res)
        assert bench.validate_obs(report) == []
        assert report["value"] == res["stitch"]["node_tracks"]

    def test_checked_in_artifact_validates_and_gates_green(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "OBS_r*.json")))
        assert paths, "no OBS artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_obs(report) == []
        assert "schema_violation" not in report
        # The acceptance headline numbers really are in the artifact.
        assert report["stitch"]["node_tracks"] >= bench.OBS_MIN_NODE_TRACKS
        assert report["heat"]["skew_score"] >= bench.OBS_MIN_SKEW_SCORE
        assert report["steps"]["performed"] is True


class TestAnalysisArtifactSchema:
    """ANALYSIS v2 (PR 11, meshcheck concurrency plane): the artifact
    gates on ZERO unsuppressed findings over the tree, every default
    checker present (now including thread-roots/guarded-by/protocol,
    each with per-checker control counts), every positive-control
    fixture tripped, a justification on every suppression, and a
    non-empty derived thread map. v1 artifacts stay valid against the
    v1 field/checker sets."""

    def _report(self, version: int | None = None) -> dict:
        version = bench.ANALYSIS_SCHEMA_VERSION if version is None else version
        checker = {
            "id": "lock-order",
            "description": "x",
            "raw_findings": 0,
            "kept_findings": 0,
            "suppressed": 0,
        }
        ids = (
            bench.ANALYSIS_CHECKER_IDS if version >= 2
            else bench.ANALYSIS_CHECKER_IDS_V1
        )
        if version >= 2:
            checker.update(controls=1, controls_tripped=1)
        report = {
            "schema_version": version,
            "metric": "unsuppressed_findings",
            "value": 0,
            "package": "radixmesh_tpu",
            "files_indexed": 80,
            "checkers": [dict(checker, id=cid) for cid in ids],
            "findings": [],
            "suppressions": [
                {
                    "file": "workload.py", "line": 19, "scope": "file",
                    "invariants": ["sleep-audit"],
                    "justification": "generators pace by wall clock",
                    "used": True,
                },
            ],
            "positive_controls": [
                {
                    "fixture": "lock_cycle",
                    "invariant": "lock-order-cycle",
                    "file": "engine/engine.py", "line": 19,
                    "tripped": True,
                },
            ],
            "clean": True,
        }
        if version >= 2:
            report["thread_roots"] = {
                "count": 2,
                "roots": [
                    {
                        "name": "mesh-sender",
                        "target": "cache/mesh_cache.py:MeshCache._sender",
                        "file": "cache/mesh_cache.py", "line": 624,
                        "multi": False, "kind": "spawn",
                    },
                    {
                        "name": "wire-receive",
                        "target": "cache/mesh_cache.py:MeshCache.oplog_received",
                        "file": "cache/mesh_cache.py", "line": 905,
                        "multi": True, "kind": "declared",
                    },
                ],
            }
        return report

    def test_valid_report_passes(self):
        assert bench.validate_analysis(self._report()) == []

    def test_v1_report_stays_valid(self):
        """A PR 10 artifact (no thread map, five checkers, no control
        counts) still validates — old rounds stay comparable."""
        assert bench.validate_analysis(self._report(version=1)) == []

    def test_v1_checked_in_artifact_still_validates(self):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(repo, "ANALYSIS_r10.json")
        with open(path) as fh:
            assert bench.validate_analysis(json.load(fh)) == []

    def test_v2_requires_concurrency_checkers(self):
        report = self._report()
        report["checkers"] = [
            c for c in report["checkers"] if c["id"] != "guarded-by"
        ]
        problems = "\n".join(bench.validate_analysis(report))
        assert "guarded-by" in problems and "checked less" in problems

    def test_v2_requires_thread_map(self):
        report = self._report()
        del report["thread_roots"]
        assert any(
            "thread_roots" in p for p in bench.validate_analysis(report)
        )
        report = self._report()
        report["thread_roots"] = {"count": 0, "roots": []}
        problems = "\n".join(bench.validate_analysis(report))
        assert "checked nothing" in problems

    def test_v2_thread_map_entries_are_schema_complete(self):
        report = self._report()
        del report["thread_roots"]["roots"][0]["multi"]
        problems = "\n".join(bench.validate_analysis(report))
        assert "mesh-sender" in problems and ".multi" in problems
        report = self._report()
        report["thread_roots"]["count"] = 5
        problems = "\n".join(bench.validate_analysis(report))
        assert "disagrees" in problems

    def test_v2_requires_control_counts(self):
        report = self._report()
        del report["checkers"][0]["controls_tripped"]
        problems = "\n".join(bench.validate_analysis(report))
        assert "controls_tripped" in problems

    def test_missing_fields_reported(self):
        report = self._report()
        del report["positive_controls"]
        del report["files_indexed"]
        missing = bench.validate_analysis(report)
        assert any("files_indexed" in p for p in missing)
        assert any("positive_controls" in p for p in missing)

    def test_non_dict_rejected(self):
        assert bench.validate_analysis([1]) == ["artifact is not a JSON object"]

    def test_findings_fail_the_gate(self):
        report = self._report()
        report["findings"] = [
            {"file": "cache/mesh_cache.py", "line": 7,
             "invariant": "send-seam", "message": "raw send"},
        ]
        report["clean"] = False
        problems = "\n".join(bench.validate_analysis(report))
        assert "unsuppressed finding" in problems

    def test_clean_flag_must_agree(self):
        report = self._report()
        report["clean"] = False
        problems = "\n".join(bench.validate_analysis(report))
        assert "clean flag disagrees" in problems

    def test_untripped_control_fails(self):
        report = self._report()
        report["positive_controls"][0]["tripped"] = False
        problems = "\n".join(bench.validate_analysis(report))
        assert "NOT tripped" in problems and "went blind" in problems

    def test_empty_controls_fail(self):
        report = self._report()
        report["positive_controls"] = []
        problems = "\n".join(bench.validate_analysis(report))
        assert "proves nothing" in problems

    def test_missing_checker_fails(self):
        report = self._report()
        report["checkers"] = report["checkers"][1:]
        problems = "\n".join(bench.validate_analysis(report))
        assert "lock-order" in problems and "checked less" in problems

    def test_unjustified_suppression_fails(self):
        report = self._report()
        report["suppressions"][0]["justification"] = "  "
        problems = "\n".join(bench.validate_analysis(report))
        assert "silencing" in problems

    def test_checked_in_artifact_validates_and_is_clean(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "ANALYSIS_r*.json")))
        assert paths, "no ANALYSIS artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_analysis(report) == [], paths[-1]
        assert "schema_violation" not in report
        assert report["clean"] is True and report["value"] == 0
        # Every checker of the default plane ran over a real tree.
        assert report["files_indexed"] >= 70
        # All controls tripped, and they cover every checker family.
        fixtures = {c["fixture"] for c in report["positive_controls"]}
        assert {
            "lock_cycle", "single_writer_alias", "hotpath_sleep",
            "wire_unregistered", "metrics_vocab", "send_seam",
            "suppression_grammar",
            # v2 (PR 11): the concurrency plane's controls.
            "guarded_race", "thread_escape", "protocol_drift",
        } <= fixtures
        # v2: the thread map shipped with the verdict it parameterized.
        assert report["thread_roots"]["count"] >= 15
        root_names = {r["name"] for r in report["thread_roots"]["roots"]}
        assert {"mesh-sender", "wire-receive", "kv-transfer"} <= root_names


class TestDoctorArtifactSchema:
    """DOCTOR v1 (PR 12, the diagnosis plane): zero findings on the
    healthy phase with EVERY rule running, each seeded pathology named
    with evidence matching the seeded ground truth, the phase
    decomposition summing to e2e within epsilon on every audited
    request, and the benchdiff sentinel's three-way self-check."""

    def _pathology(self, rule: str, evidence: dict) -> dict:
        return {
            "performed": True,
            "rule": rule,
            "detected": True,
            "evidence_correct": True,
            "score": 0.9,
            "summary": f"{rule} fired",
            "evidence": evidence,
            "expected": dict(evidence),
        }

    def _report(self) -> dict:
        from radixmesh_tpu.obs.doctor import RULES

        return {
            "schema_version": bench.DOCTOR_SCHEMA_VERSION,
            "metric": "doctor_pathologies_named",
            "value": 3,
            "unit": "of 3 seeded pathologies named with correct evidence",
            "workload": "healthy + heat storm + convoy + throttled restore",
            "nodes": 7,
            "topology": "4 prefill + 2 decode + 1 router (inproc) + engine",
            "replication_factor": 3,
            "healthy": {
                "performed": True,
                "findings": [],
                "rules_checked": list(RULES),
                "inputs": {"mesh": True, "engine": True, "slo": True,
                           "attribution": True},
                "audited_requests": 6,
            },
            "pathologies": {
                "hot_shard": self._pathology("hot_shard", {
                    "skew_score": 19.5, "shard": 7,
                    "owners": [0, 1, 2, 4, 5], "reporters": 6,
                }),
                "prefill_convoy": self._pathology("prefill_convoy", {
                    "shape": "p2048", "prefill_share": 0.95,
                    "mean_e2e_s": 0.2, "fleet_mean_e2e_s": 0.04,
                    "requests": 3,
                }),
                "restore_park_stall": self._pathology("restore_park_stall", {
                    "lane": "restore", "parked": 3, "restores_queued": 4,
                    "park_p99_s": 0.0001, "park_share": 0.0,
                }),
            },
            "attribution": {
                "audited": 18, "refused": 0, "max_sum_error_s": 0.0,
                "epsilon_s": bench.DOCTOR_SUM_EPSILON_S, "sums_ok": True,
                "phases": {},
            },
            "benchdiff": {
                "identical_clean": True, "regression_flagged": True,
                "mismatch_detected": True,
            },
            "wall_s": 12.0,
        }

    def test_complete_report_validates(self):
        assert bench.validate_doctor(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["pathologies"]["hot_shard"]
        del report["attribution"]["sums_ok"]
        del report["healthy"]["audited_requests"]
        problems = bench.validate_doctor(report)
        assert any("pathologies.hot_shard" in p for p in problems)
        assert any("attribution.sums_ok" in p for p in problems)
        assert any("healthy.audited_requests" in p for p in problems)

    def test_healthy_findings_fail_the_gate(self):
        report = self._report()
        report["healthy"]["findings"] = [{"rule": "hot_shard"}]
        problems = "\n".join(bench.validate_doctor(report))
        assert "cries wolf" in problems

    def test_all_rules_must_have_run_on_healthy(self):
        report = self._report()
        report["healthy"]["rules_checked"] = ["hot_shard"]
        problems = "\n".join(bench.validate_doctor(report))
        assert "never ran" in problems

    def test_undetected_pathology_fails(self):
        report = self._report()
        report["pathologies"]["prefill_convoy"]["detected"] = False
        problems = "\n".join(bench.validate_doctor(report))
        assert "NOT detected" in problems

    def test_wrong_evidence_fails(self):
        report = self._report()
        report["pathologies"]["hot_shard"]["evidence_correct"] = False
        problems = "\n".join(bench.validate_doctor(report))
        assert "ground truth" in problems

    def test_evidence_must_carry_pinned_fields(self):
        report = self._report()
        del report["pathologies"]["hot_shard"]["evidence"]["owners"]
        problems = "\n".join(bench.validate_doctor(report))
        assert "pinned" in problems and "owners" in problems

    def test_sum_epsilon_gate_enforced(self):
        report = self._report()
        report["attribution"]["max_sum_error_s"] = 0.01
        report["attribution"]["sums_ok"] = False
        problems = "\n".join(bench.validate_doctor(report))
        assert "sum to e2e" in problems

    def test_refusals_fail_the_acceptance_run(self):
        report = self._report()
        report["attribution"]["refused"] = 2
        problems = "\n".join(bench.validate_doctor(report))
        assert "refusal" in problems

    def test_benchdiff_sentinel_gates(self):
        for key in ("identical_clean", "regression_flagged",
                    "mismatch_detected"):
            report = self._report()
            report["benchdiff"][key] = False
            assert bench.validate_doctor(report), key

    def test_skipped_sections_are_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["healthy"] = {"performed": False}
        report["pathologies"]["hot_shard"] = {"performed": False}
        assert bench.validate_doctor(report) == []

    def test_build_report_matches_schema(self):
        core = {k: v for k, v in self._report().items()
                if k not in ("schema_version", "metric", "value", "unit",
                             "workload")}
        report = bench.build_doctor_report(core)
        assert bench.validate_doctor(report) == []
        assert report["value"] == 3

    def test_checked_in_artifact_validates_and_gates_green(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "DOCTOR_r*.json")))
        assert paths, "no DOCTOR artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_doctor(report) == [], paths[-1]
        assert "schema_violation" not in report
        assert report["value"] == len(bench.DOCTOR_PATHOLOGIES)
        assert report["healthy"]["findings"] == []
        # The hot shard's owner set was named AND matched ground truth.
        hot = report["pathologies"]["hot_shard"]
        assert hot["evidence"]["owners"] == hot["expected"]["owners"]
        assert report["attribution"]["max_sum_error_s"] <= (
            report["attribution"]["epsilon_s"]
        )


class TestCompareRounds:
    """The regression sentinel (bench.compare_rounds): per-kind pinned
    metric directions, additive-version tolerance, and the pinned
    status vocabulary the CLI's exit codes map onto."""

    def _chaos(self, **over) -> dict:
        base = {
            "metric": "chaos_heal_converge_s",
            "schema_version": bench.CHAOS_SCHEMA_VERSION,
            "value": 0.4,
            "crash": {"resurrection_hit_ratio": 0.95},
            "repair": {"converge_s": 0.4},
        }
        base.update(over)
        return base

    def test_identical_pair_is_clean(self):
        r = bench.compare_rounds(self._chaos(), self._chaos(), kind="CHAOS")
        assert r["status"] == "clean"
        assert r["regressions"] == []

    def test_adverse_move_past_threshold_flags(self):
        worse = self._chaos(value=1.8, repair={"converge_s": 1.8})
        r = bench.compare_rounds(self._chaos(), worse, kind="CHAOS")
        assert r["status"] == "regression"
        assert "repair.converge_s" in r["regressions"]

    def test_adverse_move_inside_threshold_is_noise(self):
        slightly = self._chaos(value=0.45, repair={"converge_s": 0.45})
        r = bench.compare_rounds(self._chaos(), slightly, kind="CHAOS")
        assert r["status"] == "clean"

    def test_improvement_direction_respected(self):
        better = self._chaos(
            value=0.1, repair={"converge_s": 0.1},
            crash={"resurrection_hit_ratio": 0.99},
        )
        r = bench.compare_rounds(self._chaos(), better, kind="CHAOS")
        assert r["status"] == "clean"
        assert "repair.converge_s" in r["improvements"]

    def test_higher_better_metric_drop_flags(self):
        worse = self._chaos(crash={"resurrection_hit_ratio": 0.5})
        r = bench.compare_rounds(self._chaos(), worse, kind="CHAOS")
        assert "crash.resurrection_hit_ratio" in r["regressions"]

    def test_kind_mismatch_refuses(self):
        obs = {"metric": "obs_stitched_node_tracks", "schema_version": 1,
               "value": 6}
        r = bench.compare_rounds(self._chaos(), obs)
        assert r["status"] == "schema_mismatch"

    def test_unrecognized_kind_refuses(self):
        r = bench.compare_rounds({"metric": "nope"}, {"metric": "nope"})
        assert r["status"] == "schema_mismatch"

    def test_kind_detected_from_filename(self):
        assert bench.artifact_kind({}, "CHAOS_r08.json") == "CHAOS"
        assert bench.artifact_kind({}, "/a/b/BENCH_FULL_r05.json") == (
            "BENCH_FULL"
        )
        assert bench.artifact_kind({}, "notes.json") is None

    def test_version_bump_skips_one_sided_fields(self):
        old = self._chaos(schema_version=2)
        del old["crash"]  # the section arrived with v3
        r = bench.compare_rounds(old, self._chaos(), kind="CHAOS")
        assert r["status"] == "clean"
        assert "crash.resurrection_hit_ratio" in r["skipped"]
        assert r["version_change"] == {
            "old": 2, "new": bench.CHAOS_SCHEMA_VERSION,
        }

    def test_same_version_one_sided_field_refuses(self):
        old = self._chaos()
        del old["crash"]
        r = bench.compare_rounds(old, self._chaos(), kind="CHAOS")
        assert r["status"] == "schema_mismatch"

    def test_threshold_scale_zero_flags_any_adverse_move(self):
        slightly = self._chaos(value=0.41, repair={"converge_s": 0.41})
        r = bench.compare_rounds(
            self._chaos(), slightly, kind="CHAOS", threshold_scale=0.0
        )
        assert r["status"] == "regression"

    def test_unguarded_numeric_moves_are_informational(self):
        moved = self._chaos()
        moved["wall_s"] = 99.0
        old = self._chaos()
        old["wall_s"] = 10.0
        r = bench.compare_rounds(old, moved, kind="CHAOS")
        assert r["status"] == "clean"
        assert any(c["path"] == "wall_s" for c in r["info_changes"])

    def test_every_rule_path_resolves_in_checked_in_artifacts(self):
        """Rot guard: each kind's pinned paths must exist in the LATEST
        checked-in artifact of that kind (else the sentinel silently
        guards nothing)."""
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for kind, rules in bench.COMPARE_RULES.items():
            paths = sorted(glob.glob(os.path.join(repo, f"{kind}_r*.json")))
            if not paths or not rules:
                continue
            with open(paths[-1]) as fh:
                artifact = json.load(fh)
            for path, _, _ in rules:
                v = bench._dotted_get(artifact, path)
                assert isinstance(v, (int, float)), (
                    f"{kind}: pinned path {path!r} does not resolve to a "
                    f"number in {os.path.basename(paths[-1])} (got {v!r})"
                )

    def test_selfcheck_is_green(self):
        check = bench.benchdiff_selfcheck()
        assert check["identical_clean"] is True
        assert check["regression_flagged"] is True
        assert check["mismatch_detected"] is True


class TestBenchdiffCLI:
    """scripts/benchdiff.py pinned exit codes: 0 clean / 1 regression /
    2 schema mismatch — the contract CI gates on."""

    def _run(self, tmp_path, old, new, *flags, old_name="CHAOS_r01.json",
             new_name="CHAOS_r02.json"):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        a, b = tmp_path / old_name, tmp_path / new_name
        a.write_text(json.dumps(old))
        b.write_text(json.dumps(new))
        return subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "benchdiff.py"),
             str(a), str(b), *flags],
            capture_output=True, text=True, timeout=60,
        )

    def _chaos(self, **over) -> dict:
        base = {
            "metric": "chaos_heal_converge_s",
            "schema_version": bench.CHAOS_SCHEMA_VERSION,
            "value": 0.4,
            "crash": {"resurrection_hit_ratio": 0.95},
            "repair": {"converge_s": 0.4},
        }
        base.update(over)
        return base

    def test_identical_pair_exits_0(self, tmp_path):
        p = self._run(tmp_path, self._chaos(), self._chaos())
        assert p.returncode == bench.BENCHDIFF_EXIT_CLEAN, p.stdout + p.stderr
        assert "CLEAN" in p.stdout

    def test_regression_exits_1_and_names_the_metric(self, tmp_path):
        worse = self._chaos(value=2.0, repair={"converge_s": 2.0})
        p = self._run(tmp_path, self._chaos(), worse)
        assert p.returncode == bench.BENCHDIFF_EXIT_REGRESSION
        assert "repair.converge_s" in p.stdout

    def test_cross_kind_exits_2(self, tmp_path):
        obs = {"metric": "obs_stitched_node_tracks", "schema_version": 1,
               "value": 6}
        p = self._run(tmp_path, self._chaos(), obs,
                      new_name="OBS_r02.json")
        assert p.returncode == bench.BENCHDIFF_EXIT_MISMATCH

    def test_unreadable_input_exits_2(self, tmp_path):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        a = tmp_path / "CHAOS_r01.json"
        a.write_text("{not json")
        p = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "benchdiff.py"),
             str(a), str(a)],
            capture_output=True, text=True, timeout=60,
        )
        assert p.returncode == bench.BENCHDIFF_EXIT_MISMATCH

    def test_strict_flag_zeroes_thresholds(self, tmp_path):
        slightly = self._chaos(value=0.41, repair={"converge_s": 0.41})
        p0 = self._run(tmp_path, self._chaos(), slightly)
        assert p0.returncode == bench.BENCHDIFF_EXIT_CLEAN
        p1 = self._run(tmp_path, self._chaos(), slightly, "--strict")
        assert p1.returncode == bench.BENCHDIFF_EXIT_REGRESSION

    def test_json_output_carries_the_full_diff(self, tmp_path):
        worse = self._chaos(value=2.0, repair={"converge_s": 2.0})
        p = self._run(tmp_path, self._chaos(), worse, "--json")
        out = json.loads(p.stdout)
        assert out["status"] == "regression"
        assert any(r["verdict"] == "regression" for r in out["rows"])


class TestBlackboxArtifactSchema:
    """BLACKBOX v1 (PR 13, the flight-recorder plane): zero live
    findings on the healthy phase with every rule running, the
    post-mortem naming the hot shard + a crash window containing the
    kill from the observer dump and the unclean-death truncation from
    the victim's segment-only dump, and the sampler's self-accounted
    overhead under budget."""

    def _report(self) -> dict:
        from radixmesh_tpu.obs.doctor import RULES

        return {
            "schema_version": bench.BLACKBOX_SCHEMA_VERSION,
            "metric": "blackbox_postmortem_named",
            "value": bench.BLACKBOX_NAMED_TOTAL,
            "unit": "of 3 post-mortem verdicts named from dumps alone",
            "workload": "healthy + zipf storm + hot-owner hard kill",
            "nodes": 7,
            "topology": "4 prefill + 2 decode + 1 router + engine",
            "replication_factor": 3,
            "healthy": {
                "performed": True,
                "findings": [],
                "rules_checked": list(RULES),
                "inputs": {"mesh": True, "engine": True, "slo": True,
                           "attribution": True, "history": True},
                "history_samples": 12,
            },
            "storm": {"performed": True, "expected_hot_shard": 7},
            "crash": {
                "performed": True,
                "victim_rank": 2,
                "victim_is_hot_owner": True,
                "t_kill": 1000.0,
                "observer_detected_live": True,
            },
            "postmortem": {
                "observer": {
                    "hot_shard_named": True,
                    "hot_shard_evidence": {"shard": 7, "skew_peak": 18.0},
                    "crash_window_named": True,
                    "crash_evidence": {"window": [999.4, 1000.6]},
                },
                "victim": {
                    "truncation_named": True,
                    "unclean": True,
                    "segments": 2,
                },
                "expected": {"hot_shard": 7, "t_kill": 1000.0},
            },
            "history": {
                "interval_s": 0.25,
                "capacity": 900,
                "points": 4000,
                "self_overhead": {
                    "sample_seconds_total": 0.02,
                    "wall_s": 10.0,
                    "fraction": 0.002,
                    "budget_fraction": 0.01,
                    "under_budget": True,
                },
            },
            "blackbox": {"schema_version": 1},
            "wall_s": 10.0,
        }

    def test_complete_report_validates(self):
        assert bench.validate_blackbox(self._report()) == []

    def test_missing_top_fields_named(self):
        report = self._report()
        del report["postmortem"]
        del report["history"]
        problems = bench.validate_blackbox(report)
        assert "postmortem" in problems
        assert "history" in problems

    def test_healthy_findings_fail_the_gate(self):
        report = self._report()
        report["healthy"]["findings"] = [{"rule": "hot_shard"}]
        assert any(
            "healthy" in p for p in bench.validate_blackbox(report)
        )

    def test_all_rules_must_have_run_on_healthy(self):
        report = self._report()
        report["healthy"]["rules_checked"] = ["hot_shard"]
        problems = "\n".join(bench.validate_blackbox(report))
        assert "never ran" in problems

    def test_postmortem_misses_fail(self):
        for path, key in (
            (("postmortem", "observer"), "hot_shard_named"),
            (("postmortem", "observer"), "crash_window_named"),
            (("postmortem", "victim"), "truncation_named"),
            (("postmortem", "victim"), "unclean"),
        ):
            report = self._report()
            sec = report
            for p in path:
                sec = sec[p]
            sec[key] = False
            assert bench.validate_blackbox(report), (path, key)

    def test_kill_must_land_on_a_hot_owner(self):
        report = self._report()
        report["crash"]["victim_is_hot_owner"] = False
        problems = "\n".join(bench.validate_blackbox(report))
        assert "hot" in problems

    def test_overhead_budget_gate(self):
        report = self._report()
        report["history"]["self_overhead"]["fraction"] = 0.05
        report["history"]["self_overhead"]["under_budget"] = False
        problems = "\n".join(bench.validate_blackbox(report))
        assert "overhead" in problems

    def test_value_must_count_every_verdict(self):
        report = self._report()
        report["value"] = 2
        problems = "\n".join(bench.validate_blackbox(report))
        assert "verdicts" in problems

    def test_skipped_sections_are_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["healthy"] = {"performed": False}
        report["crash"] = {"performed": False}
        assert bench.validate_blackbox(report) == []

    def test_build_report_matches_schema(self):
        core = {k: v for k, v in self._report().items()
                if k not in ("schema_version", "metric", "value", "unit",
                             "workload")}
        core["named"] = 3
        report = bench.build_blackbox_report(core)
        assert bench.validate_blackbox(report) == []
        assert report["value"] == 3
        assert report["metric"] == "blackbox_postmortem_named"

    def test_checked_in_artifact_validates_and_gates_green(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "BLACKBOX_r*.json")))
        assert paths, "no BLACKBOX artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_blackbox(report) == [], paths[-1]
        assert "schema_violation" not in report
        assert report["value"] == bench.BLACKBOX_NAMED_TOTAL
        assert report["healthy"]["findings"] == []
        pm = report["postmortem"]
        # The post-mortem named the SEEDED shard, and the crash window
        # brackets the recorded kill instant.
        assert (
            pm["observer"]["hot_shard_evidence"]["shard"]
            == pm["expected"]["hot_shard"]
        )
        lo, hi = pm["observer"]["crash_evidence"]["window"]
        assert lo - 0.05 <= pm["expected"]["t_kill"] <= hi
        assert pm["victim"]["unclean"] is True
        assert report["history"]["self_overhead"]["fraction"] < 0.01

    def test_blackbox_kind_registered_in_sentinel(self):
        # COMPARE_RULES + metric-kind detection (the satellite-5 wiring).
        assert "BLACKBOX" in bench.COMPARE_RULES
        report = self._report()
        assert bench.artifact_kind(report) == "BLACKBOX"
        assert bench.artifact_kind({}, "BLACKBOX_r13.json") == "BLACKBOX"

    def test_compare_rounds_flags_lost_verdict(self):
        old = self._report()
        new = self._report()
        new["value"] = 2
        res = bench.compare_rounds(old, new, kind="BLACKBOX")
        assert res["status"] == "regression"
        assert "value" in res["regressions"]

    def test_compare_rounds_tolerates_overhead_jitter(self):
        old = self._report()
        new = self._report()
        new["history"]["self_overhead"]["fraction"] = 0.004  # 2x, in budget
        res = bench.compare_rounds(old, new, kind="BLACKBOX")
        assert res["status"] == "clean"

    def test_selfcheck_covers_the_blackbox_schema(self):
        res = bench.benchdiff_selfcheck()
        assert res["identical_clean"] is True
        assert res["regression_flagged"] is True
        assert res["mismatch_detected"] is True
        assert "BLACKBOX" in res["kinds_covered"]


class TestRebalanceArtifactSchema:
    """The REBALANCE artifact (PR 14, the closed robustness loop):
    zipf-storm skew strictly drops under rebalancing with zero failed
    requests mid-move, a mid-traffic router kill at N >= 2 routers
    loses nothing, and meshcheck reports the new plane clean."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.REBALANCE_SCHEMA_VERSION,
            "metric": "rebalance_skew_drop_ratio",
            "value": 1.39,
            "unit": "zipf-storm skew before / after heat-driven rebalancing",
            "workload": "zipf storm + router kill (run_chaos_workload)",
            "nodes": 8,
            "topology": "4 prefill + 2 decode + 2 routers (inproc)",
            "replication_factor": 2,
            "rebalance": {
                "performed": True, "skew_before": 20.3, "skew_after": 14.6,
                "skew_dropped": True, "moves": 4,
                "max_moves_per_round": 4, "moves_bounded": True,
                "boosted_shards": [19, 42], "hot_shard": 19,
                "attempted_mid_move": 175, "ok_mid_move": 175,
                "failed_mid_move": 0, "overrides_version": 1,
                "overrides_converged": True, "handoff_entries": 8,
                "requests_wave1": 155, "rebalance_s": 6.0,
            },
            "router_kill": {
                "performed": True, "routers": 2, "killed": "cr0",
                "survivor": "cr1", "streams": 10, "inflight_at_kill": 10,
                "completed": 10, "failed": 0, "failovers": 1, "hedges": 1,
                "survivor_served": True, "router_kill_s": 0.4,
            },
            "meshcheck": {
                "files": ["cache/rebalance.py", "router/front_door.py"],
                "findings": 0, "clean": True, "detail": [],
            },
            "wall_s": 11.6,
        }

    def test_complete_report_validates(self):
        assert bench.validate_rebalance(self._report()) == []
        assert bench.validate_rebalance(7) == ["artifact is not a JSON object"]

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["replication_factor"]
        del report["rebalance"]["overrides_converged"]
        del report["router_kill"]["failovers"]
        del report["meshcheck"]["clean"]
        missing = bench.validate_rebalance(report)
        assert "replication_factor" in missing
        assert "rebalance.overrides_converged" in missing
        assert "router_kill.failovers" in missing
        assert "meshcheck.clean" in missing

    def test_gates_enforced(self):
        report = self._report()
        report["rebalance"]["skew_after"] = 25.0
        report["router_kill"]["failed"] = 2
        report["meshcheck"]["clean"] = False
        report["meshcheck"]["findings"] = 3
        problems = "\n".join(bench.validate_rebalance(report))
        assert "did not strictly drop" in problems
        assert "LOST to the router kill" in problems
        assert "statically clean" in problems

    def test_value_gate(self):
        report = self._report()
        report["value"] = 0.9
        problems = "\n".join(bench.validate_rebalance(report))
        assert "not > 1" in problems

    def test_skipped_sections_gate_exempt(self):
        report = self._report()
        report["rebalance"] = {"performed": False}
        report["router_kill"] = {"performed": False}
        report["value"] = 0.0
        assert bench.validate_rebalance(report) == []

    def test_non_dict_sections_are_violations(self):
        """A present-but-garbage section must not silently skip every
        gate and validate clean."""
        report = self._report()
        report["rebalance"] = True
        report["router_kill"] = "done"
        report["meshcheck"] = None
        problems = "\n".join(bench.validate_rebalance(report))
        assert "rebalance section is not an object" in problems
        assert "router_kill section is not an object" in problems
        assert "meshcheck section is not an object" in problems

    def test_build_report_matches_schema(self):
        res = {
            "nodes": 8,
            "topology": "4 prefill + 2 decode + 2 routers (inproc)",
            "replication_factor": 2,
            "rebalance": self._report()["rebalance"],
            "router_kill": self._report()["router_kill"],
            "wall_s": 11.6,
        }
        report = bench.build_rebalance_report(
            res, meshcheck=self._report()["meshcheck"]
        )
        assert bench.validate_rebalance(report) == []
        assert report["value"] == round(20.3 / 14.6, 4)

    def test_build_report_without_meshcheck_fails_the_gate(self):
        # A missing verdict must read as NOT clean, never as vacuously
        # green.
        res = {
            "nodes": 8, "topology": "t", "replication_factor": 2,
            "rebalance": self._report()["rebalance"],
            "router_kill": self._report()["router_kill"], "wall_s": 1.0,
        }
        report = bench.build_rebalance_report(res)
        problems = "\n".join(bench.validate_rebalance(report))
        assert "statically clean" in problems

    def test_rebalance_kind_registered_in_sentinel(self):
        assert "REBALANCE" in bench.COMPARE_RULES
        assert bench.artifact_kind(self._report()) == "REBALANCE"
        assert (
            bench.artifact_kind({}, "REBALANCE_r14.json") == "REBALANCE"
        )

    def test_compare_rounds_flags_regressions(self):
        old = self._report()
        new = self._report()
        new["rebalance"]["failed_mid_move"] = 2
        res = bench.compare_rounds(old, new, kind="REBALANCE")
        assert res["status"] == "regression"
        assert "rebalance.failed_mid_move" in res["regressions"]

    def test_checked_in_artifact_validates(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "REBALANCE_r*.json")))
        assert paths, "no REBALANCE artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_rebalance(report) == []
        assert report["rebalance"]["performed"] is True
        assert report["router_kill"]["performed"] is True
        assert report["meshcheck"]["findings"] == 0


class TestTierArtifactSchema:
    """The TIER artifact (PR 15, the durable KV spill tier): hit-rate
    at a working set >= 10x host capacity beats the no-tier baseline,
    decode never blocks on disk restores, the whole-cell kill-and-
    restart drill resumes every stream byte-identical from disk alone
    with seeded corrupt/torn extents detected and never served, and
    meshcheck reports the tier plane clean."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.TIER_SCHEMA_VERSION,
            "metric": "tier_hit_rate_gain",
            "value": 16.0,
            "unit": "tier hit-rate / no-tier baseline at 12x host capacity",
            "workload": "zipf re-visit + overlap + cold-cell drill",
            "capacity": {
                "working_set_tokens": 6144, "host_slots": 512,
                "working_set_ratio": 12.0, "tier_hit_rate": 0.99,
                "baseline_hit_rate": 0.06, "hit_rate_gain": 16.0,
                "requests": 32, "distinct_prefixes": 16,
            },
            "spill": {
                "spilled_tokens": 6144, "extents": 16, "demotes": 16,
                "promotes": 15, "drops": 0, "resident_bytes": 3_000_000,
            },
            "restore_overlap": {
                "parked_requests": 3, "disk_restored_tokens": 6912,
                "decode_steps_during_restore": 2,
                "max_decode_gap_s": 1.5, "overlap_ok": True,
            },
            "cold_start": {
                "performed": True, "interrupted": 5, "resumed": 5,
                "byte_identical": True, "failed": 0,
                "disk_hit_tokens": 2064, "grafted_nodes": 4,
                "orphaned": 0, "corrupt_detected": 2,
                "corrupt_served": 0, "restart_s": 0.005,
            },
            "corruption": {
                "extents_attacked": 2, "truncated": 1, "bitflipped": 1,
                "detected": 2, "served_corrupt": 0,
            },
            "meshcheck": {
                "files": ["cache/kv_tier.py"], "findings": 0,
                "clean": True,
            },
            "page_size": 4,
            "wall_s": 9.3,
        }

    def test_complete_report_validates(self):
        assert bench.validate_tier(self._report()) == []
        assert bench.validate_tier(7) == ["artifact is not a JSON object"]

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["capacity"]["working_set_ratio"]
        del report["cold_start"]["byte_identical"]
        del report["corruption"]["served_corrupt"]
        missing = bench.validate_tier(report)
        assert "capacity.working_set_ratio" in missing
        assert "cold_start.byte_identical" in missing
        assert "corruption.served_corrupt" in missing

    def test_capacity_gates(self):
        report = self._report()
        report["capacity"]["working_set_ratio"] = 4.0
        report["capacity"]["tier_hit_rate"] = 0.05
        problems = "\n".join(bench.validate_tier(report))
        assert "10.0x" in problems
        assert "does not beat" in problems

    def test_cold_start_gates(self):
        report = self._report()
        report["cold_start"]["failed"] = 1
        report["cold_start"]["resumed"] = 4
        report["cold_start"]["byte_identical"] = False
        report["cold_start"]["corrupt_served"] = 1
        report["cold_start"]["corrupt_detected"] = 0
        report["cold_start"]["disk_hit_tokens"] = 0
        problems = "\n".join(bench.validate_tier(report))
        assert "must lose nothing" in problems
        assert "resumed 4 != interrupted 5" in problems
        assert "byte-identical" in problems
        assert "SERVED" in problems
        assert "was not detected" in problems
        assert "never actually read the durable tier" in problems

    def test_overlap_gates(self):
        report = self._report()
        report["restore_overlap"]["parked_requests"] = 0
        report["restore_overlap"]["decode_steps_during_restore"] = 0
        report["restore_overlap"]["overlap_ok"] = False
        problems = "\n".join(bench.validate_tier(report))
        assert "zero parked disk restores" in problems
        assert "decode made zero progress" in problems

    def test_corruption_gates(self):
        report = self._report()
        report["corruption"]["detected"] = 1
        problems = "\n".join(bench.validate_tier(report))
        assert "1 of 2 attacked" in problems

    def test_meshcheck_and_value_gates(self):
        report = self._report()
        report["meshcheck"]["clean"] = False
        report["meshcheck"]["findings"] = 2
        report["value"] = 0.8
        problems = "\n".join(bench.validate_tier(report))
        assert "statically clean" in problems
        assert "not > 1" in problems

    def test_skipped_cold_start_gate_exempt(self):
        report = self._report()
        report["cold_start"] = {"performed": False}
        report["corruption"]["extents_attacked"] = 0
        assert bench.validate_tier(report) == []

    def test_non_dict_sections_are_violations(self):
        report = self._report()
        report["cold_start"] = "done"
        problems = "\n".join(bench.validate_tier(report))
        assert "cold_start section is not an object" in problems

    def test_build_report_matches_schema(self):
        base = self._report()
        res = {
            k: base[k]
            for k in (
                "capacity", "spill", "restore_overlap", "cold_start",
                "corruption", "page_size", "wall_s",
            )
        }
        report = bench.build_tier_report(res, meshcheck=base["meshcheck"])
        assert bench.validate_tier(report) == []
        assert report["value"] == base["capacity"]["hit_rate_gain"]

    def test_build_report_without_meshcheck_fails_the_gate(self):
        base = self._report()
        res = {
            k: base[k]
            for k in (
                "capacity", "spill", "restore_overlap", "cold_start",
                "corruption", "page_size", "wall_s",
            )
        }
        problems = "\n".join(bench.validate_tier(bench.build_tier_report(res)))
        assert "statically clean" in problems

    def test_tier_kind_registered_in_sentinel(self):
        assert "TIER" in bench.COMPARE_RULES
        assert bench.artifact_kind(self._report()) == "TIER"
        assert bench.artifact_kind({}, "TIER_r15.json") == "TIER"
        res = bench.benchdiff_selfcheck()
        assert "TIER" in res["kinds_covered"]

    def test_compare_rounds_flags_corrupt_served(self):
        old = self._report()
        new = self._report()
        new["cold_start"]["corrupt_served"] = 1
        res = bench.compare_rounds(old, new, kind="TIER")
        assert res["status"] == "regression"
        assert "cold_start.corrupt_served" in res["regressions"]

    def test_checked_in_artifact_validates(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "TIER_r*.json")))
        assert paths, "no TIER artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_tier(report) == []
        assert report["cold_start"]["performed"] is True
        assert report["cold_start"]["byte_identical"] is True
        assert report["corruption"]["served_corrupt"] == 0
        assert report["meshcheck"]["findings"] == 0
        # The new lint invariant's positive control demonstrably trips
        # in the artifact's meshcheck verdict.
        assert report["meshcheck"]["file_io_controls_tripped"] >= 1


class TestDoctorRuleVersionGating:
    """DOCTOR/BLACKBOX v3 (PR 15): artifacts validate against the rule
    set pinned for THEIR schema version — a checked-in v1/v2 artifact
    can never retroactively have run tier_thrash."""

    def test_v1_requires_the_pinned_six(self):
        from radixmesh_tpu.obs.doctor import RULES

        req = bench._required_doctor_rules({"schema_version": 1}, RULES)
        assert tuple(req) == bench.DOCTOR_RULES_V1

    def test_v2_requires_the_pinned_seven(self):
        from radixmesh_tpu.obs.doctor import RULES

        req = bench._required_doctor_rules({"schema_version": 2}, RULES)
        assert tuple(req) == bench.DOCTOR_RULES_V2
        assert "tier_thrash" not in req

    def test_v3_requires_every_live_rule(self):
        from radixmesh_tpu.obs.doctor import RULES

        req = bench._required_doctor_rules(
            {"schema_version": bench.DOCTOR_SCHEMA_VERSION}, RULES
        )
        assert "tier_thrash" in req
        assert tuple(req) == RULES

    def test_v4_pins_pr17_rules_without_token_plane(self):
        from radixmesh_tpu.obs.doctor import RULES

        req = bench._required_doctor_rules({"schema_version": 4}, RULES)
        assert tuple(req) == bench.DOCTOR_RULES_V4
        assert "straggler_node" in req
        assert "decode_stall" not in req


class TestSpecArtifactSchema:
    """The SPEC artifact (PR 18, the speedometer): draft-token
    conservation on every verify path with per-shape and per-draft-
    source breakdowns, seeded-stall ITL attribution, the adaptive-γ
    goodput A-B, and the token-timeline overhead bound — the artifact
    ROADMAP item 1's gate names."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.SPEC_SCHEMA_VERSION,
            "metric": "spec_accepted_tokens_per_step",
            "value": 1.6,
            "unit": "draft tokens accepted per verify wave",
            "workload": "repetitive + replayed prompts, tiny CPU model",
            "acceptance": {
                "performed": True, "proposed": 120, "accepted": 72,
                "rejected": 48, "conserved": True,
                "accepted_per_step": 1.6, "waves": 45,
                "by_shape": {
                    "p32": {"proposed": 60, "accepted": 30, "rejected": 30,
                            "acceptance": 0.5},
                    "p64": {"proposed": 60, "accepted": 42, "rejected": 18,
                            "acceptance": 0.7},
                },
                "by_source": {
                    "tree": {"proposed": 54, "accepted": 54, "rejected": 0,
                             "acceptance": 1.0},
                    "ngram": {"proposed": 66, "accepted": 18, "rejected": 48,
                              "acceptance": 0.2727},
                },
            },
            "itl": {
                "performed": True, "count": 196, "p50_s": 0.004,
                "p99_s": 1.9, "stalls": {"scheduler_wait": 9},
                "stall_seconds": {"scheduler_wait": 11.2},
                "seeded_cause": "scheduler_wait", "seeded_detected": True,
            },
            "adaptive": {
                "performed": True, "gamma_base": 4,
                "fixed_goodput_tps": 1900.0,
                "adaptive_goodput_tps": 2050.0, "goodput_ratio": 1.0789,
                "no_worse": True, "fixed_acceptance": 0.87,
                "adaptive_acceptance": 0.94,
            },
            "overhead": {
                "tokens": 1000, "timeline_on_s": 0.0019,
                "timeline_off_s": 0.0001, "fraction": 0.0018,
                "budget_fraction": 0.01, "under_budget": True,
            },
            "wall_s": 12.8,
        }

    def test_complete_report_validates(self):
        assert bench.validate_spec(self._report()) == []
        assert bench.validate_spec(7) == ["artifact is not a JSON object"]

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["wall_s"]
        del report["acceptance"]["conserved"]
        del report["itl"]["seeded_detected"]
        del report["adaptive"]["goodput_ratio"]
        del report["overhead"]["fraction"]
        missing = bench.validate_spec(report)
        assert "wall_s" in missing
        assert "acceptance.conserved" in missing
        assert "itl.seeded_detected" in missing
        assert "adaptive.goodput_ratio" in missing
        assert "overhead.fraction" in missing

    def test_conservation_gates(self):
        report = self._report()
        report["acceptance"]["conserved"] = False
        report["acceptance"]["accepted"] = 70
        problems = "\n".join(bench.validate_spec(report))
        assert "conservation broke" in problems
        report = self._report()
        report["acceptance"]["proposed"] = 0
        problems = "\n".join(bench.validate_spec(report))
        assert "zero proposed draft tokens" in problems
        report = self._report()
        report["acceptance"]["accepted_per_step"] = 0.0
        report["value"] = 0.0
        problems = "\n".join(bench.validate_spec(report))
        assert "every draft missed" in problems
        assert "not > 0" in problems

    def test_empty_breakdowns_are_violations(self):
        report = self._report()
        report["acceptance"]["by_shape"] = {}
        report["acceptance"]["by_source"] = {}
        problems = "\n".join(bench.validate_spec(report))
        assert "by_shape is empty" in problems
        assert "by_source is empty" in problems

    def test_itl_gates(self):
        report = self._report()
        report["itl"]["count"] = 0
        report["itl"]["seeded_detected"] = False
        report["itl"]["p99_s"] = 0.001
        problems = "\n".join(bench.validate_spec(report))
        assert "zero timed inter-token gaps" in problems
        assert "'scheduler_wait' stall was not attributed" in problems
        assert "p99 0.001 < p50 0.004" in problems

    def test_adaptive_and_overhead_gates(self):
        report = self._report()
        report["adaptive"]["no_worse"] = False
        report["adaptive"]["goodput_ratio"] = 0.7
        report["overhead"]["under_budget"] = False
        report["overhead"]["fraction"] = 0.04
        problems = "\n".join(bench.validate_spec(report))
        assert "the controller costs more than it saves" in problems
        assert "may not slow the car" in problems

    def test_skipped_sections_gate_exempt(self):
        # performed=False sections are schema-valid but gate-exempt
        # (the CHAOS convention) — a partial run still emits a valid,
        # honestly-labelled artifact. Overhead has no performed flag:
        # the bound is cheap enough to always measure.
        report = self._report()
        report["acceptance"] = {"performed": False}
        report["itl"] = {"performed": False}
        report["adaptive"] = {"performed": False}
        report["value"] = None
        assert bench.validate_spec(report) == []

    def test_non_dict_sections_are_violations(self):
        report = self._report()
        report["acceptance"] = "done"
        report["overhead"] = 3
        problems = "\n".join(bench.validate_spec(report))
        assert "acceptance section is not an object" in problems
        assert "overhead section is not an object" in problems

    def test_build_report_matches_schema(self):
        base = self._report()
        res = {
            k: base[k]
            for k in ("acceptance", "itl", "adaptive", "overhead", "wall_s")
        }
        report = bench.build_spec_report(res)
        assert bench.validate_spec(report) == []
        assert report["value"] == base["acceptance"]["accepted_per_step"]
        assert report["metric"] == "spec_accepted_tokens_per_step"

    def test_spec_kind_registered_in_sentinel(self):
        assert "SPEC" in bench.COMPARE_RULES
        assert bench.artifact_kind(self._report()) == "SPEC"
        assert bench.artifact_kind({}, "SPEC_r18.json") == "SPEC"
        res = bench.benchdiff_selfcheck()
        assert "SPEC" in res["kinds_covered"]

    def test_compare_rounds_flags_acceptance_drop(self):
        old = self._report()
        new = self._report()
        new["value"] = 0.9
        new["acceptance"]["accepted_per_step"] = 0.9
        res = bench.compare_rounds(old, new, kind="SPEC")
        assert res["status"] == "regression"
        assert "acceptance.accepted_per_step" in res["regressions"]

    def test_checked_in_artifact_validates(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "SPEC_r*.json")))
        assert paths, "no SPEC artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_spec(report) == []
        assert report["acceptance"]["conserved"] is True
        assert report["acceptance"]["by_shape"]
        assert report["acceptance"]["by_source"]
        assert report["itl"]["seeded_detected"] is True
        assert report["adaptive"]["no_worse"] is True
        assert report["overhead"]["under_budget"] is True


class TestConvoyArtifactSchema:
    """The CONVOY artifact (PR 19, killing the prefill convoy):
    decode-interleaved chunked prefill's TTFT win with bit-identical
    outputs, the prefill_convoy stall drop and the wave-counted
    starvation bound — the ISSUE's gate names."""

    def _report(self) -> dict:
        return {
            "schema_version": bench.CONVOY_SCHEMA_VERSION,
            "metric": "convoy_ttft_speedup",
            "value": 4.2,
            "unit": "late-arrival p50 TTFT ratio (legacy / mixed waves)",
            "workload": "carrier + 960-token convoy + late 16-token "
            "arrival, A-B across prefill_inline_budget",
            "interleave": {
                "performed": True, "reps": 5, "inline_budget": 32,
                "base_ttft_p50_s": 0.18, "mixed_ttft_p50_s": 0.043,
                "ttft_ratio": 4.2, "base_itl_p99_s": 0.09,
                "mixed_itl_p99_s": 0.05, "outputs_match": True,
                "base_accepted_per_wave": 0.36,
                "mixed_accepted_per_wave": 0.35,
                "waves": {"counts": {"mixed": 120, "boost": 0},
                          "inline_tokens": 3904},
            },
            "stalls": {
                "performed": True, "stall_threshold_s": 0.02,
                "base_convoy_s_per_req": 0.058,
                "mixed_convoy_s_per_req": 0.002,
                "convoy_drop_ratio": 29.0,
                "base_causes": {"prefill_convoy": 0.52},
                "mixed_causes": {"prefill_inline": 0.4},
                "inline_attributed_s": 0.4,
            },
            "starvation": {
                "performed": True, "skew": "320:16",
                "max_defer_bound": 2, "max_step_gap": 1,
                "max_defer_observed": 2, "boost_waves": 2,
                "bounded": True, "carrier_tokens": 48,
            },
            "wall_s": 40.0,
        }

    def test_complete_report_validates(self):
        assert bench.validate_convoy(self._report()) == []
        assert bench.validate_convoy(7) == ["artifact is not a JSON object"]

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["wall_s"]
        del report["interleave"]["ttft_ratio"]
        del report["stalls"]["convoy_drop_ratio"]
        del report["starvation"]["bounded"]
        missing = bench.validate_convoy(report)
        assert "wall_s" in missing
        assert "interleave.ttft_ratio" in missing
        assert "stalls.convoy_drop_ratio" in missing
        assert "starvation.bounded" in missing

    def test_interleave_gates(self):
        report = self._report()
        report["interleave"]["ttft_ratio"] = 1.1
        report["interleave"]["outputs_match"] = False
        problems = "\n".join(bench.validate_convoy(report))
        assert "did not beat the convoy" in problems
        assert "outputs diverged" in problems
        report = self._report()
        report["interleave"]["mixed_itl_p99_s"] = 0.5
        report["interleave"]["mixed_accepted_per_wave"] = 0.1
        problems = "\n".join(bench.validate_convoy(report))
        assert "bought by starving decode" in problems
        assert "breaking speculation" in problems

    def test_stall_gates(self):
        report = self._report()
        report["stalls"]["convoy_drop_ratio"] = 1.2
        report["stalls"]["base_causes"] = {}
        problems = "\n".join(bench.validate_convoy(report))
        assert "the convoy survived" in problems
        assert "base_causes decomposition is empty" in problems

    def test_starvation_gates(self):
        report = self._report()
        report["starvation"]["bounded"] = False
        report["starvation"]["max_step_gap"] = 7
        problems = "\n".join(bench.validate_convoy(report))
        assert "starvation bound broke" in problems
        report = self._report()
        report["starvation"]["boost_waves"] = 0
        problems = "\n".join(bench.validate_convoy(report))
        assert "proven vacuously" in problems

    def test_skipped_sections_gate_exempt(self):
        report = self._report()
        for section in ("interleave", "stalls", "starvation"):
            report[section] = {"performed": False}
        report["value"] = None
        assert bench.validate_convoy(report) == []

    def test_non_dict_sections_are_violations(self):
        report = self._report()
        report["interleave"] = "done"
        report["starvation"] = 3
        problems = "\n".join(bench.validate_convoy(report))
        assert "interleave section is not an object" in problems
        assert "starvation section is not an object" in problems

    def test_build_report_matches_schema(self):
        base = self._report()
        res = {
            k: base[k]
            for k in ("interleave", "stalls", "starvation", "wall_s")
        }
        report = bench.build_convoy_report(res)
        assert bench.validate_convoy(report) == []
        assert report["value"] == base["interleave"]["ttft_ratio"]
        assert report["metric"] == "convoy_ttft_speedup"

    def test_convoy_kind_registered_in_sentinel(self):
        assert "CONVOY" in bench.COMPARE_RULES
        assert bench.artifact_kind(self._report()) == "CONVOY"
        assert bench.artifact_kind({}, "CONVOY_r19.json") == "CONVOY"
        res = bench.benchdiff_selfcheck()
        assert "CONVOY" in res["kinds_covered"]
        assert res["identical_clean"] and res["regression_flagged"]
        assert res["mismatch_detected"]

    def test_compare_rounds_flags_ttft_collapse(self):
        old = self._report()
        new = self._report()
        new["value"] = 1.6
        new["interleave"]["ttft_ratio"] = 1.6
        res = bench.compare_rounds(old, new, kind="CONVOY")
        assert res["status"] == "regression"
        assert "interleave.ttft_ratio" in res["regressions"]

    def test_checked_in_artifact_validates(self):
        import glob
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(repo, "CONVOY_r*.json")))
        assert paths, "no CONVOY artifact checked in"
        with open(paths[-1]) as fh:
            report = json.load(fh)
        assert bench.validate_convoy(report) == []
        assert report["interleave"]["outputs_match"] is True
        assert report["starvation"]["bounded"] is True

"""The gates of the four scenarios tier-1 runs (chaos, obs, tier,
blackbox): ``radixmesh_tpu/workload.py`` keeps each ``validate_*`` /
``build_*_report`` pair beside the ``run_*_workload`` whose result it
judges; these tests feed each gate a complete report and then break it
one field at a time. The scenarios themselves run in
``test_repair_plane.py``, ``test_obs_workload.py``, ``test_kv_tier.py``
and ``test_blackbox_workload.py``."""

import pytest

from radixmesh_tpu import workload

pytestmark = pytest.mark.quick


class TestChaosArtifactSchema:
    """The CHAOS artifact (self-healing mesh, PR 5) stays machine-
    comparable across rounds: pinned top/section fields plus the three
    structural acceptance gates — converged, within the repair-round
    budget, quiescent after convergence."""

    def _report(self) -> dict:
        return {
            "schema_version": workload.CHAOS_SCHEMA_VERSION,
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
        assert workload.validate_chaos(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["round_budget"]
        del report["repair"]["converge_s"]
        del report["quiescence"]["quiet"]
        del report["drain"]["writeback_tokens"]
        del report["join"]["bootstrap_rounds"]
        del report["crash"]["resurrection_hit_ratio"]
        missing = workload.validate_chaos(report)
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
        problems = "\n".join(workload.validate_chaos(report))
        assert "never healed" in problems
        assert "exceeded round_budget" in problems
        assert "injected nothing" in problems
        assert "kept flowing" in problems
        assert workload.validate_chaos(7) == ["artifact is not a JSON object"]

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
        problems = "\n".join(workload.validate_chaos(report))
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
        problems = "\n".join(workload.validate_chaos(report))
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
        problems = "\n".join(workload.validate_chaos(report))
        assert "interrupted zero live streams" in problems

    @pytest.mark.parametrize(
        "section", ["drain", "join", "crash", "rebalance", "router_kill"]
    )
    def test_every_phase_section_is_required(self, section):
        """No report predates a phase any more: one without the section
        names it, whatever version it claims."""
        report = self._report()
        del report[section]
        report["schema_version"] = 1
        assert f"{section} section missing" in workload.validate_chaos(report)

    def test_skipped_phase_is_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["drain"] = {"performed": False}
        report["join"] = {"performed": False}
        report["crash"] = {"performed": False}
        report["rebalance"] = {"performed": False}
        report["router_kill"] = {"performed": False}
        assert workload.validate_chaos(report) == []

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
        problems = "\n".join(workload.validate_chaos(report))
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
        problems = "\n".join(workload.validate_chaos(report))
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
        report = workload.build_chaos_report(res)
        assert workload.validate_chaos(report) == []
        assert report["value"] == res["repair"]["converge_s"]


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
            "schema_version": workload.OBS_SCHEMA_VERSION,
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
        assert workload.validate_obs(self._report()) == []

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["replication_factor"]
        del report["stitch"]["trace_id"]
        del report["heat"]["skew_score"]
        del report["steps"]["prefill"]["mfu"]
        del report["wire"]["trailer_bytes"]
        missing = workload.validate_obs(report)
        assert "replication_factor" in missing
        assert "stitch.trace_id" in missing
        assert any("skew_score" in m for m in missing)
        assert "steps.prefill.mfu" in missing
        assert "wire.trailer_bytes" in missing
        assert workload.validate_obs(7) == ["artifact is not a JSON object"]

    def test_stitch_gates_enforced(self):
        report = self._report()
        report["stitch"]["node_tracks"] = workload.OBS_MIN_NODE_TRACKS - 1
        report["stitch"]["failed"] = 2
        report["stitch"]["resumed"] = 3
        report["stitch"]["replication_edges"] = 0
        problems = "\n".join(workload.validate_obs(report))
        assert "did not stitch" in problems
        assert "LOST" in problems
        assert "not all resurrected" in problems
        assert "no replication edges" in problems

    def test_heat_gates_enforced(self):
        report = self._report()
        report["heat"]["skew_score"] = workload.OBS_MIN_SKEW_SCORE - 0.5
        report["heat"]["hot_shard"] = 9
        report["heat"]["owner_set_correct"] = False
        report["heat"]["reporters"] = 0
        problems = "\n".join(workload.validate_obs(report))
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
        problems = "\n".join(workload.validate_obs(report))
        assert "zero decode waves" in problems
        assert "decode MFU" in problems
        assert "pad fraction" in problems
        assert "bit-for-bit" in problems

    def test_skipped_legs_are_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["stitch"] = {"performed": False}
        report["heat"] = {"performed": False}
        report["steps"] = {"performed": False}
        assert workload.validate_obs(report) == []

    def test_build_report_matches_schema(self):
        res = {
            k: self._report()[k]
            for k in (
                "nodes", "topology", "replication_factor", "stitch",
                "heat", "steps", "wire", "wall_s",
            )
        }
        report = workload.build_obs_report(res)
        assert workload.validate_obs(report) == []
        assert report["value"] == res["stitch"]["node_tracks"]


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
            "schema_version": workload.BLACKBOX_SCHEMA_VERSION,
            "metric": "blackbox_postmortem_named",
            "value": workload.BLACKBOX_NAMED_TOTAL,
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
                    "samples": 40,
                    "max_samples": 41,
                    "series_per_sweep": 740,
                    "segments": 9,
                    "segment_bytes_max": 128_000,
                    "dropped_series": 0,
                    "under_budget": True,
                },
            },
            "blackbox": {"schema_version": 1},
            "wall_s": 10.0,
        }

    def test_complete_report_validates(self):
        assert workload.validate_blackbox(self._report()) == []

    def test_missing_top_fields_named(self):
        report = self._report()
        del report["postmortem"]
        del report["history"]
        problems = workload.validate_blackbox(report)
        assert "postmortem" in problems
        assert "history" in problems

    def test_healthy_findings_fail_the_gate(self):
        report = self._report()
        report["healthy"]["findings"] = [{"rule": "hot_shard"}]
        assert any(
            "healthy" in p for p in workload.validate_blackbox(report)
        )

    def test_all_rules_must_have_run_on_healthy(self):
        report = self._report()
        report["healthy"]["rules_checked"] = ["hot_shard"]
        problems = "\n".join(workload.validate_blackbox(report))
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
            assert workload.validate_blackbox(report), (path, key)

    def test_kill_must_land_on_a_hot_owner(self):
        report = self._report()
        report["crash"]["victim_is_hot_owner"] = False
        problems = "\n".join(workload.validate_blackbox(report))
        assert "hot" in problems

    @pytest.mark.parametrize(
        "field,value,named",
        [
            ("samples", 42, "sweeps"),
            ("series_per_sweep", 74_000, "series a sweep"),
            ("segment_bytes_max", 12_800_000, "bytes a segment"),
            ("segments", 0, "no segment"),
            ("dropped_series", 1, "refused"),
            ("under_budget", False, "overhead"),
        ],
    )
    def test_overhead_budget_gate(self, field, value, named):
        # A sweep a hundred times dearer in any unit of its work fails,
        # whatever the report says of itself; the measured wall share
        # gates nothing.
        report = self._report()
        report["history"]["self_overhead"]["fraction"] = 0.05
        assert workload.validate_blackbox(report) == []
        report["history"]["self_overhead"][field] = value
        problems = "\n".join(workload.validate_blackbox(report))
        assert "overhead" in problems and named in problems

    def test_value_must_count_every_verdict(self):
        report = self._report()
        report["value"] = 2
        problems = "\n".join(workload.validate_blackbox(report))
        assert "verdicts" in problems

    def test_skipped_sections_are_schema_valid_but_gate_exempt(self):
        report = self._report()
        report["healthy"] = {"performed": False}
        report["crash"] = {"performed": False}
        assert workload.validate_blackbox(report) == []

    def test_build_report_matches_schema(self):
        core = {k: v for k, v in self._report().items()
                if k not in ("schema_version", "metric", "value", "unit",
                             "workload")}
        core["named"] = 3
        report = workload.build_blackbox_report(core)
        assert workload.validate_blackbox(report) == []
        assert report["value"] == 3
        assert report["metric"] == "blackbox_postmortem_named"


class TestTierArtifactSchema:
    """The TIER artifact (PR 15, the durable KV spill tier): hit-rate
    at a working set >= 10x host capacity beats the no-tier baseline,
    decode never blocks on disk restores, the whole-cell kill-and-
    restart drill resumes every stream byte-identical from disk alone
    with seeded corrupt/torn extents detected and never served, and
    meshcheck reports the tier plane clean."""

    def _report(self) -> dict:
        return {
            "schema_version": workload.TIER_SCHEMA_VERSION,
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
        assert workload.validate_tier(self._report()) == []
        assert workload.validate_tier(7) == ["artifact is not a JSON object"]

    def test_missing_fields_are_named(self):
        report = self._report()
        del report["capacity"]["working_set_ratio"]
        del report["cold_start"]["byte_identical"]
        del report["corruption"]["served_corrupt"]
        missing = workload.validate_tier(report)
        assert "capacity.working_set_ratio" in missing
        assert "cold_start.byte_identical" in missing
        assert "corruption.served_corrupt" in missing

    def test_capacity_gates(self):
        report = self._report()
        report["capacity"]["working_set_ratio"] = 4.0
        report["capacity"]["tier_hit_rate"] = 0.05
        problems = "\n".join(workload.validate_tier(report))
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
        problems = "\n".join(workload.validate_tier(report))
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
        problems = "\n".join(workload.validate_tier(report))
        assert "zero parked disk restores" in problems
        assert "decode made zero progress" in problems

    def test_corruption_gates(self):
        report = self._report()
        report["corruption"]["detected"] = 1
        problems = "\n".join(workload.validate_tier(report))
        assert "1 of 2 attacked" in problems

    def test_meshcheck_and_value_gates(self):
        report = self._report()
        report["meshcheck"]["clean"] = False
        report["meshcheck"]["findings"] = 2
        report["value"] = 0.8
        problems = "\n".join(workload.validate_tier(report))
        assert "statically clean" in problems
        assert "not > 1" in problems

    def test_skipped_cold_start_gate_exempt(self):
        report = self._report()
        report["cold_start"] = {"performed": False}
        report["corruption"]["extents_attacked"] = 0
        assert workload.validate_tier(report) == []

    def test_non_dict_sections_are_violations(self):
        report = self._report()
        report["cold_start"] = "done"
        problems = "\n".join(workload.validate_tier(report))
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
        report = workload.build_tier_report(res, meshcheck=base["meshcheck"])
        assert workload.validate_tier(report) == []
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
        problems = "\n".join(workload.validate_tier(workload.build_tier_report(res)))
        assert "statically clean" in problems

"""Mesh-wide observability acceptance (PR 9, ``workload.run_obs_workload``):
the chaos-style crash+resurrection run must export ONE stitched Perfetto
trace with the interrupted request's spans on >= 3 node tracks under a
single 64-bit trace id (publish/replication edges visible), the zipf
workload must provably drive the per-shard skew score with the hot
shard's owner set correctly named from gossip alone, and traceless
frames must stay bit-for-bit the pre-PR-9 wire. (The step-attribution
leg is exercised separately in test_trace_plane — no second tiny-engine
compile here.)"""

import json

from radixmesh_tpu.obs.trace_plane import validate_trace
from radixmesh_tpu.workload import (
    OBS_MIN_NODE_TRACKS,
    OBS_MIN_SKEW_SCORE,
    build_obs_report,
    run_obs_workload,
    validate_obs,
)


class TestObsScenario:
    def test_stitch_heat_and_wire_gates(self, tmp_path):
        trace_path = str(tmp_path / "stitched.json")
        res = run_obs_workload(
            streams=6,
            tokens_per_stream=16,
            zipf_inserts=250,
            engine_steps=False,
            stitched_trace_path=trace_path,
            timeout_s=45.0,
        )
        report = build_obs_report(res)
        # Gates (validate_obs enforces them too; asserted directly so a
        # failure names the exact leg). steps is gate-exempt here
        # (performed=False — covered by test_trace_plane's engine test).
        assert validate_obs(report) == []
        stitch = res["stitch"]
        assert stitch["failed"] == 0
        assert stitch["interrupted"] > 0
        assert stitch["resumed"] == stitch["interrupted"]
        assert stitch["node_tracks"] >= OBS_MIN_NODE_TRACKS
        assert stitch["replication_edges"] > 0
        assert stitch["publish_edges"] > 0
        heat = res["heat"]
        assert heat["skew_score"] >= OBS_MIN_SKEW_SCORE
        assert heat["hot_shard"] == heat["expected_hot_shard"]
        assert heat["owner_set_correct"]
        wire = res["wire"]
        assert wire["rf0_traceless_unchanged"]
        assert wire["trace_trailer_roundtrip"]
        assert wire["trailer_bytes"] == 8

        # The stitched artifact is ONE valid Perfetto document with one
        # process track per node and the single trace id threaded
        # through the interrupted request's events.
        with open(trace_path) as fh:
            doc = json.load(fh)
        assert validate_trace(doc) == []
        assert doc["otherData"]["stitched"] is True
        procs = {
            ev["args"]["name"]
            for ev in doc["traceEvents"]
            if ev.get("ph") == "M" and ev["name"] == "process_name"
        }
        assert set(stitch["nodes_on_track"]) <= procs
        tid = stitch["trace_id"]
        pids_under_tid = {
            ev["pid"]
            for ev in doc["traceEvents"]
            if ev.get("ph") == "X"
            and (ev.get("args") or {}).get("trace_id") == tid
        }
        assert len(pids_under_tid) >= OBS_MIN_NODE_TRACKS

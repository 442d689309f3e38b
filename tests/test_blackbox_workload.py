"""Black-box acceptance (PR 13, ``workload.run_blackbox_workload``):
the live history-backed doctor must stay silent on the healthy phase,
the hot shard's primary owner killed hard mid-zipf-storm must leave
crash-surviving dumps, and the post-mortem doctor must name the seeded
hot shard, a crash window containing the true kill time, and the
unclean-death truncation FROM THE DUMPS ALONE — with the telemetry
sampler inside its budget of sweeps, series and segment bytes."""

import os

from radixmesh_tpu.workload import (
    build_blackbox_report,
    run_blackbox_workload,
    validate_blackbox,
)


class TestBlackboxScenario:
    def test_postmortem_names_everything_from_the_dumps(self, tmp_path):
        res = run_blackbox_workload(
            seed=0, blackbox_dir=str(tmp_path), timeout_s=45.0
        )
        report = build_blackbox_report(res)
        # Gates (validate_blackbox enforces them too; asserted directly
        # so a failure names the exact leg).
        assert validate_blackbox(report) == []
        assert res["healthy"]["findings"] == []
        pm = res["postmortem"]
        assert pm["observer"]["hot_shard_named"]
        assert (
            pm["observer"]["hot_shard_evidence"]["shard"]
            == pm["expected"]["hot_shard"]
        )
        lo, hi = pm["observer"]["crash_evidence"]["window"]
        assert lo - 0.05 <= pm["expected"]["t_kill"] <= hi
        assert pm["victim"]["unclean"]
        assert pm["victim"]["truncation_named"]
        assert res["history"]["self_overhead"]["under_budget"]
        # The dumps themselves survived on disk: the victim's directory
        # holds segments only (the hard kill), the observer's a final.
        victim_dir = os.path.join(str(tmp_path), "victim")
        node_dir = os.path.join(victim_dir, os.listdir(victim_dir)[0])
        names = sorted(os.listdir(node_dir))
        assert any(n.startswith("segment-") for n in names)
        assert not any(n.startswith("final-") for n in names)

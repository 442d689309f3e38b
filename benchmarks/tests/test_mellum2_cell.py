"""The cell ``mellum2-12b-l12.batch-longtail`` is found by name — cell,
configuration, traffic mix, generator, launcher and every reader the
manifest lists for it — its traffic is the mix ISSUE 27 gives, and the
whole command runs with it (CPU rehearsal, ``--trace 2``)."""
import json
import os
import sys

import numpy as np

from benchmarks.harness import registry
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "mellum2-12b-l12.batch-longtail"
NEW_METRICS = {"moe_gmm_hbm_share", "moe_share_of_decode_step",
               "moe_expert_load_max_over_mean", "decode_attn_hbm_share.window",
               "window_kv_skipped_share"}


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "mellum2-12b-a2.5b-l12"
    assert r["config"]["server_args"] == [
        "--page-size", "16", "--kv-slots", "65536", "--max-batch", "32"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "mellum2-12b-a2.5b-l12"]
    names = {m["name"] for m in r["per_layer"]}
    assert NEW_METRICS <= names and "decode_attn_hbm_share.batch" not in names
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in names:
        assert callable(registry.load_module("layer_metrics", name).read), name
    man = registry.manifest()
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"


def test_the_traffic_is_the_issues():
    r = registry.resolve(CELL)
    p = r["traffic"]["params"]
    assert p["mix_seed"] == 27001 and p["pool_sessions"] == 512
    assert p["system_prompts"] == 0 and p["turns"] == {"dist": "const", "value": 1}
    assert p["turn_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.9,
                                "min": 256, "max": 8192, "quantum": 256}
    assert p["max_tokens"] == {"dist": "uniform", "min": 128, "max": 256,
                               "quantum": 32}
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 64}
    assert r["cell"]["min_cached_share"] == 0.0
    plan = r["generator"].plan(p, r["cell"]["loop"], 98304, 5, horizon_s=101)
    lens = plan.user_lens
    assert len(lens) == 512 and int(lens.mean()) == 1068
    assert len(set(lens)) == 24 and lens.min() == 256 and lens.max() <= 8192
    assert 0.29 < (lens > 1024).mean() < 0.32
    assert 0.63 < lens[lens > 1024].sum() / lens.sum() < 0.65
    # every bucket of the engine's prefill groups has a warm-up burst
    bursts = r["cell"]["warmup"]["bursts"]
    warmed = {int(t) for b in bursts for t in np.atleast_1d(b["new_tokens"])}
    buckets = {1 << (int(n) - 1).bit_length() for n in set(lens)}
    assert buckets == {1 << (t - 1).bit_length() for t in warmed}


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483777", "--seconds", "3", "--trace", "2", "--rehearse-cpu"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600)
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and "setup_s" in got
    # the counter-based new metrics read on the CPU; the trace-based ones
    # need a device plane and are left out of a CPU line
    assert got["window_kv_skipped_share"]["value"] > 0
    assert got["moe_expert_load_max_over_mean"]["value"] > 0
    assert got["preemptions"]["value"] == 0

"""The cell ``glm-4.7-flash-l8.docs-hotset`` is found by name — cell,
configuration, traffic mix, generator, launcher and every reader the
manifest lists for it — its traffic is the mix ISSUE 31 gives,
``opsbytes_mla`` computes what the issue's arithmetic says, and the whole
command runs with it (CPU rehearsal, ``--trace 2``)."""
import json
import os
import sys

import pytest

from benchmarks.harness import opsbytes_mla, registry
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "glm-4.7-flash-l8.docs-hotset"
NEW_METRICS = {"mla_decode_hbm_share", "mla_decode_mxu_share",
               "mla_share_of_decode_step", "prefill_share_of_busy.docs",
               "moe_gmm_hbm_share.docs", "moe_share_of_decode_step.docs",
               "moe_expert_load_max_over_mean.docs", "prefix_hit_share.docs"}
APPENDED = {"decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
            "decode_step_device_ms.batch", "compiles_in_window.batch",
            "device_idle_share.batch", "step_host_exposed_ms.batch",
            "between_steps_idle_ms.batch", "idle_attributed_share.batch",
            "decode_prebuilt_share.batch"}


def run_list(x):
    return x if isinstance(x, list) else [x]


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "glm-4.7-flash-l8"
    assert r["config"]["server_args"] == [
        "--page-size", "16", "--kv-slots", "196608", "--max-batch", "32"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "glm-4.7-flash-l8"]
    names = {m["name"] for m in r["per_layer"]}
    assert names == NEW_METRICS | APPENDED
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in names:
        assert callable(registry.load_module("layer_metrics", name).read), name
    man = registry.manifest()
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        if m["name"] in APPENDED:
            assert m["workloads"][-1] == CELL
    assert man["workloads"][-1]["name"] == CELL
    assert man["configs"][-1]["name"] == r["cell"]["config"] == "glm-4.7-flash-bf16-l8"


def test_the_traffic_is_the_issues():
    r = registry.resolve(CELL)
    p = r["traffic"]["params"]
    assert r["traffic"]["generator"] == "sessions"
    assert p["mix_seed"] == 31001 and p["pool_sessions"] == 512
    assert p["system_prompts"] == 6 and p["zipf_s"] == 1.0
    assert p["system_len"] == {"dist": "uniform", "min": 16384, "max": 32768,
                               "quantum": 4096}
    assert p["turns"] == {"dist": "const", "value": 1} and "think_s" not in p
    assert p["turn_tokens"] == {"dist": "uniform", "min": 64, "max": 256, "quantum": 32}
    assert p["max_tokens"] == {"dist": "uniform", "min": 64, "max": 192, "quantum": 32}
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 64}
    assert r["cell"]["min_cached_share"] == 0.9
    plan = r["generator"].plan(p, r["cell"]["loop"], 154880, 5, horizon_s=101)
    assert plan.system_lens.tolist() == [20480, 16384, 24576, 20480, 24576, 16384]
    assert int(plan.system_lens.sum()) == 122880
    assert 20200 < plan.system_lens[plan.sys_ids].mean() < 20400  # Zipf-weighted
    assert sorted(set(plan.user_lens)) == list(range(64, 257, 32))
    assert sorted(set(plan.reply_lens)) == list(range(64, 193, 32))
    # every request is a tree hit of its whole document + the question as the tail
    census = set(plan.census(plan.pool, 16))
    assert {c[0] for c in census} == {16384, 20480, 24576}
    assert {c[1] for c in census} == set(range(64, 257, 32))
    # the pool holds the documents, the warm-up's own and the rows in flight
    slots = int(r["config"]["server_args"][3])
    assert 122880 + 24576 + 32 * (256 + 192) == 161792 < slots
    # every row count of every bucket of the engine's prefill groups has a
    # burst, over the warm-up's own document (the longest of the mix)
    bursts = r["cell"]["warmup"]["bursts"]
    shapes = {(n, t) for b in bursts for n in run_list(b["n"])
              for t in run_list(b["new_tokens"])}
    assert shapes == {(n, t) for n in (2, 4, 8, 16, 32) for t in (64, 128, 256)} - {(32, 256)}
    assert {b["prefix_len"] for b in bursts} == {int(plan.system_lens.max())}
    # ... and set-up leaves less in the tree than the pool holds, so that no
    # document (the oldest entries when the load starts) is evicted
    probe = r["config"]["probe"]
    left = (122880 + 24576 + sum(n * (t + 16) for n, t in shapes)
            + sum(len(run_list(b["n"])) * len(run_list(b["new_tokens"]))
                  * (b["blocker"] + 16) for b in bursts)
            + len(census) * 272 // 7 + probe["n"] * (probe["prompt_len"] + 32))
    assert left < slots - 4096, left
    assert probe["prompt_len"] > max(32 * 16, 1024)  # past one bucket, chunked cold


def test_opsbytes_mla_is_the_issues_arithmetic():
    config = registry.resolve(CELL)["config"]
    assert opsbytes_mla.row_elements(config) == 576
    assert opsbytes_mla.row_bytes(config) == 1152
    assert opsbytes_mla.kv_bytes_per_token(config) == 9216
    assert opsbytes_mla.decode_attn_flops_per_token_layer(config) == 43520
    p = opsbytes_mla.param_counts(config)
    assert p["attention"] == 21_757_952  # 1.573 + 3.932 + 1.180 + 4.588 + 10.486 M
    assert p["expert"] == 9_437_184
    assert round(p["sparse_layer"] / 1e6, 1) == 635.3
    assert round(p["dense_layer"] / 1e6, 1) == 84.7
    assert round(2 * p["embed"] / 1e6, 1) == 634.4
    assert round(opsbytes_mla.weight_bytes(config) / 2 ** 30, 2) == 9.62
    # 37.8 FLOP a byte: under the chip's ridge of 240
    assert round(43520 / 1152, 1) == 37.8
    with pytest.raises(KeyError):  # a K/V-per-head configuration has no latent row
        opsbytes_mla.row_bytes(registry.resolve("qwen2-7b.batch-unshared")["config"])


def test_new_readers_find_nothing_where_there_is_nothing():
    """A program without the counters, or a run without a trace (the
    parent; a ``--trace 0`` context): None, never an exception."""
    config = registry.resolve(CELL)["config"]
    state = {"state": {"engine": {"decode_steps": 10}, "attn": {
        "window_tokens_read": 0, "window_tokens_skipped": 0}},
        "stats": {"prompt_tokens": 0, "cached_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": config,
           "before": state, "after": state}
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_METRICS - {"prefix_hit_share.docs"}:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_mla_shares_from_a_hand_made_trace():
    config = registry.resolve(CELL)["config"]

    def snap(steps, rows):
        return {"state": {"engine": {"decode_steps": steps},
                          "attn": {"latent_tokens_read": rows}}, "stats": {}}

    plane = {"busy_s": 2.0, "kernels": {"radixmesh_mla_decode": {"count": 80, "seconds": 0.5}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 1.0},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.6}}}
    ctx = {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "before": snap(100, 0), "after": snap(300, 200 * 5_000_000)}

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    # 5 M rows a step x 1152 B in 50 ms of kernel a step
    hbm = 100 * 5e6 * 1152 / 0.05 / 819e9
    assert read("mla_decode_hbm_share") == pytest.approx(hbm)
    assert read("mla_decode_mxu_share") == pytest.approx(hbm * (43520 / 1152) * 819e9 / 197e12)
    assert read("mla_decode_mxu_share") / read("mla_decode_hbm_share") == pytest.approx(0.157, abs=1e-3)
    assert read("mla_share_of_decode_step") == pytest.approx(50.0)
    assert read("prefill_share_of_busy.docs") == pytest.approx(30.0)


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
    # the counter-based metrics read on the CPU; the trace-based ones need
    # a device plane and are left out of a CPU line
    assert got["prefix_hit_share.docs"]["value"] > 95
    assert got["moe_expert_load_max_over_mean.docs"]["value"] > 0
    assert got["preemptions"]["value"] == 0
    assert got["compiles_in_window.batch"]["value"] == 0

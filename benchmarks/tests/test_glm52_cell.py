"""The cells ``glm-5.2-ep16.repo-hotset`` and
``glm-4.7-flash-l8.batch-longtail`` are found by name — cell,
configuration, traffic mix, generator, launcher and every reader the
manifest lists for them — the traffic is the mix ISSUE 33 gives, the
configuration file holds the source's numbers and names every cut, every
new reader reads a hand-made context and nothing off its cell, and the
whole command runs with both (CPU rehearsal, ``--trace 2``)."""
import json
import os
import sys

import pytest

from benchmarks.harness import dsa_trace, registry
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "glm-5.2-ep16.repo-hotset"
LONGTAIL = "glm-4.7-flash-l8.batch-longtail"
NEW_METRICS = {
    "dsa_index_hbm_share", "mla_sparse_decode_hbm_share",
    "mla_sparse_decode_mxu_share", "dsa_select_share_of_decode_step",
    "dsa_share_of_decode_step", "dsa_rows_skipped_share",
    "moe_routed_here_share", "moe_gmm_hbm_share.ep16",
    "moe_share_of_decode_step.ep16", "moe_expert_load_max_over_mean.ep16",
    "prefix_hit_share.ep16", "prefill_share_of_busy.ep16"}
SHARED = {"decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
          "decode_step_device_ms.batch", "compiles_in_window.batch",
          "device_idle_share.batch", "step_host_exposed_ms.batch",
          "between_steps_idle_ms.batch", "idle_attributed_share.batch",
          "decode_prebuilt_share.batch"}


def run_list(x):
    return x if isinstance(x, list) else [x]


def test_both_cells_resolve_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "glm-5.2-ep16"
    assert r["config"]["server_args"] == [
        "--page-size", "16", "--kv-slots", "221184", "--max-batch", "32"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "glm-5.2-ep16"]
    assert {m["name"] for m in r["per_layer"]} == NEW_METRICS | SHARED
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    lt = registry.resolve(LONGTAIL)
    assert lt["config"]["preset"] == "glm-4.7-flash-l8"
    assert lt["traffic"]["name"] == "batch-longtail"
    assert {m["name"] for m in lt["per_layer"]} == SHARED | {"prefix_hit_share.batch"}
    assert {m["name"] for m in lt["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in NEW_METRICS | SHARED:
        assert callable(registry.load_module("layer_metrics", name).read), name


def test_the_manifest_is_consistent():
    man = registry.manifest()
    cells = [w["name"] for w in man["workloads"]]
    assert len(cells) == len(set(cells)) == 6 and all(w["chips"] == 1 for w in man["workloads"])
    assert len(man["configs"]) == 4
    files = {c["name"]: c["file"] for c in man["configs"]}
    for w in man["workloads"]:
        assert w["config"] in files and len(w["why"]) <= 200
        registry.resolve(w["name"])  # cell, configuration and traffic files agree
    layers = {m["layer"] for m in man["per_layer"]}
    for m in man["per_layer"]:
        assert set(m["workloads"]) <= set(cells), m["name"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
            assert m["layer"] in layers - {None}
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert {CELL, LONGTAIL} <= set(tokens["workloads"])
    assert len(json.dumps(man)) < 64 * 1024


def test_the_configuration_file_holds_the_sources_numbers():
    config = registry.resolve(CELL)["config"]
    entry = next(c for c in registry.manifest()["configs"]
                 if c["name"] == "glm-5.2-bf16-ep16")
    assert set(entry["reduced"]) == set(config["reduced"])
    published = config["published"]
    for key in entry["reduced"]:
        assert key in published, key
    # every width is the source's
    for key, want in {
            "hidden_size": 6144, "intermediate_size": 12288,
            "moe_intermediate_size": 2048, "num_attention_heads": 64,
            "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
            "qk_rope_head_dim": 64, "v_head_dim": 256, "index_n_heads": 32,
            "index_head_dim": 128, "index_topk": 2048, "num_experts_per_tok": 8,
            "n_shared_experts": 1, "routed_scaling_factor": 2.5}.items():
        assert config[key] == want and key not in entry["reduced"], key
    assert config["indexer_types"] == ["full", "shared", "shared", "shared",
                                       "full", "shared", "shared"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 6
    assert config["n_routed_experts"] == 16 and config["serving"]["router_experts"] == 256
    assert config["vocab"] == config["vocab_size"] == 154880 // 8
    for group in ("assumed", "deployment", "aot", "reference_check"):
        assert config[group], group
    # the program's preset is this file
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert (cfg.n_layers, cfg.first_k_dense, cfg.held_experts, cfg.n_experts) == (7, 1, 16, 256)
    assert list(cfg.indexer_types) == config["indexer_types"]
    assert (cfg.ep_rank, cfg.ep_size) == (0, config["serving"]["ep_size"])
    assert cfg.vocab_size == config["vocab"] and cfg.index_topk == 2048
    assert cfg.kv_index_shape(221184) == (2, 221184, 128)


def test_the_traffic_is_the_issues():
    r = registry.resolve(CELL)
    p = r["traffic"]["params"]
    assert r["traffic"]["generator"] == "sessions"
    assert p["system_prompts"] == 3 and p["zipf_s"] == 1.0 and p["pool_sessions"] == 512
    assert p["system_len"] == {"dist": "uniform", "min": 32768, "max": 49152,
                               "quantum": 8192}
    assert p["turns"] == {"dist": "const", "value": 1} and "think_s" not in p
    assert p["turn_tokens"] == {"dist": "uniform", "min": 64, "max": 256, "quantum": 32}
    assert p["max_tokens"] == {"dist": "uniform", "min": 128, "max": 256, "quantum": 32}
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 64}
    assert r["cell"]["min_cached_share"] == 0.9
    vocab = r["config"]["vocab"]
    plan = r["generator"].plan(p, r["cell"]["loop"], vocab, 5, horizon_s=101)
    # one context of each length, ids inside the vocabulary slice
    assert sorted(plan.system_lens.tolist()) == [32768, 40960, 49152]
    assert int(plan.system_lens.sum()) == 122880
    assert 41000 < plan.system_lens[plan.sys_ids].mean() < 42500  # Zipf-weighted
    assert all(0 <= int(t.min()) and int(t.max()) < vocab == 19360
               for t in plan.prefixes())
    sess = next(plan)
    assert int(sess.turns[0].new_tokens.max()) < vocab
    assert sorted(set(plan.user_lens)) == list(range(64, 257, 32))
    assert sorted(set(plan.reply_lens)) == list(range(128, 257, 32))
    census = set(plan.census(plan.pool, 16))
    assert {c[0] for c in census} == {32768, 40960, 49152}
    assert {c[1] for c in census} == set(range(64, 257, 32))
    # every context of the cell has the one page-table width: 4096 pages
    assert all(2048 < -(-(c + n + 256) // 16) <= 4096 for c, n in census)
    # set-up leaves less in the tree than the pool holds (the cell's pool_why)
    slots = int(r["config"]["server_args"][3])
    bursts = r["cell"]["warmup"]["bursts"]
    shapes = {(n, t) for b in bursts for n in run_list(b["n"])
              for t in run_list(b["new_tokens"])}
    assert shapes == {(n, t) for n in (2, 4, 8, 16) for t in (64, 128, 256)}
    assert {b["prefix_len"] for b in bursts} == {32768}
    probe = r["config"]["probe"]
    left = (122880 + 49152 + sum(n * (t + 16) for n, t in shapes)
            + len(shapes) * (bursts[0]["blocker"] + 16)
            + len(census) * 272 // 7 + probe["n"] * (probe["prompt_len"] + 32))
    assert 196608 < left < slots - 8192, left
    assert 122880 + 32 * (256 + 256) < slots
    lt = registry.resolve(LONGTAIL)
    assert lt["traffic"] == registry.resolve("mellum2-12b-l12.batch-longtail")["traffic"]
    assert lt["cell"]["warmup"] == registry.resolve(
        "mellum2-12b-l12.batch-longtail")["cell"]["warmup"]


def _ctx(config, name=CELL):
    def snap(steps, k):
        sparse = {site: {"index_keys_scored": k * 2_000_000, "rows_selected": k * 400_000,
                         "rows_held": k * 8_000_000} for site in ("decode", "prefill")}
        moe = {site: {"layer_launches": k * 600, "assignments": k * 1000,
                      "assignments_elsewhere": k * 15000, "experts_hit": k * 30,
                      "max_load": k * 1500} for site in ("decode", "prefill")}
        return {"state": {"engine": {"decode_steps": steps}, "moe": moe,
                          "attn": {"sparse": sparse}},
                "stats": {"prompt_tokens": k * 1000, "cached_tokens": k * 996}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_dsa_index_decode": {"count": 20, "seconds": 0.02},
                         "radixmesh_mla_sparse_decode": {"count": 70, "seconds": 0.01},
                         "radixmesh_moe_gmm_decode": {"count": 180, "seconds": 0.05}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.4},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.8}}}
    return {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
            "cell": {"name": name},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "before": snap(100, 0), "after": snap(300, 200)}


def test_new_readers_read_a_hand_made_context(monkeypatch):
    config = registry.resolve(CELL)["config"]
    ctx = _ctx(config)
    monkeypatch.setattr(dsa_trace, "of_run", lambda c, module="jit_decode_step": {
        "executions": 10, "select_s": 0.06, "gather_s": 0.07, "selects": 20,
        "gathers": 70})

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    # 2 M keys a step x 256 B in 2 ms of kernel a step
    assert read("dsa_index_hbm_share") == pytest.approx(
        100 * 2e6 * 256 / 0.002 / 819e9)
    # 0.4 M rows a step x 1152 B in 1 ms of kernel + 7 ms of gather a step
    hbm = 100 * 4e5 * 1152 / 0.008 / 819e9
    assert read("mla_sparse_decode_hbm_share") == pytest.approx(hbm)
    assert read("mla_sparse_decode_mxu_share") == pytest.approx(
        hbm * (139264 / 1152) * 819e9 / 197e12)
    assert read("dsa_select_share_of_decode_step") == pytest.approx(15.0)
    assert read("dsa_share_of_decode_step") == pytest.approx(
        100 * (0.02 + 0.01 + 0.06 + 0.07) / 0.4)
    assert read("dsa_rows_skipped_share") == pytest.approx(95.0)
    assert read("moe_routed_here_share") == pytest.approx(6.25)
    assert read("moe_share_of_decode_step.ep16") == pytest.approx(12.5)
    # 30 experts hit a step x 75.5 MB in 5 ms of kernel a step
    assert read("moe_gmm_hbm_share.ep16") == pytest.approx(
        100 * 30 * 3 * 6144 * 2048 * 2 / 0.005 / 819e9)
    assert read("moe_expert_load_max_over_mean.ep16") == pytest.approx(1.5 * 16)
    assert read("prefix_hit_share.ep16") == pytest.approx(99.6)
    assert read("prefill_share_of_busy.ep16") == pytest.approx(40.0)
    for name in NEW_METRICS:  # a share of a peak stays under it here
        assert 0 < read(name) < 105 or "load_max" in name, name


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("glm-4.7-flash-l8.docs-hotset", "mellum2-12b-l12.batch-longtail",
                  "qwen2-7b.batch-unshared"):
        ctx = _ctx(registry.resolve(other)["config"], name=other)
        for name in NEW_METRICS:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    bare = {"state": {"engine": {"decode_steps": 10}, "attn": {
        "window_tokens_read": 0, "window_tokens_skipped": 0}},
        "stats": {"prompt_tokens": 0, "cached_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": mine,
           "cell": {"name": CELL}, "before": bare, "after": bare}
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_METRICS - {"prefix_hit_share.ep16"}:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


@pytest.mark.parametrize("cell", [CELL, LONGTAIL])
def test_rehearsal_runs_to_a_result_line(cell):
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "2147483833", "--seconds", "3", "--trace", "2", "--rehearse-cpu"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and "setup_s" in got
    assert got["preemptions"]["value"] == 0
    if cell == CELL:
        assert got["compiles_in_window.batch"]["value"] == 0
        # the counter-based metrics read on the CPU; the trace-based ones
        # need a device plane and are left out of a CPU line
        assert got["prefix_hit_share.ep16"]["value"] > 95
        assert got["dsa_rows_skipped_share"]["value"] > 50  # top 24 of ~1.3 k
        # the tiny preset holds every expert: no fifth counter, nothing to read
        assert "moe_routed_here_share" not in got
        assert got["moe_expert_load_max_over_mean.ep16"]["value"] > 0
    else:  # (the rehearsal's two burst lengths do not reach every tiny shape)
        assert got["compiles_in_window.batch"]["value"] >= 0
        assert got["prefix_hit_share.batch"]["value"] < 50

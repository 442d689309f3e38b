"""The Laguna-XS.2 cell, its configuration, its traffic and every new reader
resolve by name; ``opsbytes_laguna`` against the hand arithmetic of ISSUE
45 (8 192 and 12 288 B a token, 3 869.8 M parameters, 7.21 GiB); the readers
on a hand-made context, on another configuration's, and on a program
without the counters. Membership in the manifest's lists is asserted, never
position or equality: a later PR appends behind this one."""
import json
import os
import sys

import numpy as np
import pytest

from benchmarks.harness import laguna_trace, lfm2_trace, opsbytes_laguna, registry
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "laguna-xs.2-l5.agent-sessions"
CONFIG = "laguna-xs.2-bf16-l5"
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer", "max_position_embeddings"]
NEW_METRICS = {
    "decode_attn_hbm_share.full_g6", "decode_attn_hbm_share.swa512_g8",
    "chunk_attn_mxu_share.g6g8", "moe_gmm_hbm_share.x256",
    "moe_share_of_decode_step.x256", "moe_expert_load_max_over_mean.x256",
    "tail_resume_share", "tail_recomputed_share", "prefix_hit_share.laguna",
    "prefill_share_of_busy.laguna",
}
SHARED = {
    "decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
    "decode_step_device_ms.batch", "compiles_in_window.batch",
    "device_idle_share.batch", "step_host_exposed_ms.batch",
    "between_steps_idle_ms.batch", "idle_attributed_share.batch",
    "decode_prebuilt_share.batch", "window_slots_held_per_row",
    "window_kv_released_share", "kv_slots_used_peak_share.full",
    "kv_slots_used_peak_share.window",
}
# Lists that an existing benchmark test pins to the cells they had are left
# as they were (PERF.md section 7): this cell is not on them.
PINNED = {
    "prefill_wall_ms_per_ktok.batch", "prefill_pad_share.batch",
    "prefill_share_of_window.batch", "decode_step_wall_ms.batch",
    "stalled_s_in_window.batch", "gc_pause_ms_in_window.batch",
    "compile_s_in_window.batch", "prefill_host_ms_per_wave.batch",
    "state_resume_share", "state_resume_share.ssm",
}


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows if r["name"] == "Laguna-XS.2")


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "laguna-xs.2-l5"
    args = r["config"]["server_args"]
    assert args[:2] == ["--page-size", "16"] and args[-2:] == ["--max-batch", "64"]
    # no new serve flag: the window group is derived
    assert {a for a in args if str(a).startswith("--")} == {
        "--page-size", "--kv-slots", "--max-batch"}
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "laguna-xs.2-l5"]
    names = {m["name"] for m in r["per_layer"]}
    assert NEW_METRICS | SHARED <= names
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in NEW_METRICS | SHARED:
        assert callable(registry.load_module("layer_metrics", name).read), name


def test_the_manifest_lists_the_cell():
    man = registry.manifest()
    cells = [w["name"] for w in man["workloads"]]
    assert CELL in cells and len(cells) == len(set(cells)) and len(cells) >= 9
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and 1 <= len(entry["why"]) <= 200
    assert entry["traffic"] == "agent-sessions"  # the file the benchmark had
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["source"] == SOURCE and conf["reduced"] == REDUCED
    assert 1 <= len(conf["why"]) <= 200 and conf["why"].isprintable()
    layers = {m["layer"] for m in man["per_layer"] if m["name"] not in NEW_METRICS}
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
            assert m["layer"] in layers  # a layer PERF.md already names
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            # no share of a roofline or of a peak by that name: a later
            # claim in another cell owes this cell's readers nothing
            assert "roofline" not in m["name"] and "mfu" not in m["name"]
        if m["name"] in SHARED:
            assert CELL in m["workloads"]
        if m["name"] in PINNED:
            assert CELL not in m["workloads"]
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(man, indent=1)) < 64 * 1024


def test_the_configuration_file_holds_the_sources_numbers():
    config = registry.resolve(CELL)["config"]
    source = _catalog_row()
    assert config["source"] == source["source_url"] == SOURCE
    # every key of the catalog's row under the same key; what differs is listed
    for key, want in source["config"].items():
        if key in REDUCED:
            assert config[key] != want and key in config["reduced"], key
        else:
            assert config[key] == want, key
    assert set(config["reduced"]) == set(REDUCED)
    assert config["num_hidden_layers"] == 5 == len(config["layer_types"])
    assert config["layer_types"] == source["config"]["layer_types"][:5]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["max_position_embeddings"] == 16384
    assert config["published"]["num_hidden_layers"] == 40
    for group in ("assumed", "deployment", "distorts", "aot", "reference_check",
                  "probe", "rehearsal"):
        assert config[group], group
    for piece in ("gating", "router", "q_k_norm", "mtp_head"):
        assert config["assumed"][piece], piece
    assert config["probe"]["prompt_len"] % config["page_size"]
    # the program's preset is this file
    from radixmesh_tpu.engine.engine import pool_groups
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.n_layers, cfg.hidden, cfg.intermediate) == (5, 2048, 8192)
    assert (cfg.n_kv_heads, cfg.head_dim) == (8, 128)
    assert [cfg.heads_of(t) for t in cfg.layer_types] == (
        config["num_attention_heads_per_layer"])
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_intermediate) == (
        256, 8, 512)
    assert cfg.n_shared_experts * cfg.moe_intermediate == (
        config["shared_expert_intermediate_size"])
    assert cfg.routed_scaling_factor == config["moe_routed_scaling_factor"] == 2.5
    assert cfg.first_k_dense == 1 and cfg.attn_gate is config["gating"] is True
    assert cfg.vocab_size == config["vocab"] == 100352 and not cfg.tie_embeddings
    assert cfg.sliding_window == config["sliding_window"] == 512
    assert cfg.max_seq_len == config["max_position_embeddings"]
    rope = {k: dict(v) for k, v in cfg.rope_by_type}
    for kind in ("full_attention", "sliding_attention"):
        for key, want in config["rope_parameters"][kind].items():
            assert rope[kind].get(key, 1) == want, (kind, key)
    slots = int(config["server_args"][3])
    batch = config["serving"]["max_batch"]
    assert cfg.kv_pool_shape(slots) == (2, 2, 8, slots, 128)
    groups = pool_groups(cfg, slots, batch, 16)
    assert groups["window_shape"][:3] == (2, 3, 8)
    assert groups["state_shape"] is None
    assert get_config(config["rehearsal"]["preset"]).heads_by_type


def test_the_reference_reads_the_file_as_it_reads_the_preset():
    """``tools/ref_compare_laguna.py`` hands the reference the FILE's keys
    (the source's names) on the chip and the program's preset in a
    rehearsal: both give the same forward pass."""
    from benchmarks.reference import laguna as ref
    from radixmesh_tpu.models import get_config

    config = registry.resolve(CELL)["config"]
    from_file, from_preset = ref.spec_of(config), ref.spec_of(
        get_config(config["preset"]))
    rope_f, rope_p = from_file.pop("rope"), from_preset.pop("rope")
    assert from_file == from_preset
    for kind in rope_p:
        assert ref.rope_table(128, rope_f[kind])[1:] == ref.rope_table(
            128, rope_p[kind])[1:]
        np.testing.assert_array_equal(ref.rope_table(128, rope_f[kind])[0],
                                      ref.rope_table(128, rope_p[kind])[0])
    assert ref.rope_table(128, rope_f["full_attention"])[2] == 64


def test_opsbytes_against_a_hand_count():
    config = registry.resolve(CELL)["config"]
    ob = opsbytes_laguna
    assert ob.heads_of(config, "full_attention") == 48
    assert ob.heads_of(config, "sliding_attention") == 64
    assert ob.layer_kv_bytes(config) == 2 * 8 * 128 * 2 == 4096
    assert ob.full_kv_bytes_per_token(config) == 2 * 4096 == 8192
    assert ob.window_kv_bytes_per_token(config) == 3 * 4096 == 12288
    # attention + gate: W_q, W_k, W_v, W_o and [2048, heads]
    assert ob.attention_params(config, "full_attention") == (
        2048 * (48 * 128 + 2 * 1024) + 48 * 128 * 2048 + 2048 * 48)
    assert ob.attention_params(config, "sliding_attention") == (
        2048 * (64 * 128 + 2 * 1024) + 64 * 128 * 2048 + 2048 * 64)
    assert ob.expert_params(config) == 3 * 2048 * 512 == 3145728
    assert ob.expert_bytes(config) == 6291456
    assert ob.shared_expert_params(config) == 3145728
    assert ob.sparse_layers(config) == 4
    # ISSUE 45: 411.0 + 79.8 + 838.4 + 3 x 846.9 = 3 869.9 M = 7.21 GiB
    by_hand = 411.04e6 + 79.79e6 + 838.43e6 + 3 * 846.86e6
    assert ob.parameters(config) == pytest.approx(by_hand, rel=1e-4)
    assert ob.parameters(config) == 3869835264
    assert ob.parameters(config) * 2 / 2 ** 30 == pytest.approx(7.21, abs=0.005)
    # a decoded token at 6 k: all of it in 2 layers, a window of it in 3
    assert ob.full_kv_bytes_needed(config, 6000) == 6000 * 8192
    assert ob.window_kv_bytes_needed(config, 6000) == 512 * 12288
    assert ob.window_kv_bytes_needed(config, 100) == 100 * 12288
    # 64 rows x top-8 over 256 experts: 221 hit (ISSUE 45: 256 (1 - e^-2))
    assert ob.expected_experts_hit(config, 64) == pytest.approx(221.5, abs=0.5)
    # what a decode step streams beside the routed experts: ~0.9 GB
    # (172.6 M attention and gates + 50.3 M dense FFN + 2.1 M routers + 12.6 M
    # shared experts + 205.5 M head) x 2 B
    assert ob.decode_other_weight_bytes(config) == 886177792
    # the gate: a [2048, heads] product and one multiply an output element
    assert ob.gate_flops_per_token(config, "full_attention") == (
        2 * 2048 * 48 + 48 * 128)
    # a chunk's attention: causal pairs, a window's worth on a sliding layer
    assert ob.visible_pairs(0, 4) == 1 + 2 + 3 + 4
    assert ob.visible_pairs(1000, 2) == 1001 + 1002
    assert ob.visible_pairs(1000, 2, 512) == 2 * 512
    assert ob.visible_pairs(10, 3, 512) == 11 + 12 + 13
    assert ob.chunk_attn_flops_needed(config, 1000, 2) == 4 * 128 * (
        2 * 48 * 2003 + 3 * 64 * 1024)
    # the program stores exactly these, and counts the same parameters
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert np.prod(cfg.kv_pool_shape(16)) * 2 // 16 == 8192
    assert np.prod(cfg.window_pool_shape(16)) * 2 // 16 == 12288
    assert config["aot"]["parameters"] - ob.parameters(config) == (
        11 * 2048 + 4 * 256)  # the norms and the routers' selection bias


def test_the_traffic_is_the_file_the_benchmark_had():
    r = registry.resolve(CELL)
    p = r["traffic"]["params"]
    lfm2 = registry.resolve("lfm2-24b-a2b-ep8.agent-sessions")["traffic"]
    assert r["traffic"] == lfm2  # the same file, unedited
    assert r["traffic"]["generator"] == "sessions" and p["mix_seed"] == 39001
    assert p["system_prompts"] == 8 and p["zipf_s"] == 1.0
    assert p["system_len"] == {"dist": "uniform", "min": 2048, "max": 8192,
                               "quantum": 2048}
    assert p["turns"] == {"dist": "geometric", "mean": 6, "min": 2, "max": 12}
    assert p["turn_tokens"] == {"dist": "uniform", "min": 64, "max": 512, "quantum": 64}
    assert p["max_tokens"] == {"dist": "uniform", "min": 64, "max": 192, "quantum": 32}
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 128}
    assert r["cell"]["min_cached_share"] == 0.9
    assert r["cell"]["limits"] == registry.resolve(
        "lfm2-24b-a2b-ep8.agent-sessions")["cell"]["limits"]
    bursts = r["cell"]["warmup"]["bursts"]
    assert bursts[0]["n"] == 1 and "blocker" not in bursts[0]
    assert {b["prefix_len"] for b in bursts} == {2048}
    # every bucket of new tokens has its bursts up to the rows a wave holds
    by_chunk = {64: 64, 128: 32, 256: 16, 512: 8}
    for b in bursts[1:]:
        assert max(b["n"]) == by_chunk[int(b["new_tokens"])], b


def _ctx(config, name=CELL):
    def snap(k):
        return {"state": {
            "engine": {"decode_steps": k * 10, "finished": k * 2,
                       "prefill": {"waves": k * 4, "tokens_new": k * 800}},
            "pool": {"page_size": 16},
            "kv": {"write": {"launches": k * 5, "pages": k * 100, "edge_pages": 0},
                   "groups": {"full": {"slots": 1000, "used_peak": 900},
                              "window": {"slots": 2000, "used_peak": 500}}},
            "window": {"held_peak_row": 544, "pages_released": k * 30,
                       "slots_written": k * 640},
            "moe": {"decode": {"experts_hit": k * 8000, "max_load": k * 200,
                               "assignments": k * 20480, "layer_launches": k * 40}},
            "tail": {"matches": k * 50, "resumes": k * 49,
                     "kv_matched_tokens": k * 50000, "resumed_tokens": k * 49000,
                     "recomputed_tokens": k * 1000}},
            "stats": {"prompt_tokens": k * 52000, "cached_tokens": k * 49000,
                      "generated_tokens": k * 640}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_decode_fused_mh": {"count": 50, "seconds": 0.05},
                         "radixmesh_moe_gmm_decode": {"count": 40, "seconds": 0.12},
                         "radixmesh_chunk_attn": {"count": 20, "seconds": 0.02}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.3},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.1}}}

    class Rec:
        tag, ok, n_prompt, resent_tokens = "load", True, 4256, 4005
        token_t = [0.5 + 0.01 * i for i in range(65)]

    return {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
            "cell": {"name": name}, "window": (0.0, 10.0), "records": [Rec()] * 10,
            "kv_bytes_per_token": 20480,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "before": snap(0), "after": snap(1)}


def test_new_readers_read_a_hand_made_context(monkeypatch):
    config = registry.resolve(CELL)["config"]
    ctx = _ctx(config)
    seconds = {"full_attn": 0.04, "window_attn": 0.01}
    monkeypatch.setattr(
        laguna_trace, "of_run", lambda c, part, module="jit_decode_step": {
            "executions": 10, "seconds": seconds[part], "events": 20})

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    # 10 requests x 64 tokens behind their first, contexts 4257..4320, over
    # 10 steps: x 8 192 B in 4 ms of kernel a step
    contexts = 10 * sum(4256 + i for i in range(1, 65))
    assert read("decode_attn_hbm_share.full_g6") == pytest.approx(
        100 * contexts * 8192 / 10 / 0.004 / 819e9)
    assert read("decode_attn_hbm_share.swa512_g8") == pytest.approx(
        100 * 640 * 512 * 12288 / 10 / 0.001 / 819e9)
    # 10 requests of 4 256 tokens, 4 000 of them resumed, over 5 launches,
    # 5 ms of kernel a traced launch
    flops = 10 * opsbytes_laguna.chunk_attn_flops_needed(config, 4000, 256)
    assert read("chunk_attn_mxu_share.g6g8") == pytest.approx(
        100 * flops / 5 / 0.005 / 197e12)
    assert read("moe_gmm_hbm_share.x256") == pytest.approx(
        100 * 8000 * 6291456 / 10 / 0.012 / 819e9)
    assert read("moe_share_of_decode_step.x256") == pytest.approx(40.0)
    assert read("moe_expert_load_max_over_mean.x256") == pytest.approx(
        200 / (20480 / 256))
    assert read("tail_resume_share") == pytest.approx(98.0)
    assert read("tail_recomputed_share") == pytest.approx(2.0)
    assert read("prefix_hit_share.laguna") == pytest.approx(100 * 49 / 52)
    assert read("prefill_share_of_busy.laguna") == pytest.approx(5.0)
    for name in NEW_METRICS - {"moe_expert_load_max_over_mean.x256"}:
        assert 0 < read(name) < 105, name  # a share of a peak stays under it


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("lfm2-24b-a2b-ep8.agent-sessions", "mellum2-12b-l12.batch-longtail",
                  "phi-4-mini-flash.reasoning-sessions", "qwen2-7b.batch-unshared"):
        ctx = _ctx(registry.resolve(other)["config"], name=other)
        for name in NEW_METRICS - {"tail_resume_share", "tail_recomputed_share"}:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    bare = {"state": {"engine": {"decode_steps": 10, "finished": 3,
                                 "prefill": {"waves": 0, "tokens_new": 0}},
                      "pool": {"page_size": 16}, "kv": {"index_plane_bytes": 0}},
            "stats": {"prompt_tokens": 0, "cached_tokens": 0, "generated_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": mine,
           "cell": {"name": CELL}, "before": bare, "after": bare,
           "window": (0.0, 1.0), "records": [], "kv_bytes_per_token": 20480}
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_the_launches_are_told_apart_in_a_hand_made_capture():
    loaded = {
        "modules": [("jit_decode_step(7)", 0, 1000), ("jit_sample_tokens(8)", 1000, 100),
                    ("jit_decode_step(7)", 2000, 1000)],
        "scoped": [(100, 50), (120, 10), (400, 30), (1020, 40), (2500, 100)],
    }
    assert lfm2_trace.scope_seconds(loaded) == {
        "executions": 2, "events": 4, "seconds": pytest.approx(180 / 1e9)}
    config = registry.resolve(CELL)["config"]
    assert laguna_trace.is_cell({"config": config})
    # where a capture carries no framework names: the array a launch aliases
    full = laguna_trace.shape_of(config, "full_attn")
    window = laguna_trace.shape_of(config, "window_attn")
    text = "(f32[64,8,8,128], bf16[2,%d,8,45056,16,128]) custom-call"
    assert full.search(text % 2) and not full.search(text % 3)
    assert window.search(text % 3) and not window.search(text % 2)
    # groups of equally many layers cannot be told apart by shape: nothing
    even = {**config, "layer_types": ["full_attention", "sliding_attention"]}
    assert laguna_trace.shape_of(even, "full_attn") is None
    for other in ("qwen2-7b.batch-unshared", "phi-4-mini-flash.reasoning-sessions"):
        assert not laguna_trace.is_cell({"config": registry.resolve(other)["config"]})


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147484045", "--seconds", "12", "--trace", "2", "--rehearse-cpu"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and "setup_s" in got
    assert got["preemptions"]["value"] == 0
    # the counter-based metrics read on the CPU; the trace-based ones need a
    # device plane and are left out of a CPU line
    assert 0 < got["window_slots_held_per_row"]["value"] <= 16 + 2 * 16
    assert got["window_kv_released_share"]["value"] > 0
    assert 0 < got["kv_slots_used_peak_share.window"]["value"] < 100
    assert got["tail_resume_share"]["value"] >= 98
    assert got["tail_recomputed_share"]["value"] <= 2
    assert got["prefix_hit_share.laguna"]["value"] > 50
    assert "decode_attn_hbm_share.full_g6" not in got

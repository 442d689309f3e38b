"""The Phi-4-mini-flash cell, its configuration, its traffic and every new
reader resolve by name; ``opsbytes_phi4flash`` against the hand arithmetic
of ISSUE 41 (5 120 and 40 960 B a token, 3 225 600 B a snapshot, 3.85 B
parameters); the readers on a hand-made context, on another
configuration's, and on a program without the counters. Membership in the
manifest's lists is asserted, never position or equality: a later PR
appends behind this one."""
import json
import os
import sys

import numpy as np
import pytest

from benchmarks.harness import (
    lfm2_trace, opsbytes_phi4flash, phi4flash_trace, registry,
)
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "phi-4-mini-flash.reasoning-sessions"
CONFIG = "phi-4-mini-flash-reasoning-bf16"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
NEW_METRICS = {
    "shared_kv_attn_share_of_decode_step", "decode_attn_hbm_share.shared_kv",
    "decode_attn_hbm_share.swa512", "ssm_share_of_decode_step",
    "ssm_decode_hbm_share", "ssm_scan_hbm_share", "window_slots_held_per_row",
    "window_kv_released_share", "kv_slots_used_peak_share.full",
    "kv_slots_used_peak_share.window", "state_resume_share.ssm",
    "state_slots_used_peak_share.ssm", "state_host_ms_per_request.ssm",
    "prefix_hit_share.phi4", "prefill_share_of_busy.phi4",
}
SHARED = {
    "decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
    "decode_step_device_ms.batch", "compiles_in_window.batch",
    "device_idle_share.batch", "step_host_exposed_ms.batch",
    "between_steps_idle_ms.batch", "idle_attributed_share.batch",
    "decode_prebuilt_share.batch",
}
# Lists that an existing benchmark test pins to the cells they had are left
# as they were (PERF.md section 7): this cell is not on them.
PINNED = {
    "prefill_wall_ms_per_ktok.batch", "prefill_pad_share.batch",
    "prefill_share_of_window.batch", "decode_step_wall_ms.batch",
    "stalled_s_in_window.batch", "gc_pause_ms_in_window.batch",
    "compile_s_in_window.batch", "prefill_host_ms_per_wave.batch",
    "state_resume_share", "state_slots_used_peak_share",
    "state_host_ms_per_request",
}


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "phi-4-mini-flash-reasoning"
    args = r["config"]["server_args"]
    assert args[:2] == ["--page-size", "16"] and args[-2:] == ["--max-batch", "64"]
    # no new serve flag: the window group and the state slots are derived
    assert {a for a in args if str(a).startswith("--")} == {
        "--page-size", "--kv-slots", "--max-batch"}
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model",
        "phi-4-mini-flash-reasoning"]
    names = {m["name"] for m in r["per_layer"]}
    assert NEW_METRICS | SHARED <= names
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in NEW_METRICS | SHARED:
        assert callable(registry.load_module("layer_metrics", name).read), name


def test_the_manifest_lists_the_cell():
    man = registry.manifest()
    cells = [w["name"] for w in man["workloads"]]
    assert CELL in cells and len(cells) == len(set(cells))
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and 1 <= len(entry["why"]) <= 200
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["source"] == SOURCE
    assert conf["reduced"] == ["max_position_embeddings"]
    assert 1 <= len(conf["why"]) <= 200 and conf["why"].isprintable()
    layers = {m["layer"] for m in man["per_layer"] if m["name"] not in NEW_METRICS}
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert CELL in m["workloads"] and m["moves"] == "tokens_per_s"
            assert m["layer"] in layers  # a layer PERF.md already names
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in SHARED:
            assert CELL in m["workloads"]
        if m["name"] in PINNED:
            assert CELL not in m["workloads"]
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(man, indent=1)) < 64 * 1024


def test_the_configuration_file_holds_the_sources_numbers():
    config = registry.resolve(CELL)["config"]
    entry = next(c for c in registry.manifest()["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) == set(config["published"])
    # every number of the catalog's row, under the same key
    for key, want in {
            "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
            "intermediate_size": 10240, "layer_norm_eps": 1e-05,
            "mb_per_layer": 2, "model_type": "phi4flash",
            "num_attention_heads": 40, "num_hidden_layers": 32,
            "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
            "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
            "vocab_size": 200064}.items():
        assert config[key] == want and key not in entry["reduced"], key
    assert config["max_position_embeddings"] == 16384
    assert config["published"]["max_position_embeddings"] == 262144
    for group in ("assumed", "assumed_sizes", "deployment", "aot",
                  "reference_check", "probe", "rehearsal"):
        assert config[group], group
    assert config["probe"]["prompt_len"] % config["page_size"]
    # the program's preset is this file
    from radixmesh_tpu.engine.engine import pool_groups
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.n_layers, cfg.hidden, cfg.intermediate) == (32, 2560, 10240)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (40, 20, 64)
    assert cfg.vocab_size == config["vocab"] == 200064
    assert cfg.sliding_window == 512 and cfg.tie_embeddings
    sizes = config["assumed_sizes"]
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank) == (
        sizes["d_inner"], sizes["d_state"], sizes["d_conv"], sizes["dt_rank"])
    assert cfg.max_seq_len == config["max_position_embeddings"]
    slots = int(config["server_args"][3])
    batch = config["serving"]["max_batch"]
    assert cfg.kv_pool_shape(slots) == (2, 1, 10, slots, 128)
    groups = pool_groups(cfg, slots, batch, 16)
    assert groups["window_shape"][:3] == (2, 8, 10)
    assert groups["state_shape"][0] == 9 and groups["state_shape"][2:] == (16, 5120)
    assert get_config(config["rehearsal"]["preset"]).has_ssm


def test_opsbytes_against_the_issues_arithmetic():
    config = registry.resolve(CELL)["config"]
    ob = opsbytes_phi4flash
    assert ob.full_kv_bytes_per_token(config) == 1 * 2 * 20 * 64 * 2 == 5120
    assert ob.window_kv_bytes_per_token(config) == 8 * 2 * 20 * 64 * 2 == 40960
    assert ob.state_bytes(config) == 9 * (5120 * 16 * 4 + 3 * 5120 * 2) == 3225600
    assert ob.kv_readers(config) == 8
    assert ob.mamba_params(config) == pytest.approx(41.24e6, rel=1e-3)
    assert ob.attention_params(config) == 2560 * 5120 + 2560 * 2560
    assert ob.gmu_params(config) == 2 * 2560 * 5120
    assert ob.cross_params(config) == 2 * 2560 * 2560
    assert ob.mlp_params(config) == 3 * 2560 * 10240
    # ISSUE 41: 9 x 119.9 + 9 x 98.3 + 7 x 104.9 + 7 x 91.8 + 512 = 3.85 B
    by_hand = (9 * 119.9e6 + 9 * 98.3e6 + 7 * 104.9e6 + 7 * 91.8e6 + 512.2e6)
    assert ob.parameters(config) == pytest.approx(by_hand, rel=2e-3)
    assert ob.parameters(config) == pytest.approx(3.85e9, rel=5e-3)
    assert ob.parameters(config) * 2 / 2 ** 30 == pytest.approx(7.17, abs=0.02)
    # a decoded token at 3.8 k: 8 readers of one layer's rows, a window of 8
    assert ob.shared_kv_bytes_needed(config, 3800) == 3800 * 5120 * 8
    assert ob.window_kv_bytes_needed(config, 3800) == 512 * 40960
    assert ob.window_kv_bytes_needed(config, 100) == 100 * 40960
    step = ob.ssm_decode_bytes_per_step(config, 64)
    assert step == (9 * ob.mamba_params(config) + 7 * ob.gmu_params(config)) * 2 + (
        2 * 64 * 3225600)
    assert ob.ssm_scan_bytes_per_token(config) == 4 * (3 * 5120 + 32)
    # the program stores exactly these
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert np.prod(cfg.kv_pool_shape(16)) * 2 // 16 == 5120
    assert np.prod(cfg.window_pool_shape(16)) * 2 // 16 == 40960
    assert cfg.state_slot_bytes() == ob.state_bytes(config)
    assert (np.prod(cfg.state_shape(4)) * 4 + np.prod(cfg.conv_state_shape(4)) * 2
            ) // 4 == ob.state_bytes(config)


def test_the_traffic_is_the_issues():
    r = registry.resolve(CELL)
    p = r["traffic"]["params"]
    assert r["traffic"]["generator"] == "sessions" and p["mix_seed"] == 41001
    assert p["system_prompts"] == 8 and p["zipf_s"] == 1.0 and p["pool_sessions"] == 512
    assert p["system_len"] == {"dist": "uniform", "min": 1024, "max": 2048,
                               "quantum": 512}
    assert p["turns"] == {"dist": "geometric", "mean": 4, "min": 2, "max": 8}
    assert p["turn_tokens"] == {"dist": "uniform", "min": 64, "max": 256, "quantum": 64}
    assert p["max_tokens"] == {"dist": "uniform", "min": 512, "max": 1024,
                               "quantum": 128}
    assert "think_s" not in p
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 128}
    assert r["cell"]["min_cached_share"] == 0.9
    assert r["cell"]["warmup"]["traffic_s"] == 30
    vocab, page = r["config"]["vocab"], r["config"]["page_size"]
    plan = r["generator"].plan(p, r["cell"]["loop"], vocab, 5, horizon_s=111)
    assert set(plan.system_lens.tolist()) <= {1024, 1536, 2048}
    assert sorted(set(plan.user_lens)) == [64, 128, 192, 256]
    assert sorted(set(plan.reply_lens)) == list(range(512, 1025, 128))
    shapes = plan.shapes()
    turns = [len(s["turn_tokens"]) for s in shapes]
    assert min(turns) == 2 and max(turns) == 8 and 3 < sum(turns) / len(turns) < 5
    ends = [s["prefix_len"] + sum(s["turn_tokens"]) + sum(s["max_tokens"])
            for s in shapes]
    assert 1100 < min(ends) and max(ends) < 12300  # the issue's 1.1 k-12.3 k
    census = plan.census(plan.pool, page)
    # a first turn resumes exactly at its preamble's end, where the warm-up's
    # publish left a snapshot; a later turn re-sends a context that ends on
    # no chunk boundary of its own: new tokens 64k + 0 | 16
    assert set(plan.system_lens.tolist()) <= {reuse for reuse, _ in census}
    assert {new for _, new in census} <= {64 * k + r for k in range(1, 5)
                                          for r in (0, 16)}
    bursts = r["cell"]["warmup"]["bursts"]
    assert bursts[0]["n"] == 1 and "blocker" not in bursts[0]
    assert {b["prefix_len"] for b in bursts} == {bursts[0]["prefix_len"]}
    assert bursts[0]["prefix_len"] in {reuse for reuse, _ in census}
    # every bucket of new tokens has its bursts up to the rows a wave holds
    by_chunk = {64: 64, 128: 32, 256: 16, 512: 8}
    for b in bursts[1:]:
        chunk = 1 << (int(b["new_tokens"]) - 1).bit_length()
        assert max(b["n"]) == by_chunk[chunk], b


def _ctx(config, name=CELL):
    def snap(k):
        spans = {"rm.state.restore": {"n": k * 5, "s": k * 0.001},
                 "rm.state.snapshot": {"n": k * 10, "s": k * 0.003}}
        return {"state": {
            "engine": {"decode_steps": k * 10, "finished": k * 2, "spans": spans,
                       "prefill": {"waves": k * 4, "tokens_new": k * 800}},
            "pool": {"page_size": 16},
            "kv": {"groups": {"full": {"slots": 1000, "used_peak": 900},
                              "window": {"slots": 2000, "used_peak": 500}}},
            "window": {"held_peak_row": 544, "pages_released": k * 30,
                       "slots_written": k * 640},
            "state": {"slots": 256, "slots_used_peak": 64 + k * 64,
                      "kv_matched_tokens": k * 50000, "resumed_tokens": k * 49000}},
            "stats": {"prompt_tokens": k * 52000, "cached_tokens": k * 49000,
                      "generated_tokens": k * 640}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_decode_fused_mh": {"count": 160, "seconds": 0.2},
                         "radixmesh_ssm_scan": {"count": 36, "seconds": 0.0036}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.3},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.1}}}

    class Rec:
        n_prompt, token_t = 4000, [0.5 + 0.01 * i for i in range(65)]

    return {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
            "cell": {"name": name}, "window": (0.0, 10.0), "records": [Rec()] * 10,
            "kv_bytes_per_token": 163840,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "before": snap(0), "after": snap(1)}


def test_new_readers_read_a_hand_made_context(monkeypatch):
    config = registry.resolve(CELL)["config"]
    ctx = _ctx(config)
    seconds = {"shared_kv_attn": 0.15, "window_attn": 0.02, "ssm": 0.03}
    monkeypatch.setattr(
        phi4flash_trace, "of_run", lambda c, part, module="jit_decode_step": {
            "executions": 10, "seconds": seconds[part], "events": 80})

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    assert read("shared_kv_attn_share_of_decode_step") == pytest.approx(50.0)
    assert read("ssm_share_of_decode_step") == pytest.approx(10.0)
    # 10 requests x 64 tokens behind their first, contexts 4001..4064, over
    # 10 steps: x 5 120 B x 8 readers in 15 ms of kernel a step
    contexts = 10 * sum(4000 + i for i in range(1, 65))
    assert read("decode_attn_hbm_share.shared_kv") == pytest.approx(
        100 * contexts * 5120 * 8 / 10 / 0.015 / 819e9)
    assert read("decode_attn_hbm_share.swa512") == pytest.approx(
        100 * 640 * 512 * 40960 / 10 / 0.002 / 819e9)
    need = opsbytes_phi4flash.ssm_decode_bytes_per_step(config, 64)
    assert read("ssm_decode_hbm_share") == pytest.approx(100 * need / 0.003 / 819e9)
    # 200 real tokens a wave x 61.6 KB in 0.1 ms a launch
    assert read("ssm_scan_hbm_share") == pytest.approx(
        100 * 200 * 4 * (3 * 5120 + 32) / 1e-4 / 819e9)
    assert read("window_slots_held_per_row") == 544
    assert read("window_kv_released_share") == pytest.approx(75.0)
    assert read("kv_slots_used_peak_share.full") == pytest.approx(90.0)
    assert read("kv_slots_used_peak_share.window") == pytest.approx(25.0)
    assert read("state_resume_share.ssm") == pytest.approx(98.0)
    assert read("state_slots_used_peak_share.ssm") == pytest.approx(50.0)
    assert read("state_host_ms_per_request.ssm") == pytest.approx(2.0)
    assert read("prefix_hit_share.phi4") == pytest.approx(100 * 49 / 52)
    assert read("prefill_share_of_busy.phi4") == pytest.approx(5.0)
    for name in NEW_METRICS - {"window_slots_held_per_row"}:
        assert 0 < read(name) < 105, name  # a share of a peak stays under it


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("lfm2-24b-a2b-ep8.agent-sessions", "mellum2-12b-l12.batch-longtail",
                  "qwen2-7b.batch-unshared"):
        ctx = _ctx(registry.resolve(other)["config"], name=other)
        for name in NEW_METRICS - {
                "window_slots_held_per_row", "window_kv_released_share",
                "kv_slots_used_peak_share.full", "kv_slots_used_peak_share.window"}:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    bare = {"state": {"engine": {"decode_steps": 10, "finished": 3,
                                 "prefill": {"waves": 0, "tokens_new": 0}},
                      "pool": {"page_size": 16}, "kv": {"index_plane_bytes": 0}},
            "stats": {"prompt_tokens": 0, "cached_tokens": 0, "generated_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": mine,
           "cell": {"name": CELL}, "before": bare, "after": bare,
           "window": (0.0, 1.0), "records": [], "kv_bytes_per_token": 163840}
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_METRICS - {"prefix_hit_share.phi4"}:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_the_scopes_are_found_in_a_hand_made_capture():
    loaded = {
        "modules": [("jit_decode_step(7)", 0, 1000), ("jit_sample_tokens(8)", 1000, 100),
                    ("jit_decode_step(7)", 2000, 1000)],
        "scoped": [(100, 50), (120, 10), (400, 30), (1020, 40), (2500, 100)],
    }
    assert lfm2_trace.scope_seconds(loaded) == {
        "executions": 2, "events": 4, "seconds": pytest.approx(180 / 1e9)}
    config = registry.resolve(CELL)["config"]
    assert phi4flash_trace.is_cell({"config": config})
    # where a capture carries no framework names: a shape that is the part's
    ssm = phi4flash_trace.shape_of(config, "ssm")
    assert ssm.search("%fusion.7 = bf16[64,1,5120]{2,1,0} fusion(...)")
    assert ssm.search("%dot.3 = f32[64,1,192] dot(...)")
    assert not ssm.search("%fusion.9 = bf16[64,1,10240] fusion(...)")
    assert not ssm.search("%dot.1 = bf16[64,1,2560] dot(...)")
    full = phi4flash_trace.shape_of(config, "shared_kv_attn")
    window = phi4flash_trace.shape_of(config, "window_attn")
    text = "(f32[64,10,4,128], bf16[2,%d,10,32768,16,128]) custom-call"
    assert full.search(text % 1) and not full.search(text % 8)
    assert window.search(text % 8) and not window.search(text % 1)
    assert not phi4flash_trace.is_cell(
        {"config": registry.resolve("qwen2-7b.batch-unshared")["config"]})


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147484041", "--seconds", "12", "--trace", "2", "--rehearse-cpu"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and "setup_s" in got
    assert got["preemptions"]["value"] == 0
    # the counter-based metrics read on the CPU; the trace-based ones need a
    # device plane and are left out of a CPU line
    assert 0 < got["window_slots_held_per_row"]["value"] <= 32 + 2 * 16
    assert got["window_kv_released_share"]["value"] > 0
    assert 0 < got["kv_slots_used_peak_share.window"]["value"] <= 100
    assert 0 < got["state_slots_used_peak_share.ssm"]["value"] <= 100
    assert got["state_host_ms_per_request.ssm"]["value"] > 0
    assert "ssm_share_of_decode_step" not in got

"""The LFM2 cell, its configuration, its traffic and every new reader
resolve by name; ``opsbytes_lfm2`` against the hand arithmetic of ISSUE 39
(20 480 B a token, 240 KiB a snapshot, 3.76 B parameters); the readers on a
hand-made context, on another configuration's, and on a program without the
counters. Membership in the manifest's lists is asserted, never position: a
later PR appends behind this one."""
import json
import os
import sys

import pytest

from benchmarks.harness import lfm2_trace, opsbytes_lfm2, registry
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "lfm2-24b-a2b-ep8.agent-sessions"
CONFIG = "lfm2-24b-a2b-bf16-ep8"
NEW_METRICS = {
    "conv_share_of_decode_step", "conv_decode_hbm_share",
    "decode_attn_hbm_share.hd64", "state_resume_share",
    "state_slots_used_peak_share", "state_host_ms_per_request",
    "moe_gmm_hbm_share.ep8", "moe_share_of_decode_step.ep8",
    "moe_expert_load_max_over_mean.ep8", "moe_routed_here_share.ep8",
    "prefix_hit_share.ep8", "prefill_share_of_busy.ep8",
}
SHARED = {
    "decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
    "decode_step_device_ms.batch", "compiles_in_window.batch",
    "device_idle_share.batch", "step_host_exposed_ms.batch",
    "between_steps_idle_ms.batch", "idle_attributed_share.batch",
    "decode_prebuilt_share.batch",
}
# Lists that an existing benchmark test pins to the cells they had
# (``test_window_account.py``, ``test_prefill_host_metric.py``: the five
# closed loops of PR 35 / 36) are left as they were: this cell is not on them.
PINNED = {
    "prefill_wall_ms_per_ktok.batch", "prefill_pad_share.batch",
    "prefill_share_of_window.batch", "decode_step_wall_ms.batch",
    "stalled_s_in_window.batch", "gc_pause_ms_in_window.batch",
    "compile_s_in_window.batch", "prefill_host_ms_per_wave.batch",
}


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "lfm2-24b-a2b-ep8"
    assert r["config"]["server_args"][:2] == ["--page-size", "16"]
    assert r["config"]["server_args"][-2:] == ["--max-batch", "32"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "lfm2-24b-a2b-ep8"]
    # no new serve flag: the state slots are derived
    assert not any("state" in str(a) for a in r["config"]["server_args"])
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
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert set(conf["reduced"]) == {"num_experts", "max_position_embeddings"}
    # the driver refuses a why of more than 200 characters before any run
    assert 1 <= len(conf["why"]) <= 200 and conf["why"].isprintable()
    layers = {m["layer"] for m in man["per_layer"] if m["name"] not in NEW_METRICS}
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
            assert m["layer"] in layers  # a layer PERF.md already names
        if m["name"] in SHARED:
            assert CELL in m["workloads"]
        if m["name"] in PINNED:
            assert CELL not in m["workloads"]
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(man)) < 64 * 1024


def test_the_configuration_file_holds_the_sources_numbers():
    config = registry.resolve(CELL)["config"]
    entry = next(c for c in registry.manifest()["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) == set(config["published"])
    # every number of the catalog's row, under the same key
    for key, want in {
            "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
            "intermediate_size": 11776, "model_type": "lfm2_moe",
            "moe_intermediate_size": 1536, "norm_eps": 1e-05,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_dense_layers": 2, "num_experts_per_tok": 4,
            "num_hidden_layers": 40, "num_key_value_heads": 8,
            "routed_scaling_factor": 1, "use_expert_bias": True,
            "vocab_size": 65536}.items():
        assert config[key] == want and key not in entry["reduced"], key
    assert config["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert config["layer_types"] == ["conv", "conv", "full_attention", "conv"] * 10
    assert config["num_experts"] == 8 and config["published"]["num_experts"] == 64
    assert config["serving"]["router_experts"] == 64
    assert config["max_position_embeddings"] == 32768
    for group in ("assumed", "deployment", "aot", "reference_check", "probe",
                  "rehearsal"):
        assert config[group], group
    # a prompt of the probe must resume from a snapshot: no page multiple
    assert config["probe"]["prompt_len"] % config["page_size"]
    # the program's preset is this file
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert (cfg.n_layers, cfg.first_k_dense, cfg.held_experts, cfg.n_experts) == (
        40, 2, 8, 64)
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.ep_rank, cfg.ep_size) == (0, config["serving"]["ep_size"])
    assert cfg.vocab_size == config["vocab"] == 65536
    assert (cfg.hidden, cfg.intermediate, cfg.moe_intermediate) == (2048, 11776, 1536)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert cfg.conv_L_cache == 3 and cfg.qk_norm and cfg.pair_kv_heads
    assert cfg.max_seq_len == config["max_position_embeddings"]
    slots = int(config["server_args"][3])
    assert cfg.kv_pool_shape(slots) == (2, 10, 4, slots, 128)
    states = cfg.default_state_slots(slots, 32)
    assert cfg.state_shape(states) == (30, states, 32, 128)
    assert get_config(config["rehearsal"]["preset"]).has_conv


def test_opsbytes_against_the_issues_arithmetic():
    config = registry.resolve(CELL)["config"]
    ob = opsbytes_lfm2
    assert (ob.conv_layers(config), ob.attention_layers(config),
            ob.sparse_layers(config)) == (30, 10, 38)
    assert ob.kv_bytes_per_token(config) == 10 * 2 * 8 * 64 * 2 == 20480
    assert ob.state_bytes(config) == 30 * 2 * 2048 * 2 == 240 * 1024
    assert ob.conv_params(config) == 2048 * 6144 + 2048 * 2048 + 2048 * 3
    assert ob.attention_params(config) == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert ob.expert_params(config) == 3 * 2048 * 1536
    # ISSUE 39: 178 + 470 + 105 + 2 874 + 134 = 3.76 B
    by_hand = (2 * (16.79e6 + 72.35e6) + 28 * 16.79e6 + 10 * 10.49e6
               + 38 * (0.13e6 + 8 * 9.44e6) + 134.2e6)
    assert ob.parameters(config) == pytest.approx(by_hand, rel=2e-3)
    assert 3.755e9 < ob.parameters(config) < 3.765e9
    assert ob.parameters(config) * 2 / 2 ** 30 == pytest.approx(7.0, abs=0.02)
    # a decode step of 32 rows: 30 x 16.79 M x 2 B of weights + 64 states
    step = ob.conv_decode_bytes_per_step(config, 32)
    assert step == 30 * ob.conv_params(config) * 2 + 2 * 32 * 240 * 1024
    assert 1.0e9 < step < 1.05e9
    # the program stores exactly these (no padding of a 64-wide row)
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    import numpy as np

    assert np.prod(cfg.kv_pool_shape(16)) * 2 // 16 == ob.kv_bytes_per_token(config)
    assert np.prod(cfg.state_shape(4)) * 2 // 4 == ob.state_bytes(config)
    # harness/opsbytes.py would count every layer: the reader must not use it
    from benchmarks.harness.opsbytes import kv_bytes_per_token

    assert kv_bytes_per_token(config) == 4 * ob.kv_bytes_per_token(config)


def test_the_traffic_is_the_issues():
    r = registry.resolve(CELL)
    p = r["traffic"]["params"]
    assert r["traffic"]["generator"] == "sessions" and p["mix_seed"] == 39001
    assert p["system_prompts"] == 8 and p["zipf_s"] == 1.0 and p["pool_sessions"] == 512
    assert p["system_len"] == {"dist": "uniform", "min": 2048, "max": 8192,
                               "quantum": 2048}
    assert p["turns"] == {"dist": "geometric", "mean": 6, "min": 2, "max": 12}
    assert p["turn_tokens"] == {"dist": "uniform", "min": 64, "max": 512, "quantum": 64}
    assert p["max_tokens"] == {"dist": "uniform", "min": 64, "max": 192, "quantum": 32}
    assert "think_s" not in p
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 64}
    assert r["cell"]["min_cached_share"] == 0.9
    vocab, page = r["config"]["vocab"], r["config"]["page_size"]
    plan = r["generator"].plan(p, r["cell"]["loop"], vocab, 5, horizon_s=111)
    assert set(plan.system_lens.tolist()) <= {2048, 4096, 6144, 8192}
    assert int(plan.system_lens.sum()) == 40960
    assert sorted(set(plan.user_lens)) == list(range(64, 513, 64))
    assert sorted(set(plan.reply_lens)) == list(range(64, 193, 32))
    shapes = plan.shapes()
    turns = [len(s["turn_tokens"]) for s in shapes]
    assert min(turns) == 2 and max(turns) == 12 and 5 < sum(turns) / len(turns) < 6.5
    ends = [s["prefix_len"] + sum(s["turn_tokens"]) + sum(s["max_tokens"])
            for s in shapes]
    assert 2100 < min(ends) and max(ends) < 16640  # the issue's 2.1 k-16.6 k
    census = plan.census(plan.pool, page)
    # a first turn resumes exactly at its preamble's end: a 2048 multiple,
    # where the warm-up's publish left a snapshot (the census visits each)
    assert set(plan.system_lens.tolist()) <= {reuse for reuse, _ in census}
    assert all(n % 2048 == 0 for n in plan.system_lens.tolist())
    # every later turn re-sends a context that ends on no chunk boundary
    # of its own: new tokens 64k + 1..16, so the buckets are the bursts'
    assert {new for _, new in census} <= {64 * k + r for k in range(1, 9)
                                          for r in (0, 16)}
    # every prefix a burst relies on is visited alone first
    bursts = r["cell"]["warmup"]["bursts"]
    assert bursts[0]["n"] == 1 and "blocker" not in bursts[0]
    assert {b["prefix_len"] for b in bursts} == {bursts[0]["prefix_len"]}
    assert bursts[0]["prefix_len"] in {reuse for reuse, _ in census}
    # the pool holds what set-up leaves and the window's rows beside it
    # (64 sessions alive, each with ALL its own turns behind it: an upper
    # bound on what the tree has to keep of sessions that come back)
    slots = int(r["config"]["server_args"][3])
    own = [e - s["prefix_len"] for e, s in zip(ends, shapes)]
    assert 40960 + 64 * sum(own) / len(own) < slots


def _ctx(config, name=CELL):
    def snap(k):
        moe = {site: {"layer_launches": k * 380, "assignments": k * 1600,
                      "assignments_elsewhere": k * 11200, "experts_hit": k * 2660,
                      "max_load": k * 400} for site in ("decode", "prefill")}
        spans = {"rm.state.restore": {"n": k * 5, "s": k * 0.001},
                 "rm.state.snapshot": {"n": k * 10, "s": k * 0.003},
                 "rm.tree.match": {"n": k * 5, "s": k * 0.01}}
        return {"state": {"engine": {"decode_steps": k * 10, "finished": k * 2,
                                     "spans": spans},
                          "moe": moe,
                          "state": {"slots": 2048, "slots_used_peak": 512 + k * 512,
                                    "kv_matched_tokens": k * 50000,
                                    "resumed_tokens": k * 49000,
                                    "recomputed_tokens": k * 1000}},
                "stats": {"prompt_tokens": k * 52000, "cached_tokens": k * 49000,
                          "generated_tokens": k * 320}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_decode_fused_mh": {"count": 100, "seconds": 0.07},
                         "radixmesh_moe_gmm_decode": {"count": 380, "seconds": 0.08}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.2},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.5}}}

    class Rec:
        n_prompt, token_t = 5000, [0.5 + 0.01 * i for i in range(33)]

    return {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
            "cell": {"name": name}, "window": (0.0, 10.0), "records": [Rec()] * 10,
            "kv_bytes_per_token": 81920,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "before": snap(0), "after": snap(1)}


def test_new_readers_read_a_hand_made_context(monkeypatch):
    config = registry.resolve(CELL)["config"]
    ctx = _ctx(config)
    monkeypatch.setattr(lfm2_trace, "of_run", lambda c, module="jit_decode_step": {
        "executions": 10, "seconds": 0.02, "events": 900})

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    assert read("conv_share_of_decode_step") == pytest.approx(10.0)
    # 32 rows a step: 30 x 16.79 M x 2 B + 64 x 240 KiB in 2 ms a step
    need = opsbytes_lfm2.conv_decode_bytes_per_step(config, 32)
    assert read("conv_decode_hbm_share") == pytest.approx(100 * need / 0.002 / 819e9)
    # 10 requests x 32 tokens behind their first, contexts 5001..5032, over
    # 10 steps x 20 480 B in 7 ms of kernel a step
    tokens = 10 * sum(5000 + i for i in range(1, 33))
    assert read("decode_attn_hbm_share.hd64") == pytest.approx(
        100 * tokens * 20480 / 10 / 0.007 / 819e9)
    assert read("state_resume_share") == pytest.approx(98.0)
    assert read("state_slots_used_peak_share") == pytest.approx(50.0)
    assert read("state_host_ms_per_request") == pytest.approx(2.0)
    assert read("moe_share_of_decode_step.ep8") == pytest.approx(40.0)
    # 266 experts hit a step x 18.9 MB in 8 ms of kernel a step
    assert read("moe_gmm_hbm_share.ep8") == pytest.approx(
        100 * 266 * 3 * 2048 * 1536 * 2 / 0.008 / 819e9)
    assert read("moe_expert_load_max_over_mean.ep8") == pytest.approx(2.0)
    assert read("moe_routed_here_share.ep8") == pytest.approx(12.5)
    assert read("prefix_hit_share.ep8") == pytest.approx(100 * 49 / 52)
    assert read("prefill_share_of_busy.ep8") == pytest.approx(25.0)
    for name in NEW_METRICS:  # a share of a peak stays under it here
        assert 0 < read(name) < 105 or "load_max" in name, name


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("glm-5.2-ep16.repo-hotset", "mellum2-12b-l12.batch-longtail",
                  "qwen2-7b.batch-unshared"):
        ctx = _ctx(registry.resolve(other)["config"], name=other)
        for name in NEW_METRICS - {"state_resume_share", "state_host_ms_per_request",
                                   "state_slots_used_peak_share"}:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    bare = {"state": {"engine": {"decode_steps": 10, "finished": 3}},
            "stats": {"prompt_tokens": 0, "cached_tokens": 0, "generated_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": mine,
           "cell": {"name": CELL}, "before": bare, "after": bare,
           "window": (0.0, 1.0), "records": [], "kv_bytes_per_token": 81920}
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_METRICS - {"prefix_hit_share.ep8"}:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_the_scope_is_found_in_a_hand_made_capture():
    """``scope_seconds``: events inside the module's executions only, a
    matched loop and the matched ops inside it counted once."""
    loaded = {
        "modules": [("jit_decode_step(7)", 0, 1000), ("jit_sample_tokens(8)", 1000, 100),
                    ("jit_decode_step(7)", 2000, 1000)],
        "scoped": [(100, 50), (120, 10), (400, 30), (1020, 40), (2500, 100), (5000, 9)],
    }
    got = lfm2_trace.scope_seconds(loaded)
    assert got == {"executions": 2, "events": 4,
                   "seconds": pytest.approx((50 + 30 + 100) / 1e9)}
    assert lfm2_trace.scope_seconds({"modules": loaded["modules"], "scoped": []}) is None
    assert lfm2_trace.scope_seconds(loaded, "jit_prefill_chunk_paged") is None


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483939", "--seconds", "3", "--trace", "2", "--rehearse-cpu"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and "setup_s" in got
    assert got["preemptions"]["value"] == 0
    # the counter-based metrics read on the CPU; the trace-based ones need a
    # device plane and are left out of a CPU line
    assert got["state_resume_share"]["value"] > 95
    assert got["prefix_hit_share.ep8"]["value"] > 85
    assert 0 < got["state_slots_used_peak_share"]["value"] <= 100
    assert got["state_host_ms_per_request"]["value"] > 0
    assert got["moe_expert_load_max_over_mean.ep8"]["value"] > 0
    # the tiny preset holds every expert: no fifth counter, nothing to read
    assert "moe_routed_here_share.ep8" not in got
    assert "conv_share_of_decode_step" not in got

"""The Ling-3.0-flash cell, its configuration, its traffic and every new
reader resolve by name; ``opsbytes_kda`` against the hand arithmetic of
ISSUE 48 (1 280 B a token, 13 025 280 B a snapshot, 2 866 M parameters);
the readers on a hand-made context, on another configuration's, and on a
program without the counters. Membership in the manifest's lists is
asserted, never position or equality: a later PR appends behind this
one."""
import json
import os
import sys

import numpy as np
import pytest

from benchmarks.harness import kda_trace, opsbytes_kda, registry
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "ling-3.0-flash-ep8.reasoning-sessions"
CONFIG = "ling-3.0-flash-bf16-ep8"
SOURCE = "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json"
NEW_METRICS = {
    "kda_share_of_decode_step", "kda_decode_hbm_share", "kda_chunk_mxu_share",
    "kda_boundary_copy_share", "state_resume_share.kda",
    "state_slots_used_peak_share.kda", "state_host_ms_per_request.kda",
    "mla_decode_hbm_share.h32", "moe_gmm_hbm_share.ep8x512",
    "moe_share_of_decode_step.ep8x512", "moe_expert_load_max_over_mean.ep8x512",
    "prefix_hit_share.kda", "prefill_share_of_busy.kda",
}
SHARED = {
    "decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
    "decode_step_device_ms.batch", "compiles_in_window.batch",
    "device_idle_share.batch", "step_host_exposed_ms.batch",
    "between_steps_idle_ms.batch", "idle_attributed_share.batch",
    "decode_prebuilt_share.batch",
}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size", "num_nextn_predict_layers", "max_position_embeddings"]


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "ling-3.0-flash-ep8"
    args = r["config"]["server_args"]
    # no new serve flag: the state slots are derived
    assert args == ["--page-size", "16", "--kv-slots", "524288", "--max-batch", "64"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "ling-3.0-flash-ep8"]
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
    assert entry["traffic"] == "reasoning-sessions"
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
        if m["name"] in SHARED:
            assert CELL in m["workloads"]
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(man, indent=1)) < 64 * 1024


def test_the_configuration_file_holds_the_catalogs_numbers():
    config = registry.resolve(CELL)["config"]
    assert set(config["reduced"]) == set(config["published"]) == set(REDUCED)
    catalog = os.path.join(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if os.path.isfile(catalog):  # every key of the row, but the reduced ones
        with open(catalog) as fh:
            row = next(json.loads(line) for line in fh
                       if '"Ling-3.0-flash"' in line)
        assert config["source"] == row["source_url"] == SOURCE
        for key, want in row["config"].items():
            if key in REDUCED:
                assert config["published"][key] == want, key
            else:
                assert config[key] == want, key
    for key, (here, published) in {
            "num_hidden_layers": (7, 42), "first_k_dense_replace": (1, 2),
            "num_experts": (64, 512), "vocab_size": (19648, 157184),
            "num_nextn_predict_layers": (0, 1),
            "max_position_embeddings": (16384, 262144)}.items():
        assert (config[key], config["published"][key]) == (here, published)
    # no width differs
    for key, want in {"hidden_size": 2560, "intermediate_size": 6144,
                      "moe_intermediate_size": 768, "head_dim": 128,
                      "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                      "qk_rope_head_dim": 64, "v_head_dim": 128,
                      "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
                      "short_conv_kernel_size": 4}.items():
        assert config[key] == want and key not in REDUCED, key
    for group in ("assumed", "deployment", "aot", "reference_check", "probe",
                  "rehearsal", "what_the_cut_distorts"):
        assert config[group], group
    for key in ("kda_projections", "safe_gate", "qk_norm", "rotary_pairing",
                "e_score_correction_bias", "state_storage"):
        assert key in config["assumed"], key
    assert config["probe"]["prompt_len"] % config["page_size"]
    # the program's preset is this file
    from radixmesh_tpu.engine.engine import pool_groups
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert cfg.vocab_size == config["vocab"] == 19648
    s = config["serving"]
    assert (cfg.ep_size, cfg.ep_rank, cfg.n_experts, cfg.held_experts) == (
        s["ep_size"], s["ep_rank"], s["router_experts"], config["num_experts"])
    assert (cfg.kda_heads, cfg.kda_head_dim) == (s["kda_heads"], s["kda_head_dim"])
    slots, batch = int(config["server_args"][3]), s["max_batch"]
    assert cfg.kv_pool_shape(slots) == (1, 1, 1, slots, 640)
    groups = pool_groups(cfg, slots, batch, 16)
    assert groups["state_shape"] == (6, 1 + 5 * batch, 4096, 128)
    assert groups["conv_state_shape"] == (6, 1 + 5 * batch, 288, 128)
    assert get_config(config["rehearsal"]["preset"]).has_kda


def test_opsbytes_against_the_issues_arithmetic():
    config = registry.resolve(CELL)["config"]
    ob = opsbytes_kda
    assert ob.kda_channels(config) == 32 * 128
    assert ob.layer_state_bytes(config) == 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert ob.state_bytes(config) == 6 * (2 * 2 ** 20 + 73728) == 13025280
    assert ob.latent_bytes_per_token(config) == 1280
    # ISSUE 48: 63.05 M a KDA layer, 31.98 M the latent one, 5.898 M an expert
    assert ob.kda_params(config) == (
        6 * 2560 * 4096 + 2560 * 32 + 4 * 12288 + 4096 + 32 + 4096)
    assert ob.kda_params(config) == pytest.approx(63.05e6, rel=1e-3)
    assert ob.latent_params(config) == (
        2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 32 * 128 * 2560 + 2560 * 32)
    assert ob.latent_params(config) == pytest.approx(31.98e6, rel=1e-3)
    assert ob.expert_params(config) == 3 * 2560 * 768 == 5898240
    assert ob.sparse_ffn_params(config) == 2560 * 512 + 65 * 5898240
    # 110.2 + 5 x 447.7 + 416.7 + 100.6 = 2 866 M = 5.34 GiB in bf16
    by_hand = 110.2e6 + 5 * 447.7e6 + 416.7e6 + 100.6e6
    assert ob.parameters(config) == pytest.approx(by_hand, rel=1e-3)
    assert ob.parameters(config) * 2 / 2 ** 30 == pytest.approx(5.34, abs=0.01)
    # a decode launch of one layer: 64 rows' matrices read and written and
    # 96 KiB of columns, rows and outputs a row
    assert ob.kda_decode_kernel_bytes(config, 64) == 64 * (
        2 * 2 * 2 ** 20 + 6 * 4096 * 4)
    assert ob.kda_decode_bytes_per_step(config, 64) == 6 * (
        ob.kda_params(config) * 2 + 2 * 64 * ob.layer_state_bytes(config))
    # the chunked form at a block of 64, a head: 2 x 2 x 64 x 128 (K K^T and
    # Q K^T) + 64 x 128 (the solve) + 2 x 64 x 128 (Q U) + 6 x 128 x 128
    assert ob.kda_chunk_flops_per_token(config) == 32 * (
        32768 + 8192 + 16384 + 98304) == 4980736
    # the program stores exactly these
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert np.prod(cfg.kv_pool_shape(16)) * 2 // 16 == 1280
    assert cfg.state_slot_bytes() == ob.state_bytes(config)
    assert (np.prod(cfg.state_shape(4)) * 4 + np.prod(cfg.conv_state_shape(4)) * 2
            ) // 4 == ob.state_bytes(config)
    import jax

    from radixmesh_tpu.models import init_params

    abstract = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract))
    assert n == pytest.approx(ob.parameters(config), rel=1e-4)  # + norms, bias


def test_the_traffic_is_the_accepted_file_unedited():
    r = registry.resolve(CELL)
    other = registry.resolve("phi-4-mini-flash.reasoning-sessions")
    assert r["traffic"] == other["traffic"]
    assert r["traffic"]["params"]["mix_seed"] == 41001
    for key in ("loop", "limits", "min_cached_share", "warmup", "drain_s",
                "request_timeout_s", "rehearsal"):
        assert r["cell"][key] == other["cell"][key], key
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 128}
    assert r["cell"]["min_cached_share"] == 0.9 and r["cell"]["drain_s"] == 120


def _ctx(config, name=CELL):
    per = 13025280

    def snap(k):
        spans = {"rm.state.restore": {"n": k * 5, "s": k * 0.001},
                 "rm.state.snapshot": {"n": k * 10, "s": k * 0.003}}
        return {"state": {
            "engine": {"decode_steps": k * 10, "finished": k * 2, "spans": spans,
                       "prefill": {"waves": k * 4, "launches": k * 5,
                                   "tokens_new": k * 800}},
            "pool": {"page_size": 16},
            "attn": {"latent_tokens_read": k * 10 * 64 * 4000},
            "moe": {"decode": {"experts_hit": k * 10 * 6 * 40,
                               "max_load": k * 60 * 5, "assignments": k * 60 * 64},
                    "held_experts": 64},
            "kda": {"decode_rows": k * 600, "chunk_tokens": k * 800,
                    "state_bytes_read": k * 645 * per,
                    "state_bytes_written": k * 700 * per,
                    "boundary_copies": k * 40},
            "state": {"slots": 321, "slots_used_peak": 1 + k * 160,
                      "bytes_per_slot": per,
                      "kv_matched_tokens": k * 50000, "resumed_tokens": k * 49000}},
            "stats": {"prompt_tokens": k * 52000, "cached_tokens": k * 49000,
                      "generated_tokens": k * 600}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_kda_decode": {"count": 60, "seconds": 0.03},
                         "radixmesh_kda_boundary": {"count": 10, "seconds": 0.002},
                         "radixmesh_kda_chunk": {"count": 30, "seconds": 0.03},
                         "radixmesh_mla_decode": {"count": 10, "seconds": 0.005},
                         "radixmesh_moe_gmm_decode": {"count": 180, "seconds": 0.04}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.1},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.1}}}
    return {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
            "cell": {"name": name}, "window": (0.0, 10.0), "records": [],
            "kv_bytes_per_token": 0,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "before": snap(0), "after": snap(1)}


def test_new_readers_read_a_hand_made_context():
    config = registry.resolve(CELL)["config"]
    ctx = _ctx(config)

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    assert read("kda_share_of_decode_step") == pytest.approx(32.0)
    # 60 live rows a step; a launch (one layer) in 0.5 ms
    need = opsbytes_kda.kda_decode_kernel_bytes(config, 60)
    assert read("kda_decode_hbm_share") == pytest.approx(100 * need / 5e-4 / 819e9)
    # 160 real tokens a launch x 4.98 MFLOP x 6 layers; 4 programs in 30 ms
    assert read("kda_chunk_mxu_share") == pytest.approx(
        100 * 160 * 4980736 * 6 / 7.5e-3 / 197e12)
    assert read("kda_boundary_copy_share") == pytest.approx(100 * 40 / 700)
    assert read("state_resume_share.kda") == pytest.approx(98.0)
    assert read("state_slots_used_peak_share.kda") == pytest.approx(100 * 161 / 321)
    assert read("state_host_ms_per_request.kda") == pytest.approx(2.0)
    # 64 x 4000 latent rows a step x 1 152 B in 0.5 ms
    assert read("mla_decode_hbm_share.h32") == pytest.approx(
        100 * 64 * 4000 * 1152 / 5e-4 / 819e9)
    # 240 experts hit a step x 11.8 MB in 4 ms
    assert read("moe_gmm_hbm_share.ep8x512") == pytest.approx(
        100 * 240 * 5898240 * 2 / 4e-3 / 819e9)
    assert read("moe_share_of_decode_step.ep8x512") == pytest.approx(40.0)
    assert read("moe_expert_load_max_over_mean.ep8x512") == pytest.approx(5.0)
    assert read("prefix_hit_share.kda") == pytest.approx(100 * 49 / 52)
    assert read("prefill_share_of_busy.kda") == pytest.approx(5.0)
    for name in NEW_METRICS - {"state_host_ms_per_request.kda",
                               "moe_expert_load_max_over_mean.ep8x512"}:
        assert 0 < read(name) < 105, name  # a share of a peak stays under it
    # a capture that holds no prefill reads 0, not nothing
    ctx["trace"]["planes"]["/device:TPU:0"]["kernels"].pop("radixmesh_kda_chunk")
    assert read("kda_chunk_mxu_share") == 0.0


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("lfm2-24b-a2b-ep8.agent-sessions",
                  "phi-4-mini-flash.reasoning-sessions",
                  "glm-4.7-flash-l8.docs-hotset", "qwen2-7b.batch-unshared"):
        ctx = _ctx(registry.resolve(other)["config"], name=other)
        for name in NEW_METRICS:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    assert not kda_trace.is_cell({"config": registry.resolve(
        "phi-4-mini-flash.reasoning-sessions")["config"]})
    bare = {"state": {"engine": {"decode_steps": 10, "finished": 3,
                                 "prefill": {"waves": 0, "launches": 0,
                                             "tokens_new": 0}},
                      "pool": {"page_size": 16}, "kv": {"index_plane_bytes": 0}},
            "stats": {"prompt_tokens": 0, "cached_tokens": 0, "generated_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": mine,
           "cell": {"name": CELL}, "before": bare, "after": bare,
           "window": (0.0, 1.0), "records": [], "kv_bytes_per_token": 0}
    for name in NEW_METRICS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_METRICS - {"prefix_hit_share.kda"}:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147484048", "--seconds", "12", "--trace", "2", "--rehearse-cpu"],
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
    assert 0 < got["kda_boundary_copy_share"]["value"] < 50
    assert 0 < got["state_slots_used_peak_share.kda"]["value"] <= 100
    assert got["state_host_ms_per_request.kda"]["value"] > 0
    assert got["moe_expert_load_max_over_mean.ep8x512"]["value"] > 0
    assert "kda_share_of_decode_step" not in got

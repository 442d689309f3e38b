"""The Nemotron-3-Nano cell, its configuration, its traffic and every new
reader resolve by name; ``opsbytes_ssd`` against the hand arithmetic of
ISSUE 53 (3 072 B a token, 21 340 160 B a snapshot, 31.58 B parameters with
3.23 B active a token); the readers on a hand-made context, on another
configuration's, and on a program without the counters. The manifest holds
128 per-layer metrics, its cap: the new readers are FILES that no list
names yet (PERF.md section 7), and the cell is appended to the accepted
lists whose readers find something in it. Membership in the manifest's
lists is asserted, never position or equality: a later PR appends behind
this one."""
import json
import os
import sys

import numpy as np
import pytest

from benchmarks.harness import opsbytes_ssd, registry, ssd_trace
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "nemotron-3-nano-ep8.agent-sessions"
CONFIG = "nemotron-3-nano-30b-a3b-bf16-ep8"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")
NEW_READERS = {
    "ssd_share_of_decode_step", "ssd_decode_hbm_share", "ssd_chunk_mxu_share",
    "ssd_boundary_copy_share", "state_resume_share.ssd",
    "state_slots_used_peak_share.ssd", "state_host_ms_per_request.ssd",
    "moe_share_of_decode_step.ep8x128", "moe_gmm_hbm_share.ep8x128",
    "moe_expert_load_max_over_mean.ep8x128", "moe_routed_here_share.ep8x128",
    "decode_attn_hbm_share.g16", "prefix_hit_share.ssd",
    "prefill_share_of_busy.ssd",
}
SHARED = {
    "decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
    "decode_step_device_ms.batch", "compiles_in_window.batch",
    "device_idle_share.batch", "step_host_exposed_ms.batch",
    "between_steps_idle_ms.batch", "idle_attributed_share.batch",
    "decode_prebuilt_share.batch", "trace_lower_s_in_window.batch",
}
# (every accepted list of a configuration's own readers is held to that
# configuration's cells by its test — `m["workloads"] == [CELL]` — so this
# cell is on none of them, generic as some of those readers are)
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size", "max_position_embeddings"]


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "nemotron-3-nano-ep8"
    args = r["config"]["server_args"]
    # no new serve flag: the state slots are derived
    assert args == ["--page-size", "16", "--kv-slots", "524288", "--max-batch", "64"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "nemotron-3-nano-ep8"]
    names = {m["name"] for m in r["per_layer"]}
    assert SHARED <= names
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in NEW_READERS | SHARED:
        assert callable(registry.load_module("layer_metrics", name).read), name


def test_the_manifest_lists_the_cell():
    man = registry.manifest()
    cells = [w["name"] for w in man["workloads"]]
    assert CELL in cells and len(cells) == len(set(cells))
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and 1 <= len(entry["why"]) <= 200
    assert entry["traffic"] == "agent-sessions"
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["source"] == SOURCE and conf["reduced"] == REDUCED
    assert 1 <= len(conf["why"]) <= 200 and conf["why"].isprintable()
    # the cap of 128 per-layer metrics: no list names the new readers
    assert len(man["per_layer"]) <= 128
    for m in man["per_layer"]:
        if m["name"] in SHARED:
            assert CELL in m["workloads"], m["name"]
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(man, indent=1)) < 64 * 1024


def test_the_configuration_file_holds_the_catalogs_numbers():
    config = registry.resolve(CELL)["config"]
    assert set(config["reduced"]) == set(config["published"]) == set(REDUCED)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every key of the row, but the reduced ones
        with open(catalog) as fh:
            row = next(json.loads(line) for line in fh
                       if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
        assert config["source"] == row["source_url"] == SOURCE
        for key, want in row["config"].items():
            if key in REDUCED:
                assert config["published"][key] == want, key
            else:
                assert config[key] == want, key
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    for key, (here, published) in {
            "num_hidden_layers": (23, 52),
            "hybrid_override_pattern": (pattern[:23], pattern),
            "n_routed_experts": (16, 128), "vocab_size": (16384, 131072),
            "max_position_embeddings": (16384, 262144)}.items():
        assert (config[key], config["published"][key]) == (here, published)
    # no width differs
    for key, want in {"hidden_size": 2688, "mamba_num_heads": 64,
                      "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
                      "conv_kernel": 4, "chunk_size": 128,
                      "num_attention_heads": 32, "num_key_value_heads": 2,
                      "head_dim": 128, "moe_intermediate_size": 1856,
                      "moe_shared_expert_intermediate_size": 3712,
                      "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
                      "n_group": 1, "topk_group": 1}.items():
        assert config[key] == want and key not in REDUCED, key
    for group in ("assumed", "deployment", "aot", "reference_check", "probe",
                  "rehearsal", "what_the_cut_distorts"):
        assert config[group], group
    assert "EIGHT chips share each layer" in config["deployment"]
    for key in ("mamba_inner_width", "no_rotary", "gated_norm", "time_step",
                "state_dtype", "e_score_correction_bias", "other_keys"):
        assert key in config["assumed"], key
    assert config["probe"]["prompt_len"] % config["page_size"]
    # the program's preset is this file
    from radixmesh_tpu.engine.engine import pool_groups
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert cfg.vocab_size == config["vocab"] == 16384
    s = config["serving"]
    assert (cfg.ep_size, cfg.ep_rank, cfg.n_experts, cfg.held_experts) == (
        s["ep_size"], s["ep_rank"], s["router_experts"], config["n_routed_experts"])
    assert (len(cfg.mamba2_layers), len(cfg.attn_layers)) == (
        s["mamba2_blocks"], s["attention_blocks"])
    assert not cfg.use_rope and not cfg.mlp_gated
    slots, batch = int(config["server_args"][3]), s["max_batch"]
    assert cfg.kv_pool_shape(slots) == (2, 3, 2, slots, 128)
    groups = pool_groups(cfg, slots, batch, 16)
    assert groups["state_shape"] == (10, 1 + 5 * batch, 4096, 128)
    assert groups["conv_state_shape"] == (10, 1 + 5 * batch, 144, 128)
    assert get_config(config["rehearsal"]["preset"]).has_mamba2
    # the fullest program holds at least a quarter of the chip
    assert config["aot"]["widest_live_gib"] >= 0.25 * 15.75


def test_opsbytes_against_the_issues_arithmetic():
    config = registry.resolve(CELL)["config"]
    ob = opsbytes_ssd
    assert ob.inner(config) == 64 * 64 and ob.inner(config, expand=True) == 5376
    assert ob.conv_channels(config) == 4096 + 2 * 8 * 128 == 6144
    assert ob.layer_state_bytes(config) == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert ob.state_bytes(config) == 10 * (2 * 2 ** 20 + 36864) == 21340160
    assert ob.kv_bytes_per_token(config) == 3 * 1024
    # ISSUE 53: 38.74 M a Mamba-2 block, 23.40 M an attention block, 9.978 M
    # an expert, 1 297.5 M a whole expert block
    assert ob.mamba2_params(config) == pytest.approx(38.74e6, rel=1e-3)
    assert ob.attention_params(config) == pytest.approx(23.40e6, rel=1e-3)
    assert ob.expert_params(config) == 2 * 2688 * 1856 == 9977856
    whole = dict(config, serving={"router_experts": 128})
    assert ob.moe_params(whole, 128) == pytest.approx(1297.5e6, rel=1e-4)
    # the parameter count from the file's own keys: the card's 31.6B-A3.2B
    total, active = ob.published_parameters(config)
    assert total == pytest.approx(31.58e9, rel=1e-3)
    assert active == pytest.approx(3.23e9, rel=1e-3)
    assert ob.published_parameters(config, expand=True)[0] == pytest.approx(
        31.8e9, rel=2e-3)
    # 10 x 38.74 + 10 x (16 x 9.978 + 19.96 + 0.34) + 3 x 23.40 + 88.1 M
    by_hand = 10 * 38.74e6 + 10 * (16 * 9.978e6 + 19.96e6 + 0.35e6) + 3 * 23.40e6 + 88.1e6
    assert ob.parameters(config) == pytest.approx(by_hand, rel=1e-3)
    # a decode launch of one block: 64 rows' matrices read and written and
    # the step's vectors
    assert ob.ssd_decode_kernel_bytes(config, 64) == 64 * (
        2 * 2 * 2 ** 20 + (2 * 4096 + 64 + 2048) * 4)
    assert ob.ssd_decode_bytes_per_step(config, 64) == 10 * (
        ob.mamba2_params(config) * 2 + 2 * 64 * ob.layer_state_bytes(config))
    # ISSUE 53: state read + written 2.68 GB and Mamba-2 weights 0.78 a step
    assert 2 * 64 * ob.state_bytes(config) == pytest.approx(2.7e9, rel=0.02)
    assert 10 * ob.mamba2_params(config) * 2 == pytest.approx(0.78e9, rel=0.01)
    # the chunked form at a block of 128: C B^T a group, three products a head
    assert ob.ssd_chunk_flops_per_token(config) == (
        8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)) == 3407872
    # the program stores exactly these (the experts 1920 wide)
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert np.prod(cfg.kv_pool_shape(16)) * 2 // 16 == 3072
    assert cfg.state_slot_bytes() == ob.state_bytes(config)
    import jax

    from radixmesh_tpu.models import init_params

    abstract = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract))
    stored = ob.parameters(config) + 10 * 16 * 2 * 2688 * (
        config["serving"]["stored_expert_width"] - 1856)
    assert n == pytest.approx(stored, rel=1e-4)
    assert n == config["aot"]["parameters"]


def test_the_traffic_is_the_accepted_file_unedited():
    r = registry.resolve(CELL)
    other = registry.resolve("laguna-xs.2-l5.agent-sessions")
    assert r["traffic"] == other["traffic"]
    assert r["traffic"]["params"]["mix_seed"] == 39001
    for key in ("loop", "limits", "min_cached_share", "warmup", "drain_s",
                "request_timeout_s", "rehearsal"):
        assert r["cell"][key] == other["cell"][key], key
    assert r["cell"]["loop"] == {"kind": "closed", "clients": 128}
    assert r["cell"]["min_cached_share"] == 0.9 and r["cell"]["drain_s"] == 60
    ling = registry.resolve("ling-3.0-flash-ep8.reasoning-sessions")["cell"]
    assert r["cell"]["limits"] == ling["limits"]


def _ctx(config, name=CELL):
    per = 21340160

    class Rec:
        n_prompt, token_t = 6000, [1.0, 2.0, 3.0]

    def snap(k):
        spans = {"rm.state.restore": {"n": k * 5, "s": k * 0.001},
                 "rm.state.snapshot": {"n": k * 10, "s": k * 0.003}}
        moe = {"experts_hit": k * 10 * 10 * 15, "max_load": k * 100 * 6,
               "assignments": k * 100 * 48, "assignments_elsewhere": k * 100 * 336}
        return {"state": {
            "engine": {"decode_steps": k * 10, "finished": k * 2, "spans": spans,
                       "prefill": {"waves": k * 4, "launches": k * 5,
                                   "tokens_new": k * 800}},
            "pool": {"page_size": 16},
            "moe": {"decode": moe, "prefill": moe, "held_experts": 16},
            "ssd": {"decode_rows": k * 600, "chunk_tokens": k * 800,
                    "state_bytes_read": k * 645 * per,
                    "state_bytes_written": k * 700 * per,
                    "boundary_copies": k * 40},
            "state": {"slots": 321, "slots_used_peak": 1 + k * 160,
                      "bytes_per_slot": per,
                      "kv_matched_tokens": k * 50000, "resumed_tokens": k * 49000}},
            "stats": {"prompt_tokens": k * 52000, "cached_tokens": k * 49000,
                      "generated_tokens": k * 600}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_ssd_decode": {"count": 100, "seconds": 0.06},
                         "radixmesh_kda_boundary": {"count": 10, "seconds": 0.002},
                         "radixmesh_ssd_chunk": {"count": 40, "seconds": 0.02},
                         "radixmesh_decode_fused_mh": {"count": 30, "seconds": 0.03},
                         "radixmesh_moe_gmm_decode": {"count": 200, "seconds": 0.05}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.2},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.4}}}
    return {"trace": {"planes": {"/device:TPU:0": plane}}, "config": config,
            "cell": {"name": name}, "window": (0.0, 10.0), "records": [Rec()] * 64,
            "kv_bytes_per_token": 0,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "before": snap(0), "after": snap(1)}


def test_new_readers_read_a_hand_made_context():
    config = registry.resolve(CELL)["config"]
    ctx = _ctx(config)

    def read(name):
        return registry.load_module("layer_metrics", name).read(ctx)

    assert read("ssd_share_of_decode_step") == pytest.approx(31.0)
    # 60 live rows a step; a launch (one block) in 0.6 ms
    need = opsbytes_ssd.ssd_decode_kernel_bytes(config, 60)
    assert read("ssd_decode_hbm_share") == pytest.approx(100 * need / 6e-4 / 819e9)
    # 160 real tokens a launch x 3.41 MFLOP x 10 blocks; 4 programs in 20 ms
    assert read("ssd_chunk_mxu_share") == pytest.approx(
        100 * 160 * 3407872 * 10 / 5e-3 / 197e12)
    assert read("ssd_boundary_copy_share") == pytest.approx(100 * 40 / 700)
    assert read("state_resume_share.ssd") == pytest.approx(98.0)
    assert read("state_slots_used_peak_share.ssd") == pytest.approx(100 * 161 / 321)
    assert read("state_host_ms_per_request.ssd") == pytest.approx(2.0)
    # 150 experts hit a step x two matrices of 2688 x 1856 in 5 ms
    assert read("moe_gmm_hbm_share.ep8x128") == pytest.approx(
        100 * 150 * 9977856 * 2 / 5e-3 / 819e9)
    assert read("moe_share_of_decode_step.ep8x128") == pytest.approx(25.0)
    assert read("moe_expert_load_max_over_mean.ep8x128") == pytest.approx(2.0)
    assert read("moe_routed_here_share.ep8x128") == pytest.approx(12.5)
    # 64 rows x 2 decoded tokens at ~6 k of context x 3 072 B over 10 steps
    assert read("decode_attn_hbm_share.g16") == pytest.approx(
        100 * 64 * (6001 + 6002) * 3072 / 10 / 3e-3 / 819e9)
    assert read("prefix_hit_share.ssd") == pytest.approx(100 * 49 / 52)
    assert read("prefill_share_of_busy.ssd") == pytest.approx(20.0)
    for name in NEW_READERS - {"state_host_ms_per_request.ssd",
                               "moe_expert_load_max_over_mean.ep8x128"}:
        assert 0 < read(name) < 105, name  # a share of a peak stays under it
    # a capture that holds no prefill reads 0, not nothing
    ctx["trace"]["planes"]["/device:TPU:0"]["kernels"].pop("radixmesh_ssd_chunk")
    assert read("ssd_chunk_mxu_share") == 0.0


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("lfm2-24b-a2b-ep8.agent-sessions",
                  "ling-3.0-flash-ep8.reasoning-sessions",
                  "glm-4.7-flash-l8.docs-hotset", "qwen2-7b.batch-unshared"):
        ctx = _ctx(registry.resolve(other)["config"], name=other)
        for name in NEW_READERS:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    assert not ssd_trace.is_cell({"config": registry.resolve(
        "ling-3.0-flash-ep8.reasoning-sessions")["config"]})
    bare = {"state": {"engine": {"decode_steps": 10, "finished": 3,
                                 "prefill": {"waves": 0, "launches": 0,
                                             "tokens_new": 0}},
                      "pool": {"page_size": 16}, "kv": {"index_plane_bytes": 0}},
            "stats": {"prompt_tokens": 0, "cached_tokens": 0, "generated_tokens": 0}}
    ctx = {"trace": {"planes": {}}, "peaks": None, "config": mine,
           "cell": {"name": CELL}, "before": bare, "after": bare,
           "window": (0.0, 1.0), "records": [], "kv_bytes_per_token": 0}
    for name in NEW_READERS:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    ctx["trace"] = None
    for name in NEW_READERS - {"prefix_hit_share.ssd"}:
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147484053", "--seconds", "12", "--trace", "2", "--rehearse-cpu"],
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
    assert got["decode_rows_per_step"]["value"] > 0
    assert "ssd_share_of_decode_step" not in got  # (listed by no metric)

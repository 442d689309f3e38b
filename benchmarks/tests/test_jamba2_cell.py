"""The Jamba2-3B cell, its configuration, its traffic and every new reader
resolve by name; the traffic's draws pinned (the eight document lengths, the
longest context); ``opsbytes_jamba`` and — what lets the cell onto the
Phi-4 cell's ``ssm`` lists — ``phi4flash_trace.is_cell``, ``shape_of(...,
"ssm")`` and ``opsbytes_phi4flash``'s state and Mamba functions against THIS
configuration's hand arithmetic (9 318 400 B a snapshot; 26 operators, no
GMU); the new readers on a hand-made context, on another configuration's,
and on a program without the counters. The manifest holds 128 per-layer
metrics, its cap: the new readers are FILES that no list names yet (PERF.md
section 7). Membership in the manifest's lists is asserted, never position
or equality: a later PR appends behind this one."""
import json
import os
import sys

import numpy as np
import pytest

from benchmarks.generators import sessions
from benchmarks.harness import (
    jamba_trace, opsbytes_jamba, opsbytes_phi4flash, phi4flash_trace, registry,
)
from benchmarks.tests.test_by_name import REPO, run_cmd

CELL = "jamba2-3b.long-doc-sessions"
CONFIG = "jamba2-3b-bf16"
SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
NEW_READERS = {"decode_attn_hbm_share.mqa", "full_attn_share_of_decode_step",
               "table_pages_max"}
SHARED = {
    "decode_rows_per_step", "kv_slots_used_peak_share", "preemptions",
    "decode_step_device_ms.batch", "compiles_in_window.batch",
    "device_idle_share.batch", "step_host_exposed_ms.batch",
    "between_steps_idle_ms.batch", "idle_attributed_share.batch",
    "decode_prebuilt_share.batch", "trace_lower_s_in_window.batch",
    "setup_startup_s", "setup_trace_s", "setup_lower_s", "setup_compile_s",
    "setup_cache_load_s",
}
# the Phi-4 cell's readers whose gate (`"mamba" in layer_types`) and
# arithmetic hold here too (the tests below)
SSM = {"ssm_share_of_decode_step", "ssm_decode_hbm_share", "ssm_scan_hbm_share",
       "state_resume_share.ssm", "state_slots_used_peak_share.ssm",
       "state_host_ms_per_request.ssm", "prefix_hit_share.phi4",
       "prefill_share_of_busy.phi4"}
# ... and those that read the decoder-hybrid-decoder's two groups of rows
PHI4_ONLY = {"shared_kv_attn_share_of_decode_step", "decode_attn_hbm_share.shared_kv",
             "decode_attn_hbm_share.swa512", "window_slots_held_per_row",
             "window_kv_released_share", "kv_slots_used_peak_share.full",
             "kv_slots_used_peak_share.window"}


def test_everything_resolves_by_name():
    r = registry.resolve(CELL)
    assert r["chips"] == 1 and r["config"]["preset"] == "jamba2-3b"
    # no new serve flag: the page is --page-size, the state slots are derived
    assert r["config"]["server_args"] == [
        "--page-size", "128", "--kv-slots", "2097152", "--max-batch", "64"]
    assert r["launcher"].argv(r["config"], seed=7, profile_dir=None)[1:6] == [
        "-m", "radixmesh_tpu.launch", "serve", "--model", "jamba2-3b"]
    names = {m["name"] for m in r["per_layer"]}
    assert SHARED | SSM <= names and not names & PHI4_ONLY
    assert {m["name"] for m in r["end_to_end"]} == {"tokens_per_s", "setup_s"}
    for name in NEW_READERS | SHARED | SSM:
        assert callable(registry.load_module("layer_metrics", name).read), name
    assert r["traffic"]["generator"] == "sessions"


def test_the_manifest_lists_the_cell():
    man = registry.manifest()
    cells = [w["name"] for w in man["workloads"]]
    assert CELL in cells and len(cells) == len(set(cells))
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and 1 <= len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "long-doc-sessions")
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["source"] == SOURCE and conf["reduced"] == ["max_position_embeddings"]
    assert conf["file"] == "benchmarks/configs/jamba2-3b-bf16.json"
    assert 1 <= len(conf["why"]) <= 200 and conf["why"].isprintable()
    # the cap of 128 per-layer metrics: no list names the new readers
    assert len(man["per_layer"]) <= 128
    listed = {m["name"] for m in man["per_layer"]}
    assert not NEW_READERS & listed
    for m in man["per_layer"]:
        if m["name"] in SHARED | SSM:
            assert CELL in m["workloads"], m["name"]
        if m["name"] in PHI4_ONLY:
            assert CELL not in m["workloads"], m["name"]
    tokens = next(m for m in man["end_to_end"] if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(man, indent=1)) < 64 * 1024


def test_the_configuration_file_holds_the_catalogs_numbers():
    config = registry.resolve(CELL)["config"]
    assert set(config["reduced"]) == set(config["published"]) == {
        "max_position_embeddings"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # every key of the row, but the reduced one
        with open(catalog) as fh:
            row = next(json.loads(line) for line in fh
                       if '"name": "AI21-Jamba2-3B"' in line)
        assert config["source"] == row["source_url"] == SOURCE
        for key, want in row["config"].items():
            if key == "max_position_embeddings":
                assert config["published"][key] == want == 262144
            else:
                assert config[key] == want, key
    assert config["max_position_embeddings"] == 131072
    # no width, depth or vocabulary differs
    for key, want in {"hidden_size": 2560, "intermediate_size": 8192,
                      "num_hidden_layers": 28, "num_attention_heads": 20,
                      "num_key_value_heads": 1, "vocab_size": 65536,
                      "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 160,
                      "mamba_expand": 2, "attn_layer_period": 14,
                      "attn_layer_offset": 7, "num_experts": 1,
                      "rms_norm_eps": 1e-06, "tie_word_embeddings": True}.items():
        assert config[key] == want, key
    assert config["assumed_sizes"] == {"d_inner": 5120, "d_state": 16, "d_conv": 4,
                                       "dt_rank": 160, "head_dim": 128}
    assert config["layer_types"].count("mamba") == 26
    assert [i for i, t in enumerate(config["layer_types"])
            if t == "full_attention"] == [7, 21]
    for group in ("assumed", "deployment", "serving", "aot", "reference_check",
                  "probe", "rehearsal"):
        assert config[group], group
    for key in ("layer_types", "experts", "mamba_sizes", "inner_norms",
                "no_positional_encoding", "mamba_init", "precision"):
        assert key in config["assumed"], key
    assert config["probe"]["prompt_len"] % config["page_size"]
    # the program's preset is this file
    from radixmesh_tpu.engine.engine import pool_groups
    from radixmesh_tpu.models import get_config

    cfg = get_config(config["preset"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert cfg.vocab_size == config["vocab"] == 65536
    assert cfg.max_seq_len == config["max_position_embeddings"]
    assert (cfg.hidden, cfg.intermediate, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank,
            cfg.rms_eps) == (2560, 8192, 20, 1, 128, 5120, 16, 4, 160, 1e-6)
    assert cfg.ssm_inner_norms and not cfg.use_rope and cfg.tie_embeddings
    s = config["serving"]
    assert (len(cfg.mamba_layers), len(cfg.attn_layers)) == (
        s["mamba_layers"], s["full_layers"]) == (26, 2)
    slots, batch, page = int(config["server_args"][3]), s["max_batch"], 128
    assert config["page_size"] == page == int(config["server_args"][1])
    assert cfg.kv_pool_shape(slots) == (2, 2, 1, slots, 128)
    assert s["max_positions"] == s["table_pages"] * page == cfg.max_seq_len
    groups = pool_groups(cfg, slots, batch, page)
    assert groups["state_shape"] == (26, 1 + 5 * batch, 16, 5120)
    assert groups["conv_state_shape"] == (26, 1 + 5 * batch, 120, 128)
    assert groups["window_shape"] is None
    tiny = get_config(config["rehearsal"]["preset"])
    assert tiny.has_ssm and tiny.ssm_inner_norms and not tiny.cross_decoder
    # the fullest program holds at least a quarter of the chip
    assert config["aot"]["widest_live_gib"] >= 0.25 * 15.75


def test_the_traffics_draws_are_pinned():
    """The same for every seed: eight documents of three lengths on the
    32 768 grid, 491 520 tokens in all; the longest context a session
    reaches; what a turn brings and takes."""
    r = registry.resolve(CELL)
    params = r["traffic"]["params"]
    assert params["mix_seed"] == 55001 and "think_s" not in params
    assert params["system_len"] == {"dist": "uniform", "min": 32768, "max": 98304,
                                    "quantum": 32768}
    assert params["turns"] == {"dist": "geometric", "mean": 4, "min": 2, "max": 6}
    assert params["turn_tokens"] == {"dist": "uniform", "min": 64, "max": 512,
                                     "quantum": 64}
    assert params["max_tokens"] == {"dist": "uniform", "min": 64, "max": 192,
                                    "quantum": 32}
    assert (params["system_prompts"], params["pool_sessions"], params["zipf_s"]) == (
        8, 512, 1.0)
    for seed in (5, 2147483655):
        plan = sessions.plan(params, r["cell"]["loop"], 65536, seed, 200.0)
        assert plan.system_lens.tolist() == [
            32768, 65536, 65536, 32768, 98304, 98304, 32768, 65536]
        shapes = plan.shapes()
        contexts = [s["prefix_len"] + sum(s["turn_tokens"]) + sum(s["max_tokens"])
                    for s in shapes]
        assert (min(contexts), max(contexts)) == (33248, 101184)
        assert max(contexts) < r["config"]["max_position_embeddings"]
        assert {len(s["turn_tokens"]) for s in shapes} == {2, 3, 4, 5, 6}
        census = plan.census(plan.pool, 128)
        assert len(census) == 1822
        assert (min(n for _, n in census), max(n for _, n in census)) == (64, 640)
        assert min(r_ for r_, _ in census) == 32768  # no cold document
    assert [p.shape[0] for p in plan.prefixes()] == plan.system_lens.tolist()
    cell = r["cell"]
    assert cell["loop"] == {"kind": "closed", "clients": 128}
    assert cell["min_cached_share"] == 0.9
    nemotron = registry.resolve("nemotron-3-nano-ep8.agent-sessions")["cell"]
    assert cell["limits"] == nemotron["limits"]


def test_opsbytes_against_the_issues_arithmetic():
    config = registry.resolve(CELL)["config"]
    ob, ph = opsbytes_jamba, opsbytes_phi4flash
    # what a token leaves: 1 KiB for the whole model
    assert ob.kv_bytes_per_token(config) == 1024 == config["serving"]["kv_bytes_per_token"]
    assert ob.attn_decode_bytes_per_step(config, 64 * 65536) == pytest.approx(4.29e9, rel=0.01)
    # ISSUE 55: 104.16 M a Mamba layer, 76.68 M an attention layer (each with
    # its SwiGLU of 62.91 M), 3 029 M in all
    assert ph.mamba_params(config) + ob.mlp_params(config) == pytest.approx(
        104.16e6, rel=1e-4)
    assert ob.attention_params(config) + ob.mlp_params(config) == pytest.approx(
        76.68e6, rel=1e-4)
    assert ob.parameters(config) == pytest.approx(3029.3e6, rel=1e-4)
    assert ob.weight_bytes_per_step(config) == pytest.approx(6.06e9, rel=0.01)
    # the program stores exactly these (+ the norms' few thousand)
    import jax

    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    abstract = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract))
    assert n == config["aot"]["parameters"] == 3029337472
    assert 0 <= n - ob.parameters(config) < 160_000  # norms: 28 x 2 x 2560 + ...
    assert np.prod(cfg.kv_pool_shape(128)) * 2 // 128 == 1024


def test_the_phi4_cells_ssm_functions_give_this_configurations_numbers():
    """What the cell's place on the accepted ``ssm`` lists rests on."""
    config = registry.resolve(CELL)["config"]
    ph = opsbytes_phi4flash
    assert phi4flash_trace.is_cell({"config": config})
    # 26 operators, no GMU
    assert ph._count(config, "mamba") == 26 and ph._count(config, "gmu") == 0
    # one snapshot: 26 x (16 x 5120 x 4 B + 3 x 5120 x 2 B)
    assert ph.state_bytes(config) == 9_318_400
    from radixmesh_tpu.models import get_config

    assert get_config(config["preset"]).state_slot_bytes() == ph.state_bytes(config)
    # one operator: in_x | in_z, conv + bias, x_proj, dt_proj + bias, A, D, out
    by_hand = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
               + 5120 * 16 + 5120 + 5120 * 2560)
    assert ph.mamba_params(config) == by_hand == 41_241_600
    # a decode step's state-space side: 26 operators' weights + 64 rows'
    # state read and written (ISSUE 55: 2.1 GB + 1.2 GB)
    need = ph.ssm_decode_bytes_per_step(config, 64)
    assert need == 26 * by_hand * 2 + 2 * 64 * 9_318_400
    assert 26 * by_hand * 2 == pytest.approx(2.14e9, rel=0.01)
    assert 2 * 64 * 9_318_400 == pytest.approx(1.19e9, rel=0.01)
    # the scan's traffic a position and layer: x, dt in, y out, B and C
    assert ph.ssm_scan_bytes_per_token(config) == 4 * (3 * 5120 + 32)
    # by shape: the 5 120-, 160- and 192-wide tensors are the operators' alone
    pat = phi4flash_trace.shape_of(config, "ssm")
    for text in ("bf16[64,1,5120]", "f32[64,160]", "bf16[5120,192]{1,0}"):
        assert pat.search(text), text
    for width in (2560, 8192, 65536, 128, 1024, 20, 16):  # hidden, FFN, vocab, ...
        assert not pat.search(f"bf16[64,{width}]"), width


def _ctx(config, name=CELL, counters=True):
    def snap(k):
        spans = {"rm.state.restore": {"n": k * 5, "s": k * 0.001},
                 "rm.state.snapshot": {"n": k * 10, "s": k * 0.003}}
        attn = {"window_tokens_read": 0, "window_tokens_skipped": 0}
        if counters:
            attn |= {"decode_context_tokens": k * 10 * 60 * 50000,
                     "table_pages_max": 1024 if k else 512}
        return {"state": {
            "engine": {"decode_steps": k * 10, "finished": k * 2, "spans": spans,
                       "prefill": {"waves": k * 4, "launches": k * 5,
                                   "tokens_new": k * 800}},
            "pool": {"page_size": 128}, "attn": attn,
            "state": {"slots": 321, "slots_used_peak": 1 + k * 160,
                      "bytes_per_slot": 9318400,
                      "kv_matched_tokens": k * 50000, "resumed_tokens": k * 49000}},
            "stats": {"prompt_tokens": k * 52000, "cached_tokens": k * 49000,
                      "generated_tokens": k * 600}}

    plane = {"busy_s": 2.0,
             "kernels": {"radixmesh_decode_fused_mh": {"count": 20, "seconds": 0.05},
                         "radixmesh_ssm_scan": {"count": 104, "seconds": 0.02},
                         "radixmesh_chunk_attn": {"count": 8, "seconds": 0.01}},
             "modules": {"jit_decode_step": {"count": 10, "seconds": 0.2},
                         "jit_prefill_chunk_paged": {"count": 4, "seconds": 0.4}}}
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

    # 60 rows x 50 k of context a step x 1 024 B; a step's two launches 5 ms
    assert read("decode_attn_hbm_share.mqa") == pytest.approx(
        100 * 60 * 50000 * 1024 / 5e-3 / 819e9)
    assert read("full_attn_share_of_decode_step") == pytest.approx(25.0)
    assert read("table_pages_max") == 1024.0
    assert 0 < read("decode_attn_hbm_share.mqa") < 105
    # the accepted readers whose lists the cell joined read here too
    assert read("state_resume_share.ssm") == pytest.approx(98.0)
    assert read("state_slots_used_peak_share.ssm") == pytest.approx(100 * 161 / 321)
    assert read("state_host_ms_per_request.ssm") == pytest.approx(2.0)
    assert read("prefix_hit_share.phi4") == pytest.approx(100 * 49 / 52)
    assert read("prefill_share_of_busy.phi4") == pytest.approx(20.0)
    # 200 real tokens a wave x 61 568 B; a launch (one layer) in 0.19 ms
    assert read("ssm_scan_hbm_share") == pytest.approx(
        100 * 200 * 4 * (3 * 5120 + 32) / (0.02 / 104) / 819e9)


def test_new_readers_find_nothing_off_their_cell_or_on_the_parent():
    """Another configuration's context, a program without the counters
    (the parent), a run without a trace: None, never an exception."""
    mine = registry.resolve(CELL)["config"]
    for other in ("phi-4-mini-flash.reasoning-sessions",
                  "nemotron-3-nano-ep8.agent-sessions",
                  "lfm2-24b-a2b-ep8.agent-sessions", "qwen2-7b.batch-unshared"):
        conf = registry.resolve(other)["config"]
        assert not jamba_trace.is_cell({"config": conf}), other
        ctx = _ctx(conf, name=other)
        for name in NEW_READERS:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, (
                other, name)
    ctx = _ctx(mine, counters=False)  # the parent: no counters on /debug/state
    for name in ("decode_attn_hbm_share.mqa", "table_pages_max"):
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
    for trace in ({"planes": {}}, None):
        ctx = _ctx(mine) | {"trace": trace, "peaks": None}
        for name in NEW_READERS - {"table_pages_max"}:
            assert registry.load_module("layer_metrics", name).read(ctx) is None, name


def test_rehearsal_runs_to_a_result_line():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147484055", "--seconds", "6", "--trace", "2", "--rehearse-cpu"],
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
    assert got["state_slots_used_peak_share.ssm"]["value"] > 0
    assert "ssm_share_of_decode_step" not in got

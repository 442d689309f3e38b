"""``opsbytes_dsa`` computes what ISSUE 33's arithmetic says of the
GLM-5.2 cut, and ``dsa_trace`` finds the selection's sorts and the gather
of the selected rows in an op line by this configuration's shapes."""
import pytest

from benchmarks.harness import dsa_trace, opsbytes_dsa, opsbytes_mla, registry

CELL = "glm-5.2-ep16.repo-hotset"


@pytest.fixture(scope="module")
def config():
    return registry.resolve(CELL)["config"]


def test_bytes_and_flops_of_the_sparse_path(config):
    assert opsbytes_dsa.index_key_bytes(config) == 256
    assert opsbytes_dsa.full_layers(config) == 2
    assert opsbytes_dsa.selected_row_bytes(config) == 1152
    assert opsbytes_dsa.stored_row_width(config) == 640
    # 7 x 1152 B of rows + 2 x 256 B of index keys a token (needed elements;
    # 7 x 1280 + 512 = 9472 B are stored)
    assert opsbytes_dsa.cache_bytes_per_token(config) == 7 * 1152 + 512
    # 32 index heads of 128: 8192 + 96 FLOP a key, 32 FLOP a byte: memory-bound
    assert opsbytes_dsa.index_flops_per_key(config) == 8288
    assert 32 <= 8288 / 256 < 33
    # absorbed attention over a selected row: 64 heads x (576 + 512) x 2
    assert opsbytes_dsa.sparse_attn_flops_per_row(config) == 139_264
    assert round(139_264 / 1152) == 121  # half the chip's ridge of 240
    # one query at the cell's mean context: 21.4 MB of index keys + 16.5 MB of
    # selected rows where dense latent attention would read 336 MB
    assert opsbytes_dsa.dense_rows_bytes(config, 41704) == 7 * 41704 * 1152
    sparse = opsbytes_dsa.sparse_step_bytes(config, 41704)
    assert sparse == 2 * 41704 * 256 + 7 * 2048 * 1152
    assert 8.5 < opsbytes_dsa.dense_rows_bytes(config, 41704) / sparse < 9.5
    # a context within index_topk: every row, and still every index key
    assert opsbytes_dsa.sparse_step_bytes(config, 1000) == (
        2 * 1000 * 256 + 7 * 1000 * 1152)


def test_the_cut_is_the_issues(config):
    p = opsbytes_mla.attention_params(config)
    assert round(p / 1e6, 1) == 165.0  # q_a 12.58, q_b 33.55, kv_a 3.54, kv_b 14.68, o 100.66
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    assert round(expert / 1e6, 2) == 37.75
    indexer = (config["q_lora_rank"] * 32 * 128 + config["hidden_size"] * 128
               + config["hidden_size"] * 32)
    assert round(indexer / 1e6, 2) == 9.37
    dense_ffn = 3 * config["hidden_size"] * config["intermediate_size"]
    router = config["hidden_size"] * config["serving"]["router_experts"]
    sparse_layer = p + router + (16 + 1) * expert
    embed = config["hidden_size"] * config["vocab_size"]
    total = (p + dense_ffn + indexer) + 5 * sparse_layer + (
        sparse_layer + indexer) + 2 * embed
    assert round(total * 2 / 2 ** 30, 2) == 10.24
    # one sparse layer whole does not fit a chip: the share is forced
    whole = p + router + (256 + 1) * expert
    assert whole * 2 / 2 ** 30 > 18
    assert opsbytes_dsa.held_expert_bytes(config) == 6 * 16 * expert * 2
    assert opsbytes_dsa.expert_share(config) == 16
    assert opsbytes_dsa.expert_share(
        registry.resolve("glm-4.7-flash-l8.docs-hotset")["config"]) == 1


def _planes():
    sort = ("%sort.6 = (f32[32,1,65537]{2,0,1:T(8,128)S(1)}, s32[32,1,65537]{2,0,1}) "
            "sort(%pad, %iota), dimensions={2}")
    router = "%sort.16 = (f32[32,256]{1,0:T(8,128)}, s32[32,256]{1,0}) sort(%a, %b)"
    small = "%sort.9 = (f32[32,1,257]{2,0,1}, s32[32,1,257]{2,0,1}) sort(%a, %b)"
    gather = "%fusion.9 = bf16[65536,640]{1,0:T(8,128)(2,1)} fusion(%bitcast.13, %clamp)"
    other = "%fusion.12 = bf16[65536,128]{1,0} fusion(%x)"
    kernel = "%radixmesh_mla_sparse_decode.8 = (bf16[1,7,1,13824,16,640]) custom-call(%q)"
    # the selection that does not sort a row (names recorded on the chip, PR 33)
    bisect = ("%while.92 = (s32[]{:T(128)}, u32[32]{0:T(128)S(1)}, u32[32,65664]{1,0:T(8,128)"
              "S(1)}, s32[]{:T(128)}) while((s32[]{:T(128)}, u32[32]{0:T(128)S(1)}")
    layers = ("%while.97 = (s32[]{:T(128)}, bf16[32,1,6144]{2,0,1}, bf16[1,7,1,221184,640], "
              "u32[2,5,2]{2,1,0}) while(%tuple)")
    segments = ("%sort.36 = (s32[32,513,128]{2,0,1:T(8,128)}, s32[32,513,128]{2,0,1:T(8,128)"
                "S(1)}, s32[32,513,128]{2,0,1:T(8,128)}) sort(%copy.339, %copy.340)")
    compaction = ("%fusion.19 = s32[65536]{0:T(1024)S(1)} fusion(s32[32,65664]{1,0:T(8,128)"
                  "S(1)} %copy_bitcast_fusion.8, s32[65536]{0:T(1024)S(1)} %bitcast.763)")
    pages = "%fusion.1 = s32[65536]{0:T(1024)S(1)} fusion(s32[32,4096]{1,0} %pt, s32[65536]{0} %i)"
    ops = [(layers, 0, 1_900_000)]  # the layer scan holds both steps: never summed
    for step in (0, 1):  # two executions of the decode step
        t = step * 1_000_000
        ops += [(sort, t + 100, 2000), (bisect, t + 2200, 70), (segments, t + 2300, 500),
                (compaction, t + 2800, 130), (pages, t + 2950, 40),
                (sort, t + 3000, 2100 - 700), (router, t + 6000, 10),
                (small, t + 6100, 5), (gather, t + 7000, 900), (gather, t + 8000, 950),
                (other, t + 9000, 50), (kernel, t + 9100, 200)]
    ops.append((sort, 5_000_000, 20_000))  # inside a prefill module: not a step's
    mods = [("jit_decode_step(1)", 0, 500_000), ("jit_decode_step(1)", 1_000_000, 500_000),
            ("jit_prefill_chunk_paged(2)", 4_900_000, 400_000)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}


def test_trace_classifies_the_xla_pieces(config):
    got = dsa_trace.classify(_planes(), config)
    # a step: two sorts of a row (2000 + 1400), a bisection 70, a segment sort
    # 500, a compaction 130 and a page lookup of the selected positions 40
    assert got["executions"] == 2 and got["selects"] == 12 and got["gathers"] == 4
    assert got["select_s"] == pytest.approx(2 * 4140e-9)
    assert got["gather_s"] == pytest.approx(2 * 1850e-9)
    chunk = dsa_trace.classify(_planes(), config, module="jit_prefill_chunk_paged")
    assert chunk["selects"] == 1 and chunk["select_s"] == pytest.approx(20e-6)
    assert dsa_trace.classify({}, config) is None
    assert dsa_trace.classify(_planes(), config, module="jit_nothing") is None
    # a configuration without an indexer, and a run without a capture
    assert dsa_trace.of_run({"config": {"hidden_size": 1}, "trace": None}) is None
    assert dsa_trace.of_run({"config": config, "trace": {"planes": {}}}) is None

"""The sparse/window ops-bytes arithmetic against numbers worked by hand,
on the configuration file itself."""
import pytest

from benchmarks.harness import opsbytes, opsbytes_moe, registry


@pytest.fixture(scope="module")
def c():
    return registry.load_json("configs", "mellum2-12b-a2.5b-bf16-l12")


def test_a_window_layer_never_needs_more_than_the_window(c):
    per_layer_token = 2 * 4 * 128 * 2  # K and V, 4 kv heads of 128, bf16
    assert c["layer_types"].count("sliding_attention") == 9
    for ctx in (1, 512, 1024, 1025, 8192, 32768):
        want = 3 * ctx + 9 * min(ctx, 1024)
        assert opsbytes_moe.window_tokens(c, ctx) == want
        assert opsbytes_moe.kv_bytes_needed(c, ctx) == per_layer_token * want
    # a sliding layer's part stops growing at the window
    grow = opsbytes_moe.window_tokens(c, 5000) - opsbytes_moe.window_tokens(c, 4000)
    assert grow == 3 * 1000
    # below the window it is the dense arithmetic
    assert opsbytes_moe.kv_bytes_needed(c, 700) == 700 * opsbytes.kv_bytes_per_token(c)
    assert opsbytes.kv_bytes_per_token(c) == 24576  # 12 layers x 2048 B


def test_all_experts_hit_is_the_layers_expert_bytes(c):
    one = 3 * 2304 * 896 * 2
    assert opsbytes_moe.expert_bytes(c) == one == 12_386_304
    assert opsbytes_moe.expert_layer_bytes(c, 64) == 64 * one == 792_723_456
    assert opsbytes_moe.expert_layer_bytes(c, 0) == 0
    assert opsbytes_moe.expert_layer_bytes(c, 62.5) == 62.5 * one
    with pytest.raises(ValueError):
        opsbytes_moe.expert_layer_bytes(c, 65)


def test_readers_return_nothing_on_a_program_without_the_counters():
    """The parent commit has neither the counters nor the kernel: each new
    reader leaves its metric out and does not raise."""
    state = {"engine": {"decode_steps": 10}}
    ctx = {"trace": {"planes": {"/device:TPU:0": {
               "kernels": {"radixmesh_decode_fused_mh": {"count": 1, "seconds": 1.0}},
               "modules": {"jit_decode_step": {"count": 4, "seconds": 2.0}}}}},
           "peaks": {"hbm_bytes_per_s": 819e9}, "window": (0.0, 1.0),
           "records": [], "before": {"state": state}, "after": {"state": state},
           "config": registry.load_json("configs", "qwen2-7b-w8a16")}
    for name in ("moe_gmm_hbm_share", "moe_share_of_decode_step",
                 "moe_expert_load_max_over_mean", "decode_attn_hbm_share.window",
                 "window_kv_skipped_share"):
        assert registry.load_module("layer_metrics", name).read(ctx) is None, name
        assert registry.load_module("layer_metrics", name).read(
            {**ctx, "trace": {"planes": {}}}) is None, name


def test_readers_arithmetic(c):
    moe = lambda hit, assign, mx: {"decode": {  # noqa: E731
        "layer_launches": 0, "assignments": assign, "experts_hit": hit,
        "max_load": mx}}
    s0 = {"engine": {"decode_steps": 0}, "moe": moe(0, 0, 0),
          "attn": {"window_tokens_skipped": 0, "window_tokens_read": 0}}
    s1 = {"engine": {"decode_steps": 100}, "moe": moe(100 * 12 * 60, 100 * 12 * 256, 100 * 12 * 10),
          "attn": {"window_tokens_skipped": 250, "window_tokens_read": 750}}
    ctx = {"trace": {"planes": {"/device:TPU:0": {
               "kernels": {"radixmesh_moe_gmm_decode": {"count": 36, "seconds": 0.03},
                           "radixmesh_decode_fused_mh": {"count": 12, "seconds": 0.004}},
               "modules": {"jit_decode_step": {"count": 2, "seconds": 0.04}}}}},
           "peaks": {"hbm_bytes_per_s": 819e9}, "window": (0.0, 1.0), "records": [],
           "before": {"state": s0}, "after": {"state": s1}, "config": c}
    rd = lambda n: registry.load_module("layer_metrics", n).read(ctx)  # noqa: E731
    # 12 layers x 60 experts x 12.39 MB = 8.92 GB a step in 15 ms: 72.6% of 819 GB/s
    assert rd("moe_gmm_hbm_share") == pytest.approx(
        100 * 12 * 60 * 12_386_304 / 0.015 / 819e9)
    assert rd("moe_share_of_decode_step") == pytest.approx(75.0)
    assert rd("moe_expert_load_max_over_mean") == pytest.approx(10 / 4)
    assert rd("window_kv_skipped_share") == pytest.approx(25.0)

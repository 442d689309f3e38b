"""Peaks and the ops/bytes arithmetic, against numbers worked by hand."""
import pytest

from benchmarks.harness import opsbytes, registry
from benchmarks.harness.peaks import peaks_for


def config(name):
    return registry.load_json("configs", name)


def test_unknown_device_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_qwen2_7b_numbers():
    c = config("qwen2-7b-w8a16")
    assert opsbytes.kv_bytes_per_token(c) == 57344  # 2 * 28 * 4 * 128 * 2
    # 6.53 G layer params + 0.545 G head, one byte each: 7.07 GB = 6.6 GiB
    assert opsbytes.weight_bytes_per_step(c) == pytest.approx(7.07e9, rel=0.01)
    assert opsbytes.prefill_flops_per_token(c) == pytest.approx(2 * 6.53e9, rel=0.01)


def test_qwen25_14b_numbers():
    """The configuration of ISSUE 24's third cell (tp=4, bf16; not in the
    benchmark yet): the arithmetic must hold for it too."""
    c = {"hidden_size": 5120, "intermediate_size": 13824, "num_hidden_layers": 48,
         "num_attention_heads": 40, "num_key_value_heads": 8, "head_dim": 128,
         "vocab_size": 152064,
         "serving": {"tp": 4, "weight_bytes_per_param": 2, "kv_bytes_per_element": 2}}
    assert opsbytes.kv_bytes_per_token(c) == 196608  # 2 * 48 * 8 * 128 * 2
    # 13.2 G layer params + 0.78 G head, bf16, a quarter per chip
    assert opsbytes.weight_bytes_per_step(c) == pytest.approx(7.0e9, rel=0.02)
    more = opsbytes.prefill_flops_per_token(c, context=4096)
    assert more > opsbytes.prefill_flops_per_token(c)

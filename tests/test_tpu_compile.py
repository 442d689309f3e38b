"""Compile-only check against a TPU v5e topology description — no chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu", ...)`` hands
back TPU devices backed by the installed libtpu's REAL compilers: lowering a
function for them runs Pallas → Mosaic and XLA:TPU end to end and refuses an
oversize VMEM scratch or an over-HBM program exactly as a chip would. What
it cannot do is execute — numerics on silicon are ``chip_smoke.py``'s job.

The functions are lowered with their DEFAULT arguments from this CPU
process: the kernel/reference choice follows the lowering target
(``ops/attention.py::_dispatch``), so what compiles here is what ``serve``
runs on the chip, at Qwen2-7B's head geometry (Hq 28, Hkv 4 → G 7, D 128,
page 16, chunk 512).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    _init_params,
    decode_step,
    prefill_chunk_paged,
)
from radixmesh_tpu.ops.attention import paged_decode_attention

B, HQ, HKV, D, PAGE, CHUNK, MAXP, L = 8, 28, 4, 128, 16, 512, 64, 2


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert topo.devices[0].platform == "tpu"
    # A compile-only client cannot load executables back, so the suite's
    # persistent compile cache would only collect dead TPU entries.
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


def _sd(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def test_same_functions_take_the_reference_on_cpu():
    """The other side of the lowering-target rule: lowered for this
    process's CPU, the very same call holds no Mosaic kernel."""
    z = jnp.zeros
    compiled = jax.jit(
        lambda q, k, v, kv, sl, pt, ln: paged_decode_attention(
            q, k, v, kv, sl, pt, ln, 0
        )
    ).lower(
        z((2, HQ, D), jnp.bfloat16), z((2, HKV, D), jnp.bfloat16),
        z((2, HKV, D), jnp.bfloat16),
        z((2, L, HKV, 8, PAGE, D), jnp.bfloat16), z((2,), jnp.int32),
        z((2, 4), jnp.int32), z((2,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_decode_step_and_prefill_wave_fit_one_v5e(v5e):
    """``decode_step`` and the default 8 x 512 prefill wave at Qwen2-7B
    widths, W8A16, all 28 layers, the 65 536-slot pool ``chip_smoke.py``
    serves with: Mosaic accepts the fused-decode and chunk kernels inside
    them, XLA:TPU accepts both programs for a 16 GB chip (it refuses one
    over HBM), the pool is aliased through, decode allocates no
    temporaries, and the wave returns one logits row per sequence."""
    sd = _sd(v5e)
    cfg = get_config("qwen2-7b")
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(
            lambda k: _init_params(k, cfg, "int8", None),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        ),
    )
    pool = sd((2, cfg.n_layers, cfg.n_kv_heads, 65536, cfg.head_dim), cfg.dtype)
    gib = 2.0**30

    compiled = decode_step.lower(
        params, cfg, sd((B,), jnp.int32), pool, sd((B,), jnp.int32),
        sd((B, MAXP), jnp.int32), sd((B,), jnp.int32), PAGE,
    ).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.alias_size_in_bytes / gib == pytest.approx(3.5)  # the pool
    assert mem.argument_size_in_bytes / gib == pytest.approx(10.6, abs=0.05)
    assert mem.temp_size_in_bytes / gib < 0.05

    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((B, CHUNK), jnp.int32), sd((B, CHUNK), jnp.int32),
        pool, sd((B, CHUNK), jnp.int32), sd((B, 128), jnp.int32),
        sd((B,), jnp.int32), page_size=PAGE, logits_at=sd((B,), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info[0].shape == (B, 1, cfg.vocab_size)
    assert mem.alias_size_in_bytes / gib == pytest.approx(3.5)
    live = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    ) / gib
    assert live < 15.75, f"prefill wave needs {live:.2f} GiB"


def test_mellum2_period_compiles_for_one_v5e(v5e):
    """One period of Mellum2-12B-A2.5B (sliding x3 + full) at published
    widths, bf16: Mosaic accepts the grouped expert product (64 experts of
    2304 x 896 as one block each) and the fused decode and chunk kernels
    WITH a window start (32 q / 4 kv heads, G = 8), under both call-site
    names; the pool is aliased through; the step returns the expert ids
    and the counters."""
    from radixmesh_tpu.models.mellum2 import mellum2_12b_a2_5b

    sd = _sd(v5e)
    cfg = mellum2_12b_a2_5b(4)
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(
            lambda k: _init_params(k, cfg, None, None),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        ),
    )
    pool = sd((2, cfg.n_layers, cfg.n_kv_heads, 65536, cfg.head_dim), cfg.dtype)
    rows = 32
    compiled = decode_step.lower(
        params, cfg, sd((rows,), jnp.int32), pool, sd((rows,), jnp.int32),
        sd((rows, 1024), jnp.int32), sd((rows,), jnp.int32), PAGE,
    ).compile()
    text = compiled.as_text()
    assert "radixmesh_moe_gmm_decode" in text
    assert "radixmesh_decode_fused_mh" in text
    logits, _, aux = compiled.out_info
    assert logits.shape == (rows, cfg.vocab_size)
    assert aux["expert_ids"].shape == (4, rows, 1, 8)
    assert aux["moe_stats"].shape == (2, 4, 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 4 * 4 * 65536 * 128 * 2  # the pool
    assert mem.temp_size_in_bytes / 2.0**30 < 0.3

    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((B, CHUNK), jnp.int32), sd((B, CHUNK), jnp.int32),
        pool, sd((B, CHUNK), jnp.int32), sd((B, 512), jnp.int32),
        sd((B,), jnp.int32), page_size=PAGE, logits_at=sd((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "radixmesh_moe_gmm_prefill" in text and "radixmesh_chunk_attn" in text
    assert compiled.out_info[0].shape == (B, 1, cfg.vocab_size)

"""Sharded serving (SURVEY §7 stage 7; VERDICT round-1 weak #4).

The serving hot path — Engine scheduler + paged pool + decode_step — must
run unchanged on a multi-device mesh: params tp-sharded, pool sharded on
the kv-head axis, GSPMD partitioning the jnp ops and shard_map carrying
the Pallas kernel. Runs on the 8-device virtual CPU mesh (conftest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.ops.attention import (
    attend_decode_ref,
    paged_attention_pool_kernel_sharded,
)
from radixmesh_tpu.parallel.sharding import MeshPlan, make_mesh

CFG = ModelConfig.tiny().replace(n_heads=4, n_kv_heads=4)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=6)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshPlan(dp=1, sp=2, tp=4))


def test_sharded_engine_matches_single_device(mesh):
    """Same greedy tokens with and without the mesh: sharding changes
    array placement, not semantics."""
    prompts = [
        np.random.default_rng(0).integers(1, CFG.vocab_size, 24).tolist(),
        np.random.default_rng(1).integers(1, CFG.vocab_size, 17).tolist(),
    ]
    single = Engine(CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4)
    want = single.generate(prompts, GREEDY)
    sharded = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4, device_mesh=mesh
    )
    got = sharded.generate(prompts, GREEDY)
    assert want == got


def test_weights_and_pool_are_born_sharded(mesh):
    """``init_params(mesh=)`` and the engine's pool never exist whole on
    one device first: every tp-sharded leaf arrives as four quarter
    shards (a model bigger than one chip has no other way in), with the
    same values as the unsharded draw — and the engine's own placement
    step leaves them where they are."""
    sharded = init_params(CFG, jax.random.PRNGKey(0), mesh=mesh)
    for name, full in (("wq", PARAMS["layers"]["wq"]), ("embed", None)):
        leaf = sharded["layers"][name] if full is not None else sharded[name]
        shards = {s.device.id: s.data.shape for s in leaf.addressable_shards}
        assert len(shards) == 8  # dp=1 x sp=2 x tp=4: tp quarters, sp copies
        tp_axis = {"wq": 2, "embed": 0}[name]
        want = list(leaf.shape)
        want[tp_axis] //= 4
        assert set(shards.values()) == {tuple(want)}
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), sharded, PARAMS
    ))
    engine = Engine(
        CFG, sharded, num_slots=1024, page_size=4, max_batch=4, device_mesh=mesh
    )
    assert engine.params["layers"]["wq"] is sharded["layers"]["wq"]
    kv = engine.pool.kv
    assert {s.data.shape[2] for s in kv.addressable_shards} == {1}  # Hkv 4 / tp 4


def test_sharded_prefix_hit(mesh):
    """Cache publish + reuse work against the kv-head-sharded pool."""
    engine = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4, device_mesh=mesh
    )
    prompt = list(range(1, 25))
    engine.generate([prompt], GREEDY)
    engine.generate([prompt + [100, 101]], GREEDY)
    assert engine.stats.cached_tokens >= 24


def test_tp_divisibility_validated(mesh):
    bad = ModelConfig.tiny()  # 2 kv heads, tp=4
    with pytest.raises(ValueError, match="divide tp"):
        Engine(bad, init_params(bad, jax.random.PRNGKey(0)), device_mesh=mesh)


def test_shard_map_kernel_matches_oracle(mesh):
    """The shard_map'd Pallas pool kernel (interpret mode on the CPU mesh)
    agrees with the gather oracle — validates the tp partitioning specs
    independently of Mosaic."""
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, page, P_, L = 2, 8, 4, 128, 8, 16, 2
    max_pages = 4
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(2, L, Hkv, P_, page, D)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(P_)[: B * max_pages].reshape(B, max_pages), jnp.int32
    )
    ln = jnp.asarray([3, max_pages * page], jnp.int32)
    layer = 1
    pages = kv.reshape(2, L, Hkv, P_, page, D)
    want = attend_decode_ref(q, pages[0, layer], pages[1, layer], pt, ln)
    got = paged_attention_pool_kernel_sharded(
        q, pages, pt, ln, layer, mesh, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-5, atol=2e-5,
    )


class TestSequenceParallelPrefill:
    """SP serving prefill (SURVEY §5 serving-side; VERDICT round-1 item 31
    'nothing in models/ or engine/ calls them'): a fresh long prompt
    prefills sequence-sharded via ring attention over the sp axis."""

    def test_sp_prefill_matches_dense(self, mesh):
        from radixmesh_tpu.models.llama import prefill_forward, prefill_forward_sp

        cfg = CFG.replace(dtype=jnp.float32)
        params = init_params(cfg, jax.random.PRNGKey(3))
        S = 64
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(1, cfg.vocab_size, (2, S)),
            jnp.int32,
        )
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (2, S))
        got, gk, gv = prefill_forward_sp(params, cfg, tokens, positions, mesh)
        empty = jnp.zeros((cfg.n_layers, 2, 0, cfg.n_kv_heads, cfg.head_dim),
                          cfg.dtype)
        want, wk, wv = prefill_forward(
            params, cfg, tokens, positions, empty, empty,
            jnp.zeros((2,), jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(np.asarray(gk), np.asarray(wk), rtol=2e-4,
                                   atol=2e-4)

    def test_engine_sp_prefill_end_to_end(self, mesh):
        """An engine on the mesh routes a fresh long prompt through the
        sp path and its published KV is a valid cache for a follow-up."""
        cfg = CFG.replace(dtype=jnp.float32)
        params = init_params(cfg, jax.random.PRNGKey(3))
        eng = Engine(
            cfg, params, num_slots=2048, page_size=4, max_batch=2,
            device_mesh=mesh, sp_prefill_threshold=48,
        )
        single = Engine(cfg, params, num_slots=2048, page_size=4, max_batch=2)
        prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, 60).tolist()
        out_sp = eng.generate([prompt], GREEDY)[0]
        out_single = single.generate([prompt], GREEDY)[0]
        assert out_sp == out_single
        # Follow-up hits the cache published by the sp prefill.
        cached_before = eng.stats.cached_tokens
        out2 = eng.generate([prompt + [9, 8]], GREEDY)[0]
        assert len(out2) == 6
        assert eng.stats.cached_tokens - cached_before >= 56

"""Qwen2-72B / v5p scale-out rehearsal (VERDICT round-2 next-step #8).

The BASELINE.md last row — "Qwen2-72B 32k functional, v5p-64" — cannot
run here (one chip or one four-chip host, CPU tests), so this file is
the CPU-simulated stand-in:

- the REAL 72B config is instantiated abstractly (``jax.eval_shape``):
  param count, per-leaf pp×tp divisibility, per-device memory after
  sharding, and a full 32k-pool serving-chunk TRACE through
  ``pp_forward_chunk`` with the real shardings — proving the 72B serving
  program is well-formed without 72B of RAM;
- a dims-scaled live run exercises the same topology end to end on the
  8-device virtual mesh: dp=2 replicas × (pp=2 × tp=2), long-context
  chunked prefill through the pipeline, cross-replica KV migration over
  the ICI plane (``IciHandoff``), and distributed dup GC reclaiming the
  duplicate's slots — the whole v5p story, scaled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.models.qwen2 import qwen2_72b, qwen2_tiny
from radixmesh_tpu.parallel.pp_serving import (
    make_pp_serving_mesh,
    pp_forward_chunk,
    pp_layer_specs,
    pp_pool_spec,
    shard_params_pp,
)


class TestQwen272BAbstract:
    """The real 72B config, shapes only."""

    def test_param_count_and_sharding_divisibility(self):
        cfg = qwen2_72b()
        key = jax.random.PRNGKey(0)
        abstract = jax.eval_shape(lambda k: init_params(cfg, k), key)
        n_params = sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(abstract)
        )
        assert 71e9 < n_params < 74e9, f"{n_params/1e9:.1f}B params"

        pp, tp = 2, 4
        specs = pp_layer_specs()
        for name, leaf in abstract["layers"].items():
            spec = specs[name]
            for dim, axis in zip(leaf.shape, spec):
                if axis == "pp":
                    assert dim % pp == 0, (name, leaf.shape)
                elif axis == "tp":
                    assert dim % tp == 0, (name, leaf.shape)

        # Per-device bytes after pp x tp sharding: the stacked layer
        # stack must split by the full mesh; embed/lm_head replicate.
        layer_bytes = sum(
            int(np.prod(l.shape)) * 2  # bf16
            for l in jax.tree.leaves(abstract["layers"])
        )
        per_dev = layer_bytes / (pp * tp)
        # 72B: ~69B of layer params / 8 devices ≈ 17 GB < v5p's 95 GB HBM.
        assert per_dev < 20e9, f"{per_dev/1e9:.1f} GB per device"

    def test_32k_serving_chunk_traces_with_real_shardings(self):
        """jax.eval_shape of pp_forward_chunk on the FULL 72B config with
        a 32k-context paged pool: the sharded serving program traces —
        every shape constraint (head splits, layer splits, microbatch
        schedule, pool scatter) holds at target scale."""
        cfg = qwen2_72b()
        mesh = make_pp_serving_mesh(pp=2, tp=4)
        B, C, ps = 4, 256, 16
        num_slots = 32768 * B  # a full 32k context per row
        maxp = 32768 // ps

        def shaped(shape, dtype=cfg.dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        abstract_params = jax.eval_shape(
            lambda k: init_params(cfg, k), jax.random.PRNGKey(0)
        )
        out = jax.eval_shape(
            lambda p, t, pos, pool, sl, pt, kl: pp_forward_chunk(
                p, cfg, t, pos, pool, sl, pt, kl,
                page_size=ps, mesh=mesh, n_micro=2,
            ),
            abstract_params,
            shaped((B, C), jnp.int32),
            shaped((B, C), jnp.int32),
            shaped((2, cfg.n_layers, cfg.n_kv_heads, num_slots,
                    cfg.head_dim)),
            shaped((B, C), jnp.int32),
            shaped((B, maxp), jnp.int32),
            shaped((B,), jnp.int32),
        )
        logits, pool = out
        assert logits.shape == (B, C, cfg.vocab_size)
        assert pool.shape[3] == num_slots


class TestScaledLiveRehearsal:
    """dp=2 x (pp=2 x tp=2) live on 8 virtual devices, dims scaled."""

    @pytest.fixture(scope="class")
    def setup(self):
        # Qwen2 architecture (qkv biases, 1e6 rope), long-context window,
        # fp32 so cross-replica token parity is exact.
        cfg = qwen2_tiny().replace(max_seq_len=16384, dtype=jnp.float32)
        params = init_params(cfg, jax.random.PRNGKey(11))
        devs = jax.devices()
        mesh_a = make_pp_serving_mesh(pp=2, tp=2, devices=devs[:4])
        mesh_b = make_pp_serving_mesh(pp=2, tp=2, devices=devs[4:8])
        return cfg, params, mesh_a, mesh_b

    def test_long_context_pp_prefill_and_migration_and_gc(self, setup):
        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig
        from radixmesh_tpu.engine.disagg import (
            DecodeWorker,
            IciHandoff,
            PrefillWorker,
        )

        cfg, params, mesh_a, mesh_b = setup
        S = 8192  # scaled stand-in for the 32k gate (same chunked path;
        # the full 32_768 single-chip run is tests/test_long_context.py)
        ps, chunk = 32, 1024

        InprocHub.reset_default()
        prefill, decode = ["a0"], ["b0"]
        mesh_nodes = []
        for addr in prefill + decode:
            mc = MeshConfig(
                prefill_nodes=prefill, decode_nodes=decode, router_nodes=[],
                local_addr=addr, protocol="inproc",
                tick_interval_s=0.05, gc_interval_s=600.0,
            )
            mesh_nodes.append(MeshCache(mc).start())
        for m in mesh_nodes:
            assert m.wait_ready(timeout=10)
        mesh_cache_a, mesh_cache_b = mesh_nodes

        # dp replica A: pp x tp prefill worker publishing to the ring.
        pre = PrefillWorker(
            cfg, params, num_slots=S + 4096, page_size=ps, max_batch=2,
            prefill_chunk=chunk, long_prefill_threshold=2048,
            device_mesh=mesh_a, mesh=mesh_cache_a, name="72b-a",
        )
        # dp replica B: pp x tp decode engine on the OTHER device subset.
        dec_engine = Engine(
            cfg, params, num_slots=S + 4096, page_size=ps, max_batch=2,
            prefill_chunk=chunk, long_prefill_threshold=2048,
            device_mesh=mesh_b, mesh=mesh_cache_b, name="72b-b",
        )
        dec = DecodeWorker(dec_engine)

        prompt = (
            np.random.default_rng(4).integers(1, cfg.vocab_size, S).tolist()
        )
        sampling = SamplingParams(temperature=0.0, max_new_tokens=2)

        # 1) Long-context chunked prefill THROUGH THE PIPELINE on A, then
        # KV migration A→B over the ICI plane.
        ici = Mesh(np.asarray(jax.devices()[:8]), axis_names=("dp",))
        chan = IciHandoff(ici, "dp", src_rank=0, dst_rank=4, page_size=ps)
        pkt = chan.move(pre.prefill_handoff(prompt, sampling, device_kv=True))
        assert isinstance(pkt.kv, jax.Array)
        assert pre.stats.prompt_tokens == S
        req = dec.submit(pkt)
        dec.run_until_drained()
        assert len(req.output_tokens) == 2

        # Reference: a plain single-device engine agrees token-for-token.
        ref = Engine(
            cfg, params, num_slots=S + 4096, page_size=ps, max_batch=2,
            prefill_chunk=chunk, long_prefill_threshold=2048,
        )
        want = ref.generate([prompt], sampling)[0]
        assert req.output_tokens == want

        # 2) A follow-up on B sharing the migrated prefix is a cache hit.
        cached0 = dec_engine.stats.cached_tokens
        follow = prompt + [9, 8, 7]
        req2 = dec.submit(
            chan.move(pre.prefill_handoff(follow, sampling, device_kv=True))
        )
        dec.run_until_drained()
        assert len(req2.output_tokens) == 2
        assert dec_engine.stats.cached_tokens - cached0 >= S - ps

        # 3) Both replicas now hold KV for the same prefix → the ring's
        # conflict resolution recorded a duplicate → distributed GC
        # reclaims the loser's slots.
        import time as _time

        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline and not (
            mesh_cache_a.dup_nodes or mesh_cache_b.dup_nodes
        ):
            _time.sleep(0.05)
        dups = len(mesh_cache_a.dup_nodes) + len(mesh_cache_b.dup_nodes)
        assert dups > 0, "conflicting inserts never produced a dup entry"
        # These mesh nodes are advertisement-only (pool=None — the ENGINE
        # owns slot lifetime, test_mesh_serving.py's wiring), so the GC
        # laps retire the dup entries ring-wide rather than freeing pool
        # slots; allocator-freeing GC is covered by
        # tests/test_mesh_cache.py on mesh-owned pools.
        rounds0 = sum(m.metrics["gc_rounds"] for m in mesh_nodes)
        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline and (
            mesh_cache_a.dup_nodes or mesh_cache_b.dup_nodes
        ):
            for m in mesh_nodes:
                m.run_gc_round()
            _time.sleep(0.2)
        assert not mesh_cache_a.dup_nodes and not mesh_cache_b.dup_nodes, (
            "dup GC never retired the duplicate entries ring-wide"
        )
        assert sum(m.metrics["gc_rounds"] for m in mesh_nodes) > rounds0
        for m in mesh_nodes:
            m.close()

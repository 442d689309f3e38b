"""Int8 KV-cache quantization: pool storage, attention numerics (oracle +
Pallas kernels in interpreter mode), and engine integration.

Decode streams the whole context's K/V per layer per token, so int8 pages
halve the dominant HBM traffic (SURVEY §6). Correctness bar: quantized
attention must match the *quantized oracle* almost exactly (same int8
values, same scales — the only difference is contraction order), and the
end-to-end engine must stay functional with bounded numeric drift.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.cache.kv_pool import PagedKVPool
from radixmesh_tpu.engine import Engine, SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.ops.attention import attend_decode_ref
from radixmesh_tpu.ops.paged_attention import (
    paged_attention_pool_kernel,
    paged_decode_fused_kernel,
)
from radixmesh_tpu.ops.quant import dequantize_kv, quantize_kv


class TestQuantHelpers:
    def test_round_trip_error_bound(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 64, 128)) * 3.0, jnp.float32)
        q, s = quantize_kv(x, axis=-1)
        back = dequantize_kv(q, s, axis=-1)
        # Symmetric int8: |err| <= scale/2 = amax/254 per vector.
        amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
        assert np.all(np.abs(np.asarray(back) - np.asarray(x)) <= amax / 253)

    def test_zero_vector_safe(self):
        q, s = quantize_kv(jnp.zeros((3, 8)), axis=-1)
        assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s) > 0)
        assert np.all(np.asarray(dequantize_kv(q, s)) == 0)


class TestQuantPool:
    def test_write_gather_round_trip(self):
        rng = np.random.default_rng(1)
        pool = PagedKVPool(
            num_slots=64, num_layers=2, num_kv_heads=2, head_dim=16,
            page_size=4, quant="int8",
        )
        assert pool.kv.dtype == jnp.int8
        slots = pool.alloc(10)
        k = jnp.asarray(rng.normal(size=(2, 10, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 10, 2, 16)), jnp.float32)
        pool.write(slots, k, v)
        g = np.asarray(pool.gather(slots))  # dequantized [2, L, n, H, D]
        for src, got in ((k, g[0]), (v, g[1])):
            src = np.asarray(src).transpose(0, 1, 2, 3)
            amax = np.max(np.abs(src), axis=-1, keepdims=True)
            assert np.all(np.abs(got - src) <= amax / 250 + 1e-7)

    def test_rejects_unknown_quant(self):
        with pytest.raises(ValueError):
            PagedKVPool(num_slots=8, num_layers=1, num_kv_heads=1, head_dim=8,
                        quant="fp4")


def _quantized_pool_fixture(rng, L=2, Hkv=4, D=128, page=16, P=32):
    kv = jnp.asarray(rng.normal(size=(2, L, Hkv, P * page, D)), jnp.float32)
    q8, sc = quantize_kv(kv, axis=-1)
    return (
        q8.reshape(2, L, Hkv, P, page, D),
        sc.reshape(2, L, Hkv, P, page),
    )


class TestQuantKernels:
    def test_pool_kernel_matches_quant_oracle(self):
        rng = np.random.default_rng(2)
        kvp, scp = _quantized_pool_fixture(rng)
        B, Hq, D, page, P, maxp = 3, 8, 128, 16, 32, 8
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        pt = jnp.asarray(rng.permutation(P)[: B * maxp].reshape(B, maxp), jnp.int32)
        ln = jnp.asarray([1, 3 * page + 5, maxp * page], jnp.int32)
        for layer in (0, 1):
            want = np.asarray(
                attend_decode_ref(
                    q, kvp[0, layer], kvp[1, layer], pt, ln,
                    scp[0, layer], scp[1, layer],
                ),
                np.float32,
            )
            got = np.asarray(
                paged_attention_pool_kernel(
                    q, kvp, pt, ln, layer, interpret=True, kv_scales=scp
                ),
                np.float32,
            )
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_fused_kernel_writes_and_matches(self):
        rng = np.random.default_rng(3)
        kvp, scp = _quantized_pool_fixture(rng)
        B, Hq, Hkv, D, page, P, maxp = 3, 8, 4, 128, 16, 32, 8
        layer = 1
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        k_new = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        pt = jnp.asarray(rng.permutation(P)[: B * maxp].reshape(B, maxp), jnp.int32)
        ln = jnp.asarray([1, 3 * page + 6, maxp * page], jnp.int32)
        slots = jnp.asarray(
            [
                int(pt[b, (int(ln[b]) - 1) // page]) * page
                + (int(ln[b]) - 1) % page
                for b in range(B)
            ],
            jnp.int32,
        )
        out, kv2, sc2 = paged_decode_fused_kernel(
            q, k_new, v_new, kvp, slots, pt, ln, layer,
            interpret=True, kv_scales=scp,
        )
        # Oracle: quantize the row identically, scatter, attend with scales.
        kq, ksc = quantize_kv(k_new, axis=-1)
        vq, vsc = quantize_kv(v_new, axis=-1)
        S = P * page
        kvp_o = kvp.at[0, layer].set(
            kvp[0, layer].reshape(Hkv, S, D).at[:, slots]
            .set(kq.transpose(1, 0, 2)).reshape(Hkv, P, page, D)
        )
        kvp_o = kvp_o.at[1, layer].set(
            kvp[1, layer].reshape(Hkv, S, D).at[:, slots]
            .set(vq.transpose(1, 0, 2)).reshape(Hkv, P, page, D)
        )
        scp_o = scp.at[0, layer].set(
            scp[0, layer].reshape(Hkv, S).at[:, slots].set(ksc.T)
            .reshape(Hkv, P, page)
        )
        scp_o = scp_o.at[1, layer].set(
            scp[1, layer].reshape(Hkv, S).at[:, slots].set(vsc.T)
            .reshape(Hkv, P, page)
        )
        want = np.asarray(
            attend_decode_ref(
                q, kvp_o[0, layer], kvp_o[1, layer], pt, ln,
                scp_o[0, layer], scp_o[1, layer],
            ),
            np.float32,
        )
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)
        # Pool updates are bit-exact vs the reference quantizer.
        assert np.array_equal(np.asarray(kv2), np.asarray(kvp_o))
        np.testing.assert_allclose(np.asarray(sc2), np.asarray(scp_o), rtol=1e-6)


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny().replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def quant_engine(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 512)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    return Engine(cfg, params, kv_quant="int8", **kw)


class TestQuantEngine:
    def test_generates_and_first_token_exact(self, model):
        cfg, params = model
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (9, 13)]
        sp = SamplingParams(temperature=0.0, max_new_tokens=8)
        ref = Engine(cfg, params, num_slots=512, page_size=4, max_batch=4,
                     max_seq_len=128).generate(prompts, sp)
        eng = quant_engine(model)
        out = eng.generate(prompts, sp)
        assert all(len(o) == 8 for o in out)
        # Quantized engines prefill through the chunked paged path, which
        # attends the quantize→dequantized K/V — logits drift from bf16 is
        # bounded by the int8 step (~amax/254 per value), far below this
        # model's argmax margins, so the first sampled token agrees.
        for o, r in zip(out, ref):
            assert o[0] == r[0]

    def test_prefix_cache_hit_serves_from_quant_pool(self, model):
        cfg, params = model
        rng = np.random.default_rng(6)
        eng = quant_engine(model)
        prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
        sp = SamplingParams(temperature=0.0, max_new_tokens=6)
        first = eng.generate([prompt], sp)[0]
        follow = prompt + first
        out = eng.generate([follow], sp)[0]
        assert eng.stats.cached_tokens > 0
        assert len(out) == 6

    def test_chunked_long_prefill_quant(self, model):
        cfg, params = model
        rng = np.random.default_rng(7)
        eng = quant_engine(model, long_prefill_threshold=16, prefill_chunk=16,
                           num_slots=1024, max_seq_len=256)
        prompt = rng.integers(1, cfg.vocab_size, 90).tolist()
        out = eng.generate(
            [prompt], SamplingParams(temperature=0.0, max_new_tokens=5)
        )[0]
        assert len(out) == 5

    def test_spec_path_quant(self, model):
        cfg, params = model
        rng = np.random.default_rng(8)
        prompt = rng.integers(1, cfg.vocab_size, 10).tolist()
        sp = SamplingParams(temperature=0.0, max_new_tokens=9)
        ref = quant_engine(model).generate([prompt], sp)[0]
        spec = quant_engine(model, spec_decode_tokens=3)
        assert spec.generate([prompt], sp)[0] == ref

    def test_quant_engine_with_host_tier(self, model):
        # Int8 pool + host-RAM tier: evicted prefixes back up as int8 +
        # scales and restore verbatim; a follow-up still serves correctly.
        cfg, params = model
        eng = quant_engine(model, num_slots=64, host_cache_slots=256)
        rng = np.random.default_rng(12)
        sp = SamplingParams(temperature=0.0, max_new_tokens=4)
        prompts = [rng.integers(1, cfg.vocab_size, 14).tolist() for _ in range(4)]
        for p in prompts:  # churn a tiny pool to force write-backs
            eng.generate([p], sp)
        out = eng.generate([prompts[0]], sp)[0]
        assert len(out) == 4

    def test_sharded_quant_engine_matches_single_device(self, model):
        """tp-sharded serving over a quantized pool: same greedy tokens as
        the unsharded quantized engine (sharding must not change decode
        math; scales shard with their kv heads)."""
        cfg, params = model
        from radixmesh_tpu.parallel.sharding import MeshPlan, make_mesh

        mesh = make_mesh(MeshPlan(dp=1, sp=1, tp=2))
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (8, 11)]
        sp = SamplingParams(temperature=0.0, max_new_tokens=7)
        want = quant_engine(model).generate(prompts, sp)
        got = quant_engine(model, device_mesh=mesh).generate(prompts, sp)
        assert got == want

    def test_sharded_quant_kernel_matches_oracle(self):
        """The shard_map'd quantized pool kernel (interpret mode on the
        CPU mesh) against the quantized jnp oracle."""
        from radixmesh_tpu.ops.attention import (
            paged_attention_pool_kernel_sharded,
        )
        from radixmesh_tpu.parallel.sharding import MeshPlan, make_mesh

        mesh = make_mesh(MeshPlan(dp=1, sp=1, tp=2))
        rng = np.random.default_rng(10)
        kvp, scp = _quantized_pool_fixture(rng, L=2, Hkv=4, D=128, page=16, P=16)
        B, Hq, D, page, P, maxp = 2, 8, 128, 16, 16, 4
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        pt = jnp.asarray(
            rng.permutation(P)[: B * maxp].reshape(B, maxp), jnp.int32
        )
        ln = jnp.asarray([page + 2, maxp * page], jnp.int32)
        want = np.asarray(
            attend_decode_ref(
                q, kvp[0, 1], kvp[1, 1], pt, ln, scp[0, 1], scp[1, 1]
            ),
            np.float32,
        )
        got = np.asarray(
            paged_attention_pool_kernel_sharded(
                q, kvp, pt, ln, 1, mesh, interpret=True, kv_scales=scp
            ),
            np.float32,
        )
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

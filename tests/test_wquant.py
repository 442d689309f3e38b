"""W8A16 weight quantization (ops/wquant.py): storage halves, logits stay
within per-out-channel int8 error, and the serving engine runs end-to-end
on quantized weights (VERDICT round-4 next-step #7 — the path that puts
Llama-3-8B on one 16 GB v5e)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine import Engine, SamplingParams
from radixmesh_tpu.models.llama import (
    ModelConfig,
    init_params,
    param_logical_axes,
    prefill_forward,
)
from radixmesh_tpu.ops.wquant import (
    LAYER_QUANT_WEIGHTS,
    quantize_params,
    quantize_weight,
)


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny().replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(11))
    return cfg, params


class TestQuantizeWeight:
    def test_round_trip_error_bounded(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
        q, s = quantize_weight(w, axis=0)
        assert q.dtype == jnp.int8
        assert s.shape == (96,)
        deq = np.asarray(q, np.float32) * np.asarray(s)[None, :]
        # Symmetric int8 per-channel: error ≤ scale/2 per element.
        err = np.abs(deq - np.asarray(w))
        assert np.all(err <= np.asarray(s)[None, :] * 0.5 + 1e-7)

    def test_outlier_channel_isolated(self):
        """One huge output channel must not inflate the others' scales."""
        w = np.ones((8, 4), np.float32)
        w[:, 2] = 1000.0
        q, s = quantize_weight(jnp.asarray(w), axis=0)
        s = np.asarray(s)
        assert s[2] > 5.0 and np.all(s[[0, 1, 3]] < 0.01)


class TestQuantizeParams:
    def test_leaves_and_scales(self, model):
        cfg, params = model
        qp = quantize_params(params)
        for name in LAYER_QUANT_WEIGHTS:
            w = qp["layers"][name]
            assert w.dtype == jnp.int8, name
            s = qp["layers"][name + "_s"]
            assert s.shape == w.shape[:1] + w.shape[2:], name
        assert qp["embed"].dtype == jnp.int8
        assert qp["embed_s"].shape == (cfg.vocab_size,)
        assert qp["lm_head"].dtype == jnp.int8
        assert qp["lm_head_s"].shape == (cfg.vocab_size,)
        # Norms stay full precision.
        assert qp["final_norm"].dtype == params["final_norm"].dtype
        # Idempotent.
        qp2 = quantize_params(qp)
        assert qp2["layers"]["wq"] is qp["layers"]["wq"]

    def test_axes_cover_scales(self, model):
        cfg, params = model
        qp = quantize_params(params)
        axes = param_logical_axes(cfg, qp)
        for name in LAYER_QUANT_WEIGHTS:
            assert name + "_s" in axes["layers"], name
        assert axes["lm_head_s"] == ("vocab",)
        flat_p = jax.tree.leaves(qp)
        flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
        assert len(flat_p) == len(flat_a)

    def test_logits_close_to_full_precision(self, model):
        cfg, params = model
        qp = quantize_params(params)
        rng = np.random.default_rng(5)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
        ck = jnp.zeros((cfg.n_layers, 2, 0, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
        want, _, _ = prefill_forward(
            params, cfg, tokens, positions, ck, ck, jnp.zeros((2,), jnp.int32)
        )
        got, _, _ = prefill_forward(
            qp, cfg, tokens, positions, ck, ck, jnp.zeros((2,), jnp.int32)
        )
        w, g = np.asarray(want), np.asarray(got)
        # Per-channel int8 weights: logits track within a small fraction
        # of the logit RANGE (quantization noise accumulates over layers).
        span = np.abs(w).max()
        assert np.abs(g - w).max() < 0.05 * span
        # Greedy decisions overwhelmingly agree.
        agree = (w.argmax(-1) == g.argmax(-1)).mean()
        assert agree >= 0.9


class TestEngineWeightQuant:
    def test_generate_runs_and_tracks_bf16(self, model):
        cfg, params = model
        prompts = [
            np.random.default_rng(7).integers(0, cfg.vocab_size, 10).tolist()
            for _ in range(2)
        ]
        eng = Engine(
            cfg, params, num_slots=256, page_size=4, max_batch=2,
            max_seq_len=64, weight_quant="int8",
        )
        out = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        assert all(len(o) == 6 for o in out)
        assert all(0 <= t < cfg.vocab_size for o in out for t in o)

    def test_pp_matches_single_device(self, model):
        """W8A16 weights under pp x tp: the scale leaves shard with their
        weights (pp_layer_specs), the pipeline embeds/head-projects
        through the int8 table, and greedy tokens match the single-device
        int8-weight engine exactly."""
        cfg, params = model
        if len(jax.devices()) < 4:
            pytest.skip("needs >=4 devices for a pp x tp mesh")
        prompts = [
            np.random.default_rng(9).integers(1, cfg.vocab_size, 14).tolist(),
            np.random.default_rng(10).integers(1, cfg.vocab_size, 9).tolist(),
        ]
        sampling = SamplingParams(temperature=0.0, max_new_tokens=6)
        single = Engine(
            cfg, params, num_slots=512, page_size=4, max_batch=2,
            weight_quant="int8",
        )
        want = single.generate(prompts, sampling)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:4]).reshape(2, 2), ("pp", "tp")
        )
        pp_eng = Engine(
            cfg, params, num_slots=512, page_size=4, max_batch=2,
            weight_quant="int8", device_mesh=mesh,
        )
        got = pp_eng.generate(prompts, sampling)
        assert got == want


class TestDirectW8Init:
    def test_decodes_through_engine(self, model):
        """``init_params(weight_quant="int8")`` (the 7B-on-one-chip serve
        path: weights drawn directly in W8A16 form, a layer at a time)
        must produce a pytree the whole serving stack accepts — and the
        engine's own quantize step must leave it alone."""
        from radixmesh_tpu.models.llama import init_params

        cfg, _ = model
        params = init_params(cfg, jax.random.PRNGKey(3), weight_quant="int8")
        assert params["layers"]["wq"].dtype == jnp.int8
        assert params["embed"].dtype == jnp.int8
        eng = Engine(
            cfg, params, num_slots=256, page_size=4, max_batch=2,
            max_seq_len=64, weight_quant="int8",
        )
        assert eng.params["layers"]["wq"] is params["layers"]["wq"]
        prompts = [
            np.random.default_rng(4).integers(0, cfg.vocab_size, 9).tolist()
        ]
        out = eng.generate(prompts, SamplingParams(max_new_tokens=5))
        assert len(out[0]) == 5

    def test_matches_quantize_params_scheme(self, model):
        """Same layout as quantize_params: per-out-channel scales over
        the contraction axis, embed per row — and the same 1/sqrt(fan_in)
        init as the float path."""
        from radixmesh_tpu.models.llama import init_params
        from radixmesh_tpu.ops.wquant import quantize_params

        cfg, _ = model
        p = init_params(cfg, jax.random.PRNGKey(0), weight_quant="int8")
        ref = quantize_params(init_params(cfg, jax.random.PRNGKey(0)))
        assert jax.tree.map(lambda x: (x.shape, x.dtype), p) == jax.tree.map(
            lambda x: (x.shape, x.dtype), ref
        )
        deq = p["layers"]["wq"][0].astype(np.float32) * p["layers"]["wq_s"][0]
        np.testing.assert_allclose(
            float(deq.std()) * np.sqrt(cfg.hidden), 1.0, rtol=0.1
        )
        # Every output channel saturates the int8 range (its amax → ±127).
        assert (np.abs(np.asarray(p["layers"]["wq"])).max(axis=1) == 127).all()

    def test_rejects_unknown_scheme(self, model):
        from radixmesh_tpu.models.llama import init_params

        with pytest.raises(ValueError, match="unknown weight quantization"):
            init_params(model[0], jax.random.PRNGKey(0), weight_quant="int4")


class TestW8Compositions:
    def test_spec_decode_compose(self, model):
        """Speculative decoding over W8A16 weights: greedy output must be
        bit-identical to the non-speculative int8-weight engine (spec
        verify and plain decode share the same quantized forward)."""
        cfg, params = model
        prompt = np.random.default_rng(3).integers(
            0, cfg.vocab_size, 12
        ).tolist()
        # Force a repeated n-gram so prompt-lookup drafting has material.
        prompt = prompt + prompt[:6]
        sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
        plain = Engine(
            cfg, params, num_slots=256, page_size=4, max_batch=1,
            max_seq_len=96, weight_quant="int8",
        )
        want = plain.generate([prompt], sampling)[0]
        spec = Engine(
            cfg, params, num_slots=256, page_size=4, max_batch=1,
            max_seq_len=96, weight_quant="int8", spec_decode_tokens=3,
        )
        got = spec.generate([prompt], sampling)[0]
        assert got == want

    def test_qwen2_bias_compose(self):
        """Qwen2's qkv biases stay full-precision and add AFTER the
        per-out-channel scale — logits must track the bf16 engine."""
        from radixmesh_tpu.models import get_config
        from radixmesh_tpu.models.llama import init_params

        cfg = get_config("qwen2-tiny", dtype=jnp.float32)
        assert cfg.qkv_bias
        params = init_params(cfg, jax.random.PRNGKey(4))
        # Give ALL the biases real values (zeros would hide an
        # add-before-scale ordering bug in any of the three projections).
        for i, name in enumerate(("bq", "bk", "bv")):
            params["layers"][name] = (
                jax.random.normal(jax.random.PRNGKey(5 + i),
                                  params["layers"][name].shape) * 0.1
            )
        prompt = np.random.default_rng(6).integers(
            0, cfg.vocab_size, 10
        ).tolist()
        sampling = SamplingParams(temperature=0.0, max_new_tokens=6)
        base = Engine(cfg, params, num_slots=256, page_size=4, max_batch=1,
                      max_seq_len=64)
        w8 = Engine(cfg, params, num_slots=256, page_size=4, max_batch=1,
                    max_seq_len=64, weight_quant="int8")
        out_base = base.generate([prompt], sampling)[0]
        out_w8 = w8.generate([prompt], sampling)[0]
        assert len(out_w8) == 6
        # Quantization may flip a rare argmax; prefixes overwhelmingly
        # agree on a tiny model.
        agree = sum(a == b for a, b in zip(out_base, out_w8))
        assert agree >= 4, (out_base, out_w8)

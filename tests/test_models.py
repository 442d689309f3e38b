"""Model-family tests (tiny configs, fp32, CPU).

The golden test is cache-path equivalence: decode over paged radix-cache
KV must reproduce dense full-prefill logits, and prefill-with-cached-prefix
must reproduce full prefill — the exactness properties that make radix
prefix reuse sound end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    _PREC,
    _attn_out,
    _qkv,
    _wmm,
    convert_hf_state_dict,
    decode_step,
    init_params,
    param_logical_axes,
    prefill_forward,
)
from radixmesh_tpu.ops.norm import rms_norm
from radixmesh_tpu.ops.wquant import quantize_weight

PAGE = 4


def tiny(**kw):
    return get_config("llama3-tiny", dtype=jnp.float32, **kw)


def full_prefill(params, cfg, tokens):
    B, S = tokens.shape
    L = cfg.n_layers
    no_cache = jnp.zeros((L, B, 0, cfg.n_kv_heads, cfg.head_dim), dtype=jnp.float32)
    logits, new_k, new_v = prefill_forward(
        params,
        cfg,
        tokens,
        jnp.arange(S)[None, :].repeat(B, 0),
        no_cache,
        no_cache,
        jnp.zeros((B,), dtype=jnp.int32),
    )
    return logits, new_k, new_v


@pytest.fixture(scope="module")
def setup():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 13), 0, cfg.vocab_size)
    return cfg, params, tokens


class TestPrefill:
    def test_shapes(self, setup):
        cfg, params, tokens = setup
        logits, new_k, new_v = full_prefill(params, cfg, tokens)
        assert logits.shape == (1, 13, cfg.vocab_size)
        assert new_k.shape == (cfg.n_layers, 1, 13, cfg.n_kv_heads, cfg.head_dim)

    def test_cached_prefix_matches_full_prefill(self, setup):
        cfg, params, tokens = setup
        n_prefix = 8
        full_logits, new_k, new_v = full_prefill(params, cfg, tokens)
        # Continue from a cached prefix: K/V of the first 8 tokens.
        ck, cv = new_k[:, :, :n_prefix], new_v[:, :, :n_prefix]
        cont_logits, _, _ = prefill_forward(
            params,
            cfg,
            tokens[:, n_prefix:],
            jnp.arange(n_prefix, 13)[None, :],
            ck,
            cv,
            jnp.array([n_prefix], dtype=jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(cont_logits),
            np.asarray(full_logits[:, n_prefix:]),
            rtol=2e-4,
            atol=2e-4,
        )

    def test_ragged_right_aligned_prefix(self, setup):
        # Prefix region padded at the FRONT (P_max > prefix_len) must give
        # identical logits — the batched ragged-hit case.
        cfg, params, tokens = setup
        n_prefix, p_max = 8, 12
        full_logits, new_k, new_v = full_prefill(params, cfg, tokens)
        pad = p_max - n_prefix
        ck = jnp.pad(
            new_k[:, :, :n_prefix], ((0, 0), (0, 0), (pad, 0), (0, 0), (0, 0))
        )
        cv = jnp.pad(
            new_v[:, :, :n_prefix], ((0, 0), (0, 0), (pad, 0), (0, 0), (0, 0))
        )
        cont_logits, _, _ = prefill_forward(
            params,
            cfg,
            tokens[:, n_prefix:],
            jnp.arange(n_prefix, 13)[None, :],
            ck,
            cv,
            jnp.array([n_prefix], dtype=jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(cont_logits),
            np.asarray(full_logits[:, n_prefix:]),
            rtol=2e-4,
            atol=2e-4,
        )


class TestDecode:
    def test_paged_decode_matches_prefill_logits(self, setup):
        """Prefill S tokens, write KV to a paged pool, decode token S+1 —
        logits must equal dense prefill of S+1 tokens."""
        cfg, params, _ = setup
        S = 12  # multiple of PAGE
        tokens = jax.random.randint(jax.random.PRNGKey(3), (1, S + 1), 0, cfg.vocab_size)
        full_logits, new_k, new_v = full_prefill(params, cfg, tokens)

        # Paged pool holding the first S tokens' KV at slots 0..S-1.
        num_slots = 32
        kv_pool = jnp.zeros(
            (2, cfg.n_layers, cfg.n_kv_heads, num_slots, cfg.head_dim),
            dtype=jnp.float32,
        )
        # new_k: [L, B, S, Hkv, D] → head-major [L, Hkv, S, D].
        k_hm = new_k[:, 0, :S].transpose(0, 2, 1, 3)
        v_hm = new_v[:, 0, :S].transpose(0, 2, 1, 3)
        kv_pool = kv_pool.at[0, :, :, :S].set(k_hm)
        kv_pool = kv_pool.at[1, :, :, :S].set(v_hm)

        max_pages = num_slots // PAGE
        page_table = jnp.arange(max_pages, dtype=jnp.int32)[None, :]
        logits, kv_pool = decode_step(
            params,
            cfg,
            tokens[:, S],
            kv_pool,
            jnp.array([S], dtype=jnp.int32),
            page_table,
            jnp.array([S + 1], dtype=jnp.int32),
            page_size=PAGE,
        )
        np.testing.assert_allclose(
            np.asarray(logits),
            np.asarray(full_logits[:, S]),
            rtol=2e-4,
            atol=2e-4,
        )

    def test_multi_step_decode_matches_prefill(self, setup):
        """Three successive decode steps reproduce the dense logits."""
        cfg, params, _ = setup
        S = 8
        T = 3
        tokens = jax.random.randint(
            jax.random.PRNGKey(4), (1, S + T), 0, cfg.vocab_size
        )
        full_logits, new_k, new_v = full_prefill(params, cfg, tokens)
        num_slots = 16
        kv_pool = jnp.zeros(
            (2, cfg.n_layers, cfg.n_kv_heads, num_slots, cfg.head_dim),
            dtype=jnp.float32,
        )
        kv_pool = kv_pool.at[0, :, :, :S].set(new_k[:, 0, :S].transpose(0, 2, 1, 3))
        kv_pool = kv_pool.at[1, :, :, :S].set(new_v[:, 0, :S].transpose(0, 2, 1, 3))
        page_table = jnp.arange(num_slots // PAGE, dtype=jnp.int32)[None, :]
        for t in range(T):
            logits, kv_pool = decode_step(
                params,
                cfg,
                tokens[:, S + t],
                kv_pool,
                jnp.array([S + t], dtype=jnp.int32),
                page_table,
                jnp.array([S + t + 1], dtype=jnp.int32),
                page_size=PAGE,
            )
            np.testing.assert_allclose(
                np.asarray(logits),
                np.asarray(full_logits[:, S + t]),
                rtol=3e-4,
                atol=3e-4,
            )


class TestQwen2:
    def test_bias_params_exist_and_forward(self):
        cfg = get_config("qwen2-tiny", dtype=jnp.float32)
        params = init_params(cfg, jax.random.PRNGKey(0))
        assert "bq" in params["layers"]
        tokens = jnp.array([[1, 2, 3]])
        logits, _, _ = full_prefill(params, cfg, tokens)
        assert logits.shape == (1, 3, cfg.vocab_size)
        # Bias actually participates.
        params2 = dict(params)
        params2["layers"] = dict(params["layers"])
        params2["layers"]["bq"] = params["layers"]["bq"] + 1.0
        logits2, _, _ = full_prefill(params2, cfg, tokens)
        assert not np.allclose(np.asarray(logits), np.asarray(logits2))


class TestHFConversion:
    def test_roundtrip_against_init_shapes(self):
        cfg = tiny()
        params = init_params(cfg, jax.random.PRNGKey(0))
        # Build a synthetic HF state dict with matching shapes.
        state = {
            "model.embed_tokens.weight": np.asarray(params["embed"]),
            "model.norm.weight": np.asarray(params["final_norm"]),
            "lm_head.weight": np.asarray(params["lm_head"]).T,
        }
        hf_names = {
            "wq": "self_attn.q_proj",
            "wk": "self_attn.k_proj",
            "wv": "self_attn.v_proj",
            "wo": "self_attn.o_proj",
            "w_gate": "mlp.gate_proj",
            "w_up": "mlp.up_proj",
            "w_down": "mlp.down_proj",
        }
        for i in range(cfg.n_layers):
            state[f"model.layers.{i}.input_layernorm.weight"] = np.asarray(
                params["layers"]["attn_norm"][i]
            )
            state[f"model.layers.{i}.post_attention_layernorm.weight"] = np.asarray(
                params["layers"]["mlp_norm"][i]
            )
            for ours, theirs in hf_names.items():
                state[f"model.layers.{i}.{theirs}.weight"] = np.asarray(
                    params["layers"][ours][i]
                ).T
        converted = convert_hf_state_dict(cfg, state)
        # Converted params must produce identical logits.
        tokens = jnp.array([[5, 6, 7]])
        a, _, _ = full_prefill(params, cfg, tokens)
        b, _, _ = full_prefill(converted, cfg, tokens)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)

    def test_logical_axes_cover_every_param(self):
        for name in ("llama3-tiny", "qwen2-tiny"):
            cfg = get_config(name, dtype=jnp.float32)
            params = init_params(cfg, jax.random.PRNGKey(0))
            axes = param_logical_axes(cfg)
            flat_p = jax.tree_util.tree_leaves_with_path(params)
            flat_a = dict(
                (jax.tree_util.keystr(k), v)
                for k, v in jax.tree_util.tree_leaves_with_path(
                    axes, is_leaf=lambda x: isinstance(x, tuple)
                )
            )
            for path, leaf in flat_p:
                key = jax.tree_util.keystr(path)
                assert key in flat_a, f"no logical axes for {key}"
                assert len(flat_a[key]) == leaf.ndim, f"rank mismatch for {key}"


def _qkv_reshaped_behind_the_product(lp, x, cfg):
    """``_qkv`` as it was until PR 40: nothing between the three products
    and the bias / reshape to heads."""
    B, S = x.shape[:2]
    q, k, v = (_wmm(lp, w, "bsh,hd->bsd", x) for w in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_head_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_head_norm"], cfg.rms_eps)
    return q, k, v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)


def _attn_out_by_head(lp, attn, cfg):
    """``_attn_out`` as it was until PR 40 for a config without latent
    attention: ``wo`` viewed ``[Hq, D, H]`` and contracted per head."""
    B, S = attn.shape[:2]
    w = lp["wo"]
    y = jnp.einsum(
        "bsqd,qdh->bsh", attn.reshape(B, S, cfg.n_heads, cfg.head_dim),
        w.astype(attn.dtype).reshape(cfg.n_heads, cfg.head_dim, cfg.hidden),
        precision=_PREC,
    )
    if w.dtype == jnp.int8:
        y = (y * lp["wo_s"]).astype(attn.dtype)
    return y


class TestProjectionForms:
    """PR 40 changed how ``_qkv`` and ``_attn_out`` are WRITTEN (a barrier
    behind the products, ``wo`` as one flat product) so that a layer scan
    reads each weight where it lies in its stack; the values are the old
    forms', at the served head geometries, in both weight storages."""

    LAYERS, TOKENS = 2, 5

    def _stack(self, preset, quant):
        cfg = get_config(preset)
        keys = iter(jax.random.split(jax.random.PRNGKey(40), 16))
        H, q_w, kv_w = cfg.hidden, cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        stack = {}
        for name, shape in (("wq", (H, q_w)), ("wk", (H, kv_w)),
                            ("wv", (H, kv_w)), ("wo", (q_w, H))):
            w = jax.random.normal(next(keys), (self.LAYERS, *shape)) * shape[0] ** -0.5
            if quant:
                stack[name], stack[name + "_s"] = quantize_weight(w, axis=1)
            else:
                stack[name] = w.astype(cfg.dtype)
        if cfg.qkv_bias:
            for name, n in (("bq", q_w), ("bk", kv_w), ("bv", kv_w)):
                stack[name] = jax.random.normal(
                    next(keys), (self.LAYERS, n)).astype(cfg.dtype)
        if cfg.qk_norm:
            for name in ("q_head_norm", "k_head_norm"):
                stack[name] = 1 + 0.1 * jax.random.normal(
                    next(keys), (self.LAYERS, cfg.head_dim)).astype(cfg.dtype)
        x = jax.random.normal(next(keys), (2, self.TOKENS, H)).astype(cfg.dtype)
        return cfg, stack, x

    @staticmethod
    def _scanned(qkv, attn_out, cfg, stack, x):
        """Both projections of every layer of the stack under one
        ``lax.scan`` on the same ``x``, the heads' q standing in for the
        attention's output: ``(q, k, v, out)``, each ``[layers, ...]``."""
        def layer(x, lp):
            q, k, v = qkv(lp, x, cfg)
            return x, (q, k, v, attn_out(lp, q, cfg))

        return jax.jit(lambda x, stack: jax.lax.scan(layer, x, stack)[1])(x, stack)

    @pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("preset", ["qwen2-7b", "lfm2-24b-a2b-ep8"])
    def test_values_are_the_old_forms(self, preset, quant):
        cfg, stack, x = self._stack(preset, quant)
        assert cfg.qkv_bias == (preset == "qwen2-7b")
        assert cfg.qk_norm == (preset != "qwen2-7b")
        new = self._scanned(_qkv, _attn_out, cfg, stack, x)
        old = self._scanned(
            _qkv_reshaped_behind_the_product, _attn_out_by_head, cfg, stack, x)
        # One bf16 unit on values of order 1: XLA:CPU rounds a product that
        # feeds a float32 norm once less where nothing stands between them,
        # and wo's contraction is associated otherwise.
        for a, b in zip(new, old):
            assert a.shape == b.shape and a.dtype == b.dtype == cfg.dtype
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2.0 ** -7, atol=2.0 ** -7)

    @pytest.mark.parametrize("preset", ["qwen2-7b", "lfm2-24b-a2b-ep8"])
    def test_attn_out_takes_heads_or_flat(self, preset):
        cfg, stack, x = self._stack(preset, quant=False)
        lp = jax.tree.map(lambda w: w[0], stack)
        q, _, _ = _qkv(lp, x, cfg)
        flat = q.reshape(*q.shape[:2], -1)
        np.testing.assert_array_equal(
            np.asarray(_attn_out(lp, q, cfg), np.float32),
            np.asarray(_attn_out(lp, flat, cfg), np.float32))

"""Numerics tests for the TPU compute ops (CPU jax; the Pallas kernel runs
in interpreter mode here and compiled on real TPU via chip_smoke.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.ops.attention import attend_decode_ref, attend_prefill
from radixmesh_tpu.ops.norm import rms_norm
from radixmesh_tpu.ops.paged_attention import paged_attention_kernel
from radixmesh_tpu.ops.rope import apply_rope, rope_frequencies
from radixmesh_tpu.ops.sampling import sample_tokens


class TestRmsNorm:
    def test_matches_manual(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16,))
        got = rms_norm(x, w)
        want = x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True) + 1e-5) * w
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    def test_bf16_computes_in_fp32(self):
        x = (jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 100).astype(
            jnp.bfloat16
        )
        w = jnp.ones((64,), dtype=jnp.bfloat16)
        got = rms_norm(x, w)
        assert got.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


class TestRope:
    def test_rotation_preserves_norm(self):
        inv = rope_frequencies(64)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 4, 64))
        pos = jnp.arange(10)[None, :]
        y = apply_rope(x, pos, inv)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1),
            rtol=1e-5,
        )

    def test_position_zero_is_identity(self):
        inv = rope_frequencies(32)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 32))
        y = apply_rope(x, jnp.zeros((1, 1), dtype=jnp.int32), inv)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)

    def test_relative_property(self):
        # <R(p)q, R(p+k)x> depends only on k: shift both positions, dot
        # products are unchanged.
        inv = rope_frequencies(64)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))
        def dot_at(p0, p1):
            qr = apply_rope(q, jnp.array([[p0]]), inv)
            kr = apply_rope(k, jnp.array([[p1]]), inv)
            return float(jnp.sum(qr * kr))
        assert dot_at(3, 7) == pytest.approx(dot_at(103, 107), rel=1e-4)

    def test_llama3_scaling_changes_low_freqs(self):
        base = rope_frequencies(128)
        scaled = rope_frequencies(
            128,
            llama3_scaling={
                "factor": 8.0,
                "low_freq_factor": 1.0,
                "high_freq_factor": 4.0,
                "original_max_position_embeddings": 8192,
            },
        )
        # High-frequency (early) components unchanged, low-frequency scaled.
        np.testing.assert_allclose(np.asarray(base[:8]), np.asarray(scaled[:8]))
        assert np.all(np.asarray(scaled[-8:]) < np.asarray(base[-8:]))


class TestPrefillAttention:
    def test_causal_first_token_attends_self_only(self):
        B, S, H, D = 1, 4, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
        pos = jnp.arange(S)[None, :]
        out = attend_prefill(q, k, v, pos, jnp.array([S]))
        np.testing.assert_allclose(
            np.asarray(out[0, 0]), np.asarray(v[0, 0]), rtol=1e-5
        )

    def test_prefix_continuation_matches_full_prefill(self):
        # Attention over [prefix + new] computed in one shot must equal
        # prefill of the new chunk against cached prefix KV — the equality
        # that makes radix prefix reuse exact.
        B, S, H, D = 1, 8, 2, 16
        n_prefix = 5
        rng = jax.random.PRNGKey(0)
        q = jax.random.normal(rng, (B, S, H, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
        pos = jnp.arange(S)[None, :]
        full = attend_prefill(q, k, v, pos, jnp.array([S]))
        cont = attend_prefill(
            q[:, n_prefix:], k, v, pos[:, n_prefix:], jnp.array([S])
        )
        np.testing.assert_allclose(
            np.asarray(full[:, n_prefix:]), np.asarray(cont), rtol=2e-5, atol=1e-5
        )

    def test_gqa_grouping(self):
        # 4 q heads over 2 kv heads == repeating kv to 4 heads.
        B, S, D = 1, 6, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, 4, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, D))
        pos = jnp.arange(S)[None, :]
        got = attend_prefill(q, k, v, pos, jnp.array([S]))
        krep = jnp.repeat(k, 2, axis=2)
        vrep = jnp.repeat(v, 2, axis=2)
        want = attend_prefill(q, krep, vrep, pos, jnp.array([S]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def _paged_setup(key, B=3, Hq=8, Hkv=2, D=32, page=8, n_pages_pool=16, max_pages=4):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype=jnp.float32)
    # Head-major pool layout (PagedKVPool.pages_for_layer).
    k_pages = jax.random.normal(ks[1], (Hkv, n_pages_pool, page, D), dtype=jnp.float32)
    v_pages = jax.random.normal(ks[2], (Hkv, n_pages_pool, page, D), dtype=jnp.float32)
    # Non-contiguous, per-sequence page tables.
    page_table = jax.random.permutation(ks[3], n_pages_pool)[: B * max_pages].reshape(
        B, max_pages
    )
    lengths = jnp.array([1, page + 3, page * max_pages])[:B]
    return q, k_pages, v_pages, page_table.astype(jnp.int32), lengths.astype(jnp.int32)


class TestPagedAttention:
    def test_kernel_matches_reference(self):
        args = _paged_setup(jax.random.PRNGKey(0))
        want = attend_decode_ref(*args)
        got = paged_attention_kernel(*args, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    def test_kernel_matches_reference_bf16(self):
        q, kp, vp, pt, ln = _paged_setup(jax.random.PRNGKey(7))
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        want = attend_decode_ref(q, kp, vp, pt, ln).astype(jnp.float32)
        got = paged_attention_kernel(q, kp, vp, pt, ln, interpret=True).astype(
            jnp.float32
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-2, atol=3e-2)

    def test_single_token_context(self):
        q, kp, vp, pt, ln = _paged_setup(jax.random.PRNGKey(1), B=1)
        ln = jnp.array([1], dtype=jnp.int32)
        want = attend_decode_ref(q, kp, vp, pt, ln)
        got = paged_attention_kernel(q, kp, vp, pt, ln, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    def test_kernel_reads_the_page_table(self):
        # Attention is permutation-invariant over its KV set (positions are
        # baked into K via RoPE), so page *order* must NOT change the output
        # — but substituting a different page must.
        q, kp, vp, pt, ln = _paged_setup(jax.random.PRNGKey(2), B=1)
        ln = jnp.array([32], dtype=jnp.int32)
        base = paged_attention_kernel(q, kp, vp, pt, ln, interpret=True)
        swapped = pt.at[0, 0].set(pt[0, 1]).at[0, 1].set(pt[0, 0])
        perm = paged_attention_kernel(q, kp, vp, swapped, ln, interpret=True)
        np.testing.assert_allclose(
            np.asarray(base), np.asarray(perm), rtol=2e-5, atol=2e-5
        )
        unused = [p for p in range(kp.shape[1]) if p not in np.asarray(pt[0])][0]
        substituted = pt.at[0, 1].set(unused)
        other = paged_attention_kernel(q, kp, vp, substituted, ln, interpret=True)
        assert not np.allclose(np.asarray(base), np.asarray(other))


class TestSampling:
    def test_greedy(self):
        logits = jnp.array([[0.1, 5.0, 0.2], [3.0, 0.0, 0.1]])
        out = sample_tokens(logits, jax.random.PRNGKey(0), temperature=0.0)
        np.testing.assert_array_equal(np.asarray(out), [1, 0])

    def test_top_k_restricts_support(self):
        logits = jnp.array([[10.0, 9.0, -5.0, -6.0]])
        draws = [
            int(sample_tokens(logits, jax.random.PRNGKey(i), temperature=1.0, top_k=2)[0])
            for i in range(50)
        ]
        assert set(draws) <= {0, 1}
        assert len(set(draws)) == 2  # actually samples, not greedy

    def test_top_p_keeps_argmax(self):
        logits = jnp.array([[100.0, 0.0, 0.0, 0.0]])
        out = sample_tokens(
            logits, jax.random.PRNGKey(0), temperature=1.0, top_p=0.1
        )
        assert int(out[0]) == 0

    def test_top_p_zero_degenerates_to_argmax(self):
        # top_p=0 must still keep the argmax (the keep-first carve-out).
        logits = jnp.array([[0.0, 3.0, 1.0]])
        for i in range(5):
            out = sample_tokens(
                logits, jax.random.PRNGKey(i), temperature=1.0, top_p=0.0
            )
            assert int(out[0]) == 1

    def test_temperature_flattens(self):
        logits = jnp.array([[2.0, 1.0]])
        hot = [
            int(sample_tokens(logits, jax.random.PRNGKey(i), temperature=10.0)[0])
            for i in range(200)
        ]
        # At high temperature both tokens appear frequently.
        assert min(hot.count(0), hot.count(1)) > 30


def _logits_with_ties(rows, vocab, dtype, seed=0):
    """Random logits whose row maximum appears two or three times (bf16
    rounding adds ties of its own): first-index argmax is the contract."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, vocab)).astype(np.float32) * 3.0
    for r in range(rows):
        at = rng.choice(vocab, size=2 + r % 2, replace=False)
        x[r, at] = x[r].max() + 1.0
    return jnp.asarray(x).astype(dtype)


class TestSamplerRunsWhatItsRowsAsk:
    """``sample_tokens(..., draws=...)`` (static): a launch no row of
    which draws is the argmax alone; every other launch is the executable
    of before PR 26, to the bit (``frozen_sampler``)."""

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("rows", [1, 4, 32])
    def test_argmax_variant_equals_drawing_variant_on_greedy_rows(
        self, rows, dtype
    ):
        logits = _logits_with_ties(rows, 1000, dtype, seed=rows)
        zeros = jnp.zeros((rows,), jnp.float32)
        drawing = sample_tokens(
            logits, jax.random.PRNGKey(3), temperature=zeros,
            top_k=jnp.zeros((rows,), jnp.int32), top_p=jnp.ones((rows,)),
        )
        argmax = sample_tokens(logits, None, draws=False)
        assert argmax.dtype == drawing.dtype and argmax.shape == drawing.shape
        np.testing.assert_array_equal(np.asarray(argmax), np.asarray(drawing))
        # Ties were really there, and the first index won.
        x = np.asarray(logits.astype(jnp.float32))
        assert ((x == x.max(-1, keepdims=True)).sum(-1) >= 2).all()
        np.testing.assert_array_equal(np.asarray(argmax), x.argmax(-1))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("rows,seed", [(1, 0), (5, 1), (5, 2), (32, 3)])
    def test_drawing_variant_is_frozen(self, rows, seed, dtype):
        from tests import frozen_sampler

        # greedy, temperature only, top-k, top-p, all three — cycled.
        kinds = [(0.0, 0, 1.0), (0.8, 0, 1.0), (1.0, 7, 1.0),
                 (1.3, 0, 0.9), (0.7, 50, 0.95)]
        rows_kinds = [kinds[(i + seed) % len(kinds)] for i in range(rows)]
        temps = jnp.asarray([k[0] for k in rows_kinds], jnp.float32)
        top_k = jnp.asarray([k[1] for k in rows_kinds], jnp.int32)
        top_p = jnp.asarray([k[2] for k in rows_kinds], jnp.float32)
        logits = _logits_with_ties(rows, 1000, dtype, seed=seed)
        for key in (jax.random.PRNGKey(seed), jax.random.PRNGKey(2**31 + seed)):
            want = frozen_sampler.sample_tokens(
                logits, key, temperature=temps, top_k=top_k, top_p=top_p
            )
            got = sample_tokens(
                logits, key, temperature=temps, top_k=top_k, top_p=top_p
            )
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if rows > 1:  # the draws are draws: not every row came out greedy
            greedy = np.asarray(jnp.argmax(logits, axis=-1))
            assert (np.asarray(got) != greedy).any()

    def test_greedy_launch_at_served_size_is_one_reduction(self):
        """[32 rows, Qwen2's 152 064]: the program of an all-greedy launch
        holds no vocabulary sort, no cumulative sum, no random bits —
        as lowered and after XLA's passes (guards the gain with no chip).
        The drawing variant is the positive control: the same search
        finds all three there."""
        import re

        logits = jax.ShapeDtypeStruct((32, 152064), jnp.float32)
        per_row = [jax.ShapeDtypeStruct((32,), t)
                   for t in (jnp.float32, jnp.int32, jnp.float32)]
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)

        def ops(lowered):
            """Op and callee names of the StableHLO text, and opcodes of
            the compiled HLO (its metadata quotes this file: left out)."""
            hlo = re.sub(r"metadata=\{[^}]*\}", "", lowered.compile().as_text())
            named = re.findall(r"stablehlo\.(\w+)|@(\w+)", lowered.as_text())
            return {a or b for a, b in named} | set(
                re.findall(r" ([a-z][\w-]*)\(", hlo)
            )

        def heavy(names):
            return sorted(n for n in names if re.search(
                r"sort|cumsum|cumulative|reduce[_-]window|rng|threefry|"
                r"^exp|^log|erf", n))

        greedy = ops(sample_tokens.lower(logits, None, draws=False))
        assert "reduce" in greedy and heavy(greedy) == []
        drawing = heavy(ops(sample_tokens.lower(logits, key, *per_row)))
        assert "sort" in drawing
        assert any("threefry" in n or "rng" in n for n in drawing)
        assert any("cumsum" in n or "window" in n for n in drawing)


class TestPagedDecodeFused:
    """Fused write+attend decode kernel: one aliased pallas_call writes the
    current token's K/V row into the pool and attends over all ``length``
    tokens (the current one folded in from VMEM, never read back)."""

    def _setup(self, key, B=3, Hq=8, Hkv=2, D=32, page=8, n_pages=16, maxp=4,
               L=2):
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, Hq, D), dtype=jnp.float32)
        kv = jax.random.normal(
            ks[1], (2, L, Hkv, n_pages, page, D), dtype=jnp.float32
        )
        k_new = jax.random.normal(ks[2], (B, Hkv, D), dtype=jnp.float32)
        v_new = jax.random.normal(ks[3], (B, Hkv, D), dtype=jnp.float32)
        # Non-overlapping per-sequence page tables.
        pt = jax.random.permutation(ks[4], n_pages)[: B * maxp].reshape(B, maxp)
        lengths = jnp.array([1, page + 3, page * maxp])[:B]
        # Current token slot = position (length-1) within row b's pages.
        pos = lengths - 1
        slots = pt[jnp.arange(B), pos // page] * page + pos % page
        return (q, k_new, v_new, kv, slots.astype(jnp.int32),
                pt.astype(jnp.int32), lengths.astype(jnp.int32))

    def _oracle(self, q, k_new, v_new, kv, slots, pt, lengths, layer):
        page = kv.shape[4]
        pg, off = slots // page, slots % page
        layer_arr = jnp.asarray(layer)
        kv = kv.at[0, layer_arr, :, pg, off].set(k_new)
        kv = kv.at[1, layer_arr, :, pg, off].set(v_new)
        return attend_decode_ref(q, kv[0, layer], kv[1, layer], pt, lengths), kv

    @pytest.mark.parametrize("layer", [0, 1])
    def test_matches_scatter_then_oracle(self, layer):
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        args = self._setup(jax.random.PRNGKey(3))
        want_attn, want_kv = self._oracle(*args, layer)
        got_attn, got_kv = paged_decode_fused_kernel(*args, layer, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got_attn), np.asarray(want_attn), rtol=2e-5, atol=2e-5
        )
        # The pool row writes landed, and nothing else changed.
        np.testing.assert_allclose(
            np.asarray(got_kv), np.asarray(want_kv), rtol=1e-6, atol=1e-6
        )

    def test_single_token_rows(self):
        """length == 1 rows (fresh/scratch decode rows) take no HBM blocks:
        output is attention over just the current token — i.e. v_new."""
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        q, k_new, v_new, kv, slots, pt, lengths = self._setup(
            jax.random.PRNGKey(4), B=1
        )
        lengths = jnp.array([1], dtype=jnp.int32)
        got_attn, _ = paged_decode_fused_kernel(
            q, k_new, v_new, kv, slots, pt, lengths, 0, interpret=True
        )
        G = q.shape[1] // v_new.shape[1]
        want = jnp.repeat(v_new, G, axis=1)
        np.testing.assert_allclose(
            np.asarray(got_attn), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_dispatch_fallback_matches(self):
        """paged_decode_attention's jnp fallback equals the oracle."""
        from radixmesh_tpu.ops.attention import paged_decode_attention

        args = self._setup(jax.random.PRNGKey(5))
        want_attn, want_kv = self._oracle(*args, 1)
        got_attn, got_kv = paged_decode_attention(*args, 1, use_kernel=False)
        np.testing.assert_allclose(
            np.asarray(got_attn), np.asarray(want_attn), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(np.asarray(got_kv), np.asarray(want_kv))


class TestChunkHybrid:
    """attend_chunk_hybrid (chunk K/V dense, prior context from pages) must
    equal attend_prefill_paged with the chunk already written to the pool —
    the latter is the retained oracle for the hybrid online-softmax merge."""

    def test_hybrid_matches_paged_oracle(self):
        from radixmesh_tpu.ops.attention import (
            attend_chunk_hybrid,
            attend_prefill_paged,
        )

        rng = np.random.default_rng(11)
        B, C, Hq, Hkv, D, page, L = 2, 8, 8, 2, 32, 4, 2
        maxp, kvb = 8, 4
        prior = np.array([9, 17])  # ragged, not page-aligned
        kv = jnp.asarray(rng.normal(size=(2, L, Hkv, 64, page, D)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, C, Hq, D)), jnp.float32)
        k_cur = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
        v_cur = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
        pt = jnp.asarray(
            rng.permutation(64)[: B * maxp].reshape(B, maxp), jnp.int32
        )
        n_valid = np.array([C, C - 3])  # second row's chunk is partial
        positions = jnp.asarray(prior[:, None] + np.arange(C)[None], jnp.int32)
        prior_l = jnp.asarray(prior, jnp.int32)
        kv_len = jnp.asarray(prior + n_valid, jnp.int32)

        got = attend_chunk_hybrid(
            q, k_cur, v_cur, kv, pt, positions, prior_l, kv_len, 1,
            kv_block_pages=kvb,
        )

        # Oracle: write the chunk into its pool slots, then the pure-paged
        # blockwise path over everything.
        slots = np.empty((B, C), np.int64)
        for b in range(B):
            for j in range(C):
                pos = prior[b] + j
                slots[b, j] = int(pt[b, pos // page]) * page + pos % page
        kv_o = kv
        for b in range(B):
            for j in range(int(n_valid[b])):
                s = slots[b, j]
                kv_o = kv_o.at[0, 1, :, s // page, s % page].set(k_cur[b, j])
                kv_o = kv_o.at[1, 1, :, s // page, s % page].set(v_cur[b, j])
        want = attend_prefill_paged(
            q, kv_o, pt, positions, kv_len, 1, kv_block_pages=kvb
        )
        valid_mask = np.arange(C)[None] < n_valid[:, None]
        np.testing.assert_allclose(
            np.asarray(got)[valid_mask], np.asarray(want)[valid_mask],
            rtol=2e-5, atol=2e-5,
        )


class TestPagedChunkKernel:
    """``paged_chunk_attention_kernel`` (Pallas, interpret mode) vs the
    jnp ``attend_chunk_hybrid`` oracle: SURVEY §7 hard part (a) for the
    prefill side. Canonical query positions (prior + arange(C)) are the
    kernel's contract — the only form any serving path produces."""

    def _setup(self, seed, B=3, C=8, Hq=4, Hkv=2, D=32, page=4, maxp=8, L=2):
        rng = np.random.default_rng(seed)
        P = B * maxp + 2
        q = jnp.asarray(rng.normal(size=(B, C, Hq, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(2, L, Hkv, P, page, D)), jnp.float32)
        pt = jnp.asarray(
            rng.permutation(P)[: B * maxp].reshape(B, maxp).astype(np.int32)
        )
        return q, kc, vc, kv, pt

    @pytest.mark.parametrize("layer", [0, 1])
    def test_matches_hybrid(self, layer):
        from radixmesh_tpu.ops.attention import attend_chunk_hybrid
        from radixmesh_tpu.ops.paged_attention import (
            paged_chunk_attention_kernel,
        )

        q, kc, vc, kv, pt = self._setup(layer)
        C = q.shape[1]
        # Row 0: no prior (cold prefill); row 1: mid-page prior; row 2:
        # long prior + PARTIAL chunk (3 valid of 8).
        prior = jnp.asarray([0, 5, 17], jnp.int32)
        kvlen = prior + jnp.asarray([C, C, 3], jnp.int32)
        pos = prior[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        want = attend_chunk_hybrid(
            q, kc, vc, kv, pt, pos, prior, kvlen, layer, kv_block_pages=4
        )
        got = paged_chunk_attention_kernel(
            q, kc, vc, kv, pt, prior, kvlen, layer, interpret=True
        )
        valid = np.arange(C)[None, :] < np.asarray(kvlen - prior)[:, None]
        np.testing.assert_allclose(
            np.asarray(got)[valid], np.asarray(want)[valid],
            rtol=2e-5, atol=2e-5,
        )

    def test_query_blocking_invariant(self):
        """Splitting the chunk into query blocks must not change results
        (each block re-streams the prior pages independently)."""
        from radixmesh_tpu.ops.paged_attention import (
            paged_chunk_attention_kernel,
        )

        q, kc, vc, kv, pt = self._setup(7, C=16)
        prior = jnp.asarray([9, 0, 33], jnp.int32)
        kvlen = prior + 16
        base = paged_chunk_attention_kernel(
            q, kc, vc, kv, pt, prior, kvlen, 0, interpret=True, q_block=16
        )
        for qb in (1, 4, 8):
            blocked = paged_chunk_attention_kernel(
                q, kc, vc, kv, pt, prior, kvlen, 0, interpret=True, q_block=qb
            )
            np.testing.assert_allclose(
                np.asarray(blocked), np.asarray(base), rtol=2e-5, atol=2e-5
            )

    def test_int8_pool_matches_hybrid(self):
        from radixmesh_tpu.ops.attention import attend_chunk_hybrid
        from radixmesh_tpu.ops.paged_attention import (
            paged_chunk_attention_kernel,
        )

        rng = np.random.default_rng(3)
        B, C, Hq, Hkv, D, page, maxp, L = 2, 16, 8, 2, 32, 4, 16, 1
        P = B * maxp
        q = jnp.asarray(rng.normal(size=(B, C, Hq, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
        kv8 = jnp.asarray(
            rng.integers(-127, 128, (2, L, Hkv, P, page, D)), jnp.int8
        )
        sc = jnp.asarray(
            np.abs(rng.normal(size=(2, L, Hkv, P, page))) * 0.02, jnp.float32
        )
        pt = jnp.asarray(rng.permutation(P).reshape(B, maxp).astype(np.int32))
        prior = jnp.asarray([33, 7], jnp.int32)
        kvlen = prior + C
        pos = prior[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        want = attend_chunk_hybrid(
            q, kc, vc, kv8, pt, pos, prior, kvlen, 0, kv_block_pages=4,
            kv_scales=sc,
        )
        got = paged_chunk_attention_kernel(
            q, kc, vc, kv8, pt, prior, kvlen, 0, q_block=4, interpret=True,
            kv_scales=sc,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_page_table_permutation_invariant(self):
        """Page indirection is honored: permuting a row's pages together
        with its table entries must not change the output."""
        from radixmesh_tpu.ops.paged_attention import (
            paged_chunk_attention_kernel,
        )

        q, kc, vc, kv, pt = self._setup(11)
        prior = jnp.asarray([8, 20, 12], jnp.int32)
        kvlen = prior + q.shape[1]
        base = paged_chunk_attention_kernel(
            q, kc, vc, kv, pt, prior, kvlen, 0, interpret=True
        )
        # Swap two of row 1's prior pages in the table AND in the pool.
        pt2 = np.asarray(pt).copy()
        pt2[1, 0], pt2[1, 1] = pt2[1, 1], pt2[1, 0]
        kv2 = np.asarray(kv).copy()
        a, b = int(pt[1, 0]), int(pt[1, 1])
        kv2[:, :, :, [a, b]] = kv2[:, :, :, [b, a]]
        perm = paged_chunk_attention_kernel(
            q, kc, vc, jnp.asarray(kv2), jnp.asarray(pt2), prior, kvlen, 0,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(perm), np.asarray(base), rtol=2e-5, atol=2e-5
        )


    # One launch whose rows are every way a prefix can lie (block = 8
    # tokens: ``pages_per_block=2`` of 4): nothing cached; a prefix that
    # ends mid-page; exactly one block; several blocks on fragmented pages,
    # with a chunk whose last three query blocks hold no token; four blocks
    # on one ascending run of pages; and a row on the scratch slot.
    PRIOR = np.asarray([0, 5, 8, 27, 32, 0], np.int32)
    NEW = np.asarray([16, 16, 16, 3, 16, 0], np.int32)

    @pytest.mark.parametrize("window", [None, 3, 20, 0],
                             ids=["full", "in-block", "spans-blocks", "w0"])
    @pytest.mark.parametrize(
        "hq,hkv,scale", [(8, 2, 0.25), (7, 1, None), (8, 1, None)],
        ids=["G4-paired-wide", "G7", "G8"])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, None)],
                             ids=["f32-pool", "bf16-pool"])
    def test_operands_edges_and_empty_query_blocks(
            self, dtype, tol, hq, hkv, scale, window):
        """Operands in the pool's dtype (a bf16 pool against the float32
        oracle at ``kernel_check``'s tolerance), the prior bound and the
        window masked in whichever block they fall, blocks counted from a
        window's first page, no work for a
        query block without a token — and NaN wherever the kernel must not
        look: past a prefix inside its last page, and in the page that
        every unused table entry names."""
        from radixmesh_tpu.ops.attention import attend_chunk_hybrid
        from radixmesh_tpu.ops.kernel_check import TOLERANCE
        from radixmesh_tpu.ops.paged_attention import (
            paged_chunk_attention_kernel,
        )

        rng = np.random.default_rng(43)
        B, C, D, page, maxp, L = 6, 16, 32, 4, 16, 2
        P = B * maxp + 1
        # pages 1..80 shuffled over the five other rows, row 4's own run
        pt = 1 + rng.permutation(P - 1 - maxp).astype(np.int32)
        pt = np.insert(pt.reshape(B - 1, maxp), 4, P - maxp + np.arange(maxp), 0)
        used = -(-(self.PRIOR + C) // page)
        pt[np.arange(maxp)[None] >= used[:, None]] = 0  # the poisoned page
        kv = rng.normal(size=(2, L, hkv, P, page, D)).astype(np.float32)
        dirty = kv.copy()
        dirty[:, :, :, 0] = np.nan
        for b in np.flatnonzero(self.PRIOR % page):
            at = self.PRIOR[b]
            dirty[:, :, :, pt[b, at // page], at % page:] = np.nan
        q, kc, vc = (
            jnp.asarray(rng.normal(size=(B, C, h, D)), dtype)
            for h in (hq, hkv, hkv))
        prior, kvlen = jnp.asarray(self.PRIOR), jnp.asarray(self.PRIOR + self.NEW)
        pos = prior[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        w = None if window is None else jnp.int32(window)
        f32 = lambda x: jnp.asarray(x, dtype).astype(jnp.float32)  # noqa: E731
        want = attend_chunk_hybrid(
            f32(q), f32(kc), f32(vc), f32(kv), jnp.asarray(pt), pos, prior,
            kvlen, 1, kv_block_pages=4, window=w, scale=scale,
        )
        got = paged_chunk_attention_kernel(
            q, kc, vc, jnp.asarray(dirty, dtype), jnp.asarray(pt), prior,
            kvlen, 1, pages_per_block=2, q_block=4, interpret=True, window=w,
            scale=scale,
        )
        assert got.dtype == dtype
        got = np.asarray(got, np.float32)
        valid = np.arange(C)[None, :] < self.NEW[:, None]
        tol = tol or TOLERANCE
        np.testing.assert_allclose(
            got[valid], np.asarray(want)[valid], rtol=tol, atol=tol)
        # Query blocks 1..3 of row 3 and the whole scratch row: zeros.
        assert not got[3, 4:].any() and not got[5].any()

    @pytest.mark.parametrize("hkv,heads", [(2, 2), (64, 1)])
    def test_heads_a_program_follow_the_vmem_budget(self, hkv, heads, monkeypatch):
        """All kv heads a program while their double-buffered block fits
        (``_auto_fuse_heads``), one a program past that — the same kernel,
        the same answer."""
        from radixmesh_tpu.ops import paged_attention as pa

        # 64 heads of 128-token pages, float32: one page of K+V, double-
        # buffered, is 16 MiB
        geometry = pa._chunk_geometry(
            256, 4, hkv, 128, 128, jnp.float32, jnp.float32, 8, None, None,
            False)
        assert geometry[0] == heads
        q, kc, vc, kv, pt = self._setup(5, Hq=4, Hkv=2, C=8)
        prior = jnp.asarray([9, 0, 30], jnp.int32)
        args = (q, kc, vc, kv, pt, prior, prior + 8, 1)
        fused = pa.paged_chunk_attention_kernel(*args, interpret=True)
        monkeypatch.setattr(pa, "_auto_fuse_heads", lambda *a: False)
        jax.clear_caches()  # the entry point is jitted: trace it again
        per_head = pa.paged_chunk_attention_kernel(*args, interpret=True)
        jax.clear_caches()
        np.testing.assert_allclose(
            np.asarray(per_head), np.asarray(fused), rtol=2e-5, atol=2e-5)


class TestPoolKernelFusedHeads:
    """Heads-batched pool-kernel variant (``fuse_heads=True``): one
    program per sequence, one strided DMA per page for ALL kv heads —
    must be numerically identical to the per-head-program kernel."""

    def _setup(self, key, B=4, Hq=8, Hkv=2, D=32, page=8, n_pages=32, maxp=4,
               L=2):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, Hq, D), dtype=jnp.float32)
        kv = jax.random.normal(
            ks[1], (2, L, Hkv, n_pages, page, D), dtype=jnp.float32
        )
        pt = jax.random.permutation(ks[2], n_pages)[: B * maxp].reshape(B, maxp)
        # Ragged: empty row, single token, mid-page, full.
        lengths = jnp.array([0, 1, page + 3, page * maxp])[:B]
        return q, kv, pt.astype(jnp.int32), lengths.astype(jnp.int32)

    @pytest.mark.parametrize("layer", [0, 1])
    def test_matches_per_head_kernel(self, layer):
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel

        q, kv, pt, lengths = self._setup(jax.random.PRNGKey(9))
        want = paged_attention_pool_kernel(
            q, kv, pt, lengths, layer, interpret=True, fuse_heads=False
        )
        got = paged_attention_pool_kernel(
            q, kv, pt, lengths, layer, interpret=True, fuse_heads=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_bf16_and_multiblock(self):
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel

        q, kv, pt, lengths = self._setup(
            jax.random.PRNGKey(4), B=2, Hq=4, Hkv=4, maxp=6, n_pages=16
        )
        lengths = jnp.array([8 * 6, 13], jnp.int32)
        want = paged_attention_pool_kernel(
            q.astype(jnp.bfloat16), kv.astype(jnp.bfloat16), pt, lengths, 0,
            interpret=True, pages_per_block=2, fuse_heads=False,
        )
        got = paged_attention_pool_kernel(
            q.astype(jnp.bfloat16), kv.astype(jnp.bfloat16), pt, lengths, 0,
            interpret=True, pages_per_block=2, fuse_heads=True,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    @pytest.mark.parametrize("layer", [0, 1])
    def test_int8_matches_per_head_kernel(self, layer):
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel
        from radixmesh_tpu.ops.quant import quantize_kv

        q, kv, pt, lengths = self._setup(jax.random.PRNGKey(5))
        kv8, scales = quantize_kv(
            kv.reshape(*kv.shape[:3], -1, kv.shape[-1]), axis=-1
        )
        kv8 = kv8.reshape(kv.shape).astype(jnp.int8)
        scales = scales.reshape(kv.shape[:-1])
        want = paged_attention_pool_kernel(
            q, kv8, pt, lengths, layer, interpret=True, kv_scales=scales,
            fuse_heads=False,
        )
        got = paged_attention_pool_kernel(
            q, kv8, pt, lengths, layer, interpret=True, kv_scales=scales,
            fuse_heads=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_fused_int8_matches_per_head(self):
        """Round 5: heads-batched fused decode now supports int8 pools
        (round 4 raised NotImplementedError) — attn, pool rows, AND scale
        pool must match the per-head fused kernel bit-for-bit."""
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel
        from radixmesh_tpu.ops.quant import quantize_kv

        rng = np.random.default_rng(17)
        B, Hq, Hkv, D, page, n_pages, maxp, L = 3, 8, 2, 32, 8, 32, 4, 2
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        k_new = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        kvf = rng.normal(size=(2, L, Hkv, n_pages, page, D))
        kv8, scales = quantize_kv(
            jnp.asarray(kvf.reshape(2, L, Hkv, -1, D), jnp.float32), axis=-1
        )
        kv8 = kv8.reshape(2, L, Hkv, n_pages, page, D).astype(jnp.int8)
        scales = scales.reshape(2, L, Hkv, n_pages, page)
        pt = jnp.asarray(
            rng.permutation(n_pages)[: B * maxp].reshape(B, maxp), jnp.int32
        )
        # Inactive row, single-token row, multi-block row.
        lengths = jnp.asarray([0, 1, page * 2 + 3], jnp.int32)
        slots = (pt[:, 0] * page).astype(jnp.int32)
        for layer in range(L):
            want = paged_decode_fused_kernel(
                q, k_new, v_new, kv8, slots, pt, lengths, layer,
                interpret=True, kv_scales=scales, fuse_heads=False,
            )
            got = paged_decode_fused_kernel(
                q, k_new, v_new, kv8, slots, pt, lengths, layer,
                interpret=True, kv_scales=scales, fuse_heads=True,
            )
            np.testing.assert_allclose(
                np.asarray(got[0]), np.asarray(want[0]), rtol=2e-5, atol=2e-5
            )
            np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
            np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


class TestFusedHeadsDecode:
    """Heads-batched fused decode (``fuse_heads=True``): the write+attend
    contract must match the per-head fused kernel exactly — pool row
    writes included."""

    @pytest.mark.parametrize("layer", [0, 1])
    def test_matches_per_head_fused(self, layer):
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        helper = TestPagedDecodeFused()
        args = helper._setup(jax.random.PRNGKey(7), B=3, Hq=8, Hkv=2, maxp=4)
        # Zero the MIDDLE row: the batch then covers inactive (no write,
        # zero output), length==1 (zero-iteration block loop — the whole
        # context is the current token), and multi-block rows at once.
        q, k_new, v_new, kv, slots, pt, lengths = args
        lengths = lengths.at[1].set(0)
        args = (q, k_new, v_new, kv, slots, pt, lengths)
        want_attn, want_kv = paged_decode_fused_kernel(
            *args, layer, interpret=True, fuse_heads=False
        )
        got_attn, got_kv = paged_decode_fused_kernel(
            *args, layer, interpret=True, fuse_heads=True
        )
        np.testing.assert_allclose(
            np.asarray(got_attn), np.asarray(want_attn), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_array_equal(np.asarray(got_kv), np.asarray(want_kv))


class TestMhBlockLoop:
    """The heads-batched block loop at every block size the wrapper can
    be given or can choose: a row's last block moves only the pages the
    row still holds, a window row starts at the page of its first visible
    key, and only those two edge blocks are masked. Against the jnp
    oracle, interpret mode."""

    PAGE, MAXP, WINDOW = 4, 24, 20
    # tokens incl. the current one: alone, inside the first page, inside
    # the first block of every size, ending mid-block, on a block edge of
    # every size (hbm 64), one past it, inside the window, the whole table.
    LENGTHS = (1, 3, 7, 35, 65, 66, 18, 96)

    def _case(self, G, layout, dtype=jnp.float32, seed=0):
        rng = np.random.default_rng(seed)
        B, Hkv, D, page, maxp = len(self.LENGTHS), 2, 32, self.PAGE, self.MAXP
        P = B * maxp + maxp
        q = jnp.asarray(rng.standard_normal((B, Hkv * G, D)), dtype)
        kn = jnp.asarray(rng.standard_normal((B, Hkv, D)), dtype)
        vn = jnp.asarray(rng.standard_normal((B, Hkv, D)), dtype)
        kv = jnp.asarray(rng.standard_normal((2, 2, Hkv, P, page, D)), dtype)
        if layout == "run":  # every block of every row coalesces
            pt = np.arange(B * maxp).reshape(B, maxp)
        else:  # no two neighbours consecutive: the per-page plan
            pt = rng.permutation(B * maxp).reshape(B, maxp)
        lengths = np.asarray(self.LENGTHS, np.int32)
        pos = lengths - 1
        slots = pt[np.arange(B), pos // page] * page + pos % page
        return (q, kn, vn, kv, jnp.asarray(slots, jnp.int32),
                jnp.asarray(pt, jnp.int32), jnp.asarray(lengths))

    @pytest.mark.parametrize("ppb", [None, 2, 8, 16])
    @pytest.mark.parametrize("layout", ["run", "perm"])
    @pytest.mark.parametrize("windowed", [False, True])
    @pytest.mark.parametrize("G", [7, 8])
    def test_matches_oracle(self, G, windowed, layout, ppb):
        from radixmesh_tpu.ops.attention import paged_decode_attention
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        args = self._case(G, layout)
        # A windowed executable serves its full-attention layers too.
        for w in (self.WINDOW, 0) if windowed else (None,):
            window = None if w is None else jnp.int32(w)
            want, want_kv = paged_decode_attention(
                *args, 1, use_kernel=False, window=window)
            got, got_kv = paged_decode_fused_kernel(
                *args, 1, interpret=True, pages_per_block=ppb, window=window,
                fuse_heads=True)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
            np.testing.assert_array_equal(
                np.asarray(got_kv), np.asarray(want_kv))

    @pytest.mark.parametrize("windowed", [False, True])
    @pytest.mark.parametrize("G", [7, 8])
    def test_pool_dtype_operands_match_oracle(self, G, windowed):
        """A bf16 pool is contracted as fetched (bf16 operands, float32
        accumulation, bf16 ``p``): within the chip check's tolerance of
        the float32 oracle on the same bf16 values."""
        from radixmesh_tpu.ops.attention import paged_decode_attention
        from radixmesh_tpu.ops.kernel_check import TOLERANCE
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        args = self._case(G, "perm", jnp.bfloat16, seed=1)
        window = jnp.int32(self.WINDOW) if windowed else None
        want, _ = paged_decode_attention(
            *(a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
              for a in args), 0, use_kernel=False, window=window)
        got, _ = paged_decode_fused_kernel(
            *args, 0, interpret=True, window=window)
        err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
        assert np.max(err / (1 + np.abs(np.asarray(want)))) <= TOLERANCE

    @pytest.mark.parametrize("ppb", [2, 8])
    def test_unfetched_pages_of_a_last_block_hold_anything(self, ppb):
        """Pages past a row's end are not moved: whatever the staging
        buffer held there (here: the NaN block of the row before) is
        zeroed in both factors."""
        from radixmesh_tpu.ops.attention import paged_decode_attention
        from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

        q, kn, vn, kv, slots, pt, lengths = self._case(7, "run", seed=2)
        # Rows 0 and 2 share a buffer half; row 1 has no pool tokens.
        lengths = jnp.asarray([8 * self.PAGE + 1, 1, 5] + [0] * 5, jnp.int32)
        pos = np.asarray(lengths) - 1
        slots = jnp.asarray(
            np.asarray(pt)[np.arange(8), np.maximum(pos, 0) // self.PAGE]
            * self.PAGE + np.maximum(pos, 0) % self.PAGE, jnp.int32)
        want, _ = paged_decode_attention(
            q, kn, vn, kv, slots, pt, lengths, 0, use_kernel=False)
        poisoned = kv.at[:, :, :, pt[0, 2:8]].set(jnp.nan)
        got, _ = paged_decode_fused_kernel(
            q, kn, vn, poisoned, slots, pt, lengths, 0, interpret=True,
            pages_per_block=ppb)
        np.testing.assert_allclose(
            np.asarray(got)[1:3], np.asarray(want)[1:3], rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("hkv,max_pages,want", [
        (4, 128, 32),   # Qwen2-7B / Mellum2: 512 tokens, 2 MiB of VMEM
        (8, 512, 32),   # 4 MiB: still 512 tokens
        (16, 512, 16),  # halved to stay within 4 MiB
        (4, 24, 24),    # never wider than the page table
        (4, 1, 1),
    ])
    def test_block_size_follows_what_the_call_shows(self, hkv, max_pages, want):
        from radixmesh_tpu.ops.paged_attention import _mh_pages_per_block

        assert _mh_pages_per_block(
            hkv, 16, 128, jnp.bfloat16, max_pages) == want
        # An int8 pool's block is half the bytes: twice the heads fit.
        assert _mh_pages_per_block(
            2 * hkv, 16, 128, jnp.int8, max_pages) == want


    def test_block_tokens_shown_on_debug_state(self):
        from radixmesh_tpu.ops.paged_attention import decode_block_tokens

        assert decode_block_tokens(4, 16, 128, jnp.bfloat16, 2048) == 512
        assert decode_block_tokens(4, 16, 128, jnp.int8, 2048, True) == 512
        assert decode_block_tokens(2, 4, 32, jnp.float32, 8) == 32


class TestContigCoalescing:
    """Round-5 run-coalesced block DMAs: the wrapper's per-(row, block)
    flags (``_contig_flags``) choose between one contiguous descriptor and
    per-page copies — both paths must produce identical attention, and the
    flag logic itself is pinned here (a wrong flag on hardware is a silent
    wrong-data fetch, so the rules get exact-value coverage)."""

    def test_flag_rules(self):
        from radixmesh_tpu.ops.paged_attention import _contig_flags

        page, ppb, P = 4, 2, 64
        pt = jnp.asarray(
            [
                [10, 11, 12, 13],  # fully consecutive → both blocks flagged
                [10, 12, 20, 22],  # neither block consecutive
                [5, 6, 0, 0],      # valid prefix consecutive, pad entries 0
                [62, 63, 0, 0],    # consecutive but next run out of bounds
            ],
            jnp.int32,
        )
        # Row 2: only 1.5 pages valid (6 tokens) → block 0's two entries
        # are both valid-and-consecutive, block 1 is all pad (flagged:
        # its fetch is masked). Row 3: block 0's run [63, 64) overflows P
        # at ppb=2? first=62, 62+2=64 <= 64 → in bounds, flagged.
        lengths = jnp.asarray([16, 16, 6, 8], jnp.int32)
        flags = np.asarray(
            _contig_flags(pt, lengths, page, ppb, P)
        ).reshape(4, 2)
        np.testing.assert_array_equal(flags[0], [1, 1])
        np.testing.assert_array_equal(flags[1], [0, 0])
        # Row 2 block 0: entries (5, 6) consecutive → 1. Block 1: zero
        # valid entries → every position is pad → flagged (first=0,
        # 0+2<=64).
        np.testing.assert_array_equal(flags[2], [1, 1])
        np.testing.assert_array_equal(flags[3], [1, 1])
        # Out-of-bounds veto: first + ppb > P must clear the flag even
        # when entries are consecutive.
        pt_oob = jnp.asarray([[63, 64, 0, 0]], jnp.int32)
        flags_oob = np.asarray(
            _contig_flags(pt_oob, jnp.asarray([8], jnp.int32), page, ppb, P)
        )
        np.testing.assert_array_equal(flags_oob, [0, 1])

    @pytest.mark.parametrize("fuse_heads", [False, True])
    def test_coalesced_matches_fragmented(self, fuse_heads):
        """Same pool contents reachable through a consecutive table (all
        blocks coalesce) and a permuted table (no block coalesces) must
        attend identically — and both must match the jnp oracle."""
        from radixmesh_tpu.ops.paged_attention import (
            paged_attention_pool_kernel,
        )

        rng = np.random.default_rng(23)
        B, Hq, Hkv, D, page, maxp = 2, 4, 2, 32, 4, 8
        P = 64
        L = 1
        lengths = jnp.asarray([maxp * page, 13], jnp.int32)
        # Consecutive layout: row 0 pages 8..15, row 1 pages 30..37.
        pt_run = jnp.asarray(
            [np.arange(8, 8 + maxp), np.arange(30, 30 + maxp)], jnp.int32
        )
        kv = jnp.asarray(rng.normal(size=(2, L, Hkv, P, page, D)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        base = paged_attention_pool_kernel(
            q, kv, pt_run, lengths, 0, interpret=True, fuse_heads=fuse_heads
        )
        # Fragmented layout: permute each row's pages and move the data.
        perm0 = rng.permutation(maxp)
        perm1 = rng.permutation(maxp)
        pt_frag = np.zeros((B, maxp), np.int32)
        kv_frag = np.array(kv)
        scatter = rng.permutation(np.arange(40, 40 + 2 * maxp))
        for r, perm in enumerate([perm0, perm1]):
            for j, src_j in enumerate(perm):
                dst = scatter[r * maxp + j]
                pt_frag[r, src_j] = dst
                kv_frag[:, :, :, dst] = np.asarray(
                    kv[:, :, :, int(pt_run[r, src_j])]
                )
        frag = paged_attention_pool_kernel(
            q, jnp.asarray(kv_frag), jnp.asarray(pt_frag), lengths, 0,
            interpret=True, fuse_heads=fuse_heads,
        )
        np.testing.assert_allclose(
            np.asarray(frag), np.asarray(base), rtol=2e-5, atol=2e-5
        )
        want = attend_decode_ref(
            q, kv[0, 0], kv[1, 0], pt_run, lengths
        )
        np.testing.assert_allclose(
            np.asarray(base), np.asarray(want), rtol=2e-5, atol=2e-5
        )


class TestMaskedPadNaNIsolation:
    """ADVICE round-5 #1: a coalesced all-pad block fetches the contiguous
    page range implied by a row's FIRST table entry — which can stage pool
    pages NO table entry references. NaN/Inf resident in such a page (or
    in its scale rows, for int8 pools) must never reach a masked row's
    output: the block loops zero both factors of the p·v contraction at
    masked positions, so there is no finite-pool invariant to uphold."""

    def _case(self, seed=0, Hq=8, Hkv=2, D=32, page=8, n_pages=16):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        q = jax.random.normal(ks[0], (1, Hq, D), dtype=jnp.float32)
        kv = jax.random.normal(
            ks[1], (2, 1, Hkv, n_pages, page, D), dtype=jnp.float32
        )
        return q, kv, page

    @pytest.mark.parametrize("fuse_heads", [False, True])
    def test_coalesced_pad_fetch_of_unreferenced_nan_page(self, fuse_heads):
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel

        q, kv, page = self._case()
        # Valid entries (0, 1) are consecutive, so the block coalesces —
        # the pad entries (7, 9) don't veto it — and the single
        # ``pl.ds(0, 4)`` descriptor stages pages 2 and 3, which no table
        # entry references at all.
        pt = jnp.array([[0, 1, 7, 9]], dtype=jnp.int32)
        ln = jnp.array([page + 3], dtype=jnp.int32)  # 2 valid pages
        clean = paged_attention_pool_kernel(
            q, kv, pt, ln, 0, interpret=True, fuse_heads=fuse_heads
        )
        poisoned = kv.at[:, :, :, 2:4].set(jnp.nan)
        got = paged_attention_pool_kernel(
            q, poisoned, pt, ln, 0, interpret=True, fuse_heads=fuse_heads
        )
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(clean), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("fuse_heads", [False, True])
    def test_fragmented_pad_fetch_of_nan_page(self, fuse_heads):
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel

        q, kv, page = self._case(seed=1)
        # Non-consecutive valid entries: the per-page fallback path
        # fetches the pad entries' pages (9, 2) directly.
        pt = jnp.array([[0, 5, 9, 2]], dtype=jnp.int32)
        ln = jnp.array([page + 3], dtype=jnp.int32)
        clean = paged_attention_pool_kernel(
            q, kv, pt, ln, 0, interpret=True, fuse_heads=fuse_heads
        )
        poisoned = kv.at[:, :, :, 9].set(jnp.nan).at[:, :, :, 2].set(jnp.inf)
        got = paged_attention_pool_kernel(
            q, poisoned, pt, ln, 0, interpret=True, fuse_heads=fuse_heads
        )
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(clean), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("fuse_heads", [False, True])
    def test_int8_nan_scales_on_pad_pages(self, fuse_heads):
        from radixmesh_tpu.ops.paged_attention import paged_attention_pool_kernel
        from radixmesh_tpu.ops.quant import quantize_kv

        q, kv, page = self._case(seed=2)
        kv8, scales = quantize_kv(
            kv.reshape(*kv.shape[:3], -1, kv.shape[-1]), axis=-1
        )
        kv8 = kv8.reshape(kv.shape).astype(jnp.int8)
        scales = scales.reshape(kv.shape[:-1])
        pt = jnp.array([[0, 1, 7, 9]], dtype=jnp.int32)
        ln = jnp.array([page + 3], dtype=jnp.int32)
        clean = paged_attention_pool_kernel(
            q, kv8, pt, ln, 0, interpret=True, kv_scales=scales,
            fuse_heads=fuse_heads,
        )
        # int8 pages can't hold NaN, but their SCALE rows can: poison the
        # scales of every page the coalesced pad fetch touches or the pad
        # entries name.
        bad = scales.at[:, :, :, 2:4].set(jnp.nan).at[:, :, :, 7].set(
            jnp.nan
        ).at[:, :, :, 9].set(jnp.nan)
        got = paged_attention_pool_kernel(
            q, kv8, pt, ln, 0, interpret=True, kv_scales=bad,
            fuse_heads=fuse_heads,
        )
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(clean), rtol=1e-6, atol=1e-6
        )

    def test_chunk_kernel_nan_beyond_prior(self):
        from radixmesh_tpu.ops.attention import attend_chunk_hybrid
        from radixmesh_tpu.ops.paged_attention import (
            paged_chunk_attention_kernel,
        )

        C, Hq, Hkv, D, page = 8, 4, 2, 32, 8
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (1, C, Hq, D), dtype=jnp.float32)
        kc = jax.random.normal(ks[1], (1, C, Hkv, D), dtype=jnp.float32)
        vc = jax.random.normal(ks[2], (1, C, Hkv, D), dtype=jnp.float32)
        kv = jax.random.normal(
            ks[3], (2, 1, Hkv, 16, page, D), dtype=jnp.float32
        )
        pt = jnp.array([[0, 1, 7, 9]], dtype=jnp.int32)
        prior = jnp.array([page + 3], dtype=jnp.int32)
        kvlen = prior + C
        clean = paged_chunk_attention_kernel(
            q, kc, vc, kv, pt, prior, kvlen, 0, interpret=True
        )
        poisoned = kv.at[:, :, :, 2:4].set(jnp.nan)
        got = paged_chunk_attention_kernel(
            q, kc, vc, poisoned, pt, prior, kvlen, 0, interpret=True
        )
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(clean), rtol=1e-6, atol=1e-6
        )

"""Speculative decoding by prompt lookup (n-gram drafting + chunked
verification). The correctness bar is exactness: greedy output with
speculation ON must be bit-identical to greedy output with it OFF — every
accepted draft token is one the model would have produced anyway, and a
rejected draft's stale K/V must never leak into later steps (positions are
overwritten; attention is length-masked)."""

import numpy as np
import pytest

from radixmesh_tpu.engine import Engine, SamplingParams
from tests.test_engine import PAGE, make_engine, model, prompts_rng  # noqa: F401


class TestNgramDraft:
    def test_finds_latest_continuation(self):
        hist = np.array([1, 2, 3, 9, 1, 2, 3, 7, 5, 1, 2, 3], dtype=np.int32)
        d = Engine._ngram_draft(hist, gamma=2, n=3)
        # Tail [1,2,3] last previously occurred at 4..6, followed by 7, 5.
        assert d.tolist() == [7, 5]

    def test_bigram_fallback(self):
        hist = np.array([4, 5, 8, 0, 4, 5], dtype=np.int32)
        d = Engine._ngram_draft(hist, gamma=3, n=3)
        assert d.tolist() == [8, 0, 4]  # trigram misses, bigram [4,5] hits

    def test_no_repeat_no_draft(self):
        hist = np.arange(10, dtype=np.int32)
        assert Engine._ngram_draft(hist, gamma=4, n=3).size == 0

    def test_draft_truncated_at_history_end(self):
        hist = np.array([1, 2, 6, 1, 2], dtype=np.int32)
        d = Engine._ngram_draft(hist, gamma=4, n=2)
        assert d.tolist() == [6, 1, 2]  # continuation runs off the end


class TestSpecExactness:
    @pytest.mark.parametrize("gamma", [2, 4])
    def test_random_prompts_match_vanilla(self, model, gamma):
        cfg, params = model
        vanilla = make_engine(model)
        spec = make_engine(model, spec_decode_tokens=gamma)
        rng = prompts_rng()
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (11, 7, 15)]
        sp = SamplingParams(temperature=0.0, max_new_tokens=14)
        want = vanilla.generate(prompts, sp)
        got = spec.generate(prompts, sp)
        assert got == want

    def test_repetitive_prompt_accepts_and_matches(self, model):
        # A prompt whose tail n-grams repeat makes the drafter fire; with
        # a tiny random model most drafts still miss — exactness is the
        # invariant either way, and the drafter must have proposed.
        cfg, params = model
        vanilla = make_engine(model)
        spec = make_engine(model, spec_decode_tokens=4)
        base = prompts_rng().integers(1, cfg.vocab_size, 6).tolist()
        prompt = base * 4  # heavy n-gram repetition
        sp = SamplingParams(temperature=0.0, max_new_tokens=16)
        want = vanilla.generate([prompt], sp)
        got = spec.generate([prompt], sp)
        assert got == want
        assert spec.stats.spec_proposed > 0

    def test_cyclic_generation_gets_accepts(self, model):
        # Tiny random models typically fall into output cycles under
        # greedy decode; once the cycle enters the history the drafter
        # predicts it perfectly and acceptance must kick in. Scan a few
        # prompts for one whose vanilla output cycles, then require
        # accepted tokens AND exactness on it.
        cfg, params = model
        rng = prompts_rng()
        sp = SamplingParams(temperature=0.0, max_new_tokens=120)
        for _ in range(4):
            prompt = rng.integers(1, cfg.vocab_size, 5).tolist()
            vanilla = make_engine(model, max_seq_len=256)
            want = vanilla.generate([prompt], sp)[0]
            tail = want[-6:]
            cycles = any(tail[i:] == tail[:-i] for i in range(1, 4))
            if len(want) == 120 and cycles:
                spec = make_engine(model, max_seq_len=256, spec_decode_tokens=4)
                got = spec.generate([prompt], sp)[0]
                assert got == want
                assert spec.stats.spec_accepted > 0
                assert spec.stats.decode_steps < vanilla.stats.decode_steps
                return
        pytest.skip("no cyclic greedy output among probed prompts")

    def test_stop_token_mid_accept_matches(self, model):
        cfg, params = model
        vanilla = make_engine(model)
        ref_prompt = prompts_rng().integers(1, cfg.vocab_size, 9).tolist()
        ref = vanilla.generate(
            [ref_prompt], SamplingParams(temperature=0.0, max_new_tokens=12)
        )[0]
        stop = ref[6]
        sp = SamplingParams(
            temperature=0.0, max_new_tokens=12, stop_token_ids=(stop,)
        )
        v2 = make_engine(model)
        want = v2.generate([ref_prompt], sp)
        spec = make_engine(model, spec_decode_tokens=4)
        got = spec.generate([ref_prompt], sp)
        assert got == want

    def test_stochastic_rows_join_spec_launches(self, model):
        # Stochastic rows no longer disable speculation: a mixed batch
        # (greedy repetitive row whose drafts fire + a temperature row)
        # runs the verify launch for BOTH; the stochastic row is verified
        # by exact rejection sampling and still emits max_new_tokens
        # valid ids. (Its own drafts rarely fire with a tiny random
        # model — the sampled tail almost never repeats — so the greedy
        # row supplies the launch trigger.)
        cfg, params = model
        spec = make_engine(model, spec_decode_tokens=4)
        rng = prompts_rng()
        rep = (rng.integers(1, cfg.vocab_size, 5).tolist()) * 4
        rnd = rng.integers(1, cfg.vocab_size, 9).tolist()
        reqs = spec.add_request(rep, SamplingParams(temperature=0.0, max_new_tokens=10))
        reqs2 = spec.add_request(rnd, SamplingParams(temperature=0.9, max_new_tokens=10))
        while spec.has_work():
            spec.step()
        for r in (reqs, reqs2):
            assert len(r.output_tokens) == 10
            assert all(0 <= t < cfg.vocab_size for t in r.output_tokens)
        assert spec.stats.spec_proposed > 0

    def test_nonrepetitive_stochastic_falls_through(self, model):
        # No repeating tail → empty drafts → the cheap plain path runs.
        cfg, params = model
        spec = make_engine(model, spec_decode_tokens=4)
        prompt = prompts_rng().integers(1, cfg.vocab_size, 8).tolist()
        out = spec.generate(
            [prompt], SamplingParams(temperature=0.9, max_new_tokens=6)
        )[0]
        assert len(out) == 6
        assert spec.stats.spec_proposed == 0


    def test_cache_publish_after_spec_serves_followup(self, model):
        # Accepted-token KV written by the verify pass must be real: a
        # follow-up sharing prompt+output as its prefix should hit the
        # radix cache and still match vanilla output.
        cfg, params = model
        spec = make_engine(model, spec_decode_tokens=4)
        prompt = (prompts_rng().integers(1, cfg.vocab_size, 6).tolist()) * 3
        sp = SamplingParams(temperature=0.0, max_new_tokens=10)
        first = spec.generate([prompt], sp)[0]
        follow = prompt + first
        got = spec.generate([follow], sp)[0]
        assert spec.stats.cached_tokens > 0
        vanilla = make_engine(model)
        vanilla.generate([prompt], sp)
        want = vanilla.generate([follow], sp)[0]
        assert got == want


class TestRejectionSamplingExactness:
    def test_emitted_distribution_matches_target(self):
        """The verifier's first emitted token must be distributed exactly
        as plain sampling from the same filtered distribution, whatever
        the draft is — the core speculative-sampling identity
        P(accept d)·δ_d + P(reject)·residual = p."""
        import jax
        import jax.numpy as jnp

        from radixmesh_tpu.ops.sampling import (
            _filtered_logits,
            spec_verify_sample,
        )

        V, N = 12, 30_000
        rng = np.random.default_rng(0)
        logits_row = jnp.asarray(rng.normal(size=(V,)) * 2.0, jnp.float32)
        temperature, top_p = 0.8, 0.85
        # Target distribution: exactly what sample_tokens would draw from.
        filt = _filtered_logits(
            logits_row[None, :],
            jnp.asarray([temperature]),
            jnp.asarray([top_p]),
        )
        target = np.asarray(jax.nn.softmax(filt, axis=-1))[0]

        # Batch N independent verifications of a 1-token draft (both an
        # in-nucleus and an out-of-nucleus draft token).
        for draft_tok in (int(np.argmax(target)), int(np.argmin(target))):
            logits = jnp.broadcast_to(logits_row, (N, 2, V))
            drafts = jnp.full((N, 1), draft_tok, jnp.int32)
            dlen = jnp.ones((N,), jnp.int32)
            accept_len, bonus = spec_verify_sample(
                logits, drafts, dlen, jax.random.PRNGKey(7),
                jnp.full((N,), temperature), jnp.full((N,), top_p),
            )
            accept_len = np.asarray(accept_len)
            bonus = np.asarray(bonus)
            emitted = np.where(accept_len > 0, draft_tok, bonus)
            freq = np.bincount(emitted, minlength=V) / N
            # TV distance well under sampling noise for N=30k.
            tv = 0.5 * np.abs(freq - target).sum()
            assert tv < 0.02, (draft_tok, tv, freq, target)



def _verify_case(rows, chunk, vocab, seed):
    """Verify-pass logits whose argmax the drafts follow for a while (so
    accept lengths differ by row), with drafts shorter than the chunk."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(rows, chunk, vocab)).astype(np.float32) * 2.0
    greedy = logits.argmax(-1)
    drafts = greedy[:, : chunk - 1].copy()
    for r in range(rows):  # row r's draft leaves the argmax at r % chunk
        drafts[r, r % chunk :] = (drafts[r, r % chunk :] + 1) % vocab
    draft_len = rng.integers(0, chunk, rows)
    return (jnp.asarray(logits), jnp.asarray(drafts, jnp.int32),
            jnp.asarray(draft_len, jnp.int32))


class TestVerifierRunsWhatItsRowsAsk:
    """``spec_verify_sample(..., draws=...)`` (static), as
    ``sample_tokens``: an all-greedy launch is argmax-prefix matching
    alone; any other launch is the executable of before PR 26 to the bit
    (``tests/frozen_sampler.py``)."""

    @pytest.mark.parametrize("rows,chunk", [(1, 3), (4, 5), (8, 2)])
    def test_matching_alone_equals_the_drawing_variant_on_greedy_rows(
        self, rows, chunk
    ):
        import jax
        import jax.numpy as jnp

        from radixmesh_tpu.ops.sampling import spec_verify_sample

        logits, drafts, draft_len = _verify_case(rows, chunk, 64, seed=rows)
        zeros, ones = jnp.zeros((rows,), jnp.float32), jnp.ones((rows,))
        want = spec_verify_sample(
            logits, drafts, draft_len, jax.random.PRNGKey(5), zeros, ones,
            jnp.zeros((rows,), jnp.int32),
        )
        got = spec_verify_sample(
            logits, drafts, draft_len, None, zeros, ones, draws=False
        )
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        if rows > 1:  # some drafts accepted, some cut: not a vacuous match
            assert len(set(np.asarray(got[0]).tolist())) > 1

    @pytest.mark.parametrize("rows,chunk,seed", [(5, 3, 0), (5, 5, 1), (10, 4, 2)])
    def test_drawing_variant_is_frozen(self, rows, chunk, seed):
        import jax
        import jax.numpy as jnp

        from radixmesh_tpu.ops.sampling import spec_verify_sample
        from tests import frozen_sampler

        kinds = [(0.0, 0, 1.0), (0.8, 0, 1.0), (1.0, 7, 1.0),
                 (1.3, 0, 0.9), (0.7, 20, 0.95)]
        rows_kinds = [kinds[(i + seed) % len(kinds)] for i in range(rows)]
        temps = jnp.asarray([k[0] for k in rows_kinds], jnp.float32)
        top_k = jnp.asarray([k[1] for k in rows_kinds], jnp.int32)
        top_p = jnp.asarray([k[2] for k in rows_kinds], jnp.float32)
        logits, drafts, draft_len = _verify_case(rows, chunk, 64, seed)
        for key in (jax.random.PRNGKey(seed), jax.random.PRNGKey(2**31 + seed)):
            args = (logits, drafts, draft_len, key, temps, top_p, top_k)
            want = frozen_sampler.spec_verify_sample(*args)
            got = spec_verify_sample(*args)
            for g, w in zip(got, want):
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestTreeDrafts:
    """The radix cache doubles as the drafter: a replayed request finds
    the previous generation's published tokens cached beyond its history
    and accepts them wholesale under greedy decode."""

    def test_peek_continuation_basics(self, model):
        from radixmesh_tpu.cache.radix_tree import RadixTree

        tree = RadixTree(page_size=1)
        tree.insert([1, 2, 3, 4, 5, 6], np.arange(6, dtype=np.int32))
        assert tree.peek_continuation([1, 2, 3], 2).tolist() == [4, 5]
        assert tree.peek_continuation([1, 2, 3], 10).tolist() == [4, 5, 6]
        assert tree.peek_continuation([1, 9], 4).size == 0  # diverged
        assert tree.peek_continuation([1, 2, 3, 4, 5, 6], 4).size == 0  # exhausted

    def test_replay_accepts_heavily_and_matches(self, model):
        cfg, params = model
        vanilla = make_engine(model)
        spec = make_engine(model, spec_decode_tokens=4)
        prompt = prompts_rng().integers(1, cfg.vocab_size, 13).tolist()
        sp = SamplingParams(temperature=0.0, max_new_tokens=16)
        want = vanilla.generate([prompt], sp)
        first = spec.generate([prompt], sp)
        assert first == want
        steps_first = spec.stats.decode_steps
        # Replay: the tree now holds the full previous sequence, so the
        # drafter proposes the real continuation every launch.
        second = spec.generate([prompt], sp)
        assert second == want
        assert spec.stats.spec_accepted >= 8, spec.stats
        assert (spec.stats.decode_steps - steps_first) < steps_first, (
            "replay did not speed up",
            spec.stats.decode_steps,
            steps_first,
        )

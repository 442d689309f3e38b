"""Serving-engine tests: continuous batching + prefix reuse against a
full-recompute oracle (tiny fp32 model on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine import Engine, RequestState, SamplingParams
from radixmesh_tpu.engine import engine as engine_mod
from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    ModelConfig,
    init_params,
    new_moe_stats,
    prefill_forward,
)
from radixmesh_tpu.ops.sampling import sample_tokens

pytestmark = pytest.mark.quick

PAGE = 4


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny().replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


@functools.cache
def preset_model(preset):
    """(cfg, params) of a tiny preset in float32, for the cases that run
    more than the module's one model."""
    cfg = get_config(preset).replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(7))


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 512)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    return Engine(cfg, params, **kw)


def oracle_steps(cfg, params, prompt, n_new, forced=None):
    """Decode by full dense recompute each step — no cache, no pool.
    Returns ``(tokens, logits [n_new, V] float32)``: greedy tokens, or the
    ``forced`` ones (what an engine drew) under teacher forcing."""
    kw = {"moe_stats": new_moe_stats()} if cfg.n_experts else {}
    toks = list(int(t) for t in prompt)
    rows = []
    for i in range(n_new):
        s = len(toks)
        s_b = max(8, 1 << (s - 1).bit_length())
        tokens = np.zeros((1, s_b), dtype=np.int32)
        tokens[0, :s] = toks
        positions = np.arange(s_b, dtype=np.int32)[None]
        ck = jnp.zeros((cfg.n_layers, 1, 0, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
        logits = prefill_forward(
            params, cfg, jnp.asarray(tokens), jnp.asarray(positions),
            ck, ck, jnp.zeros((1,), jnp.int32), **kw,
        )[0]
        rows.append(np.asarray(logits[0, s - 1], np.float32))
        toks.append(
            int(rows[-1].argmax()) if forced is None else int(forced[i])
        )
    return toks[len(prompt) :], np.stack(rows)


def oracle_generate(cfg, params, prompt, n_new):
    return oracle_steps(cfg, params, prompt, n_new)[0]


def prompts_rng():
    return np.random.default_rng(3)


class TestGenerate:
    def test_matches_oracle_single(self, model):
        cfg, params = model
        prompt = prompts_rng().integers(0, cfg.vocab_size, 13).tolist()
        eng = make_engine(model)
        out = eng.generate([prompt], SamplingParams(max_new_tokens=9))[0]
        assert out == oracle_generate(cfg, params, prompt, 9)

    def test_batch_matches_sequential(self, model):
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 12, 21)]
        eng = make_engine(model)
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=7))
        for p, o in zip(prompts, outs):
            assert o == oracle_generate(cfg, params, p, 7)

    def test_stop_token(self, model):
        cfg, params = model
        prompt = prompts_rng().integers(0, cfg.vocab_size, 10).tolist()
        ref = oracle_generate(cfg, params, prompt, 8)
        stop = ref[3]
        eng = make_engine(model)
        out = eng.generate(
            [prompt], SamplingParams(max_new_tokens=8, stop_token_ids=(stop,))
        )[0]
        assert out == ref[:3]

    def test_more_requests_than_rows(self, model):
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(0, cfg.vocab_size, 6 + i).tolist() for i in range(7)]
        eng = make_engine(model, max_batch=2)
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=5))
        for p, o in zip(prompts, outs):
            assert o == oracle_generate(cfg, params, p, 5)


class TestPrefixReuse:
    def test_second_request_hits_cache(self, model):
        cfg, params = model
        prompt = prompts_rng().integers(0, cfg.vocab_size, 24).tolist()
        eng = make_engine(model)
        out1 = eng.generate([prompt], SamplingParams(max_new_tokens=6))[0]
        assert eng.stats.cached_tokens == 0
        out2 = eng.generate([prompt], SamplingParams(max_new_tokens=6))[0]
        assert out1 == out2
        # ≥ the page-aligned prompt minus the one-token prefill floor
        assert eng.stats.cached_tokens >= (len(prompt) - 1) // PAGE * PAGE
        assert eng.stats.hit_rate > 0.4

    def test_shared_prefix_across_requests(self, model):
        cfg, params = model
        rng = prompts_rng()
        common = rng.integers(0, cfg.vocab_size, 16).tolist()
        p1 = common + rng.integers(0, cfg.vocab_size, 4).tolist()
        p2 = common + rng.integers(0, cfg.vocab_size, 5).tolist()
        eng = make_engine(model)
        o1, o2 = eng.generate([p1, p2], SamplingParams(max_new_tokens=4))
        assert o1 == oracle_generate(cfg, params, p1, 4)
        assert o2 == oracle_generate(cfg, params, p2, 4)

    def test_generated_tokens_are_reusable(self, model):
        cfg, params = model
        prompt = prompts_rng().integers(0, cfg.vocab_size, 8).tolist()
        eng = make_engine(model)
        out = eng.generate([prompt], SamplingParams(max_new_tokens=10))[0]
        # A prompt extending into the generated text should hit the cache
        # beyond the original prompt (cache_finished_req published it).
        longer = prompt + out[:6]
        eng.generate([longer], SamplingParams(max_new_tokens=2))
        assert eng.stats.cached_tokens >= (len(longer) - 1) // PAGE * PAGE


class TestMemoryPressure:
    def test_eviction_keeps_engine_alive(self, model):
        cfg, params = model
        rng = prompts_rng()
        # Pool of 96 slots; each request needs ~24 — the 10 requests only
        # fit because finished trees get evicted under pressure.
        eng = make_engine(model, num_slots=96, max_batch=2)
        prompts = [rng.integers(0, cfg.vocab_size, 16).tolist() for _ in range(10)]
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        for p, o in zip(prompts, outs):
            assert o == oracle_generate(cfg, params, p, 6)

    @pytest.mark.parametrize("num_slots", [1024, 4096])
    def test_one_pressure_frees_a_share_of_the_pool(self, model, num_slots):
        """A pool that runs full would otherwise walk the whole tree for
        every page: the walk that finds room frees a 64th of the pool (or
        what is asked, if that is more), so few allocations walk at all."""
        cfg, params = model
        rng = prompts_rng()
        eng = make_engine(model, num_slots=num_slots, max_batch=2)
        calls = []
        evict = eng.tree.evict

        def counted(n, **kw):
            freed = evict(n, **kw)
            calls.append((n, freed))
            return freed

        eng.tree.evict = counted
        share = num_slots // 64
        n_req = num_slots // 16 + 8  # a page and a half each: the pool fills
        prompts = [rng.integers(0, cfg.vocab_size, 16).tolist()
                   for _ in range(n_req)]
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        assert outs[-1] == oracle_generate(cfg, params, prompts[-1], 6)
        assert calls and all(asked >= share for asked, _ in calls)
        freed = sum(f for _, f in calls)
        # Each walk freed about what it was asked for (a leaf is a page or
        # two here), so the walks are one for every `share` slots consumed
        # and not one an allocation.
        assert len(calls) <= freed // share + 1
        assert len(calls) < n_req // 2

    def test_all_slots_recovered_after_reset(self, model):
        eng = make_engine(model)
        rng = prompts_rng()
        prompts = [rng.integers(0, eng.cfg.vocab_size, 12).tolist() for _ in range(3)]
        eng.generate(prompts, SamplingParams(max_new_tokens=4))
        eng.tree.reset()
        # everything except the scratch page is back
        assert eng.pool.free_slots == eng.pool.num_slots - PAGE


class TestSamplingIntegration:
    def test_temperature_sampling_runs(self, model):
        eng = make_engine(model)
        prompt = prompts_rng().integers(0, eng.cfg.vocab_size, 9).tolist()
        out = eng.generate(
            [prompt], SamplingParams(max_new_tokens=5, temperature=0.8, top_p=0.9)
        )[0]
        assert len(out) == 5
        assert all(0 <= t < eng.cfg.vocab_size for t in out)


class TestCancel:
    def test_cancel_queued(self, model):
        cfg, params = model
        eng = make_engine(model, max_batch=1)
        r1 = eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=4))
        r2 = eng.add_request([4, 5, 6], SamplingParams(max_new_tokens=4))
        assert eng.cancel(r2.rid)
        while eng.has_work():
            eng.step()
        assert len(r1.output_tokens) == 4
        assert r2.cancelled and r2.output_tokens == []
        assert not eng.cancel(r2.rid)  # already finished

    def test_cancel_running_releases_row_and_publishes(self, model):
        cfg, params = model
        eng = make_engine(model, max_batch=1)
        prompt = prompts_rng().integers(1, cfg.vocab_size, 10).tolist()
        req = eng.add_request(prompt, SamplingParams(max_new_tokens=64))
        for _ in range(6):  # prefill + a few decode steps
            eng.step()
        eng._settle()  # the last step's token is part of what it produced
        produced = len(req.output_tokens)
        assert 0 < produced < 64
        assert eng.cancel(req.rid)
        assert req.cancelled and len(req.output_tokens) == produced
        # The row is free for new work and the computed prefix is cached.
        follow = eng.generate(
            [prompt + req.output_tokens],
            SamplingParams(temperature=0.0, max_new_tokens=2),
        )[0]
        assert len(follow) == 2
        assert eng.stats.cached_tokens > 0

    def test_cancel_unknown_rid(self, model):
        eng = make_engine(model)
        assert not eng.cancel(10_000)


class TestTopK:
    def test_top_k_one_is_greedy(self, model):
        cfg, params = model
        prompt = prompts_rng().integers(1, cfg.vocab_size, 9).tolist()
        ref = make_engine(model).generate(
            [prompt], SamplingParams(temperature=0.0, max_new_tokens=8)
        )[0]
        # k=1 restricts sampling to the argmax even at high temperature.
        out = make_engine(model).generate(
            [prompt],
            SamplingParams(temperature=1.5, top_k=1, max_new_tokens=8),
        )[0]
        assert out == ref

    def test_per_row_top_k_mixed_batch(self, model):
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (7, 9)]
        eng = make_engine(model)
        r1 = eng.add_request(
            prompts[0], SamplingParams(temperature=1.2, top_k=1, max_new_tokens=6)
        )
        r2 = eng.add_request(
            prompts[1], SamplingParams(temperature=0.8, max_new_tokens=6)
        )
        while eng.has_work():
            eng.step()
        ref = make_engine(model).generate(
            [prompts[0]], SamplingParams(temperature=0.0, max_new_tokens=6)
        )[0]
        assert r1.output_tokens == ref  # k=1 row is effectively greedy
        assert len(r2.output_tokens) == 6

    def test_top_k_with_spec(self, model):
        cfg, params = model
        prompt = (prompts_rng().integers(1, cfg.vocab_size, 5).tolist()) * 3
        sp = SamplingParams(temperature=1.0, top_k=1, max_new_tokens=9)
        ref = make_engine(model).generate([prompt], sp)[0]
        spec = make_engine(model, spec_decode_tokens=3)
        assert spec.generate([prompt], sp)[0] == ref


class TestSeqLenBoundary:
    @pytest.mark.parametrize("kw", [
        {},
        {"spec_decode_tokens": 3},
    ])
    def test_generation_truncates_at_max_seq_len(self, model, kw):
        """A budget larger than the remaining context must truncate at
        max_seq_len on both decode paths (plain, speculative) — spec
        declines near the cap and the plain path finishes."""
        cfg = model[0]
        eng = make_engine(model, max_seq_len=32, max_batch=1, **kw)
        prompt = prompts_rng().integers(1, cfg.vocab_size, 25).tolist()
        out = eng.generate([prompt], SamplingParams(max_new_tokens=64))[0]
        assert len(prompt) + len(out) == 32
        # The (only) row is genuinely released and the engine still serves.
        assert all(r is None for r in eng._rows)
        out2 = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))[0]
        assert len(out2) == 2


class TestPrefillWaveSlicing:
    """Round-5 cold-burst fairness (VERDICT r4 weak #4): a burst of equal
    cold requests must prefill in arrival-ordered slices of at most
    ``prefill_wave_tokens // chunk`` rows — each slice finalizing its own
    first tokens — instead of one convoy whose every member waits for the
    last. Output correctness is pinned against the unsliced engine."""

    def test_burst_slices_and_matches_unsliced(self, model):
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(0, cfg.vocab_size, 24).tolist() for _ in range(6)]
        # Distinct first tokens so no prefix-wave deferral kicks in.
        for i, p in enumerate(prompts):
            p[0] = i + 1

        # prefill_wave_tokens=64 with 24-token cold prompts (bucket 32)
        # → slices of 2 rows.
        eng = make_engine(
            model, max_batch=6, prefill_wave_tokens=64,
            long_prefill_threshold=0,  # force the grouped paged path
        )
        waves: list[int] = []
        orig = eng._prefill_group

        def spy(group):
            waves.append(len(group))
            return orig(group)

        eng._prefill_group = spy
        out = eng.generate(prompts, SamplingParams(max_new_tokens=4))

        assert waves and max(waves) <= 2, waves
        assert sum(waves) == 6

        eng_wide = make_engine(
            model, max_batch=6, prefill_wave_tokens=1 << 20,
            long_prefill_threshold=0,
        )
        want = eng_wide.generate(prompts, SamplingParams(max_new_tokens=4))
        assert out == want


class TestSamplerLaunchRule:
    """``Engine._launch_draws``: every sampler launch runs the argmax
    alone unless a LIVE row of that launch draws (``engine.sampler`` on
    /debug/state counts both kinds)."""

    @staticmethod
    def _first_token_launches(eng):
        """Spy: non-seeded ``_finalize_first_tokens`` calls (one batched
        sampler launch each)."""
        calls = []
        orig = eng._finalize_first_tokens

        def spy(pending):
            calls.append(len(pending))
            return orig(pending)

        eng._finalize_first_tokens = spy
        return calls

    @pytest.mark.parametrize("preset", ["llama3-tiny", "mellum2-tiny"])
    def test_all_greedy_traffic_never_draws(self, preset):
        model = preset_model(preset)
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 12, 21)]
        eng = make_engine(model)
        first = self._first_token_launches(eng)
        rng_before = np.asarray(eng._rng).copy()
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=7))
        for p, o in zip(prompts, outs):
            assert o == oracle_generate(cfg, params, p, 7)
        assert eng.sampler["drawing_launches"] == 0
        # one launch a decode step, one a first-token round
        assert eng.sampler["argmax_launches"] == (
            eng.stats.decode_steps + len(first)
        )
        # The unseeded stream carries no contract; an argmax launch reads
        # no key, so it does not advance it.
        np.testing.assert_array_equal(np.asarray(eng._rng), rng_before)

    def test_one_drawing_row_pins_the_launch_only_while_it_lives(self, model):
        cfg, params = model
        rng = prompts_rng()
        long_p, short_p = (rng.integers(0, cfg.vocab_size, n).tolist()
                           for n in (9, 6))
        eng = make_engine(model)
        greedy = eng.add_request(long_p, SamplingParams(max_new_tokens=24))
        drawer = eng.add_request(
            short_p, SamplingParams(max_new_tokens=4, temperature=0.8)
        )
        while len(drawer.output_tokens) < 3:  # its fourth is made, and pending
            before = dict(eng.sampler)
            eng.step()
            # Every launch that held the drawing row drew: its first-token
            # launch and each decode step.
            assert eng.sampler["argmax_launches"] == before["argmax_launches"]
            assert eng.sampler["drawing_launches"] > before["drawing_launches"]
        row = next(i for i, t in enumerate(eng._temps) if t > 0)
        # It ends by its length: it sits in its row until the advance has
        # run, behind the next launch — which is of the greedy row alone.
        assert eng._rows[row] is drawer and eng._pending is not None
        assert drawer.state is RequestState.RUNNING
        drew = eng.sampler["drawing_launches"]
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
            # The finished request's row is empty and still holds its
            # temperature: the rule reads the launch's rows, not ``_temps``.
            assert eng._rows[row] is None
            assert drawer.state is RequestState.FINISHED
            assert eng._temps[row] == np.float32(0.8)
        assert steps > 5 and greedy.state is RequestState.FINISHED
        assert eng.sampler["drawing_launches"] == drew
        assert greedy.generated == oracle_generate(cfg, params, long_p, 24)

    def test_seeded_greedy_rows_take_the_argmax_and_replay_the_same(self, model):
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (7, 11)]
        eng = make_engine(model)
        outs = eng.generate(
            prompts, SamplingParams(max_new_tokens=6, temperature=0.0, seed=99)
        )
        assert eng.sampler["drawing_launches"] == 0
        # The canonical seeded path is one [1, V] launch a row and token.
        assert eng.sampler["argmax_launches"] == sum(len(o) for o in outs)
        for p, o in zip(prompts, outs):
            assert o == oracle_generate(cfg, params, p, 6)
        # A seeded row that draws still takes the keyed [1, V] draw, and
        # draws the same tokens in a second life.
        sp = SamplingParams(max_new_tokens=6, temperature=0.9, seed=99)
        a = make_engine(model).generate(prompts[:1], sp)
        eng_b = make_engine(model)
        b = eng_b.generate(prompts[:1], sp)
        assert a == b and eng_b.sampler["argmax_launches"] == 0
        assert eng_b.sampler["drawing_launches"] == len(b[0])

    def test_spec_verify_launches_follow_the_same_rule(self, model):
        cfg, params = model
        base = prompts_rng().integers(1, cfg.vocab_size, 6).tolist()
        eng = make_engine(model, spec_decode_tokens=4)
        sp = SamplingParams(temperature=0.0, max_new_tokens=16)
        want = make_engine(model).generate([base * 4], sp)
        assert eng.generate([base * 4], sp) == want
        assert eng.stats.spec_accepted + eng.stats.spec_rejected > 0
        assert eng.sampler["drawing_launches"] == 0
        eng.generate(
            [base * 3], SamplingParams(temperature=0.7, max_new_tokens=8)
        )
        assert eng.sampler["drawing_launches"] > 0


class TestOneDecodeLaunch:
    """A decode step is ``Engine._decode_once``: one ``decode_step``
    launch, then one ``sample_tokens`` launch, for every model and on
    every backend — the program the benchmark's cells run on the chip is
    the one these tests run on a CPU."""

    # preset, engine keywords, float32 (comparable with the oracle)
    KINDS = {
        "llama3-tiny": ("llama3-tiny", {}, True),
        "qwen2-tiny": ("qwen2-tiny", {}, True),
        "mellum2-tiny": ("mellum2-tiny", {}, True),
        "int8-kv": ("llama3-tiny", {"kv_quant": "int8"}, False),
        "w8a16": ("llama3-tiny", {"weight_quant": "int8"}, False),
    }

    @staticmethod
    def _spy_launches(monkeypatch):
        """Every device program the engine module can launch, logged by
        name in call order."""
        log = []
        for name in ("decode_step", "sample_tokens", "spec_verify_sample",
                     "prefill_forward", "prefill_chunk_paged"):
            orig = getattr(engine_mod, name)

            def spy(*a, _orig=orig, _name=name, **kw):
                log.append(_name)
                return _orig(*a, **kw)

            monkeypatch.setattr(engine_mod, name, spy)
        return log

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_step_launches_decode_step_then_sampler(self, kind, monkeypatch):
        preset, kw, exact = self.KINDS[kind]
        model = preset_model(preset)
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (5, 9, 14)]
        eng = make_engine(model, **kw)
        log = self._spy_launches(monkeypatch)
        per_step = []
        orig = eng._decode_once

        def decode_once(*a, **k):
            del log[:]
            orig(*a, **k)
            per_step.append(list(log))

        eng._decode_once = decode_once
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=7))
        assert len(per_step) == eng.stats.decode_steps == 6
        assert all(s == ["decode_step", "sample_tokens"] for s in per_step)
        assert eng._last_dispatch == {
            "path": "dense", "batch": 3, "bucket": 4,
            "max_len": len(prompts[2]) + 5,
        }
        assert [len(o) for o in outs] == [7, 7, 7]
        if exact:
            for p, o in zip(prompts, outs):
                assert o == oracle_generate(cfg, params, p, 7)

    @pytest.mark.parametrize("kind", ["llama3-tiny", "int8-kv", "mellum2-tiny"])
    def test_page_crossing_allocates_in_step_and_preempts_when_full(self, kind):
        """Two rows reach a page boundary in the same step with one free
        page and nothing to evict: the first takes it, the second is
        preempted and served later; both end token-exact."""
        preset, kw, exact = self.KINDS[kind]
        model = preset_model(preset)
        cfg, _ = model
        rng = prompts_rng()
        # two pages each; the first decode step writes position 8
        prompts = [rng.integers(1, cfg.vocab_size, 2 * PAGE).tolist()
                   for _ in range(2)]
        sp = SamplingParams(max_new_tokens=6)
        want = make_engine(model, **kw).generate(prompts, sp)
        # scratch page + 2 x 2 prompt pages + ONE more
        eng = make_engine(model, num_slots=6 * PAGE, max_batch=2, **kw)
        a, b = (eng.add_request(p, sp) for p in prompts)
        eng.step()  # admits both, prefills, and runs the first decode step
        eng._settle()  # a step is counted where its tokens are
        assert eng.stats.preemptions == 1 and eng.stats.decode_steps == 1
        assert eng.pool.free_slots == 0  # a took the one page left
        assert b.state is RequestState.QUEUED and len(a.output_tokens) == 2
        while eng.has_work():
            eng.step()
        assert a.output_tokens == want[0]
        assert len(b.output_tokens) == 6
        if exact:  # a second life re-reads its own tokens as a prompt
            assert b.output_tokens == want[1]

    def test_step_whose_every_row_is_preempted_launches_nothing(
        self, model, monkeypatch
    ):
        cfg, _ = model
        prompt = prompts_rng().integers(1, cfg.vocab_size, 2 * PAGE).tolist()
        # scratch page + the prompt's two pages: no page for position 8
        eng = make_engine(model, num_slots=3 * PAGE, max_batch=1)
        req = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        log = self._spy_launches(monkeypatch)
        eng.step()
        assert eng.stats.preemptions == 1 and eng.stats.decode_steps == 0
        assert "decode_step" not in log
        assert req.state is RequestState.QUEUED and all(
            r is None for r in eng._rows
        )

    @pytest.mark.parametrize("preset", ["llama3-tiny", "qwen2-tiny"])
    def test_return_logits_rows_match_oracle(self, preset):
        """The logits hook of a dense model's step: one host copy of the
        launch's logits, with the last step's emission flushed before it."""
        model = preset_model(preset)
        cfg, params = model
        rng = prompts_rng()
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (6, 11)]
        eng = make_engine(model)
        asked = eng.add_request(
            prompts[0], SamplingParams(max_new_tokens=8, return_logits=True)
        )
        plain = eng.add_request(prompts[1], SamplingParams(max_new_tokens=8))
        flushed_at_copy = []
        keep = eng._keep_expert_ids  # called right after the host copy

        def spy(*a, **k):
            flushed_at_copy.append(not eng._emit_rows)
            return keep(*a, **k)

        eng._keep_expert_ids = spy
        while eng.has_work():
            eng.step()
        toks, logits = oracle_steps(cfg, params, prompts[0], 8)
        assert asked.output_tokens == toks and plain.logit_rows == []
        got = np.stack(asked.logit_rows)
        assert got.dtype == np.float32 and got.shape == logits.shape
        np.testing.assert_allclose(got, logits, rtol=1e-4, atol=1e-4)
        assert flushed_at_copy and all(flushed_at_copy)

    @pytest.mark.parametrize("how", ["top_p", "top_k"])
    def test_unseeded_draw_is_sample_tokens_of_the_step_logits(
        self, model, how, monkeypatch
    ):
        """The unseeded stream is one split of the engine's key per
        drawing launch, handed to ``sample_tokens`` with the step's
        logits: what the chip draws, and now what a CPU draws."""
        cfg, params = model
        prompt = prompts_rng().integers(1, cfg.vocab_size, 9).tolist()
        sp = SamplingParams(
            max_new_tokens=8, temperature=0.9,
            **({"top_p": 0.7} if how == "top_p" else {"top_k": 5}),
        )
        eng = make_engine(model, max_batch=1, rng_seed=11)
        seen = []  # (logits, key) of every sampler launch
        orig = engine_mod.sample_tokens

        def spy(logits, key, **kw):
            seen.append((np.asarray(logits, np.float32), key))
            return orig(logits, key, **kw)

        monkeypatch.setattr(engine_mod, "sample_tokens", spy)
        out = eng.generate([prompt], sp)[0]
        assert len(seen) == 8 and eng.sampler["drawing_launches"] == 8
        _, logits = oracle_steps(cfg, params, prompt, 8, forced=out)
        rng = jax.random.PRNGKey(11)
        for i, (row, key) in enumerate(seen):
            rng, want_key = jax.random.split(rng)
            np.testing.assert_array_equal(np.asarray(key), np.asarray(want_key))
            np.testing.assert_allclose(row[0], logits[i], rtol=1e-4, atol=1e-4)
            tok = sample_tokens(
                jnp.asarray(logits[i])[None], want_key,
                temperature=jnp.asarray([sp.temperature], jnp.float32),
                top_p=jnp.asarray([sp.top_p], jnp.float32),
                top_k=jnp.asarray([sp.top_k], jnp.int32),
            )
            assert int(tok[0]) == out[i]
        np.testing.assert_array_equal(np.asarray(eng._rng), np.asarray(rng))


class TestDeferredEmission:
    """A decode step's emission (output counter, token timeline, stream
    wake-up) runs behind the NEXT launch (``Engine._flush_emit``); the
    advance half stays in the step. At quiescence nothing observable
    differs from counting every token where it was made — on every decode
    path, with a stop token, a length cap, a preemption and a cancel that
    each meet a non-empty queue."""

    PATHS = {
        "single": {},
        "spec": {"spec_decode_tokens": 2},
    }

    @pytest.mark.parametrize("path", list(PATHS))
    def test_nothing_observable_changes_at_quiescence(self, model, path):
        cfg, params = model
        rng = prompts_rng()
        base = rng.integers(1, cfg.vocab_size, 5).tolist()
        prompts = [
            base * 3,  # repeats its tail: the speculative path has drafts
            rng.integers(1, cfg.vocab_size, 11).tolist(),
            rng.integers(1, cfg.vocab_size, 9).tolist(),
            rng.integers(1, cfg.vocab_size, 13).tolist(),
        ]
        want = [oracle_generate(cfg, params, p, 12) for p in prompts]
        # a stop token the stopped row makes late, and for the first time
        cut = next(i for i in range(5, 12) if want[1][i] not in want[1][:i])
        stop = want[1][cut]
        eng = make_engine(model, **self.PATHS[path])
        advanced = []  # (rid, token was a stop) per _advance_token call
        orig = eng._advance_token

        def spy(req, row, slot, token, stamp):
            advanced.append((req.rid, token in req.sampling.stop_token_ids))
            return orig(req, row, slot, token, stamp)

        eng._advance_token = spy
        a = eng.add_request(prompts[0], SamplingParams(max_new_tokens=9))
        b = eng.add_request(prompts[1], SamplingParams(
            max_new_tokens=12, stop_token_ids=(stop,)))
        c = eng.add_request(prompts[2], SamplingParams(max_new_tokens=12))
        d = eng.add_request(prompts[3], SamplingParams(max_new_tokens=12))
        while min(len(r.output_tokens) for r in (a, b, c, d)) < 3:
            eng.step()
        if path == "single":
            # Mid-flight the last plain launch's advance waits for the
            # next launch; nothing of it is queued or counted yet.
            assert eng._pending is not None and not eng._emit_rows
            assert eng.emit["tokens"] == eng.stats.generated_tokens
            eng._settle()
        # Once advanced the last launch's tokens are queued, not yet
        # emitted, yet already in output_tokens.
        queued = [e[0].rid for e in eng._emit_rows]
        assert c.rid in queued and d.rid in queued
        assert eng.emit["tokens"] < eng.stats.generated_tokens
        d_partial = list(d.output_tokens)
        assert eng.cancel(d.rid)
        eng._preempt(c)
        assert not {c.rid, d.rid} & {e[0].rid for e in eng._emit_rows}
        assert eng.emit["flushes_exposed"] == 2
        while eng.has_work():
            eng.step()

        assert not eng._emit_rows and not eng._emit_steps
        assert all(r.state is RequestState.FINISHED for r in (a, b, c, d))
        assert a.output_tokens == want[0][:9]
        assert b.output_tokens == want[1][:cut]
        assert c.output_tokens == want[2] and not c.cancelled
        assert d.cancelled and d.output_tokens == d_partial
        assert d_partial == want[3][: len(d_partial)]
        n_stop = sum(s for _, s in advanced)
        assert n_stop == 1
        assert eng.stats.finished == 4 and eng.stats.preemptions == 1
        assert eng.stats.generated_tokens == len(advanced) - n_stop
        assert eng._m_generated.value == eng.stats.generated_tokens
        assert eng.emit["tokens"] == eng.stats.generated_tokens
        # One timeline entry per decode token (the stop token's gap
        # included), one useful token per first token and decode token.
        assert eng.timeline.appends == len(advanced)
        useful = sum(
            t["useful_tokens"] for t in eng.goodput.report()["tenants"].values()
        )
        assert useful == len(advanced) + eng.stats.prefills
        assert eng.stats.prefills == 5  # c's second life prefilled again
        if path == "spec":
            assert eng.stats.spec_proposed > 0
        # Every launch's latency samples arrived too: one per decode step.
        assert eng._m_tpot.count >= eng.stats.decode_steps > 0


class TestHandOver:
    """Between two plain decode launches the engine does no more than
    "sync, did a row sample a stop id?, launch": the next launch's inputs
    are built behind the one on the device (``_build_launch(1, ...)``),
    for the rows that go on — a row that reaches its length cap is known
    before the launch is sent and is none of the next one's — and a
    step's per-row advance, the release and publish of the rows it ends
    by length included, runs behind the next launch (``_pending``),
    unless a row samples a stop id, no row goes on, or anything but a
    plain launch of those rows follows (``Engine._settle``). Each case runs once as served and once settled
    after every step — every step's advance in the step, as before — and
    the two agree on tokens, finish state, stats, tree and pool; greedy
    unseeded rows also agree with the float32 oracle, and
    ``engine.handover`` counts what happened."""

    # Each case: (engine keywords, script). A script drives ``eng`` with
    # ``tick`` (one step) and returns {name: request}; ``served`` tells
    # the run whose advances stay pending between steps.

    @staticmethod
    def _length(eng, tick, served, model, rng):
        ps = [rng.integers(1, 500, n).tolist() for n in (5, 9, 13)]
        reqs = {k: eng.add_request(p, SamplingParams(max_new_tokens=n))
                for k, p, n in zip("abc", ps, (4, 6, 9))}
        while eng.has_work():
            tick()
        return reqs, {
            # d1 is built in the gap (first step). d3 and d5 end a request
            # beside rows that go on: d4 and d6 are built ahead without it
            # and sent before it is released. d8 ends the last row.
            "launches_rebuilt": 1, "launches_prebuilt": 7,
            "advances_deferred": 7, "finishes_deferred": 2,
            "settled_for": {"finish": 1},
        }

    @staticmethod
    def _stop(eng, tick, served, model, rng):
        p = rng.integers(1, 500, 10).tolist()
        want = oracle_generate(*model, p, 12)
        # a stop token made late, for the first time, by the step whose
        # build ahead crosses into a fresh page for the stopping row
        cut = next(i for i in range(3, 12)
                   if want[i] not in want[:i] and (len(p) + i) % PAGE == 0)
        reqs = {
            "a": eng.add_request(p, SamplingParams(
                max_new_tokens=12, stop_token_ids=(want[cut],))),
            "b": eng.add_request(rng.integers(1, 500, 7).tolist(),
                                 SamplingParams(max_new_tokens=12)),
        }
        a = reqs["a"]
        while a.state is not RequestState.FINISHED:
            if served and len(a.output_tokens) == cut - 1:
                held = len(a.own_slots)  # before the step that stops it
            tick()
        if served:
            # the build ahead had taken a page for the row that stopped:
            # released with the rest
            assert held > 0 and len(a.own_slots) == 0
        assert a.output_tokens == want[:cut]
        while eng.has_work():
            tick()
        return reqs, {"settled_for": {"finish": 2}}

    @staticmethod
    def _cancel(eng, tick, served, model, rng):
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=12))
                for k, n in zip("ab", (6, 11))}
        for _ in range(4):
            tick()
        if served:
            assert eng._pending is not None and eng._prebuilt is not None
            assert len(reqs["b"].output_tokens) == 4  # the 5th is pending
        assert eng.cancel(reqs["b"].rid)
        assert len(reqs["b"].output_tokens) == 5 and reqs["b"].cancelled
        while eng.has_work():
            tick()
        return reqs, {"settled_for": {"cancel": 1, "finish": 1},
                      "launches_rebuilt": 2}  # first step; a row left

    @staticmethod
    def _preempt(eng, tick, served, model, rng):
        # a: 7 tokens in two pages; the first step writes position 7 and
        # the build behind it crosses into a third page
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=8))
                for k, n in zip("ab", (7, 5))}
        a = reqs["a"]
        free0 = eng.pool.free_slots
        tick()
        row = a.row
        assert eng._page_table[row, 2] != eng._scratch_page
        assert eng.pool.free_slots == free0 - 5 * PAGE  # 2 + 2 prompt pages, 1 ahead
        eng._preempt(a)
        # the page taken ahead went back with the request's others (what
        # the tree holds of the prompt stays cached)
        assert eng._page_table[row, 2] == eng._scratch_page
        assert a.own_slots.size == 0 and a.state is RequestState.QUEUED
        while eng.has_work():
            tick()
        return reqs, {"settled_for": {"preempt": 1, "finish": 2}}

    @staticmethod
    def _no_pages(eng, tick, served, model, rng):
        # scratch + 2 x 2 prompt pages + ONE more: the build ahead of the
        # first step takes it for a (position 8); the one behind the
        # second step finds none for b (position 8), is dropped, and the
        # ordinary build preempts b once the rows are settled
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=6))
                for k, n in zip("ab", (7, 6))}
        tick()
        assert eng.pool.free_slots == 0 and eng.stats.preemptions == 0
        tick()
        assert eng._prebuilt is None and eng._pending is None
        tick()
        assert eng.stats.preemptions == 1
        assert reqs["b"].state is RequestState.QUEUED
        while eng.has_work():
            tick()
        return reqs, {"settled_for": {"no_pages": 1, "finish": 2}}

    @staticmethod
    def _admission(eng, tick, served, model, rng):
        sp = lambda n: SamplingParams(max_new_tokens=n)  # noqa: E731
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(), sp(m))
                for k, n, m in (("a", 6, 4), ("b", 9, 20), ("c", 7, 4))}
        assert eng.max_batch == 2
        while reqs["c"].state is not RequestState.FINISHED:
            tick()  # c took a's row once a's advance had freed it
        assert reqs["b"].state is RequestState.RUNNING
        tick()
        if served:
            assert eng._pending is not None
        # a free row and an advance pending: admission settles first (c's
        # did too); a and c ended behind a launch of b, and b or d ends last
        reqs["d"] = eng.add_request(rng.integers(1, 500, 8).tolist(), sp(5))
        while eng.has_work():
            tick()
        return reqs, {"finishes_deferred": 3,
                      "settled_for": {"admit": 2, "finish": 1}}

    @staticmethod
    def _seeded(eng, tick, served, model, rng):
        draw = SamplingParams(max_new_tokens=10, temperature=0.9, seed=5)
        reqs = {"s": eng.add_request(rng.integers(1, 500, 8).tolist(), draw)}
        tick()
        tick()  # all-seeded launches: per-row draws on the host, never fast
        assert eng._pending is None and eng.handover["advances_deferred"] == 0
        reqs["a"] = eng.add_request(rng.integers(1, 500, 6).tolist(),
                                    SamplingParams(max_new_tokens=4))
        while reqs["a"].state is not RequestState.FINISHED:
            tick()  # a mixed launch is the plain one
        if served:
            assert eng.handover["advances_deferred"] == 2
        while eng.has_work():
            tick()
        # a ends by length beside s: its advance waits, and settles for
        # the all-seeded launch that follows, not for the finish
        return reqs, {"advances_deferred": 2, "launches_prebuilt": 2,
                      "finishes_deferred": 0, "settled_for": {"launch_kind": 7}}

    @staticmethod
    def _return_logits(eng, tick, served, model, rng):
        cfg, params = model
        p = rng.integers(1, 500, 9).tolist()
        reqs = {
            "r": eng.add_request(p, SamplingParams(
                max_new_tokens=5, return_logits=True)),
            "a": eng.add_request(rng.integers(1, 500, 6).tolist(),
                                 SamplingParams(max_new_tokens=9)),
        }
        while reqs["r"].state is not RequestState.FINISHED:
            tick()
        assert eng.handover["advances_deferred"] == 0  # r kept every launch's logits
        _, logits = oracle_steps(cfg, params, p, 5)
        np.testing.assert_allclose(
            np.stack(reqs["r"].logit_rows), logits, rtol=2e-4, atol=2e-4)
        while eng.has_work():
            tick()
        return reqs, {"advances_deferred": 3, "launches_prebuilt": 3,
                      "settled_for": {"launch_kind": 4, "finish": 1}}


    @staticmethod
    def _beside(eng, tick, served, model, rng):
        # a ends by length in d3; the build behind d3 crosses into a fresh
        # page for b (position 12) — and would for a (position 8), were a
        # not left out of it
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=m))
                for k, n, m in (("a", 5, 4), ("b", 9, 9))}
        a, b = reqs["a"], reqs["b"]
        for _ in range(3):
            tick()
        assert eng._page_table[b.row, 3] != eng._scratch_page
        if served:
            # a's last token is made and its advance pending: it sits in
            # its row, holds what d3 left it, and is no row of d4
            assert a.state is RequestState.RUNNING and eng._rows[a.row] is a
            assert len(a.output_tokens) == 3 and eng.stats.finished == 0
            row, nxt = a.row, eng._prebuilt
            assert nxt.ending == {row} and [r for r, _ in nxt.active] == [b.row]
            assert eng._page_table[row, 2] == eng._scratch_page
            assert nxt.slots[row] == eng._scratch_slot
            tick()  # d4 goes out, then a is advanced, released, published
            assert a.state is RequestState.FINISHED and eng._rows[row] is None
            assert eng.handover["finishes_deferred"] == 1
        while eng.has_work():
            tick()
        return reqs, {"launches_rebuilt": 1, "finishes_deferred": 1,
                      "settled_for": {"finish": 1}}  # b, the last row

    @staticmethod
    def _all_end(eng, tick, served, model, rng):
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=5))
                for k, n in zip("ab", (6, 10))}
        while eng.has_work():
            tick()
            # no row goes on after d4: nothing is built, and it settles
            assert eng._prebuilt is None or eng.stats.finished == 0
        return reqs, {"finishes_deferred": 0, "launches_prebuilt": 3,
                      "settled_for": {"finish": 1}}

    @staticmethod
    def _stop_and_length(eng, tick, served, model, rng):
        p = rng.integers(1, 500, 10).tolist()
        want = oracle_generate(*model, p, 12)
        cut = next(i for i in range(3, 12) if want[i] not in want[:i])
        # d<cut> makes a's stop id and b's last token; c goes on
        reqs = {
            "a": eng.add_request(p, SamplingParams(
                max_new_tokens=12, stop_token_ids=(want[cut],))),
            "b": eng.add_request(rng.integers(1, 500, 7).tolist(),
                                 SamplingParams(max_new_tokens=cut + 1)),
            "c": eng.add_request(rng.integers(1, 500, 6).tolist(),
                                 SamplingParams(max_new_tokens=cut + 4)),
        }
        a, b = reqs["a"], reqs["b"]
        while a.state is not RequestState.FINISHED:
            tick()
        # the stop id is known only after the sync: both finish in the gap,
        # and the launch built ahead for a and c is dropped
        assert b.state is RequestState.FINISHED and eng._pending is None
        assert a.output_tokens == want[:cut] and len(b.output_tokens) == cut + 1
        while eng.has_work():
            tick()
        return reqs, {"finishes_deferred": 0, "launches_rebuilt": 2,
                      "settled_for": {"finish": 2}}

    @staticmethod
    def _cancel_beside(eng, tick, served, model, rng):
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=m))
                for k, n, m in (("a", 6, 4), ("b", 11, 12), ("c", 7, 12))}
        a, b = reqs["a"], reqs["b"]
        for _ in range(3):
            tick()
        if served:
            assert eng._pending is not None and a.state is RequestState.RUNNING
        # a's own advance takes it to its cap: it is finished, not cancelled
        assert not eng.cancel(a.rid)
        assert a.state is RequestState.FINISHED and not a.cancelled
        assert len(a.output_tokens) == 4 and eng._pending is None
        assert eng.cancel(b.rid) and len(b.output_tokens) == 4
        while eng.has_work():
            tick()
        return reqs, {"finishes_deferred": 0,
                      "settled_for": {"cancel": 1, "finish": 1}}

    @staticmethod
    def _preempt_beside(eng, tick, served, model, rng):
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(),
                                   SamplingParams(max_new_tokens=m))
                for k, n, m in (("a", 6, 4), ("b", 11, 8))}
        a, b = reqs["a"], reqs["b"]
        for _ in range(3):
            tick()
        if served:
            assert eng._pending is not None and eng._prebuilt.ending == {a.row}
        eng._preempt(b)  # settles first: a ends with its tokens, b starts over
        assert a.state is RequestState.FINISHED and len(a.output_tokens) == 4
        assert b.state is RequestState.QUEUED and eng.stats.preemptions == 1
        while eng.has_work():
            tick()
        return reqs, {"finishes_deferred": 0,
                      "settled_for": {"preempt": 1, "finish": 1}}

    @staticmethod
    def _successor(eng, tick, served, model, rng):
        sp = lambda n: SamplingParams(max_new_tokens=n)  # noqa: E731
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(), sp(m))
                for k, n, m in (("a", 6, 4), ("b", 9, 14), ("c", 7, 4))}
        a, c = reqs["a"], reqs["c"]
        assert eng.max_batch == 2
        for _ in range(3):
            tick()  # d3 makes a's last token
        joined = 0
        while c.state is RequestState.QUEUED:
            tick()
            joined += 1
        # the row is free when a's advance has run, behind d4: c joins in
        # the step after, one later than where every step is settled
        assert joined == (2 if served else 1) and c.row >= 0
        assert a.state is RequestState.FINISHED
        while eng.has_work():
            tick()
        return reqs, {"finishes_deferred": 2,
                      "settled_for": {"admit": 1, "finish": 1}}

    @staticmethod
    def _two_steps(eng, tick, served, model, rng):
        sp = lambda n: SamplingParams(max_new_tokens=n)  # noqa: E731
        reqs = {k: eng.add_request(rng.integers(1, 500, n).tolist(), sp(m))
                for k, n, m in (("a", 6, 4), ("b", 9, 5), ("c", 7, 16),
                                ("d", 5, 6), ("e", 8, 6))}
        assert eng.max_batch == 3
        for _ in range(3):
            tick()  # d3 makes a's last token; d4 will make b's
        waves = eng.prefill["waves"]
        joined = []
        for _ in range(3):
            tick()
            joined.append([k for k in "de"
                           if reqs[k].state is not RequestState.QUEUED])
        # a's row is free before the admission that settles d4, b's is
        # freed BY that settle: both are filled by that one admission, in
        # one wave, where a run that settles every step fills them in two
        assert joined == ([[], ["d", "e"], ["d", "e"]] if served
                          else [["d"], ["d", "e"], ["d", "e"]])
        assert eng.prefill["waves"] == waves + (1 if served else 2)
        while eng.has_work():
            tick()
        # a, then d and e beside c, end behind a launch; b in the settle
        return reqs, {"finishes_deferred": 3,
                      "settled_for": {"admit": 1, "finish": 1}}

    CASES = {
        "finish_by_length": ({}, "_length"),
        "finish_by_stop_id": ({}, "_stop"),
        "cancel_with_an_advance_pending": ({}, "_cancel"),
        "preempt_after_a_page_taken_ahead": ({}, "_preempt"),
        "no_page_for_the_build_ahead": (
            {"num_slots": 6 * PAGE, "max_batch": 2}, "_no_pages"),
        "admission_into_a_freed_row": ({"max_batch": 2}, "_admission"),
        "seeded_row_joined": ({}, "_seeded"),
        "return_logits_row": ({}, "_return_logits"),
        "finisher_beside_a_row_crossing_a_page": ({}, "_beside"),
        "every_live_row_ends": ({}, "_all_end"),
        "stop_id_and_length_cap_in_one_launch": ({}, "_stop_and_length"),
        "cancel_with_a_finish_pending": ({}, "_cancel_beside"),
        "preempt_with_a_finish_pending": ({}, "_preempt_beside"),
        "successor_of_a_deferred_finish": ({"max_batch": 2}, "_successor"),
        "finishers_of_two_steps_join_in_one_wave": ({"max_batch": 3}, "_two_steps"),
    }

    def _run(self, model, case, served):
        kw, script = self.CASES[case]
        eng = make_engine(model, **kw)
        tree0 = (eng.tree.evictable_size_, eng.tree.protected_size_)
        free0 = eng.pool.free_slots

        def tick():
            eng.step()
            if not served:
                eng._settle()

        reqs, counts = getattr(self, script)(
            eng, tick, served, model, prompts_rng())
        assert eng._pending is None and not eng.has_work()
        assert not eng._emit_rows and not eng._emit_steps
        assert all(r is None for r in eng._rows)
        assert (eng._page_table == eng._scratch_page).all()
        assert eng.tree.protected_size_ == tree0[1] == 0
        state = {
            "tokens": {k: list(r.output_tokens) for k, r in reqs.items()},
            "finished": {k: (r.state, r.cancelled) for k, r in reqs.items()},
            "stats": {k: getattr(eng.stats, k) for k in (
                "decode_steps", "generated_tokens", "finished", "prefills",
                "preemptions", "prompt_tokens", "cached_tokens")},
            "tree": (eng.tree.evictable_size_, eng.tree.protected_size_),
            "free": eng.pool.free_slots,
            "emitted": eng.emit["tokens"],
        }
        eng.tree.reset()
        assert eng.pool.free_slots == free0  # back where it started
        assert (eng.tree.evictable_size_, eng.tree.protected_size_) == tree0
        return eng, reqs, state, counts

    @pytest.mark.parametrize("case", list(CASES))
    def test_served_as_settled_and_counted(self, model, case):
        eng, reqs, served, counts = self._run(model, case, served=True)
        settled_eng, _, settled, _ = self._run(model, case, served=False)
        assert served == settled
        for k, r in reqs.items():
            sp = r.sampling
            if sp.seed is None and not sp.stop_token_ids and not r.cancelled:
                assert r.output_tokens == oracle_generate(
                    *model, r.prompt, sp.max_new_tokens), k
        h, steps = eng.handover, eng.stats.decode_steps
        assert h["launches_prebuilt"] + h["launches_rebuilt"] == steps
        assert h["advances_deferred"] + h["advances_settled"] == steps
        assert sum(h["settled_for"].values()) == h["advances_settled"]
        assert h["advances_deferred"] > 0
        for key, n in counts.items():
            if key == "settled_for":
                got = {k: v for k, v in h[key].items() if v}
                assert got == n, (got, n)
            else:
                assert h[key] == n, (key, h[key], n)
        # settled after every step, no advance ever ran behind a launch
        assert settled_eng.handover["advances_deferred"] == 0


class TestTailOnlyResume:
    """A model that keeps a window group and no state (``laguna-tiny``):
    what the tree holds for a resume is a tail without a state slot."""

    @pytest.fixture(scope="class")
    def eng_of(self):
        cfg, params = preset_model("laguna-tiny")

        def make(**kw):
            return Engine(cfg, params, num_slots=2048, page_size=16, max_batch=2,
                          max_seq_len=512, prefill_chunk=64, **kw)

        return make

    @staticmethod
    def prompt(n, seed):
        return np.random.default_rng([7, seed]).integers(0, 512, n).tolist()

    def test_attach_match_evict_free(self, eng_of):
        eng = eng_of()
        assert eng.state_stats is None and eng.tail_stats is not None
        assert eng.pool.state is None and eng.pool.window is not None
        p = self.prompt(70, 0)
        out = eng.generate([p], SamplingParams(max_new_tokens=30))[0]
        m = eng.tree.match_prefix(np.asarray(p + out, np.int32))
        # the prompt's cut and the release's boundary, no slot at either
        assert m.states == [(64, None), (96, None)]
        assert [len(t) for t in m.tails] == [1, 1]  # a window = one page
        assert eng.tail_stats["snapshots"] == 2
        held = eng.pool.window_slots_used
        assert 0 < held <= eng.window_stats["tail_slots_in_tree"] == 32
        # a second turn resumes from the deeper one: counted, not copied
        p2 = p + out + self.prompt(9, 1)
        req = eng.add_request(p2, SamplingParams(max_new_tokens=4))
        while eng.has_work():
            eng.step()
        assert req.prefix_len == 96 and eng.stats.cached_tokens == 96
        assert eng.tail_stats["resumes"] == eng.tail_stats["matches"] == 1
        # evicted under the rows: every count returns, the rows stay
        assert eng.tree.evict_states(16) > 0
        assert eng.pool.window_slots_used == 0
        assert eng.window_stats["tail_slots_in_tree"] == 0
        assert eng.pool.window_refs.sum() == 1
        assert eng.tree.match_prefix(np.asarray(p2, np.int32)).length >= 96

    def test_a_cancelled_admission_returns_its_window_pages(self, eng_of):
        eng = eng_of()
        p = self.prompt(50, 2)
        req = eng.add_request(p, SamplingParams(max_new_tokens=40))
        while req.kv_len < 60:
            eng.step()
            eng._settle()
        assert req.w_pages and eng.pool.window_slots_used > 0
        assert eng.cancel(req.rid)
        eng.tree.reset()
        assert eng.pool.window_slots_used == 0 and eng.pool.window_refs.sum() == 1

    def test_the_debug_state_publishes_the_tail_group(self, eng_of):
        from radixmesh_tpu.server.http_frontend import ServingFrontend
        import json
        import urllib.request

        eng = eng_of()
        fe = ServingFrontend(eng, port=0)
        try:
            p = self.prompt(40, 3)
            for prompt in (p, p + [1, 2, 3]):
                body = json.dumps({"input_ids": prompt, "max_tokens": 20}).encode()
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{fe.port}/generate", data=body,
                    headers={"Content-Type": "application/json"}), timeout=120).read()
            state = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/debug/state", timeout=30).read())
        finally:
            fe.close(drain_s=0.0)
        assert "state" not in state
        assert state["tail"]["matches"] == state["tail"]["resumes"] == 1
        assert state["tail"]["snapshots"] >= 2 and state["tail"]["evictions"] == 0
        assert state["kv"]["groups"]["window"]["bytes_per_token"] == 2 * 3 * 2 * 32 * 4
        assert state["window"]["pages_released"] > 0
        assert state["attn"]["full_tokens_read"] > 0
        assert {"rm.window.release", "rm.state.restore", "rm.state.snapshot"} <= set(
            state["engine"]["spans"])


@pytest.mark.parametrize(
    "model", ["ling-tiny", "nemotron-tiny", "lfm2-tiny", "phi4flash-tiny"])
def test_a_decode_launch_names_a_boundary_slot_only_where_a_page_completes(model):
    """Every state-bearing model: a launch's second state slot is the row's
    boundary slot when its token completes a page, slot 0 otherwise — and a
    model with linear-attention layers copies for those rows alone
    (``kda.boundary_copies``), the others write both every step."""
    from radixmesh_tpu.models import init_params

    cfg = get_config(model).replace(dtype=jnp.float32)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(1)), num_slots=4096,
                 page_size=16, max_batch=4, max_seq_len=512)
    rng = np.random.default_rng(3)
    reqs = [eng.add_request(rng.integers(0, 500, n).tolist(),
                            SamplingParams(max_new_tokens=24))
            for n in (10, 27)]
    seen = 0
    while eng.has_work():
        eng.step()
        eng._settle()
        launch = eng._build_launch(0, preempt=False)
        if launch is None:
            continue
        work, keep = np.asarray(launch.state_d)
        for row, req in launch.active:
            completes = (req.kv_len + 1) % 16 == 0
            assert work[row] == req.state_slot
            assert keep[row] == (req.state_boundary if completes else 0)
            seen += completes
        assert launch.boundary_copies == int(np.count_nonzero(keep))
    assert seen >= 2 and all(len(r.generated) == 24 for r in reqs)
    if cfg.matrix_state:
        assert eng.matrix_stats["boundary_copies"] >= 2
        assert eng.matrix_stats["boundary_copies"] <= seen
    else:
        assert eng.matrix_stats is None

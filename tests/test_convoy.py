"""Decode-interleaved chunked prefill (PR 19): engine-level proofs that
mixed compute waves change WHEN prefill runs, never WHAT is generated —
output equivalence against the legacy alternating schedule, exact chunk
resume offsets, spec-decode composition, the ``prefill_inline`` stall
attribution, draft-ahead from promoted prefixes, and the small-batch
paged dispatch seam."""

import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine import Engine, RequestState, SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.obs.token_timeline import STALL_CAUSES
from radixmesh_tpu.ops.attention import batch_bucket

pytestmark = pytest.mark.quick

PAGE = 4


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny().replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 512)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    return Engine(cfg, params, **kw)


def repetitive_prompt(n_tokens: int, seed: int, vocab: int) -> list[int]:
    head = np.random.default_rng(seed).integers(1, vocab - 1, size=4)
    return (list(map(int, head)) * ((n_tokens // 4) + 1))[:n_tokens]


def staggered_run(eng, prompts, samp, lead_steps=3, cap=600):
    """First prompt admitted and decoding, the rest arriving mid-decode
    — the arrival pattern that exposes the convoy."""
    reqs = [eng.add_request(prompts[0], samp)]
    for _ in range(lead_steps):
        eng.step()
    reqs += [eng.add_request(p, samp) for p in prompts[1:]]
    steps = 0
    while eng.has_work() and steps < cap:
        eng.step()
        steps += 1
    assert not eng.has_work(), "engine failed to drain"
    return [list(map(int, r.output_tokens)) for r in reqs]


class TestMixedWaveEquivalence:
    def test_outputs_match_legacy_schedule(self, model):
        cfg, _ = model
        prompts = [
            repetitive_prompt(n, i, cfg.vocab_size)
            for i, n in enumerate((12, 90, 9))
        ]
        samp = SamplingParams(temperature=0.0, max_new_tokens=10)
        base = staggered_run(make_engine(model), prompts, samp)
        mixed_eng = make_engine(model, prefill_inline_budget=16)
        mixed = staggered_run(mixed_eng, prompts, samp)
        assert base == mixed
        # The mixed arm actually interleaved: inline tokens advanced
        # inside decode-bearing waves, not as legacy bulk prefill.
        snap = mixed_eng.waves.snapshot()
        assert snap["inline_tokens"] > 0
        assert snap["counts"]["mixed"] > 0

    def test_spec_decode_composes_with_inline_prefill(self, model):
        cfg, _ = model
        prompts = [
            repetitive_prompt(n, 20 + i, cfg.vocab_size)
            for i, n in enumerate((16, 80, 12))
        ]
        samp = SamplingParams(temperature=0.0, max_new_tokens=12)
        base_eng = make_engine(model, spec_decode_tokens=2)
        base = staggered_run(base_eng, prompts, samp)
        mixed_eng = make_engine(
            model, spec_decode_tokens=2, prefill_inline_budget=16
        )
        mixed = staggered_run(mixed_eng, prompts, samp)
        assert base == mixed
        st = mixed_eng.stats
        assert st.spec_proposed > 0, "speculation never engaged"
        assert st.spec_proposed == st.spec_accepted + st.spec_rejected
        assert mixed_eng.waves.snapshot()["inline_tokens"] > 0

    def test_chunk_resume_offsets_exact(self, model):
        cfg, _ = model
        samp = SamplingParams(temperature=0.0, max_new_tokens=6)
        budget = 8
        eng = make_engine(model, prefill_inline_budget=budget)
        eng.add_request(repetitive_prompt(10, 30, cfg.vocab_size), samp)
        for _ in range(3):
            eng.step()
        long_prompt = repetitive_prompt(50, 31, cfg.vocab_size)
        long_req = eng.add_request(long_prompt, samp)
        positions = []
        steps = 0
        while eng.has_work() and steps < 400:
            job = next(
                (j for j in eng._inline if j.req.rid == long_req.rid), None
            )
            if job is not None:
                positions.append(job.pos)
            eng.step()
            steps += 1
        assert positions, "the long prompt never entered the inline backlog"
        # Resume offsets: monotone, each advance at most the budget, and
        # the final chunk lands exactly at the prompt length (no token
        # skipped, none fed twice).
        for a, b in zip(positions, positions[1:]):
            assert a <= b <= a + budget
        assert long_req.kv_len >= len(long_prompt)
        assert long_req.state == RequestState.FINISHED
        assert len(long_req.output_tokens) == 6

    def test_cancel_mid_inline_releases_everything(self, model):
        cfg, _ = model
        samp = SamplingParams(temperature=0.0, max_new_tokens=8)
        eng = make_engine(model, prefill_inline_budget=8)
        carrier = eng.add_request(
            repetitive_prompt(12, 40, cfg.vocab_size), samp
        )
        for _ in range(3):
            eng.step()
        victim = eng.add_request(
            repetitive_prompt(60, 41, cfg.vocab_size), samp
        )
        eng.step()  # victim enters the backlog, advances one chunk
        assert any(j.req.rid == victim.rid for j in eng._inline)
        assert eng.cancel(victim.rid)
        assert not eng._inline
        assert not eng._inline_rows
        assert victim.cancelled
        assert victim.state == RequestState.FINISHED
        steps = 0
        while eng.has_work() and steps < 200:
            eng.step()
            steps += 1
        assert len(carrier.output_tokens) == 8
        # The freed row is admissible again.
        late = eng.add_request(
            repetitive_prompt(9, 42, cfg.vocab_size), samp
        )
        while eng.has_work():
            eng.step()
        assert len(late.output_tokens) == 8


class TestStallAttribution:
    """Satellite: the one-shot stall-cause latch. A gap spanning an
    inline chunk must attribute to the new ``prefill_inline`` cause —
    before PR 19 it fell through to ``scheduler_wait``."""

    def test_prefill_inline_in_taxonomy(self):
        assert "prefill_inline" in STALL_CAUSES

    def test_inline_gap_attributed_not_scheduler_wait(self, model):
        eng = make_engine(model, prefill_inline_budget=8)
        req = eng.make_request([1, 2, 3])
        now = time.monotonic()
        eng._last_prefill_t = now - 100.0  # no bulk prefill in the gap
        eng._last_inline_prefill_t = now - 0.01  # inline chunk inside it
        assert eng._stall_cause(req, now, gap_s=0.05) == "prefill_inline"

    def test_bulk_convoy_outranks_inline(self, model):
        eng = make_engine(model, prefill_inline_budget=8)
        req = eng.make_request([1, 2, 3])
        now = time.monotonic()
        eng._last_prefill_t = now - 0.01
        eng._last_inline_prefill_t = now - 0.01
        assert eng._stall_cause(req, now, gap_s=0.05) == "prefill_convoy"

    def test_inline_outranks_spec_miss_and_wait(self, model):
        eng = make_engine(model, prefill_inline_budget=8)
        req = eng.make_request([1, 2, 3])
        req.spec_miss = 1
        now = time.monotonic()
        eng._last_prefill_t = now - 100.0
        eng._last_inline_prefill_t = now - 0.01
        assert eng._stall_cause(req, now, gap_s=0.05) == "prefill_inline"
        # With no inline chunk in the gap the latch must NOT stick:
        # the next attribution falls through to the real cause.
        eng._last_inline_prefill_t = now - 100.0
        assert eng._stall_cause(req, now, gap_s=0.05) == "spec_verify_miss"
        assert eng._stall_cause(req, now, gap_s=0.05) == "scheduler_wait"


class TestDraftAhead:
    """Satellite: draft-ahead from the mesh. A prefix promoted by a
    PREFETCH fill or disk promotion must draft exactly like a natively
    published one — the tree's draft_ready_epoch re-arms requests whose
    tree drafting had latched off."""

    def test_promoted_prefix_yields_same_draft_as_native(self, model):
        cfg, _ = model
        eng = make_engine(model, spec_decode_tokens=4)
        prompt = repetitive_prompt(16, 50, cfg.vocab_size)
        eng.generate([prompt], SamplingParams(temperature=0.0, max_new_tokens=8))

        def mid_decode_request(prefix_len: int, tree_ok: bool):
            r = eng.make_request(prompt)
            r.kv_len = len(prompt) - 1  # history key = the full prompt
            r.prefix_len = prefix_len
            r.tree_draft_ok = tree_ok
            return r

        native = mid_decode_request(prefix_len=len(prompt), tree_ok=True)
        native_draft, native_src = eng._draft_for(native)
        assert native_src == "tree"
        assert len(native_draft) > 0

        # A remote/disk-restored request: no native prefix hit, tree
        # drafting latched off by an earlier empty peek.
        promoted = mid_decode_request(prefix_len=0, tree_ok=False)
        _, before_src = eng._draft_for(promoted)
        assert before_src != "tree"

        # The promotion lands (what kv_transfer's apply site does after
        # installing a PREFETCH/disk unit) — the epoch bump re-arms.
        eng.tree.note_draft_ready()
        promoted_draft, promoted_src = eng._draft_for(promoted)
        assert promoted_src == "tree"
        assert np.array_equal(promoted_draft, native_draft)
        assert promoted.draft_epoch == eng.tree.draft_ready_epoch

    def test_kv_transfer_apply_site_bumps_epoch(self, model):
        # The contract the draft-ahead path rides: the transfer plane's
        # apply site calls note_draft_ready (duck-typed, trees without
        # the hook are tolerated).
        import radixmesh_tpu.cache.kv_transfer as kv_transfer

        assert "note_draft_ready" in inspect.getsource(kv_transfer)
        eng = make_engine(model)
        before = eng.tree.draft_ready_epoch
        note = getattr(eng.tree, "note_draft_ready", None)
        assert note is not None
        note()
        assert eng.tree.draft_ready_epoch == before + 1


class TestStarvationVirtualTime:
    def test_decode_never_deferred_past_bound(self, model):
        # 12:1 prompt-length skew with boost waves enabled
        # (prefill_wave_tokens shrunk below the backlog). The judgment
        # is in STEP COUNTS: while inline work is pending, the carrier
        # never goes more than max_defer consecutive steps tokenless.
        cfg, _ = model
        max_defer = 1
        eng = make_engine(
            model,
            prefill_inline_budget=8,
            prefill_inline_max_defer=max_defer,
            prefill_wave_tokens=16,
        )
        carrier = eng.add_request(
            repetitive_prompt(8, 60, cfg.vocab_size),
            SamplingParams(temperature=0.0, max_new_tokens=24),
        )
        for _ in range(3):
            eng.step()
        eng.add_request(
            repetitive_prompt(96, 61, cfg.vocab_size),
            SamplingParams(temperature=0.0, max_new_tokens=4),
        )
        gap = max_gap = 0
        last = len(carrier.output_tokens)
        steps = 0
        while eng.has_work() and steps < 400:
            pending = bool(eng._inline)
            eng.step()
            steps += 1
            n = len(carrier.output_tokens)
            if n > last or not pending or n >= 24:
                gap = 0
            else:
                gap += 1
                max_gap = max(max_gap, gap)
            last = n
        snap = eng.waves.snapshot()
        assert snap["counts"]["boost"] >= 1, "skew never exercised deferral"
        assert max_gap <= max_defer
        assert snap["max_defer_observed"] <= max_defer


class TestPagedDispatch:
    def test_batch_bucket_powers_of_two(self):
        assert [batch_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [
            1, 2, 4, 8, 8, 16,
        ]
        assert batch_bucket(3, floor=8) == 8

    def test_engine_exposes_dispatch_and_wave_snapshot(self, model):
        # The fields /debug/state renders: the last plain decode launch
        # and the wave-mix counters.
        cfg, _ = model
        eng = make_engine(model, prefill_inline_budget=8)
        staggered_run(
            eng,
            [
                repetitive_prompt(10, 70, cfg.vocab_size),
                repetitive_prompt(40, 71, cfg.vocab_size),
            ],
            SamplingParams(temperature=0.0, max_new_tokens=6),
        )
        assert eng._last_dispatch is not None
        assert eng._last_dispatch["path"] in ("dense", "paged")
        snap = eng.waves.snapshot()
        assert set(snap) >= {
            "budget", "max_defer", "counts", "inline_tokens",
            "decode_defer", "max_defer_observed",
        }

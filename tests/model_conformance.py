"""One conformance suite for every served configuration.

This file is the written form of the model seam until the product side has
one (ROADMAP D7): what a configuration has to do, through the engine, to be
served. A family's test file states a ``ModelSpec`` — the rehearsal twin's
preset as ``benchmarks/configs/*.json`` names it, a seed, the plain float32
reference as one callable, the engine's keywords — and brings the cases
below into its module::

    from tests.model_conformance import *  # noqa: F401,F403
    SPEC = ModelSpec(preset=..., seed=..., reference=..., engine=..., lengths=...)

Every case serves requests through ``Engine`` on the CPU in float32 and
compares LOGITS (not tokens) with the reference's full forward pass over
prompt + generated tokens. Each decides whether it applies from what
``ModelConfig`` and the built ``Engine`` say, never from a name, and
``pytest.skip``s with the reason where it does not.

The contract, by case:

(0) ``test_the_engine_holds_the_arrays_the_config_states_and_no_other`` —
    always. The pool's arrays are the ones ``ModelConfig`` states; the
    tree's handlers and the engine's counters exist exactly where the
    array they follow does.
(a) ``test_prefill_then_decode_equals_the_reference`` — always. A lone cold
    prompt inside a page, at a page multiple and over several chunks, by
    the dense launch and by chunk launches, then decode steps through the
    pool (and the state slots, the window group, the index plane). The
    launch taken is the one asked for wherever the planner's floor
    (``prefill_cost[0]``) admits the dense one.
(b) ``test_a_batch_of_ragged_rows`` — always. Rows of other lengths and other
    ``max_new_tokens`` prefill in one wave and decode in one batch.
(c) ``test_many_decode_steps_stay_exact`` — always. Forty steps; ninety
    where ``pool.window`` is set, and then a row never holds more than its
    window and two pages and gives the rest back.
(d) ``test_the_identical_prompt_again_resumes`` — always. The same prompt
    twice more: served from rows alone where ``engine._resumes`` is false,
    from the deepest snapshot or tail at or before the match otherwise (a
    page-multiple prompt leaves a token to compute and resumes a page
    earlier); the engine's own counters (``state_stats`` / ``tail_stats``,
    ``stats.cached_tokens``) agree with where the requests resumed.
(e) ``test_the_next_turn_resumes_from_what_the_release_left`` — always. Turn
    2 resumes at the last page boundary turn 1's DECODE passed: no prefill
    computed what it resumes from, and nothing is computed again.
(f) ``test_two_requests_branch_inside_a_shared_prefix`` — always. Where only
    rows are matched the branch is served at once; where a resume point is
    needed the first visitor computes from 0 and leaves one AT the branch,
    and the second resumes from it.
(f') ``test_a_split_leaves_the_resume_point_with_the_lower_half`` — where
    ``engine._resumes``: a node split above its snapshot or tail leaves it
    with the lower half; each visitor resumes at the deepest point at or
    before its match.
(g) ``test_one_launch_holds_a_row_that_resumes_and_a_cold_one`` — always.
(h) ``test_a_resume_point_evicted_under_its_rows_is_computed_again`` — where
    ``engine._resumes``: the tree gives up snapshots / tails alone, the
    rows stay, the next visit computes the prefix again and is exact.
(i) ``test_plain_launches_keep_what_a_turn_resumes_from`` — always. Without
    the logits hook the decode launches hand over (inputs built a step
    ahead): the session continues as a cold engine computes it.
(j) ``test_a_row_that_ends_by_its_length_is_released_behind_the_next_launch``
    — always. What its release gives the tree is what its OWN last launch
    left; the run agrees with one that settles after every step.
(k) ``test_a_preempted_request_resumes_from_what_it_published`` — always.
(l) ``test_what_a_turn_resumes_from_zeroed_fails_the_tolerance`` — the
    control of (d)-(k), for ``rows`` always, for ``state`` where
    ``pool.state`` is set, for ``tail`` where ``pool.window`` is: the same
    second turn misses the reference by a hundred tolerances. Where
    ``spec.forget`` names the reference's own control, the reference that
    forgets there does not agree with the whole program (and, where the
    control is exact, does agree with the spoiled one).
(m) ``test_the_reference_without_a_part_disagrees`` — for each of
    ``spec.parts``: what the tolerance can tell apart.
(o) ``test_a_boundary_copy_is_made_on_the_step_that_completes_a_page`` and
(p) ``test_a_full_state_group_gives_up_superseded_snapshots_first`` — where
    ``engine.matrix_stats`` is set (a float32 matrix a head in the state
    slots): a decode step copies the working state to the boundary slot on
    the step that completes a page and on no other, and slots under
    pressure give up superseded snapshots before any a session resumes from.
(n) ``test_the_engine_refuses_by_name`` and ``test_other_entries_refuse_by_
    name`` — for every option ``unsupported_reason`` names for this
    configuration (what assumes the dense layer; what moves or advertises a
    prefix without its state): refused at start-up with that very reason.

``test_every_configuration_of_the_manifest_has_a_spec`` (collected once, in
the dense twin's file) holds every ``rehearsal.preset`` under
``benchmarks/configs/`` to be some family file's ``SPEC``.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import importlib
import json
import os
import re
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import UnsupportedForModel, unsupported_reason

__all__ = [  # what ``import *`` brings into a family's module
    "ModelSpec", "OPTIONS", "device_mesh", "distance", "logits_from",
    "abstract_params", "kernels_in", "copied_shapes",
    "aliased_and_uncopied", "equations", "kernel_calls",
    "spec", "model", "v5e", "pytest_generate_tests",
    "test_the_engine_holds_the_arrays_the_config_states_and_no_other",
    "test_prefill_then_decode_equals_the_reference",
    "test_a_batch_of_ragged_rows", "test_many_decode_steps_stay_exact",
    "test_the_identical_prompt_again_resumes",
    "test_the_next_turn_resumes_from_what_the_release_left",
    "test_two_requests_branch_inside_a_shared_prefix",
    "test_a_split_leaves_the_resume_point_with_the_lower_half",
    "test_one_launch_holds_a_row_that_resumes_and_a_cold_one",
    "test_a_resume_point_evicted_under_its_rows_is_computed_again",
    "test_plain_launches_keep_what_a_turn_resumes_from",
    "test_a_row_that_ends_by_its_length_is_released_behind_the_next_launch",
    "test_a_preempted_request_resumes_from_what_it_published",
    "test_a_boundary_copy_is_made_on_the_step_that_completes_a_page",
    "test_a_full_state_group_gives_up_superseded_snapshots_first",
    "test_what_a_turn_resumes_from_zeroed_fails_the_tolerance",
    "test_the_reference_without_a_part_disagrees",
    "test_the_engine_refuses_by_name", "test_other_entries_refuse_by_name",
]


def distance(got, want) -> float:
    """``max|a - b| / max|b|``."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def logits_from(forward):
    """The adapter of a reference whose ``forward(params, cfg, tokens, *,
    logits_from, **control)`` answers a dict (seven of the nine under
    ``benchmarks/reference/``)."""

    def reference(params, cfg, seq, logits_from, **control):
        return forward(params, cfg, seq, logits_from=logits_from, **control)

    return reference


def device_mesh(**axes):
    from radixmesh_tpu.parallel.sharding import MeshPlan, make_mesh

    return make_mesh(MeshPlan(**axes))


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """What a family's test file states of its configuration.

    ``reference(params, cfg, seq, logits_from, **control)``: the logits
    [len(seq) - logits_from, V] of the plain float32 reference, or its dict
    with ``logits`` (and ``expert_ids`` [S, sparse layers, k], which
    ``check`` then holds the request's expert rows to). ``engine``:
    ``Engine``'s keywords (page_size, num_slots, max_batch, max_seq_len,
    prefill_chunk). ``lengths``: case (a)'s prompt lengths; ``pages``:
    further page sizes cases (a), (b), (d), (e) run at. ``parts``: name ->
    (the reference's control keywords, how many tolerances it must miss
    by). ``forget``: ``"state"`` / ``"tail"`` -> the control keywords, given
    a position, with which the reference forgets it there, and
    ``forget_is_exact``: those of them under which the reference computes
    what the program computes from zeroed slots (a control that also changes
    what the layers before the position hand the attention layers does
    not). ``says`` / ``omits``: what every refusal says, and does not say,
    of this configuration. ``hooks``:
    case name -> the family's further assertions on what the case built."""

    preset: str
    seed: int
    reference: Callable
    engine: dict
    lengths: tuple
    tol: float = 1e-4  # float32 against float32: other orders of the same sums
    band_bf16: float | None = None
    n_new: int = 9  # one token from the prefill, 8 decode steps
    pages: tuple = ()
    ragged: tuple = (20, 75, 130, 41)
    parts: dict = dataclasses.field(default_factory=dict)
    parts_prompt: int = 60
    forget: dict = dataclasses.field(default_factory=dict)
    forget_is_exact: tuple = ()
    says: tuple = ()
    omits: tuple = ()
    hooks: dict = dataclasses.field(default_factory=dict)

    def build(self, dtype=jnp.float32):
        cfg = get_config(self.preset).replace(dtype=dtype)
        return cfg, init_params(cfg, jax.random.PRNGKey(self.seed))

    def make_engine(self, model, page=None, **kw):
        cfg, params = model
        kw = {**self.engine, **kw}
        if page is not None:
            kw["page_size"] = page
        return Engine(cfg, params, **kw)

    def prompt_of(self, n, seed=0):
        return np.random.default_rng([self.seed, seed]).integers(0, 512, n).tolist()

    def ask(self, eng, prompts, n_new=None, grow=0):
        """Every prompt added at once (``grow`` more tokens each than the
        one before), the engine stepped until none is left."""
        reqs = [eng.add_request(p, SamplingParams(
            max_new_tokens=(n_new or self.n_new) + grow * i, return_logits=True))
            for i, p in enumerate(prompts)]
        while eng.has_work():
            eng.step()
        return reqs

    def run(self, eng, prompt, n_new=None):
        return self.ask(eng, [prompt], n_new)[0]

    def _reference(self, model, req, prompt, **control):
        cfg, params = model
        seq = list(prompt) + req.generated[:-1]
        want = self.reference(params, cfg, seq, len(prompt) - 1, **control)
        return want if isinstance(want, dict) else {"logits": want}

    def error(self, model, req, prompt, **control):
        """``distance`` of the request's logits rows from the reference's
        full forward pass of the same sequence."""
        want = self._reference(model, req, prompt, **control)
        return distance(np.stack(req.logit_rows), want["logits"])

    def check(self, model, req, prompt, n_new=None, tol=None):
        """The request's kept logits rows (and expert ids) against the
        reference; what was sampled is their argmax. ``tol``: a band of
        lower precision (then only the distance is held)."""
        n_new = n_new or self.n_new
        assert len(req.generated) == n_new and len(req.logit_rows) == n_new
        want = self._reference(model, req, prompt)
        got = np.stack(req.logit_rows)
        assert got.dtype == np.float32 and got.shape == want["logits"].shape
        err = distance(got, want["logits"])
        assert err < (tol or self.tol), err
        if tol is not None:
            return want
        assert [int(x) for x in got.argmax(-1)] == req.generated  # greedy
        if want.get("expert_ids") is not None:
            ids_ref = np.asarray(want["expert_ids"])
            assert ids_ref.shape[2] == model[0].n_experts_per_tok
            seen = set()
            for start, ids in req.expert_rows:  # every computed position's choice
                n = ids.shape[0]
                assert ids.shape[1:] == ids_ref.shape[1:]
                np.testing.assert_array_equal(
                    np.sort(ids, -1), np.sort(ids_ref[start:start + n], -1))
                seen.update(range(start, start + n))
            assert seen == set(range(req.prefix_len, len(prompt) + n_new - 1))
        return want

    def hook(self, case, **built):
        if case in self.hooks:
            self.hooks[case](**built)


@pytest.fixture(scope="module")
def spec(request) -> ModelSpec:
    return request.module.SPEC


@pytest.fixture(scope="module")
def model(spec):
    return spec.build()


def pytest_generate_tests(metafunc):
    """The cases of this file take their lengths, pages and parts from the
    module's ``SPEC`` (a family's own tests parametrize themselves)."""
    if metafunc.function.__module__ != __name__:
        return
    s = metafunc.module.SPEC
    if "n_prompt" in metafunc.fixturenames:
        metafunc.parametrize("n_prompt", s.lengths)
    if "page" in metafunc.fixturenames:
        metafunc.parametrize("page", (s.engine["page_size"], *s.pages))
    if "part" in metafunc.fixturenames:
        metafunc.parametrize("part", sorted(s.parts))


def _long(page: int) -> int:
    """A prompt of whole pages, ~96 tokens: 96 at pages of 4 and 16, two
    pages at 128."""
    return max(2, 96 // page) * page


def _counters(eng):
    """The resume counters of a configuration that resumes from a state
    (``state_stats``) or from a tail alone (``tail_stats``); None where the
    rows are all there is."""
    assert eng._resumes == (
        eng.state_stats is not None or eng.tail_stats is not None)
    return eng.state_stats if eng.state_stats is not None else eng.tail_stats


# ---------------------------------------------------------------------------
# (a)-(c): the arrays the engine holds, the three entry points against the
# reference
# ---------------------------------------------------------------------------


def test_the_engine_holds_the_arrays_the_config_states_and_no_other(model, spec):
    """The pool's groups are ``ModelConfig``'s (``kv_pool_shape``,
    ``kv_index_shape``, ``window_pool_shape``, ``state_shape``), the tree
    and the counters follow them, and what a configuration does not have
    leaves no array, no handler, no counter and no span behind."""
    cfg = model[0]
    eng = spec.make_engine(model)
    pool, slots = eng.pool, spec.engine["num_slots"]
    assert pool.kv.shape == cfg.kv_pool_shape(slots)
    assert (pool.index is None) == (cfg.kv_index_shape(64) is None) == (not cfg.dsa)
    assert pool.index_plane_bytes == (0 if pool.index is None else pool.index.nbytes)
    assert (pool.window is None) == (cfg.window_pool_shape(64) is None)
    stateless = cfg.state_shape(64) is None
    assert (pool.state is None) == stateless == (
        cfg.default_state_slots(slots, spec.engine["max_batch"]) == 0)
    assert (eng.state_stats is None) == (eng.tree.on_free_state is None) == stateless
    assert (eng.tree.on_free_tail is None) == (pool.window is None)
    assert (eng.tail_stats is not None) == (stateless and pool.window is not None)
    assert ("sparse" in eng.attn_window) == bool(cfg.dsa)
    assert cfg.held_experts == cfg.n_experts // cfg.ep_size
    if stateless and pool.window is None:  # every layer keeps rows in the pool
        assert cfg.attn_layers == tuple(range(cfg.n_layers))
    out = eng.generate([spec.prompt_of(9)], SamplingParams(max_new_tokens=3))
    assert len(out[0]) == 3
    if not eng._resumes:  # no resume point is looked for, kept or restored
        assert not any(name.startswith("rm.state") for name in eng.spans.n)


@pytest.mark.parametrize("path", ["dense", "chunk"])
def test_prefill_then_decode_equals_the_reference(model, spec, path, n_prompt, page):
    eng = spec.make_engine(
        model, page, long_prefill_threshold=1024 if path == "dense" else 0)
    prompt = spec.prompt_of(n_prompt)
    req = spec.run(eng, prompt)
    # the dense launch where it is bound by the weights it streams
    admitted = 1 << (n_prompt - 1).bit_length() <= eng.prefill_cost[0]
    assert eng._last_prefill_path == (path if admitted else "chunk")
    want = spec.check(model, req, prompt)
    spec.hook("prefill", eng=eng, req=req, want=want, path=path,
              n_prompt=n_prompt, prompt=prompt)


def test_a_batch_of_ragged_rows(model, spec, page):
    eng = spec.make_engine(model, page)
    prompts = [spec.prompt_of(n, seed=30 + i) for i, n in enumerate(spec.ragged)]
    reqs = spec.ask(eng, prompts, grow=1)
    for i, (req, p) in enumerate(zip(reqs, prompts)):
        spec.check(model, req, p, spec.n_new + i)


def test_many_decode_steps_stay_exact(model, spec):
    """Forty steps; where the window layers keep a group of rows of their
    own, ninety: the window slides windows past the prompt, the row gives
    back what left it and never holds more than window + 2 pages, and the
    last logits agree with the reference as the first do."""
    eng = spec.make_engine(model)
    windowed = eng.pool.window is not None
    n_new = 90 if windowed else 41
    page, window = eng.page_size, model[0].sliding_window
    prompt = spec.prompt_of(50, seed=1)
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=n_new, return_logits=True))
    held = []
    while eng.has_work():
        eng.step()
        if windowed:
            eng._settle()
            if req.row >= 0:
                held.append(len(req.w_pages) * page)
    spec.check(model, req, prompt, n_new)
    if windowed:
        assert max(held) <= window + 2 * page
        ws = eng.window_stats
        assert ws["held_peak_row"] <= window + 2 * page
        assert ws["pages_released"] >= (50 + n_new - window) // page - 1
        # what is left: the tails of the release's and the prompt cut's
        # resume points (a page two tails share is held once)
        assert 0 < eng.pool.window_slots_used <= ws["tail_slots_in_tree"]
        assert eng.attn_window["window_tokens_skipped"] > 0
    spec.hook("long_decode", eng=eng, req=req)


# ---------------------------------------------------------------------------
# (d)-(k): every way a request reaches what it resumes from
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ends", ["inside_a_page", "on_a_page"])
def test_the_identical_prompt_again_resumes(model, spec, page, ends):
    """A prompt that ends inside a page resumes AT its aligned cut; a page
    multiple one page short of it (a token must be left to compute): a
    configuration that needs a resume point then starts from nothing, and
    leaves the one its third visit resumes from."""
    eng = spec.make_engine(model, page)
    n_prompt = _long(page) + (4 if ends == "inside_a_page" else 0)
    resumed = (n_prompt - 1) // page * page
    prompt = spec.prompt_of(n_prompt, seed=3)
    first = spec.run(eng, prompt)
    assert first.prefix_len == 0
    again = spec.run(eng, prompt)
    third = spec.run(eng, prompt)
    assert third.prefix_len == resumed and eng._last_prefill_path == "chunk"
    at_once = n_prompt % page or not eng._resumes
    assert again.prefix_len == (resumed if at_once else 0)
    for req in (first, again, third):
        spec.check(model, req, prompt)
    served = again.prefix_len + third.prefix_len
    assert eng.stats.cached_tokens == served
    st = _counters(eng)
    if st is not None:
        assert st["kv_matched_tokens"] == 2 * resumed
        assert st["resumed_tokens"] == served
        assert st["recomputed_tokens"] == 2 * resumed - served
    if eng.tail_stats is not None:
        assert st["matches"] == 2 and st["resumes"] == 1 + bool(at_once)


def test_the_next_turn_resumes_from_what_the_release_left(model, spec, page):
    """Turn 2 = turn 1's prompt + what it generated + a few new tokens: it
    resumes at the last page boundary a decode step passed — rows, a
    boundary state, a tail that no prefill ever wrote."""
    eng = spec.make_engine(model, page)
    n1, out1 = _long(page) - 26, 30  # the context at release: 3 past a page
    p1 = spec.prompt_of(n1, seed=4)
    t1 = spec.run(eng, p1, out1)
    spec.check(model, t1, p1, out1)
    p2 = p1 + t1.generated + spec.prompt_of(11, seed=5)
    t2 = spec.run(eng, p2)
    assert t2.prefix_len == (n1 + out1 - 1) // page * page == _long(page)
    st = _counters(eng)
    assert st is None or st["recomputed_tokens"] == 0
    spec.check(model, t2, p2)
    p3 = p2 + t2.generated + spec.prompt_of(5, seed=6)
    t3 = spec.run(eng, p3)
    assert t3.prefix_len == (len(p2) + spec.n_new - 1) // page * page
    spec.check(model, t3, p3)
    spec.hook("next_turn", eng=eng)


def test_two_requests_branch_inside_a_shared_prefix(model, spec):
    """B shares 64 tokens with A, whose resume points lie at its own cuts:
    the rows match 64 tokens; where a resume point is needed none lies
    there, so B computes from 0 and leaves one AT the branch, and C,
    branching there too, resumes from it."""
    eng = spec.make_engine(model)
    shared = spec.prompt_of(64, seed=7)
    spec.run(eng, shared + spec.prompt_of(50, seed=8))
    b = shared + spec.prompt_of(40, seed=9)
    rb = spec.run(eng, b)
    st = _counters(eng)
    assert rb.prefix_len == (0 if eng._resumes else 64)
    if st is not None:
        assert st["kv_matched_tokens"] == 64 and st["recomputed_tokens"] == 64
    c = shared + spec.prompt_of(33, seed=10)
    rc = spec.run(eng, c)
    assert rc.prefix_len == 64
    assert st is None or st["recomputed_tokens"] == 64  # nothing more
    spec.check(model, rb, b)
    spec.check(model, rc, c)


def test_a_split_leaves_the_resume_point_with_the_lower_half(model, spec):
    """A's one node holds a resume point at its end. B splits it three
    pages in (above the point): the upper half has none, the lower keeps
    A's; A again still resumes at its cut, B again at its own, and a prompt
    that ends inside A's lower half at the deepest point at or before."""
    eng = spec.make_engine(model)
    if not eng._resumes:
        pytest.skip("a prefix is served from its rows alone: a split node's "
                    "halves hold nothing but rows")
    page = eng.page_size
    a = spec.prompt_of(7 * page + 3, seed=11)
    spec.run(eng, a)
    b = a[:3 * page] + spec.prompt_of(30, seed=12)
    rb = spec.run(eng, b)
    assert rb.prefix_len == 0  # rows for three pages, no resume point there yet
    ra = spec.run(eng, a)
    assert ra.prefix_len == 7 * page
    spec.check(model, ra, a)
    m = eng.tree.match_prefix(np.asarray(a, np.int32))
    assert [pos for pos, _ in m.states] == [3 * page, 7 * page]  # B's, then A's
    if eng.pool.window is not None:
        pages = -(-model[0].sliding_window // page)  # a window of pages each
        assert [len(t) for t in m.tails] == [pages, pages]
        assert m.tail_at(7 * page) is m.tails[1] and m.tail_at(4 * page) is None
    rb2 = spec.run(eng, b)
    assert rb2.prefix_len == (len(b) - 1) // page * page  # b's own aligned cut
    spec.check(model, rb2, b)
    c = a[:5 * page] + spec.prompt_of(9, seed=13)  # ends inside a's lower half
    rc = spec.run(eng, c)
    assert rc.prefix_len == 3 * page
    spec.check(model, rc, c)


def test_one_launch_holds_a_row_that_resumes_and_a_cold_one(model, spec):
    eng = spec.make_engine(model)
    first = spec.prompt_of(100, seed=11)
    spec.run(eng, first)
    warm = first + spec.prompt_of(80, seed=13)
    cold = spec.prompt_of(90, seed=12)
    before = dict(eng.prefill)
    reqs = spec.ask(eng, (warm, cold))
    assert [r.prefix_len for r in reqs] == [100 // eng.page_size * eng.page_size, 0]
    # ONE wave of two rows: one reads what it resumes from, one starts cold
    assert eng.prefill["waves"] - before["waves"] == 1
    assert eng.prefill["rows"] - before["rows"] == 2
    spec.check(model, reqs[0], warm)
    spec.check(model, reqs[1], cold)
    spec.hook("warm_and_cold", eng=eng, before=before)


def test_a_resume_point_evicted_under_its_rows_is_computed_again(model, spec):
    """Pressure on the state slots or the window group alone: the tree
    gives up its snapshots and tails, the rows stay, and the next visit
    computes its prefix again — still exact — and leaves the point back."""
    eng = spec.make_engine(model)
    if not eng._resumes:
        pytest.skip("a prefix is served from its rows alone: the tree holds "
                    "no resume point it could give up under them")
    page, pool = eng.page_size, eng.pool
    prompt = spec.prompt_of(100, seed=14)
    cut = 99 // page * page
    spec.run(eng, prompt)
    spec.run(eng, prompt + spec.prompt_of(30, seed=15))
    spec.hook("before_eviction", eng=eng)
    if pool.window is not None:
        assert 0 < pool.window_slots_used <= eng.window_stats["tail_slots_in_tree"]
    if pool.state is not None:
        n = pool.state_slots_used - 1  # all but the scratch slot
        assert n > 0 and eng.tree.evict_states(8) == n
        assert eng.tree.state_evictions == n and pool.state_slots_used == 1
    else:
        assert eng.tree.evict_states(8) > 0
    if pool.window is not None:
        assert pool.window_slots_used == 0
        assert eng.window_stats["tail_slots_in_tree"] == 0
        assert pool.window_refs.sum() == 1  # the scratch page's own
    m = eng.tree.match_prefix(np.asarray(prompt, np.int32))
    assert m.length == cut and m.states == []  # the rows stay
    again = spec.run(eng, prompt)
    assert again.prefix_len == 0 and _counters(eng)["recomputed_tokens"] == cut
    spec.check(model, again, prompt)
    assert spec.run(eng, prompt).prefix_len == cut  # the point is back


def test_plain_launches_keep_what_a_turn_resumes_from(model, spec):
    """Without the logits hook the decode launches hand over (inputs built
    a step ahead, window pages released behind the next launch): a session
    served that way continues exactly as a cold engine computes it, and its
    next turn resumes at the boundary the hand-over kept."""
    n_new = 70
    sp = SamplingParams(max_new_tokens=n_new)
    page = spec.engine["page_size"]
    p1 = spec.prompt_of(50, seed=17)
    warm = spec.make_engine(model)
    o1 = warm.generate([p1], sp)[0]
    p2 = p1 + o1 + spec.prompt_of(7, seed=18)
    o2 = warm.generate([p2], sp)[0]
    assert warm.handover["launches_prebuilt"] > 0
    assert warm.stats.cached_tokens == (50 + n_new - 1) // page * page
    if warm.pool.window is not None:
        assert warm.window_stats["pages_released"] > 0
    cold = spec.make_engine(model)
    assert cold.generate([p2], sp)[0] == o2
    p3 = p2 + o2 + spec.prompt_of(3, seed=19)
    req = spec.run(warm, p3)
    assert req.prefix_len == (len(p2) + n_new - 1) // page * page
    spec.check(model, req, p3)


# on_a_page: the launch that makes its last token completes a page (kv_len
# 64), so what its release hands over is that launch's; inside: kv_len 61
@pytest.mark.parametrize("n_new", [15, 12], ids=["on_a_page", "inside_a_page"])
def test_a_row_that_ends_by_its_length_is_released_behind_the_next_launch(
        model, spec, n_new):
    """A request reaches its length cap while another goes on (long enough
    to slide a window): the next launch goes out first, without it, and it
    is advanced, released and published behind that launch. What its
    release gives the tree is what its OWN last launch left: its next turn
    resumes there and equals the reference (logits, not tokens), the other
    row's too, and the run agrees with one that settles after every step."""
    pa, pb = spec.prompt_of(50, seed=20), spec.prompt_of(37, seed=21)
    page = spec.engine["page_size"]

    def serve(settled):
        eng = spec.make_engine(model)
        other = 70 if eng.pool.window is not None else 40
        a = eng.add_request(pa, SamplingParams(max_new_tokens=n_new))
        b = eng.add_request(pb, SamplingParams(max_new_tokens=other))
        while eng.has_work():
            eng.step()
            if settled:
                eng._settle()
        assert eng.handover["finishes_deferred"] == (0 if settled else 1)
        turns = [pa + a.generated + spec.prompt_of(7, seed=22),
                 pb + b.generated + spec.prompt_of(5, seed=23)]
        return eng, [a, b] + [spec.run(eng, t) for t in turns], turns, other

    eng, reqs, turns, other = serve(settled=False)
    assert reqs[2].prefix_len == (50 + n_new - 1) // page * page
    assert reqs[3].prefix_len == (37 + other - 1) // page * page
    if eng.pool.window is not None:
        assert eng.window_stats["pages_released"] > 0
    for req, turn in zip(reqs[2:], turns):
        spec.check(model, req, turn)
    eng2, reqs2, _, _ = serve(settled=True)
    assert [r.generated for r in reqs] == [r.generated for r in reqs2]
    assert [r.prefix_len for r in reqs] == [r.prefix_len for r in reqs2]
    assert eng.state_stats == eng2.state_stats
    assert eng.tail_stats == eng2.tail_stats
    assert eng.window_stats == eng2.window_stats
    assert eng.pool.state_slots_used == eng2.pool.state_slots_used
    assert eng.pool.window_slots_used == eng2.pool.window_slots_used
    assert eng.pool.free_slots == eng2.pool.free_slots
    assert eng.tree.evictable_size_ == eng2.tree.evictable_size_


def test_a_preempted_request_resumes_from_what_it_published(model, spec):
    """Preempted after some decode steps (context past 96), the second life
    is the PROMPT again: it resumes at the prompt's own cut (a token is
    left to compute), where its first life left rows and a resume point."""
    eng = spec.make_engine(model)
    page, pool = eng.page_size, eng.pool
    prompt = spec.prompt_of(90, seed=16)
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=20, return_logits=True))
    while req.kv_len < 98:
        eng.step()
        eng._settle()
    eng._preempt(req)
    if pool.state is not None:
        assert pool.state_slots_used > 1  # the snapshots its release left
    if pool.window is not None:
        assert req.w_pages == [] and 0 < pool.window_slots_used <= (
            eng.window_stats["tail_slots_in_tree"])
    spec.hook("preempted", eng=eng, req=req)
    req.logit_rows.clear()
    req.expert_rows.clear()
    eng._pressure = False
    while eng.has_work():
        eng.step()
    assert eng.stats.preemptions == 1 and req.prefix_len == 89 // page * page
    spec.check(model, req, prompt, 20)


# ---------------------------------------------------------------------------
# (o), (p): a float32 matrix state a head beside the rows
# ---------------------------------------------------------------------------


def _matrix_state(spec, model, **kw):
    eng = spec.make_engine(model, **kw)
    if eng.matrix_stats is None:
        pytest.skip("no layer carries a float32 matrix a head "
                    "(cfg.matrix_state is None): engine.matrix_stats is None")
    return eng


def test_a_boundary_copy_is_made_on_the_step_that_completes_a_page(model, spec):
    """44 decode steps from a context of 20: the tokens at positions 31, 47
    and 63 complete a page. Three copies in all, none on any other step,
    and each leaves the working slot's state in the boundary slot."""
    eng = _matrix_state(spec, model)
    page, pool = eng.page_size, eng.pool
    req = eng.add_request(spec.prompt_of(20, seed=21), SamplingParams(
        max_new_tokens=45, return_logits=True))
    copies = 0
    while eng.has_work():
        before = eng.matrix_stats["boundary_copies"]
        kv_before = req.kv_len
        eng.step()
        eng._settle()
        made = eng.matrix_stats["boundary_copies"] - before
        copies += made
        if made:
            assert req.kv_len % page == 0 and req.kv_len == kv_before + 1
            work, keep = req.state_slot, req.state_boundary
            if work and keep:  # still in its row
                assert np.array_equal(pool.state[:, work], pool.state[:, keep])
                assert np.array_equal(
                    pool.state_conv[:, work], pool.state_conv[:, keep])
    assert copies == 3
    st = eng.matrix_stats
    assert st["decode_rows"] == 44 and st["chunk_tokens"] == 20
    per = pool.state_slot_bytes
    # a launch moves every row of the launched batch; 3 prefill snapshots
    assert st["state_bytes_written"] == (44 * 4 + 3 + 3) * per
    assert st["state_bytes_read"] == 44 * 4 * per


def test_a_full_state_group_gives_up_superseded_snapshots_first(model, spec):
    """Slots squeezed to what two sessions need: the tree gives up the
    snapshots a later one of the same sequence supersedes before any a
    waiting session resumes from, and nothing served goes wrong."""
    eng = _matrix_state(spec, model, max_batch=2)
    assert eng._ordered_evict
    page = eng.page_size
    taken = []
    while eng.pool.state_allocator.free_slots > 4:
        taken.append(eng.pool.alloc_state())
    p = spec.prompt_of(60, seed=22)
    first = spec.run(eng, p, 40)
    spec.check(model, first, p, 40)
    p2 = p + first.generated + spec.prompt_of(9, seed=23)
    second = spec.run(eng, p2, 40)
    assert second.prefix_len == 99 // page * page
    spec.check(model, second, p2, 40)
    p3 = p2 + second.generated + spec.prompt_of(9, seed=24)
    third = spec.run(eng, p3, 40)
    spec.check(model, third, p3, 40)
    assert third.prefix_len == (len(p2) + 39) // page * page
    assert eng.tree.state_evictions > 0 and eng.stats.preemptions == 0
    # what is left is the deepest: the earlier turns' snapshots went first
    m = eng.tree.match_prefix(np.asarray(p3 + third.generated, np.int32))
    assert m.states and max(pos for pos, _ in m.states) >= third.prefix_len
    for slot in taken:
        eng.pool.free_state(slot)


# ---------------------------------------------------------------------------
# (l), (m): what must not agree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("what", ["rows", "state", "tail"])
def test_what_a_turn_resumes_from_zeroed_fails_the_tolerance(model, spec, what):
    """The control of the resume cases: the same second turn with the rows,
    the state slots or the window group zeroed under it misses the
    reference by far more than the tolerance."""

    def second_turn(spoil):
        eng = spec.make_engine(model)
        pool = eng.pool
        held = {"rows": ["kv"], "state": ["state", "state_conv"],
                "tail": ["window"]}[what]
        if getattr(pool, held[0]) is None:
            pytest.skip(f"the pool holds no {what} beside the rows "
                        f"(pool.{held[0]} is None)")
        p1 = spec.prompt_of(70, seed=4)
        t1 = spec.run(eng, p1, 30)
        if spoil:
            for name in held:
                if getattr(pool, name) is not None:
                    setattr(pool, name, jnp.zeros_like(getattr(pool, name)))
        p2 = p1 + t1.generated + spec.prompt_of(11, seed=5)
        t2 = spec.run(eng, p2)
        assert t2.prefix_len == 99 // eng.page_size * eng.page_size
        return t2, p2, t2.prefix_len

    t2, p2, at = second_turn(spoil=True)
    assert spec.error(model, t2, p2) > 100 * spec.tol
    if what in spec.forget:
        forgets = spec.forget[what](at)
        if what in spec.forget_is_exact:
            # ... and the reference's own control reads what the program computed
            assert spec.error(model, t2, p2, **forgets) < spec.tol
        whole, p2, _ = second_turn(spoil=False)
        assert spec.error(model, whole, p2) < spec.tol
        assert spec.error(model, whole, p2, **forgets) > 100 * spec.tol


def test_the_reference_without_a_part_disagrees(model, spec, part):
    """Each part matters: without it the reference is far outside the
    tolerance the program meets."""
    control, factor = spec.parts[part]
    eng = spec.make_engine(model)
    prompt = spec.prompt_of(spec.parts_prompt, seed=50)
    req = spec.run(eng, prompt)
    assert spec.error(model, req, prompt) < spec.tol
    assert spec.error(model, req, prompt, **control) > factor * spec.tol


# ---------------------------------------------------------------------------
# (n): what is refused, by name
# ---------------------------------------------------------------------------

# Engine keywords -> the name the refusal gives them.
OPTIONS = {
    "weight_quant": (dict(weight_quant="int8"), "int8 weights"),
    "kv_quant": (dict(kv_quant="int8"), "int8 KV"),
    "tp": (lambda: dict(device_mesh=device_mesh(tp=2)), "device mesh"),
    "sp": (lambda: dict(device_mesh=device_mesh(sp=2)), "device mesh"),
    "speculative": (dict(spec_decode_tokens=3), "speculative decode"),
    "inline_waves": (dict(prefill_inline_budget=64), "inline prefill"),
    "host_tier": (dict(host_cache_slots=64), "host KV tier"),
    "disk_tier": (dict(host_cache_slots=64, kv_tier_dir="/nonexistent"),
                  "disk KV tier"),
    # what moves or advertises a prefix without its state or its tail
    "prefix_ring": (dict(mesh=object()), "prefix ring"),
    "mid_decode_publishing": (dict(stream_publish_tokens=8),
                              "mid-decode publishing"),
}


def _options(what):
    kw, name = OPTIONS[what]
    return (kw() if callable(kw) else kw), name


def _reason(cfg, kw):
    return unsupported_reason(
        cfg, **{"mesh_cache" if k == "mesh" else k: v for k, v in kw.items()})


def _said(spec, msg):
    for text in spec.says:
        assert text in msg, msg
    for text in spec.omits:
        assert text not in msg, msg


@pytest.mark.parametrize("what", sorted(OPTIONS))
def test_the_engine_refuses_by_name(model, spec, what):
    kw, name = _options(what)
    reason = _reason(model[0], kw)
    if reason is None:
        pytest.skip(f"unsupported_reason names nothing for {sorted(kw)}: "
                    f"this configuration is served with it")
    with pytest.raises(UnsupportedForModel, match=name) as e:
        spec.make_engine(model, **kw)
    assert str(e.value) == reason
    _said(spec, reason)


@pytest.mark.parametrize("what", ["pp", "disagg_decode", "disagg_prefill",
                                  "init_int8", "hf_checkpoint", "sp_prefill"])
def test_other_entries_refuse_by_name(model, spec, what):
    """The entry points beside ``Engine``: each refuses by name what
    ``unsupported_reason`` names for this configuration."""
    from radixmesh_tpu.engine.disagg import DecodeWorker, PrefillWorker
    from radixmesh_tpu.models.llama import convert_hf_state_dict, prefill_forward_sp

    cfg, params = model
    asked, name = {
        "pp": ("device_mesh", "device mesh"),
        "sp_prefill": ("device_mesh", "device mesh"),
        "disagg_decode": ("disaggregated", "disaggregated prefill/decode"),
        "disagg_prefill": ("disaggregated", "disaggregated prefill/decode"),
        "init_int8": ("weight_quant", "int8 weights"),
        "hf_checkpoint": ("hf_checkpoint", "HuggingFace checkpoint")}[what]
    probe = device_mesh(sp=2) if asked == "device_mesh" else True
    if unsupported_reason(cfg, **{asked: probe}) is None:
        pytest.skip(f"unsupported_reason names nothing for {asked}: this "
                    f"configuration is served with it")
    with pytest.raises(UnsupportedForModel, match=name) as e:
        if what == "pp":
            from jax.sharding import Mesh

            spec.make_engine(model, device_mesh=Mesh(
                np.asarray(jax.devices()[:2]).reshape(2), ("pp",)))
        elif what == "disagg_decode":
            DecodeWorker(spec.make_engine(model))
        elif what == "disagg_prefill":
            PrefillWorker(cfg, params, num_slots=256,
                          page_size=spec.engine["page_size"], max_batch=2,
                          max_seq_len=64).prefill_handoff([1, 2, 3])
        elif what == "init_int8":
            init_params(cfg, jax.random.PRNGKey(0), weight_quant="int8")
        elif what == "hf_checkpoint":
            convert_hf_state_dict(cfg, {})
        else:
            prefill_forward_sp(params, cfg, jnp.zeros((1, 8), jnp.int32),
                               jnp.arange(8)[None], device_mesh(sp=2))
    assert "does not support" in str(e.value)
    _said(spec, str(e.value))


# ---------------------------------------------------------------------------
# what the entry points lower to, at published widths: the families' own
# cases (each names its kernels and its arrays) share the v5e and these
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e():
    """One device of a ``v5e:2x2`` description: real Mosaic and XLA:TPU, no
    chip (the compile cache off: a compiled TPU program does not reload)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


def abstract_params(cfg, sharding):
    """``(shapes, params, sd)``: the parameter pytree's shapes, the same as
    ``ShapeDtypeStruct``s on ``sharding``, and ``sd(shape, dtype)`` for a
    program's other arguments. No array is made."""
    from radixmesh_tpu.models.llama import _init_params

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return shapes, jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes), sd


def _jaxprs_in(eqn):
    """The jaxprs an equation holds."""
    for val in eqn.params.values():
        for x in val if isinstance(val, (list, tuple)) else (val,):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr its equations hold."""
    return sum(1 + sum(map(equations, _jaxprs_in(e))) for e in jaxpr.eqns)


def kernel_calls(jaxpr, name: str) -> list:
    """The ``pallas_call`` equations named ``name`` of a traced program."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            found += [e] * (name in str(e.params["name"]))
        else:
            found += [c for j in _jaxprs_in(e) for c in kernel_calls(j, name)]
    return found


def kernels_in(compiled) -> set:
    return set(re.findall(r"radixmesh_[a-z_]+[a-z]", compiled.as_text()))


def copied_shapes(compiled) -> set:
    """The shapes (``"2,3,8,4096,128"``) a compiled program copies whole."""
    return set(re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text()))


def aliased_and_uncopied(compiled, arrays):
    """Every array of ``arrays`` goes through the program in place: aliased
    from argument to result, and none copied (a scatter or gather on the
    slot axis relayouts a whole pool; whole pages do not, PR 34)."""
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    shapes = {",".join(map(str, a.shape)) for a in arrays}
    assert not shapes & copied_shapes(compiled), shapes & copied_shapes(compiled)


# ---------------------------------------------------------------------------
# the manifest's configurations, counted
# ---------------------------------------------------------------------------


@functools.cache
def _manifest_presets() -> dict:
    from benchmarks.harness import registry

    presets = {}
    for entry in registry.manifest()["configs"]:
        with open(os.path.join(registry.REPO, entry["file"])) as fh:
            presets[entry["name"]] = json.load(fh)["rehearsal"]["preset"]
    return presets


@functools.cache
def _specs() -> dict:
    """``preset -> file`` of every ``tests/test_*.py`` that states a SPEC."""
    found = {}
    here = os.path.dirname(__file__)
    for path in sorted(glob.glob(os.path.join(here, "test_*.py"))):
        with open(path) as fh:
            if "\nSPEC = ModelSpec(" not in fh.read():
                continue
        name = os.path.splitext(os.path.basename(path))[0]
        found[importlib.import_module(f"tests.{name}").SPEC.preset] = name
    return found


@pytest.mark.parametrize("config", sorted(_manifest_presets()))
def test_every_configuration_of_the_manifest_has_a_spec(config):
    """Every ``rehearsal.preset`` under ``benchmarks/configs/`` is some
    family file's ``SPEC``: a configuration joins the benchmark with its
    conformance cases, not after."""
    preset = _manifest_presets()[config]
    assert preset in _specs(), (
        f"{config}: no tests/test_*.py states SPEC = ModelSpec(preset="
        f"{preset!r}, ...) (tests/model_conformance.py)")

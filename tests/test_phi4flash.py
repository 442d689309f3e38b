"""Phi-4-mini-flash-reasoning (``models/phi4flash.py``) at toy widths on the
CPU: the three entry points, the pool's two groups of rows, the state
slots and the radix tree's snapshots (state + window tail) against the
plain float32 reference (``benchmarks/reference/phi4flash.py``) — logits,
not tokens — and what must NOT agree (the controls), what is refused, what
the programs lower to for a v5e at published widths."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash as ref
from radixmesh_tpu.cache.radix_tree import RadixTree
from radixmesh_tpu.engine.engine import Engine, pool_groups
from radixmesh_tpu.engine.request import RequestState, SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    ModelConfig,
    UnsupportedForModel,
    _diff_combine,
    _init_params,
    _pair_heads,
    decode_step,
    new_moe_stats,
    prefill_chunk_paged,
)
from radixmesh_tpu.ops import ssm
from radixmesh_tpu.ops.attention import attend_prefill
from radixmesh_tpu.ops.norm import layer_norm, rms_norm
from tests.test_mellum2 import REFUSED  # the same options, by name

pytestmark = pytest.mark.quick

PAGE = 16
WINDOW = 32
N_NEW = 9  # one token from the prefill, 8 decode steps through the pool
TOL = 1e-4  # float32 against float32: other orders of the same sums


@pytest.fixture(scope="module")
def model():
    cfg = get_config("phi4flash-tiny").replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(41))


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 4096)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 1024)
    kw.setdefault("prefill_chunk", 64)
    return Engine(cfg, params, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng([41, seed]).integers(0, 512, n).tolist()


def run(eng, prompt, n_new=N_NEW):
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=n_new, return_logits=True))
    while eng.has_work():
        eng.step()
    return req


def error(model, req, prompt, at=None, **control):
    """``max|a - b| / max|b|`` of the request's logits rows against the
    reference's full forward pass of the same sequence (``at``: the rows
    compared, default all)."""
    cfg, params = model
    seq = list(prompt) + req.generated[:-1]
    rows = np.arange(len(req.logit_rows)) if at is None else np.asarray(at)
    want = ref.forward(params, cfg, seq, at=len(prompt) - 1 + rows, **control)
    return ref.distance(np.stack(req.logit_rows)[rows], want)[0]


def check(model, req, prompt, n_new=N_NEW):
    assert len(req.generated) == n_new and len(req.logit_rows) == n_new
    err = error(model, req, prompt)
    assert err < TOL, err
    assert [int(x) for x in np.stack(req.logit_rows).argmax(-1)] == req.generated


# ---------------------------------------------------------------------------
# the three entry points against the reference
# ---------------------------------------------------------------------------


# 7: inside one page; 48: a page multiple past the window; 100: chunks of
# 64 that cut the window (32) and the snapshot at 96
@pytest.mark.parametrize("n_prompt", [7, 48, 100])
@pytest.mark.parametrize("path", ["dense", "chunk"])
def test_prefill_then_decode_through_both_groups_and_the_state(model, path, n_prompt):
    eng = make_engine(
        model, long_prefill_threshold=1024 if path == "dense" else 0)
    prompt = prompt_of(n_prompt)
    req = run(eng, prompt)
    assert eng._last_prefill_path == path
    check(model, req, prompt)
    cfg = model[0]
    # rows of the full layer alone in the pool, of the two window layers in
    # the window group, two heads to a 128-lane row; float32 states
    assert eng.pool.kv.shape == (2, 1, 1, 4096, 128)
    assert eng.pool.window.shape == (2, 2, 1, eng.pool.window_slots, 128)
    assert eng.pool.state.shape[0] == 3 and eng.pool.state.dtype == jnp.float32
    assert eng.pool.state.shape[2:] == (cfg.ssm_state, cfg.ssm_inner)
    assert eng.pool.state_conv.shape[2:] == (3 * cfg.ssm_inner // 128, 128)
    assert eng.pool.state_slot_bytes == cfg.state_slot_bytes() == 3 * (
        16 * 512 * 4 + 3 * 512 * 4)


def test_eighty_decode_steps_release_window_pages_and_still_agree(model):
    """The window slides two windows past the prompt: the row gives back
    what left it, never holds more than window + 2 pages, and the last
    logits agree with the reference's full forward pass as the first do."""
    eng = make_engine(model)
    prompt = prompt_of(50, seed=1)
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=90, return_logits=True))
    held = []
    while eng.has_work():
        eng.step()
        eng._settle()
        if req.row >= 0:
            held.append(len(req.w_pages) * PAGE)
    check(model, req, prompt, n_new=90)
    assert max(held) <= WINDOW + 2 * PAGE
    ws = eng.window_stats
    assert ws["held_peak_row"] <= WINDOW + 2 * PAGE
    assert ws["pages_released"] >= (50 + 90 - WINDOW) // PAGE - 1
    # what is left: the release snapshot's tail and the prompt-cut's
    # (counted a tail each; a page two tails share is held once)
    assert 0 < eng.pool.window_slots_used <= ws["tail_slots_in_tree"]
    assert eng.attn_window["shared_kv_tokens_read"] > 0
    assert eng.attn_window["window_tokens_skipped"] > 0


def test_the_cross_decoder_at_the_last_position_equals_every_position(model):
    """The program runs layers 17's attention and the cross-decoder on a
    row's last position only; the reference runs every layer at every
    position. Prompts whose last position sits at every offset of a page
    and of a chunk, chunked and dense."""
    for n in (33, 64, 65, 79):
        for threshold in (0, 1024):
            eng = make_engine(model, long_prefill_threshold=threshold)
            req = run(eng, prompt_of(n, seed=2), n_new=1)
            assert error(model, req, prompt_of(n, seed=2)) < TOL


def test_a_batch_of_ragged_rows(model):
    eng = make_engine(model)
    prompts = [prompt_of(n, seed=30 + i) for i, n in enumerate((20, 75, 130, 41))]
    reqs = [eng.add_request(p, SamplingParams(
        max_new_tokens=N_NEW + i, return_logits=True))
        for i, p in enumerate(prompts)]
    while eng.has_work():
        eng.step()
    for i, (req, p) in enumerate(zip(reqs, prompts)):
        check(model, req, p, n_new=N_NEW + i)


# ---------------------------------------------------------------------------
# every way a snapshot (state + window tail) is reached
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_prompt, resumed", [(100, 96), (96, 80)])
def test_the_identical_prompt_again_resumes_from_the_snapshot(
        model, n_prompt, resumed):
    eng = make_engine(model)
    prompt = prompt_of(n_prompt, seed=3)
    first = run(eng, prompt)
    assert first.prefix_len == 0
    again = run(eng, prompt)
    third = run(eng, prompt)
    assert third.prefix_len == resumed and eng._last_prefill_path == "chunk"
    assert again.prefix_len == (resumed if n_prompt % PAGE else 0)
    for req in (first, again, third):
        check(model, req, prompt)
    st = eng.state_stats
    assert st["resumed_tokens"] == again.prefix_len + third.prefix_len
    assert eng.stats.cached_tokens == st["resumed_tokens"]


def test_the_next_turn_resumes_from_the_release_snapshot(model):
    """Turn 2 = turn 1's prompt + what it generated + a few new tokens, at
    a length that is no page multiple: it resumes from the state the
    decode steps kept at the last page boundary and the window tail the
    row held there (none of either was ever prefilled)."""
    eng = make_engine(model)
    p1 = prompt_of(70, seed=4)
    t1 = run(eng, p1, n_new=30)  # context 99 at release: cut at 96
    check(model, t1, p1, n_new=30)
    p2 = p1 + t1.generated + prompt_of(11, seed=5)
    t2 = run(eng, p2)
    assert t2.prefix_len == 96 and eng.state_stats["recomputed_tokens"] == 0
    check(model, t2, p2)
    p3 = p2 + t2.generated + prompt_of(5, seed=6)
    t3 = run(eng, p3)
    assert t3.prefix_len == (len(p2) + N_NEW - 1) // PAGE * PAGE
    check(model, t3, p3)


def test_two_requests_branch_inside_a_shared_prefix(model):
    eng = make_engine(model)
    shared = prompt_of(64, seed=7)
    a = shared + prompt_of(50, seed=8)
    run(eng, a)
    b = shared + prompt_of(40, seed=9)
    rb = run(eng, b)
    assert rb.prefix_len == 0  # rows for 64 tokens, no snapshot there
    assert eng.state_stats["recomputed_tokens"] == 64
    c = shared + prompt_of(33, seed=10)
    rc = run(eng, c)
    assert rc.prefix_len == 64  # b left one at the branch point (rule b)
    assert eng.state_stats["recomputed_tokens"] == 64
    check(model, rb, b)
    check(model, rc, c)


def test_a_split_leaves_state_and_tail_with_the_lower_half(model):
    eng = make_engine(model)
    a = prompt_of(115, seed=11)
    run(eng, a)
    b = a[:48] + prompt_of(30, seed=12)
    rb = run(eng, b)
    assert rb.prefix_len == 0
    ra = run(eng, a)
    assert ra.prefix_len == 112
    check(model, ra, a)
    m = eng.tree.match_prefix(np.asarray(a, np.int32))
    assert [pos for pos, _ in m.states] == [48, 112]
    assert [len(t) for t in m.tails] == [2, 2]  # a window = 2 pages each
    assert m.tail_at(112) is m.tails[1] and m.tail_at(64) is None
    rb2 = run(eng, b)
    assert rb2.prefix_len == 64
    check(model, rb2, b)
    c = a[:80] + prompt_of(9, seed=13)  # ends inside a's lower half
    rc = run(eng, c)
    assert rc.prefix_len == 48
    check(model, rc, c)


def test_a_snapshot_evicted_under_its_rows_frees_state_and_tail(model):
    eng = make_engine(model)
    prompt = prompt_of(100, seed=14)
    run(eng, prompt)
    run(eng, prompt + prompt_of(30, seed=15))
    assert 0 < eng.pool.window_slots_used <= eng.window_stats["tail_slots_in_tree"]
    n = eng.pool.state_slots_used - 1
    assert eng.tree.evict_states(8) == n
    assert eng.pool.state_slots_used == 1 and eng.pool.window_slots_used == 0
    assert eng.window_stats["tail_slots_in_tree"] == 0
    m = eng.tree.match_prefix(np.asarray(prompt, np.int32))
    assert m.length == 96 and m.states == []  # the full layer's rows stay
    again = run(eng, prompt)
    assert again.prefix_len == 0 and eng.state_stats["recomputed_tokens"] == 96
    check(model, again, prompt)
    assert run(eng, prompt).prefix_len == 96  # the snapshot is back


def test_a_preempted_request_resumes_from_what_it_published(model):
    eng = make_engine(model)
    prompt = prompt_of(90, seed=16)
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=20, return_logits=True))
    while req.kv_len < 98:
        eng.step()
        eng._settle()
    eng._preempt(req)
    assert req.w_pages == [] and 0 < eng.pool.window_slots_used <= (
        eng.window_stats["tail_slots_in_tree"])
    req.logit_rows.clear()
    eng._pressure = False
    while eng.has_work():
        eng.step()
    assert eng.stats.preemptions == 1 and req.prefix_len == 80
    check(model, req, prompt, n_new=20)


def test_plain_launches_keep_the_boundary_state_and_the_tail(model):
    """Without the logits hook the decode launches hand over (inputs built
    a step ahead, pages released behind the next launch): a session served
    that way continues exactly as a cold engine computes it."""
    sp = SamplingParams(max_new_tokens=70)
    p1 = prompt_of(50, seed=17)
    warm = make_engine(model)
    o1 = warm.generate([p1], sp)[0]
    p2 = p1 + o1 + prompt_of(7, seed=18)
    o2 = warm.generate([p2], sp)[0]
    assert warm.handover["launches_prebuilt"] > 0
    assert warm.stats.cached_tokens == 112  # 50 + 70 - 1 = 119 -> 112
    assert warm.window_stats["pages_released"] > 0
    cold = make_engine(model)
    assert cold.generate([p2], sp)[0] == o2
    p3 = p2 + o2 + prompt_of(3, seed=19)
    req = run(warm, p3)
    assert req.prefix_len == (len(p2) + 70 - 1) // PAGE * PAGE
    check(model, req, p3)


# 15: the launch that makes its last token completes page 4 (kv_len 64), so
# the boundary state and the tail its release hands over is that launch's; 11: kv_len 60
@pytest.mark.parametrize("n_new", [15, 11], ids=["on_a_page", "inside_a_page"])
def test_a_row_that_ends_by_length_is_released_behind_the_next_launch(model, n_new):
    """A request reaches its length cap while another goes on (70 tokens:
    its window slides, and gives pages back, all the while): the next
    launch goes out first, without it, and it is advanced, released and
    published behind that launch. What its release gives the tree is what
    its OWN last launch left: its next turn resumes there and equals the
    reference (logits, not tokens), the other row's too, and the run
    agrees with one that settles after every step."""
    pa, pb = prompt_of(50, seed=20), prompt_of(37, seed=21)

    def serve(settled):
        eng = make_engine(model)
        a = eng.add_request(pa, SamplingParams(max_new_tokens=n_new))
        b = eng.add_request(pb, SamplingParams(max_new_tokens=70))
        while eng.has_work():
            eng.step()
            if settled:
                eng._settle()
        assert eng.handover["finishes_deferred"] == (0 if settled else 1)
        turns = [pa + a.generated + prompt_of(7, seed=22),
                 pb + b.generated + prompt_of(5, seed=23)]
        return eng, [a, b] + [run(eng, t) for t in turns], turns

    eng, reqs, turns = serve(settled=False)
    cut = (50 + n_new - 1) // PAGE * PAGE
    assert reqs[2].prefix_len == cut and reqs[3].prefix_len == 96
    assert eng.window_stats["pages_released"] > 0
    for req, turn in zip(reqs[2:], turns):
        check(model, req, turn)
    eng2, reqs2, _ = serve(settled=True)
    assert [r.generated for r in reqs] == [r.generated for r in reqs2]
    assert [r.prefix_len for r in reqs] == [r.prefix_len for r in reqs2]
    assert eng.state_stats == eng2.state_stats
    assert eng.window_stats == eng2.window_stats
    assert eng.pool.window_slots_used == eng2.pool.window_slots_used
    assert eng.pool.free_slots == eng2.pool.free_slots
    assert eng.tree.evictable_size_ == eng2.tree.evictable_size_


def test_pressure_on_the_window_group_evicts_snapshots_not_rows(model):
    """A window group with room for the live rows and little else: the
    tree's snapshots go (superseded ones first, locked or not), no request
    fails, and what is served stays exact."""
    eng = make_engine(model, max_batch=2)
    taken = eng.pool.alloc_window(len(eng.pool._window_free) - 5)
    assert taken is not None
    p = prompt_of(60, seed=20)
    first = run(eng, p, n_new=40)
    check(model, first, p, n_new=40)
    p2 = p + first.generated + prompt_of(9, seed=21)
    second = run(eng, p2, n_new=40)
    check(model, second, p2, n_new=40)
    assert eng.tree.state_evictions > 0 and eng.stats.preemptions == 0
    eng.pool.unref_window(taken)


# room beside the live rows' own windows: one cold prompt of the longest
# history (a prefill holds its whole new span until it installs), and a
# few tails more
@pytest.mark.parametrize("spare_pages", [18, 24, 40])
def test_a_closed_loop_of_sessions_under_pressure_fails_no_request_and_leaks_nothing(
        model, spare_pages):
    """The cell's traffic at toy size: twice as many callers as rows,
    sessions of several turns over shared preambles, replies long enough
    to slide the window, the window group squeezed to what the live rows
    need plus ``spare_pages``. Every request ends whole, nothing that is
    being resumed from is lost, and when the tree has given up every
    snapshot both the window group and the state slots are whole again."""
    rows = 4
    eng = make_engine(model, max_batch=rows)
    free = len(eng.pool._window_free)
    taken = eng.pool.alloc_window(free - rows * (WINDOW // PAGE + 2) - spare_pages)
    rng = np.random.default_rng([41, spare_pages])
    preambles = [rng.integers(0, 512, int(n) * PAGE).tolist() for n in (4, 6, 8)]
    eng.generate(preambles, SamplingParams(max_new_tokens=2))

    def open_session():
        return {"history": list(preambles[int(rng.integers(3))]),
                "turns": int(rng.integers(2, 6))}

    def send(s):
        s["history"] += rng.integers(0, 512, int(rng.integers(1, 5)) * 4).tolist()
        s["req"] = eng.add_request(s["history"], SamplingParams(
            max_new_tokens=int(rng.integers(1, 4)) * PAGE))
        s["turns"] -= 1

    callers = [open_session() for _ in range(2 * rows)]
    for s in callers:
        send(s)
    finished = steps = 0
    while finished < 40:
        eng.step()
        steps += 1
        assert steps < 4000, "the loop stopped making progress"
        for i, s in enumerate(callers):
            if s["req"].state is RequestState.FINISHED:
                req = s["req"]
                assert req.error is None
                assert len(req.generated) == req.sampling.max_new_tokens
                finished += 1
                s["history"] += req.generated
                if s["turns"] <= 0 or len(s["history"]) > 200:
                    callers[i] = s = open_session()
                send(s)
    while eng.has_work():
        eng.step()
    assert eng.stats.preemptions == 0 and not eng._resuming
    assert eng.window_stats["pages_released"] > 0
    assert eng.state_stats["resumed_tokens"] > 0
    assert eng.tree.state_evictions > 0  # the pressure was real
    while eng.tree.evict_states(64):
        pass
    eng.pool.unref_window(taken)
    assert len(eng.pool._window_free) == free
    assert int(eng.pool.window_refs[1:].sum()) == 0
    assert eng.window_stats["tail_slots_in_tree"] == 0
    assert eng.pool.state_slots_used == 1  # the scratch slot


def test_a_prompt_the_window_group_cannot_hold_is_refused_by_name(model):
    """A prefill holds the window pages of its whole uncached span: what
    the group (derived from the rows, not from ``max_seq_len``) cannot hold
    is refused when it arrives, not left at the head of the queue."""
    eng = make_engine(model, max_batch=1, num_slots=16384, max_seq_len=8192)
    have = eng.pool.window_slots - PAGE
    assert have < 8192
    with pytest.raises(ValueError, match="window group"):
        eng.add_request(prompt_of(have + 1), SamplingParams(max_new_tokens=2))
    assert len(run(eng, prompt_of(40), n_new=2).generated) == 2


# ---------------------------------------------------------------------------
# controls: each must fail the tolerance the paths above pass
# ---------------------------------------------------------------------------


def _second_turn(model, spoil=None):
    eng = make_engine(model)
    p1 = prompt_of(70, seed=4)
    t1 = run(eng, p1, n_new=30)
    if spoil is not None:
        spoil(eng)
    p2 = p1 + t1.generated + prompt_of(11, seed=5)
    t2 = run(eng, p2)
    assert t2.prefix_len == 96
    return t2, p2


def test_control_a_zeroed_restored_state_fails(model):
    def spoil(eng):
        eng.pool.state = jnp.zeros_like(eng.pool.state)
        eng.pool.state_conv = jnp.zeros_like(eng.pool.state_conv)

    t2, p2 = _second_turn(model, spoil)
    assert error(model, t2, p2) > 100 * TOL
    # ... and the reference's own control reads what the program computed
    assert error(model, t2, p2, forget_state_at=96) < TOL


def test_control_a_zeroed_restored_tail_fails(model):
    def spoil(eng):
        eng.pool.window = jnp.zeros_like(eng.pool.window)

    t2, p2 = _second_turn(model, spoil)
    assert error(model, t2, p2) > 100 * TOL
    assert error(model, t2, p2, zero_tail_before=96) < TOL


def test_control_lambda_forced_to_zero_fails(model):
    t2, p2 = _second_turn(model)
    assert error(model, t2, p2) < TOL
    assert error(model, t2, p2, lambda_zero=True) > 100 * TOL


def test_control_a_window_of_33_fails(model):
    t2, p2 = _second_turn(model)
    assert error(model, t2, p2, window=WINDOW + 1) > 100 * TOL


def test_control_int8_weights_fail(model):
    t2, p2 = _second_turn(model)
    assert error(model, t2, p2, weight_map=ref.int8_weights) > 100 * TOL


# ---------------------------------------------------------------------------
# the tree's handle
# ---------------------------------------------------------------------------


def test_tree_tails_attach_split_and_free():
    freed_states, freed_tails = [], []
    tree = RadixTree(page_size=4, on_free_state=freed_states.append,
                     on_free_tail=freed_tails.append)
    key = np.arange(24, dtype=np.int32)
    tree.insert(key, np.arange(100, 124, dtype=np.int32))
    tail = np.asarray([7, 8], np.int32)
    assert tree.attach_state(key, 7, tail)
    assert tree.attach_state(key[:8], 5, np.asarray([3], np.int32))  # splits at 8
    m = tree.match_prefix(key)
    assert m.states == [(8, 5), (24, 7)] and m.tail_at(24) is tail
    assert list(m.tail_at(8)) == [3] and m.tail_at(16) is None
    tree.match_prefix(np.concatenate([key[:16], key[:4]]))  # splits at 16
    assert tree.match_prefix(key).tail_at(24) is tail  # with the lower half
    tree.evict(8)  # the leaf [16, 24) goes: state and tail with it
    assert freed_states == [7] and freed_tails[0] is tail
    tree.reset()
    assert sorted(freed_states) == [5, 7] and len(freed_tails) == 2


def test_pinned_eviction_takes_superseded_snapshots_first_locked_or_not():
    freed = []
    clock = iter(range(1000))
    tree = RadixTree(page_size=1, on_free_state=freed.append,
                     on_free_tail=lambda t: None, time_fn=lambda: next(clock))
    session = np.arange(9, dtype=np.int32)
    tree.insert(session, np.arange(9, dtype=np.int32))
    for cut, slot in ((3, 13), (6, 16), (9, 19)):  # three turns of one session
        assert tree.attach_state(session[:cut], slot)
    other = np.asarray([50, 51, 52], np.int32)
    tree.insert(other, np.arange(20, 23, dtype=np.int32))
    assert tree.attach_state(other, 30)  # older than the session's last touch
    tree.match_prefix(session)
    tree.inc_lock_ref(tree.match_prefix(session).last_node)  # a live row
    assert tree.evict_states(5) == 1 and freed == [30]  # plain: unlocked only
    freed.clear()
    # pinned: the session's superseded turns go first although locked and
    # newer; the slot a request is about to resume from is never taken
    assert tree.evict_states(1, pinned={16}) == 1 and freed == [13]
    # ... then the least recently touched, locked or not
    assert tree.evict_states(5, pinned={16}) == 1 and freed == [13, 19]
    assert tree.match_prefix(session).states == [(6, 16)]


# ---------------------------------------------------------------------------
# the operators
# ---------------------------------------------------------------------------


def test_paired_row_differential_attention_equals_per_head(model):
    """What the kernels compute on the paired row — every query zero in
    its partner's key lanes, all 128 output lanes wanted — combined by
    ``_diff_combine``, against the reference's per-head form."""
    cfg, params = model
    rng = np.random.default_rng(0)
    S = 40
    q = jnp.asarray(rng.normal(size=(1, S, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, 2, 64)), jnp.float32)
    ap = {n: w[1] for n, w in params["attn"].items()}
    qw, kw, vw = _pair_heads(cfg, q, k, v)
    assert qw.shape == (1, S, 4, 128) and kw.shape == (1, S, 1, 128)
    pos = jnp.arange(S)[None]
    wide = attend_prefill(qw, kw, vw, pos, jnp.asarray([S]),
                          window=jnp.asarray(WINDOW), scale=64 ** -0.5)
    got = _diff_combine(ap, wide, cfg, 3)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.diff_attention(ap, q[0], k[0], v[0], 3, cfg.rms_eps, WINDOW)
    assert ref.distance(got, want)[0] < 1e-5


def test_the_scan_kernel_interpreted_equals_the_plain_scan():
    """From a non-zero state, at a padded row (the snapshot at its true
    end, before the padding), a snapshot inside the chunk, none (0)."""
    rng = np.random.default_rng(0)
    B, T, C, N = 2, 32, 256, 16
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, b, c, h0 = f(B, T, C), f(B, T, N), f(B, T, N), f(B, N, C)
    dt = jax.nn.softplus(f(B, T, C) - 3)
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, C))
    snap = jnp.asarray([[32, 7, 0], [19, 16, 1]], jnp.int32)
    want = ssm.selective_scan_ref(x, dt, b, c, a, jnp.ones(C), h0, snap)
    got = ssm.selective_scan_kernel(x, dt, b, c, a, jnp.ones(C), h0, snap,
                                    interpret=True)
    for g, w in zip(got, want):
        assert ref.distance(g, w)[0] < 1e-6
    assert np.array_equal(np.asarray(got[1][0, 2]), np.asarray(h0[0]))
    # the padded row: what follows its true end changes nothing before it
    x2 = x.at[1, 19:].set(99.0)
    again = ssm.selective_scan_kernel(x2, dt, b, c, a, jnp.ones(C), h0, snap,
                                      interpret=True)
    assert np.array_equal(np.asarray(again[1][1]), np.asarray(got[1][1]))
    # one position a row is the decode step's update
    y1, h1 = ssm.ssm_step(x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, jnp.ones(C), h0)
    assert ref.distance(y1, want[0][:, 0])[0] < 1e-6


def test_the_chunk_program_with_its_kernels_interpreted(model):
    """``prefill_chunk_paged`` with ``use_kernel=True, interpret=True``:
    the scan kernel and the paged attention kernels on the 128-wide row,
    against the jnp path of the same program."""
    cfg, params = model
    rng = np.random.default_rng(1)
    B, C, maxp = 2, 32, 32
    toks = jnp.asarray(rng.integers(0, 512, (B, C)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(C), (B, C)).astype(jnp.int32)
    groups = pool_groups(cfg, 1024, 2, PAGE)
    args = dict(page_size=PAGE, logits_at=jnp.asarray([C - 1, 20]),
                state_in=jnp.zeros(B, jnp.int32),
                snap_at=jnp.asarray([[C, 16, 0], [21, 0, 0]]),
                state_out=jnp.asarray([[1, 2, 0], [3, 0, 0]]))

    def launch(**kw):
        pt = jnp.asarray(np.arange(2, 2 + B * maxp).reshape(B, maxp), jnp.int32)
        sl = pt[:, :C // PAGE, None] * PAGE + jnp.arange(PAGE)
        return prefill_chunk_paged(
            params, cfg, toks, pos, jnp.zeros(cfg.kv_pool_shape(2048), jnp.float32),
            sl.reshape(B, C), pt, jnp.asarray([C, 21]),
            state_pool=(jnp.zeros(groups["state_shape"], jnp.float32),
                        jnp.zeros(groups["conv_state_shape"], jnp.float32)),
            window_pool=jnp.zeros(cfg.window_pool_shape(2048), jnp.float32),
            window_slots=sl.reshape(B, C), window_table=pt, **args, **kw)

    want = launch()
    got = launch(use_kernel=True, interpret=True)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert ref.distance(g, w)[0] < 1e-5


def test_layer_norm_beside_rms_norm():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)) * 3 + 1, jnp.float32)
    w = jnp.asarray(rng.normal(size=64), jnp.float32)
    b = jnp.asarray(rng.normal(size=64), jnp.float32)
    got = layer_norm(x, w, 1e-5, b)
    mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
    np.testing.assert_allclose(
        got, (x - mu) / np.sqrt(var + 1e-5) * w + b, rtol=1e-5, atol=1e-5)
    # the same signature as rms_norm; centred input: the same result
    centred = x - mu
    np.testing.assert_allclose(
        layer_norm(centred, w, 1e-5), rms_norm(centred, w, 1e-5), rtol=1e-5,
        atol=1e-5)
    assert layer_norm(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# the sizes, and what is refused, by name
# ---------------------------------------------------------------------------


def test_the_published_configuration_by_the_numbers():
    cfg = get_config("phi-4-mini-flash-reasoning")
    assert cfg.layer_types[:4] == ("mamba", "sliding_attention") * 2
    assert cfg.layer_types[16:20] == (
        "mamba", "full_attention", "gmu", "cross_attention")
    assert len(cfg.mamba_layers) == 9 and len(cfg.window_layers) == 8
    assert cfg.attn_layers == (17,)
    assert cfg.kv_pool_shape(655360) == (2, 1, 10, 655360, 128)  # 5 120 B a token
    assert cfg.window_pool_shape(1024) == (2, 8, 10, 1024, 128)  # 40 960 B
    assert cfg.state_shape(4) == (9, 4, 16, 5120)
    assert cfg.conv_state_shape(4) == (9, 4, 120, 128)
    assert cfg.state_slot_bytes() == 3225600
    shapes = jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert abs(n / 3.85e9 - 1) < 0.005, n
    # by bytes: a quarter of what the rows take, never under five a row
    assert cfg.default_state_slots(655360, 64) == 321  # 5 a row + scratch
    assert cfg.default_state_slots(4 * 655360, 64) == 1040  # a quarter of the rows
    assert cfg.default_window_slots(64, 16) == 64 * 544 + 80 * 512 + 4096 + 16
    lfm2 = get_config("lfm2-24b-a2b-ep8")
    assert lfm2.default_state_slots(196608, 32) == 2048
    assert lfm2.default_window_slots(32, 16) == 0
    assert lfm2.window_pool_shape(64) is None and lfm2.conv_state_shape(4) is None


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_by_name(model, what):
    kw, name = REFUSED[what]
    with pytest.raises(UnsupportedForModel, match=name) as e:
        make_engine(model, **(kw() if callable(kw) else kw))
    assert "state-space layers with a recurrent state" in str(e.value)
    assert "a cross-decoder that reads one layer's K/V" in str(e.value)


def test_the_prefix_ring_and_mid_decode_publishing_are_refused(model):
    for kw in ({"mesh": object()}, {"stream_publish_tokens": 8}):
        with pytest.raises(UnsupportedForModel, match="state-space"):
            make_engine(model, **kw)


@pytest.mark.parametrize("change, message", [
    (dict(layer_types=("mamba", "cross_attention", "mamba", "sliding_attention",
                       "mamba", "full_attention", "gmu", "cross_attention")),
     "no full_attention layer below"),
    (dict(layer_types=("gmu", "sliding_attention", "mamba", "sliding_attention",
                       "mamba", "full_attention", "gmu", "cross_attention")),
     "no mamba layer below"),
    (dict(sliding_window=40), "multiple of the page"),
    (dict(layer_types=("mamba", "sliding_attention") * 3 + ("mamba",
                                                            "full_attention")),
     None),  # no cross-decoder at all: still this program's shape
    (dict(layer_types=("mamba", "full_attention") + ("gmu", "cross_attention") * 3),
     "layer_types this program runs"),
    (dict(diff_attention=False), "nothing else is implemented"),
    (dict(ssm_state=0), "ssm_state"),
])
def test_a_layer_pattern_it_cannot_run_is_refused_by_name(change, message):
    cfg = get_config("phi4flash-tiny")
    if message is None:
        assert isinstance(cfg.replace(**change), ModelConfig)
        return
    with pytest.raises(ValueError, match=message):
        cfg.replace(**change)


# ---------------------------------------------------------------------------
# what the entry points lower to
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


def _published(v5e):
    """Published widths and depth, a small vocabulary: shapes only."""
    cfg = get_config("phi-4-mini-flash-reasoning").replace(vocab_size=4096)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    shapes = jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert shapes["mamba"]["in_x"].shape == shapes["mamba"]["in_z"].shape == (
        9, 2560, 5120)
    assert shapes["mamba"]["x_proj"].shape == (9, 5120, 192)
    assert shapes["attn"]["wk"].shape == (9, 2560, 1280)
    assert shapes["cross"]["wq"].shape == (7, 2560, 2560) and "wk" not in shapes["cross"]
    assert shapes["gmu"]["w_g"].shape == (7, 2560, 5120)
    groups = pool_groups(cfg, 8192, 8, 16)
    pools = dict(
        kv=sd(cfg.kv_pool_shape(8192), cfg.dtype),
        window_pool=sd(groups["window_shape"], cfg.dtype),
        state_pool=(sd(groups["state_shape"], jnp.float32),
                    sd(groups["conv_state_shape"], cfg.dtype)))
    return cfg, jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes), sd, pools


def _aliased_and_uncopied(compiled, pools):
    arrays = [pools["kv"], pools["window_pool"], *pools["state_pool"]]
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    shapes = {",".join(map(str, a.shape)) for a in arrays}
    copied = set(re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text()))
    assert not shapes & copied, shapes & copied


def test_decode_lowers_for_a_v5e_at_published_widths(v5e):
    """16 launches of the fused decode kernel on 10 wide heads (8 on the
    window group, 8 on the full layer's rows: no jnp attention), both
    groups and both state arrays aliased through, none copied."""
    cfg, params, sd, pools = _published(v5e)
    i32 = jnp.int32
    compiled = decode_step.lower(
        params, cfg, sd((8,), i32), pools["kv"], sd((8,), i32), sd((8, 64), i32),
        sd((8,), i32), 16, state_pool=pools["state_pool"],
        state_slots=sd((2, 8), i32), window_pool=pools["window_pool"],
        window_slots=sd((8,), i32), window_table=sd((8, 64), i32)).compile()
    text = compiled.as_text()
    assert "radixmesh_decode_fused_mh" in text
    _aliased_and_uncopied(compiled, pools)


def test_chunk_lowers_for_a_v5e_at_published_widths(v5e):
    cfg, params, sd, pools = _published(v5e)
    i32 = jnp.int32
    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((2, 256), i32), sd((2, 256), i32), pools["kv"],
        sd((2, 256), i32), sd((2, 256), i32), sd((2,), i32), page_size=16,
        logits_at=sd((2,), i32), state_pool=pools["state_pool"],
        state_in=sd((2,), i32), snap_at=sd((2, 3), i32),
        state_out=sd((2, 3), i32), window_pool=pools["window_pool"],
        window_slots=sd((2, 256), i32), window_table=sd((2, 256), i32)).compile()
    names = set(re.findall(r"radixmesh_[a-z_]+[a-z]", compiled.as_text()))
    assert {"radixmesh_chunk_attn", "radixmesh_decode_fused_mh",
            "radixmesh_ssm_scan"} <= names, names
    _aliased_and_uncopied(compiled, pools)


# The decode program of the five configurations the benchmark had before
# this one, as the PARENT of PR 41 lowers it (md5 of the lowered text at
# the shapes below) and the pool each builds: a configuration with one
# group of rows keeps its arrays and its programs. A PR that changes one of
# these programs on purpose replaces its line (the shapes and the command
# are the test's own).
_PARENT_DECODE = {
    "qwen2-tiny": ((2, 2, 2, 256, 32), "184c941650049c3078942e1c1f80d349"),
    "mellum2-tiny": ((2, 4, 2, 256, 32), "ac79bf5d4b16253920bd3e45e36d01a0"),
    # PR 42: its latent decode attention takes the launch's groups of rows
    "glm-4.7-flash-tiny": ((1, 3, 1, 256, 128), "7c06567bf0c0c1fefda7daf496814d38"),
    "glm-5.2-tiny": ((1, 6, 1, 256, 128), "d330e61c07ed7fe974d15b6c1c92b250"),
    "lfm2-tiny": ((2, 3, 1, 256, 128), "80b67549c5f43f5be490f4ddf58da9ad"),
}


@pytest.mark.parametrize("preset", sorted(_PARENT_DECODE))
def test_the_other_configurations_keep_their_pool_and_their_decode_program(preset):
    cfg = get_config(preset)
    pool_shape, digest = _PARENT_DECODE[preset]
    assert cfg.kv_pool_shape(256) == pool_shape
    assert not cfg.has_ssm and cfg.window_pool_shape(64) is None
    groups = pool_groups(cfg, 256, 4, 16)
    assert groups["window_shape"] is None and groups["conv_state_shape"] is None
    params = jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                            jax.random.PRNGKey(0))
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    kw = {}
    if cfg.kv_index_shape(256) is not None:
        kw["index_pool"] = jnp.zeros(cfg.kv_index_shape(256), cfg.dtype)
    if cfg.state_shape(8) is not None:
        kw |= {"state_pool": jnp.zeros(cfg.state_shape(8), cfg.dtype),
               "state_slots": z(2, 4)}
    if cfg.n_experts:
        kw["moe_stats"] = new_moe_stats(cfg.ep_size > 1)
    low = decode_step.lower(
        params, cfg, z(4), jnp.zeros(cfg.kv_pool_shape(256), cfg.dtype), z(4),
        z(4, 8), z(4) + 1, 16, **kw)
    assert hashlib.md5(low.as_text().encode()).hexdigest() == digest

"""Laguna-XS.2 (``models/laguna.py``) at toy widths on the CPU: the three
entry points, the pool's two groups of rows and the radix tree's resume
points that are a window tail and nothing else, against the plain float32
reference (``benchmarks/reference/laguna.py``) — logits, not tokens — and
what must NOT agree (the controls), what is refused, what the programs
lower to for a v5e at published widths."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from radixmesh_tpu.engine.engine import Engine, pool_groups
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    ModelConfig,
    UnsupportedForModel,
    _init_params,
    _layer_kinds,
    _mixed_runs,
    decode_step,
    new_moe_stats,
    prefill_chunk_paged,
    prefill_forward,
)
from tests.test_mellum2 import REFUSED  # the same options, by name

pytestmark = pytest.mark.quick

PAGE = 16
WINDOW = 16
N_NEW = 9  # one token from the prefill, 8 decode steps through both groups
TOL = 1e-4  # float32 against float32: other orders of the same sums


@pytest.fixture(scope="module")
def model():
    cfg = get_config("laguna-tiny").replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(45))


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 4096)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 512)
    kw.setdefault("prefill_chunk", 64)
    return Engine(cfg, params, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng([45, seed]).integers(0, 512, n).tolist()


def run(eng, prompt, n_new=N_NEW):
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=n_new, return_logits=True))
    while eng.has_work():
        eng.step()
    return req


def error(model, req, prompt, **control):
    """``max|a - b| / max|b|`` of the request's logits rows against the
    reference's full forward pass of the same sequence."""
    cfg, params = model
    seq = list(prompt) + req.generated[:-1]
    want = ref.forward(params, cfg, seq, logits_from=len(prompt) - 1,
                       **control)["logits"]
    got = np.stack(req.logit_rows)
    return float(np.abs(got - want).max() / np.abs(want).max())


def check(model, req, prompt, n_new=N_NEW):
    assert len(req.generated) == n_new and len(req.logit_rows) == n_new
    err = error(model, req, prompt)
    assert err < TOL, err
    assert [int(x) for x in np.stack(req.logit_rows).argmax(-1)] == req.generated


# ---------------------------------------------------------------------------
# the three entry points against the reference
# ---------------------------------------------------------------------------


# 7: inside one page; 48: a page multiple past the window; 100: chunks of
# 64 that cut the window (16) and the tail at 96
@pytest.mark.parametrize("n_prompt", [7, 48, 100])
@pytest.mark.parametrize("path", ["dense", "chunk"])
def test_prefill_then_decode_through_both_groups(model, path, n_prompt):
    eng = make_engine(
        model, long_prefill_threshold=1024 if path == "dense" else 0)
    prompt = prompt_of(n_prompt)
    req = run(eng, prompt)
    assert eng._last_prefill_path == path
    check(model, req, prompt)
    # the two full layers' rows in the pool, the three window layers' in
    # the window group; no state array, and a tree that tracks tails alone
    assert eng.pool.kv.shape == (2, 2, 2, 4096, 32)
    assert eng.pool.window.shape == (2, 3, 2, eng.pool.window_slots, 32)
    assert eng.pool.state is None and eng.state_stats is None
    assert eng.tree.on_free_state is None and eng.tree.on_free_tail is not None
    # the expert ids of the four sparse layers ride the logits hook
    assert req.expert_rows[0][1].shape[1:] == (4, 2)


def test_eighty_decode_steps_release_window_pages_and_still_agree(model):
    """The window slides five windows past the prompt: the row gives back
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
    assert 0 < eng.pool.window_slots_used <= ws["tail_slots_in_tree"]
    aw = eng.attn_window
    # two full layers read every context token of every step, three window
    # layers at most a window of them
    assert aw["full_tokens_read"] == 2 * sum(range(51, 140))
    assert 0 < aw["window_tokens_read"] <= 3 * 89 * WINDOW
    assert aw["window_tokens_skipped"] > aw["window_tokens_read"]
    assert "shared_kv_tokens_read" not in aw


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
# every way a resume point (a tail and nothing else) is reached
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_prompt, resumed", [(100, 96), (96, 80)])
def test_the_identical_prompt_again_resumes_from_the_tail(model, n_prompt, resumed):
    eng = make_engine(model)
    prompt = prompt_of(n_prompt, seed=3)
    first = run(eng, prompt)
    assert first.prefix_len == 0
    again = run(eng, prompt)
    third = run(eng, prompt)
    assert third.prefix_len == resumed and eng._last_prefill_path == "chunk"
    # 100: resumed AT the match; 96: a token must be left to compute, so the
    # match is cut to 80, where the second visit leaves a tail (rule b) and
    # the third finds it
    assert again.prefix_len == (resumed if n_prompt % PAGE else 0)
    for req in (first, again, third):
        check(model, req, prompt)
    st = eng.tail_stats
    assert st["matches"] == 2 and st["resumes"] == 1 + bool(n_prompt % PAGE)
    assert st["resumed_tokens"] == again.prefix_len + third.prefix_len
    assert eng.stats.cached_tokens == st["resumed_tokens"]
    assert st["recomputed_tokens"] == (0 if n_prompt % PAGE else 80)


def test_the_next_turn_resumes_from_the_tail_its_release_left(model):
    """Turn 2 = turn 1's prompt + what it generated + a few new tokens: it
    resumes at the last page boundary a decode step passed, from the pages
    the row held there (no prefill wrote them)."""
    eng = make_engine(model)
    p1 = prompt_of(70, seed=4)
    t1 = run(eng, p1, n_new=30)  # context 99 at release: cut at 96
    check(model, t1, p1, n_new=30)
    p2 = p1 + t1.generated + prompt_of(11, seed=5)
    t2 = run(eng, p2)
    assert t2.prefix_len == 96 and eng.tail_stats["recomputed_tokens"] == 0
    check(model, t2, p2)
    p3 = p2 + t2.generated + prompt_of(5, seed=6)
    t3 = run(eng, p3)
    assert t3.prefix_len == (len(p2) + N_NEW - 1) // PAGE * PAGE
    check(model, t3, p3)
    assert eng.tail_stats["resumes"] == 2 and eng.tail_stats["snapshots"] >= 4


def _session(eng, model, seed=7):
    """A served sequence of 40 + 30 tokens: tails at 32 (the prompt's cut)
    and 64 (the release), rows for 64 tokens."""
    p = prompt_of(40, seed=seed)
    r = run(eng, p, n_new=30)
    m = eng.tree.match_prefix(np.asarray(p + r.generated, np.int32))
    assert [pos for pos, _ in m.states] == [32, 64]
    assert all(slot is None for _, slot in m.states)  # no state slot anywhere
    return p + r.generated


@pytest.mark.parametrize("shared, resumed, recomputed", [
    (66, 64, 0),  # the match ends at a tail: served from it
    (60, 32, 16),  # rows to 48, the deepest tail at or before: one page short
    (30, 0, 16),  # rows to 16 and no tail there: computed from the start
])
def test_a_prefix_resumes_at_the_deepest_tail_at_or_before_its_match(
        model, shared, resumed, recomputed):
    eng = make_engine(model)
    seq = _session(eng, model)
    prompt = seq[:shared] + prompt_of(25, seed=8)
    req = run(eng, prompt)
    assert req.prefix_len == resumed
    st = eng.tail_stats
    assert st["matches"] == 1 and st["resumes"] == (resumed > 0)
    assert st["recomputed_tokens"] == recomputed
    assert st["kv_matched_tokens"] == resumed + recomputed
    check(model, req, prompt)
    # the second visitor of the branch point left a tail there (rule b)
    m = eng.tree.match_prefix(np.asarray(prompt, np.int32))
    assert (resumed + recomputed) in [pos for pos, _ in m.states]


def test_two_rows_of_one_launch_in_different_kinds_of_resume(model):
    """One prefill group: a row that resumes at its match, one that resumes
    a page short of it and recomputes, one with nothing cached."""
    eng = make_engine(model, long_prefill_threshold=0)
    seq, other = _session(eng, model), _session(eng, model, seed=12)
    prompts = [seq[:66] + prompt_of(40, seed=9), other[:60] + prompt_of(31, seed=10),
               prompt_of(45, seed=11)]
    waves = eng.prefill["waves"]
    reqs = [eng.add_request(p, SamplingParams(
        max_new_tokens=N_NEW, return_logits=True)) for p in prompts]
    while eng.has_work():
        eng.step()
    assert eng.prefill["waves"] == waves + 1
    assert [r.prefix_len for r in reqs] == [64, 32, 0]
    for req, p in zip(reqs, prompts):
        check(model, req, p)


def test_a_tail_evicted_under_its_rows_gives_its_pages_back(model):
    eng = make_engine(model)
    prompt = prompt_of(100, seed=14)
    run(eng, prompt)
    run(eng, prompt + prompt_of(30, seed=15))
    assert 0 < eng.pool.window_slots_used <= eng.window_stats["tail_slots_in_tree"]
    assert eng.tree.evict_states(8) > 0
    assert eng.pool.window_slots_used == 0
    assert eng.window_stats["tail_slots_in_tree"] == 0
    assert eng.pool.window_refs.sum() == 1  # the scratch page's own
    m = eng.tree.match_prefix(np.asarray(prompt, np.int32))
    assert m.length == 96 and m.states == []  # the full layers' rows stay
    again = run(eng, prompt)
    assert again.prefix_len == 0 and eng.tail_stats["recomputed_tokens"] == 96
    check(model, again, prompt)
    assert run(eng, prompt).prefix_len == 96  # the tail is back


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
    # the retry is the PROMPT again: rows to 80 (a token is left to compute),
    # where its first life's prompt cut left a tail
    assert eng.stats.preemptions == 1 and req.prefix_len == 80
    check(model, req, prompt, n_new=20)


def test_plain_launches_keep_the_tail(model):
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


# 15: the launch that makes its last token completes page 4 (kv_len 64), so
# the tail its release hands over is that launch's; 11: kv_len 60
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
    assert eng.tail_stats == eng2.tail_stats
    assert eng.window_stats == eng2.window_stats
    assert eng.pool.window_slots_used == eng2.pool.window_slots_used
    assert eng.pool.free_slots == eng2.pool.free_slots
    assert eng.tree.evictable_size_ == eng2.tree.evictable_size_


def test_pressure_on_the_window_group_evicts_tails_not_rows(model):
    """A window group with room for one prompt's prefill and nothing else:
    the tree's tails go, no request fails or is preempted, the full
    layers' rows stay, and what is served stays exact."""
    eng = make_engine(model, max_batch=2)
    taken = eng.pool.alloc_window(len(eng.pool._window_free) - 4)
    assert taken is not None
    p = prompt_of(60, seed=20)
    first = run(eng, p, n_new=40)
    check(model, first, p, n_new=40)
    assert eng.tree.state_evictions == 0
    q = prompt_of(60, seed=21)  # its prefill holds all four pages
    second = run(eng, q, n_new=40)
    check(model, second, q, n_new=40)
    assert eng.tree.state_evictions > 0 and eng.stats.preemptions == 0
    again = run(eng, p)  # rows for 48 tokens, no tail left: from the start
    assert again.prefix_len == 0 and eng.tail_stats["recomputed_tokens"] == 48
    check(model, again, p)
    eng.pool.unref_window(taken)


def test_a_closed_loop_of_sessions_leaks_no_window_page(model):
    """Six sessions of three turns over two rows; at the end every count on
    a window page is a tail's in the tree, and a reset returns them all."""
    eng = make_engine(model, max_batch=2)
    rng = np.random.default_rng(45)
    histories = [prompt_of(int(rng.integers(20, 70)), seed=40 + i) for i in range(6)]
    for _turn in range(3):
        reqs = [eng.add_request(h, SamplingParams(max_new_tokens=12))
                for h in histories]
        while eng.has_work():
            eng.step()
        histories = [h + r.generated + prompt_of(5, seed=len(h))
                     for h, r in zip(histories, reqs)]
    st = eng.tail_stats
    assert st["matches"] == st["resumes"] == 12 and eng.stats.preemptions == 0
    assert eng.pool.window_slots_used <= eng.window_stats["tail_slots_in_tree"]
    eng.tree.reset()
    assert eng.pool.window_slots_used == 0 and eng.pool.window_refs.sum() == 1


# ---------------------------------------------------------------------------
# what must not agree: the reference with one piece left out
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("piece", ["gate", "partial_rotary", "head_groups"])
def test_the_reference_without_one_piece_disagrees(model, piece):
    """The gate, the half-rotated head and each kind's own head count
    (which kv head a query head reads) each matter: leave one out of the
    reference and the program is no longer within a hundred times the
    tolerance of it."""
    eng = make_engine(model)
    prompt = prompt_of(60, seed=22)
    req = run(eng, prompt)
    assert error(model, req, prompt) < TOL
    assert error(model, req, prompt, without=(piece,)) > 100 * TOL


def test_the_reference_refuses_an_unknown_piece(model):
    with pytest.raises(ValueError, match="unknown pieces"):
        ref.forward(model[1], model[0], [1, 2, 3], without=("window",))


def test_the_reference_reads_the_source_keys_as_it_reads_the_program(model):
    """``spec_of`` from a ``config.json`` dict and from the ModelConfig."""
    cfg = model[0]
    source = {
        "num_hidden_layers": 5, "num_key_value_heads": 2, "head_dim": 32,
        "rms_norm_eps": 1e-6, "layer_types": list(cfg.layer_types),
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6], "sliding_window": 16,
        "rope_parameters": {k: dict(v) for k, v in cfg.rope_by_type},
        "gating": True, "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "num_experts_per_tok": 2, "moe_routed_scaling_factor": 2.5,
    }
    assert ref.spec_of(source) == ref.spec_of(cfg)


# ---------------------------------------------------------------------------
# the kernels in interpret mode against the jnp path
# ---------------------------------------------------------------------------


def test_the_chunk_path_through_the_kernels_in_interpret_mode(model):
    cfg, params = model
    rng = np.random.default_rng(1)
    B, C, maxp = 2, 32, 32
    toks = jnp.asarray(rng.integers(0, 512, (B, C)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(C), (B, C)).astype(jnp.int32)

    def launch(**kw):
        pt = jnp.asarray(np.arange(2, 2 + B * maxp).reshape(B, maxp), jnp.int32)
        sl = (pt[:, :C // PAGE, None] * PAGE + jnp.arange(PAGE)).reshape(B, C)
        return prefill_chunk_paged(
            params, cfg, toks, pos, jnp.zeros(cfg.kv_pool_shape(2048), jnp.float32),
            sl, pt, jnp.asarray([C, 21]), page_size=PAGE,
            logits_at=jnp.asarray([C - 1, 20]),
            window_pool=jnp.zeros(cfg.window_pool_shape(2048), jnp.float32),
            window_slots=sl, window_table=pt, **kw)

    want = launch()
    got = launch(use_kernel=True, interpret=True)
    assert len(want) == 4  # logits, the pool, the window group, aux
    for g, w in zip(jax.tree.leaves(got[:3]), jax.tree.leaves(want[:3])):
        assert float(jnp.abs(g - w).max()) < 1e-4


# ---------------------------------------------------------------------------
# the sizes, and what is refused, by name
# ---------------------------------------------------------------------------


def _count(cfg):
    shapes = jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return shapes, sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def test_the_published_configuration_by_the_numbers():
    cfg = get_config("laguna-xs.2")
    assert cfg.layer_types[:5] == ("full_attention",) + ("sliding_attention",) * 3 + (
        "full_attention",)
    assert cfg.layer_types.count("full_attention") == 10
    assert len(cfg.window_layers) == 30 and cfg.attn_layers[:3] == (0, 4, 8)
    assert cfg.heads_of("full_attention") == 48
    assert cfg.heads_of("sliding_attention") == 64
    assert _mixed_runs(cfg) == [(0, 1, 1), (1, 4, 9), (37, 3, 1)]
    shapes, n = _count(cfg)
    assert abs(n / 33.44e9 - 1) < 0.002, n  # the card's "33.4B"
    assert shapes["full_attention"]["wq"].shape == (10, 2048, 48 * 128)
    assert shapes["sliding_attention"]["wo"].shape == (30, 64 * 128, 2048)
    assert shapes["full_attention"]["wg"].shape == (10, 2048, 48)
    assert shapes["layers"]["w_gate"].shape == (39, 256, 2048, 512)
    assert shapes["dense_layers"]["w_gate"].shape == (1, 2048, 8192)
    assert "wq" not in shapes["layers"] and "wq" not in shapes["dense_layers"]


def test_the_one_chip_cut_by_the_numbers():
    cfg = get_config("laguna-xs.2-l5")
    assert cfg.n_layers == 5 and cfg.attn_layers == (0, 4)
    assert cfg.window_layers == (1, 2, 3)
    assert _layer_kinds(cfg) == list(cfg.layer_types)
    assert _mixed_runs(cfg) == [(0, 1, 1), (1, 4, 1)]
    _, n = _count(cfg)
    assert abs(n / 3869.9e6 - 1) < 0.001, n  # 7.21 GiB in bf16
    assert cfg.kv_pool_shape(524288) == (2, 2, 8, 524288, 128)  # 8 192 B a token
    assert cfg.window_pool_shape(1024) == (2, 3, 8, 1024, 128)  # 12 288 B
    assert cfg.default_window_slots(64, 16) == 64 * 544 + 80 * 512 + 4096 + 16
    assert cfg.state_shape(4) is None and cfg.default_state_slots(524288, 64) == 0
    groups = pool_groups(cfg, 4096, 8, 16)
    assert groups["state_shape"] is None and groups["conv_state_shape"] is None
    assert groups["window_shape"][:3] == (2, 3, 8)
    # Mellum2's window layers share its one array: no group (ROADMAP R4)
    mellum = get_config("mellum2-12b-a2.5b-l12")
    assert mellum.window_layers == () and mellum.window_pool_shape(64) is None
    assert mellum.default_window_slots(32, 16) == 0


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_by_name(model, what):
    kw, name = REFUSED[what]
    with pytest.raises(UnsupportedForModel, match=name) as e:
        make_engine(model, **(kw() if callable(kw) else kw))
    assert "attention kinds of two shapes" in str(e.value)
    assert "a window group of rows resumed from a tail" in str(e.value)


def test_the_prefix_ring_and_mid_decode_publishing_are_refused(model):
    for kw in ({"mesh": object()}, {"stream_publish_tokens": 8}):
        with pytest.raises(UnsupportedForModel, match="resumed from a tail"):
            make_engine(model, **kw)


def test_int8_weights_are_refused_at_init(model):
    with pytest.raises(UnsupportedForModel, match="int8 weights"):
        init_params(model[0], jax.random.PRNGKey(0), weight_quant="int8")


@pytest.mark.parametrize("change, message", [
    (dict(heads_by_type=(("full_attention", 6),)), "heads_by_type names"),
    (dict(layer_types=("full_attention",) * 5), "layer_types holds both"),
    (dict(heads_by_type=(("full_attention", 5), ("sliding_attention", 8))),
     "multiple of n_kv_heads"),
    (dict(n_heads=6), "n_heads the most"),
    (dict(sliding_window=40), "multiple of the page"),
    (dict(qkv_bias=True), "plain GQA layers"),
    (dict(rope_by_type=(("full_attention", (("partial_rotary_factor", 0.3),)),
                        ("sliding_attention", ()))), "even number of rotating"),
    (dict(heads_by_type=None, rope_by_type=(
        ("full_attention", ()), ("sliding_attention", ()))), "attn_gate's weights"),
])
def test_a_configuration_it_cannot_run_is_refused_by_name(change, message):
    with pytest.raises(ValueError, match=message):
        get_config("laguna-tiny").replace(**change)


def test_dense_prefill_takes_no_cached_prefix(model):
    cfg, params = model
    cached = jnp.zeros((2, 1, 16, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="window tail"):
        prefill_forward(params, cfg, jnp.zeros((1, 16), jnp.int32),
                        jnp.arange(16)[None], cached, cached,
                        jnp.asarray([16], jnp.int32))


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
    """Published widths and the cut's depth, a small vocabulary and 16 of
    the 256 experts: shapes only."""
    cfg = get_config("laguna-xs.2-l5").replace(
        vocab_size=4096, n_experts=16, n_experts_per_tok=8)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    shapes = jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    pools = dict(kv=sd(cfg.kv_pool_shape(8192), cfg.dtype),
                 window_pool=sd(cfg.window_pool_shape(4096), cfg.dtype))
    return cfg, jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes), sd, pools


def _aliased_and_uncopied(compiled, pools):
    arrays = [pools["kv"], pools["window_pool"]]
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    shapes = {",".join(map(str, a.shape)) for a in arrays}
    copied = set(re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text()))
    assert not shapes & copied, shapes & copied


def test_decode_lowers_for_a_v5e_at_published_widths(v5e):
    """Five launches of the fused decode kernel (48 q heads on the pool, 64
    on the window group: no jnp attention), the expert kernel, both groups
    aliased through, neither copied."""
    cfg, params, sd, pools = _published(v5e)
    i32 = jnp.int32
    compiled = decode_step.lower(
        params, cfg, sd((8,), i32), pools["kv"], sd((8,), i32), sd((8, 64), i32),
        sd((8,), i32), 16, moe_stats=sd((2, 4, 2), jnp.uint32),
        window_pool=pools["window_pool"], window_slots=sd((8,), i32),
        window_table=sd((8, 64), i32)).compile()
    names = set(re.findall(r"radixmesh_[a-z_]+[a-z]", compiled.as_text()))
    assert {"radixmesh_decode_fused_mh", "radixmesh_moe_gmm_decode"} <= names, names
    _aliased_and_uncopied(compiled, pools)


def test_chunk_lowers_for_a_v5e_at_published_widths(v5e):
    """The chunk kernel at 64 and at 48 query heads of 128 (its query block
    falls back to what fits) and the prefill expert kernel."""
    cfg, params, sd, pools = _published(v5e)
    i32 = jnp.int32
    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((2, 512), i32), sd((2, 512), i32), pools["kv"],
        sd((2, 512), i32), sd((2, 256), i32), sd((2,), i32), page_size=16,
        logits_at=sd((2,), i32), moe_stats=sd((2, 4, 2), jnp.uint32),
        window_pool=pools["window_pool"], window_slots=sd((2, 512), i32),
        window_table=sd((2, 256), i32)).compile()
    names = set(re.findall(r"radixmesh_[a-z_]+[a-z]", compiled.as_text()))
    assert {"radixmesh_chunk_attn", "radixmesh_moe_gmm_prefill"} <= names, names
    _aliased_and_uncopied(compiled, pools)


def test_the_counters_have_one_shape_for_this_model():
    assert new_moe_stats().shape == (2, 4, 2)
    assert isinstance(get_config("laguna-tiny"), ModelConfig)

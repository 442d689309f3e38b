"""Phi-4-mini-flash-reasoning (``models/phi4flash.py``) at toy widths on the
CPU: the three entry points, the pool's two groups of rows, the state
slots and the radix tree's snapshots (state + window tail) against the
plain float32 reference (``benchmarks/reference/phi4flash.py``) — logits,
not tokens — and what must NOT agree (the controls), what is refused, what
the programs lower to for a v5e at published widths."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash as ref
from radixmesh_tpu.cache.radix_tree import RadixTree
from radixmesh_tpu.engine.engine import pool_groups
from radixmesh_tpu.engine.request import RequestState, SamplingParams
from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    ModelConfig,
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
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec

pytestmark = pytest.mark.quick

WINDOW = 32


def reference(params, cfg, seq, logits_from, **control):
    return ref.forward(params, cfg, seq, at=np.arange(logits_from, len(seq)),
                       **control)


def _both_groups_and_the_slots(eng, **_):
    """Rows of the full layer alone in the pool, of the two window layers in
    the window group, two heads to a 128-lane row; float32 states."""
    cfg = eng.cfg
    assert eng.pool.kv.shape == (2, 1, 1, 4096, 128)
    assert eng.pool.window.shape == (2, 2, 1, eng.pool.window_slots, 128)
    assert eng.pool.state.shape[0] == 3 and eng.pool.state.dtype == jnp.float32
    assert eng.pool.state.shape[2:] == (cfg.ssm_state, cfg.ssm_inner)
    assert eng.pool.state_conv.shape[2:] == (3 * cfg.ssm_inner // 128, 128)
    assert eng.pool.state_slot_bytes == cfg.state_slot_bytes() == 3 * (
        16 * 512 * 4 + 3 * 512 * 4)


def _the_cross_decoder_read_the_full_layers_rows(eng, **_):
    assert eng.attn_window["shared_kv_tokens_read"] > 0


SPEC = ModelSpec(
    preset="phi4flash-tiny", seed=41, reference=reference,
    engine=dict(page_size=16, num_slots=4096, max_batch=4, max_seq_len=1024,
                prefill_chunk=64),
    # 7: inside one page; 48: a page multiple past the window; 100: chunks of
    # 64 that cut the window (32) and the snapshot at 96
    lengths=(7, 48, 100),
    # each must fail the tolerance the paths pass: lambda forced to zero, a
    # window of 33, int8 weights
    parts={"lambda": (dict(lambda_zero=True), 100),
           "window_of_32": (dict(window=WINDOW + 1), 100),
           "int8_weights": (dict(weight_map=ref.int8_weights), 100)},
    parts_prompt=100,
    forget={"state": lambda at: dict(forget_state_at=at),
            "tail": lambda at: dict(zero_tail_before=at)},
    forget_is_exact=("state", "tail"),
    says=("state-space layers with a recurrent state",
          "a cross-decoder that reads one layer's K/V"),
    hooks={"prefill": _both_groups_and_the_slots,
           "long_decode": _the_cross_decoder_read_the_full_layers_rows},
)
make_engine, prompt_of, run, error, check = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.run, SPEC.error, SPEC.check)
PAGE, TOL = SPEC.engine["page_size"], SPEC.tol


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


# ---------------------------------------------------------------------------
# a snapshot is a state AND a window tail: splits, pressure, a closed loop
# ---------------------------------------------------------------------------


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


def _published(v5e):
    """Published widths and depth, a small vocabulary: shapes only."""
    cfg = get_config("phi-4-mini-flash-reasoning").replace(vocab_size=4096)
    shapes, params, sd = abstract_params(cfg, v5e)
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
    return cfg, params, sd, pools


def _aliased_and_uncopied(compiled, pools):
    aliased_and_uncopied(
        compiled, [pools["kv"], pools["window_pool"], *pools["state_pool"]])


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
    names = kernels_in(compiled)
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

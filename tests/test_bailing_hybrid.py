"""Ling-3.0-flash (``models/bailing_hybrid.py``) at toy widths on the CPU:
the three entry points, the state slots (a float32 matrix a head and the
convolutions' inputs), the latent pool and the radix tree's snapshots
against the plain float32 reference (``benchmarks/reference/
bailing_hybrid.py``) — logits, not tokens — the forms of the recurrence
against each other, the group-limited router, what must NOT agree (the
controls), the expert shares, and what is refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import bailing_hybrid as ref
from radixmesh_tpu.engine.engine import pool_groups
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import ModelConfig, _ffn, read_moe_stats
from radixmesh_tpu.ops import kda
from radixmesh_tpu.ops.kernel_check import KDA_CASES, KDA_TOLERANCE, check_kda
from radixmesh_tpu.ops.moe import GROUP_STATS, moe_ffn, route
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec, distance, logits_from

pytestmark = pytest.mark.quick


def _the_pool_and_the_slots(eng, **_):
    """Rows of the one latent layer alone in the pool; a float32 matrix a
    head and the three convolutions' inputs in the state slots."""
    cfg = eng.cfg
    assert eng.pool.kv.shape == (1, 1, 1, 4096, 128)
    assert eng.pool.state.shape[0] == 3 and eng.pool.state.dtype == jnp.float32
    assert eng.pool.state.shape[2:] == (4 * 16, 16)
    assert eng.pool.state_conv.shape[2:] == (5, 128)  # 3 x 192 in 640
    assert eng.pool.state_slot_bytes == cfg.state_slot_bytes() == 3 * (
        64 * 16 * 4 + 5 * 128 * 4)


def _two_chunk_launches(eng, before):
    """One wave of two rows, chunk launches of both (64 + 32 > 84, 90): one
    row reads its state from the tree's snapshot, the other starts from
    zeros."""
    assert eng.prefill["launches"] - before["launches"] == 2


SPEC = ModelSpec(
    preset="ling-tiny", seed=48, reference=logits_from(ref.forward),
    engine=dict(page_size=16, num_slots=4096, max_batch=4, max_seq_len=1024,
                prefill_chunk=64),
    # 7: inside one page; 48: a page multiple; 150: chunks of 64 over ten pages
    lengths=(7, 48, 150),
    # the per-channel decay (against one decay a head), beta, the convolution,
    # the latent layers' head-wise gate, the KDA output gate and the router's
    # groups each matter; so does a state held in bf16
    parts={part: (dict(without=(part,)), 100) for part in (
        "channel_decay", "beta", "conv", "gate", "kda_gate", "groups")} | {
        "f32_state": (dict(state_dtype=jnp.bfloat16), 10)},
    forget={"state": lambda at: dict(forget_at=at)},
    says=("linear-attention (kda) layers with a recurrent matrix state",),
    hooks={"prefill": _the_pool_and_the_slots,
           "warm_and_cold": _two_chunk_launches},
)
make_engine, prompt_of, run, check = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.run, SPEC.check)
PAGE, TOL = SPEC.engine["page_size"], SPEC.tol


# ---------------------------------------------------------------------------
# the state path, by bytes
# ---------------------------------------------------------------------------


def test_state_slots_are_counted_by_bytes_at_published_widths():
    cfg = get_config("ling-3.0-flash-ep8")
    # 6 kda layers x (32 x 128 x 128 float32 + 3 x 12 288 bf16)
    assert cfg.state_slot_bytes() == 6 * (2 * 2**20 + 3 * 12288 * 2) == 13025280
    assert cfg.state_shape(321) == (6, 321, 4096, 128)
    assert cfg.conv_state_shape(321) == (6, 321, 288, 128)
    assert cfg.state_dtype == jnp.float32
    # a quarter of the latent pool buys 12 snapshots: the five a row hold
    assert cfg.kv_pool_shape(524288) == (1, 1, 1, 524288, 640)
    assert cfg.default_state_slots(524288, 64) == 1 + 5 * 64
    assert cfg.default_state_slots(524288, 32) == 1 + 5 * 32
    groups = pool_groups(cfg, 524288, 64, 16)
    assert groups["state_shape"] == (6, 321, 4096, 128)
    assert groups["conv_state_shape"] == (6, 321, 288, 128)
    assert groups["window_shape"] is None
    whole = get_config("ling-3.0-flash")
    assert len(whole.kda_layers) == 35 and len(whole.attn_layers) == 7
    assert whole.attn_layers == (5, 11, 17, 23, 29, 35, 41)


# ---------------------------------------------------------------------------
# the forms of the recurrence
# ---------------------------------------------------------------------------


def _kda_inputs(B, T, H, d, seed, g=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    if g is None:
        g = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (B, T, H, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, d, d))
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("gate", ["drawn", "at_its_bound", "open"])
@pytest.mark.parametrize("block", [16, 64])
def test_the_chunked_form_equals_the_recurrence(gate, block):
    """With every gate driven to its bound (g = -5 over 64 tokens: the
    cumulative log-decay reaches -320) the chunked form stays finite and
    within 1e-4: it works in differences of the cumulative log-decay."""
    g = {"drawn": None, "at_its_bound": jnp.full((2, 64, 4, 16), -5.0),
         "open": jnp.full((2, 64, 4, 16), -1e-4)}[gate]
    q, k, v, g, beta, S0 = _kda_inputs(2, 64, 4, 16, 1, g)
    o, snaps = kda.kda_recurrent_ref(q, k, v, g, beta, S0, jnp.full((2, 1), 64))
    ob, Sb = kda.kda_blocked_ref(q, k, v, g, beta, S0, block=block)
    assert bool(jnp.isfinite(ob).all()) and bool(jnp.isfinite(Sb).all())
    assert distance(np.asarray(ob), np.asarray(o)) < TOL
    assert distance(np.asarray(Sb), np.asarray(snaps[:, 0])) < TOL


# the kernel is the blocked form: other orders of the same float32 sums than
# the walk's (1e-6 at these sizes, 2.4e-6 on the chip at 32 heads of 128)
KERNEL_TOL = 1e-5


def _slots_of(S0, spare):
    """A slot array of two layers: ``S0``'s rows in slots 3.. of layer 1,
    ``spare`` slots of ones behind them."""
    B, H, d, _ = S0.shape
    ones = lambda n: jnp.ones((n, H * d, d))  # noqa: E731
    return jnp.stack([ones(3 + B + spare), jnp.concatenate(
        [ones(3), S0.reshape(B, H * d, d), ones(spare)])])


@pytest.mark.parametrize("T", [1, 8, 16, 48, 64, 128, 512])
def test_the_chunk_kernel_equals_the_recurrence_with_snapshots_inside(T):
    """Blocks of 64 where ``T`` allows (64, 128, 512), parts of 16 (16,
    48) or one shorter part (1, 8) otherwise; snapshots at the end, at 0,
    and INSIDE a part (no multiple of 16)."""
    q, k, v, g, beta, S0 = _kda_inputs(2, T, 4, 16, 2)
    inside = T // 2 + 5 if T >= 16 else T // 2
    snap = jnp.asarray([[T, inside, 0], [max(T - 3, 0), 1, T]], jnp.int32)
    o, snaps = kda.kda_recurrent_ref(q, k, v, g, beta, S0, snap)
    pool = _slots_of(S0, 5)  # the rows start from slots 3, 4 of layer 1
    # row 0 keeps to slots 5, 6, 7 and row 1 to 8, 0 (not kept), 9
    keep = jnp.asarray([[5, 6, 7], [8, 0, 9]], jnp.int32)
    ok, pk = kda.kda_chunk(q, k, v, g, beta, pool + 0, 1, jnp.asarray([3, 4]),
                           jnp.zeros((2,)), snap, keep, use_kernel=True,
                           interpret=True)
    assert ok.dtype == pk.dtype == jnp.float32
    assert distance(np.asarray(ok), np.asarray(o)) < KERNEL_TOL
    for b, j in ((0, 0), (0, 1), (0, 2), (1, 0), (1, 2)):
        assert distance(np.asarray(pk[1, keep[b, j]]),
                        np.asarray(snaps[b, j].reshape(64, 16))) < KERNEL_TOL
    assert np.array_equal(pk[1, 7], S0[0].reshape(64, 16))  # 0: as it started
    # nothing else of the array moved: not slot 0, not the other layer
    assert np.array_equal(pk[0], pool[0])
    assert np.array_equal(pk[1, :5], pool[1, :5])
    # a fresh row starts from zeros whatever its slot holds
    zero = jnp.zeros_like(S0)
    o0, _ = kda.kda_recurrent_ref(q, k, v, g, beta, zero, snap)
    for kernel in (True, False):
        of, pf = kda.kda_chunk(q, k, v, g, beta, pool + 0, 1, jnp.asarray([3, 4]),
                               jnp.ones((2,)), snap, keep, use_kernel=kernel,
                               interpret=True)
        assert distance(np.asarray(of), np.asarray(o0)) < KERNEL_TOL
        assert not np.asarray(pf[1, 7]).any()


@pytest.mark.parametrize("bound", ["a_block", "the_chunk", "a_run_then_open"])
def test_the_chunk_kernel_holds_a_gate_at_its_bound(bound):
    """g = -5 over a WHOLE block of 64 (the cumulative log-decay reaches
    -320: ``exp`` of its negative is not a float32), over the whole chunk,
    and over a run followed by gates near 0 (the decays after the run are
    differences of sums near -160: taken part by part they stay exact).
    One row resumes, the other is fresh; snapshots inside the run."""
    T = 128
    q, k, v, g, beta, S0 = _kda_inputs(2, T, 4, 16, 7)
    g = {"a_block": g.at[:, 64:].set(-5.0),
         "the_chunk": jnp.full_like(g, -5.0),
         "a_run_then_open": g.at[:, :32].set(-5.0).at[:, 32:].multiply(1e-3),
         }[bound]
    fresh = jnp.asarray([0, 1])
    snap = jnp.asarray([[T, 77, 0], [101, 64, 13]], jnp.int32)
    start = S0 * (1 - fresh)[:, None, None, None]
    o, snaps = kda.kda_recurrent_ref(q, k, v, g, beta, start, snap)
    keep = jnp.asarray([[5, 6, 7], [8, 9, 10]], jnp.int32)
    ok, pk = kda.kda_chunk(q, k, v, g, beta, _slots_of(S0, 6), 1,
                           jnp.asarray([3, 4]), fresh, snap, keep,
                           use_kernel=True, interpret=True)
    assert bool(jnp.isfinite(ok).all()) and bool(jnp.isfinite(pk).all())
    assert distance(np.asarray(ok), np.asarray(o)) < KERNEL_TOL
    for b in range(2):
        for j in range(3):
            want = np.asarray(snaps[b, j].reshape(64, 16))
            got = np.asarray(pk[1, keep[b, j]])
            assert np.abs(got - want).max() < KERNEL_TOL * max(
                1.0, np.abs(want).max())


def test_a_state_carried_over_three_chunks_is_one_longer_chunks():
    """Three launches of 64 through a working slot (the cold long prompt's
    way: fresh, then from the slot the launch before kept to) against one
    launch of 192 and against the recurrence."""
    q, k, v, g, beta, S0 = _kda_inputs(2, 192, 4, 16, 8)
    whole = jnp.full((2, 1), 192, jnp.int32)
    o, snaps = kda.kda_recurrent_ref(
        q, k, v, g, beta, jnp.zeros_like(S0), whole)
    rows, pool = jnp.asarray([3, 4]), _slots_of(S0, 0)
    one = kda.kda_chunk(q, k, v, g, beta, pool + 0, 1, rows, jnp.ones((2,)),
                        whole, rows[:, None], use_kernel=True, interpret=True)
    outs = []
    for c in range(3):
        part = [t[:, 64 * c:64 * (c + 1)] for t in (q, k, v, g, beta)]
        oc, pool = kda.kda_chunk(
            *part, pool, 1, rows, jnp.full((2,), c == 0),
            jnp.full((2, 1), 64, jnp.int32), rows[:, None], use_kernel=True,
            interpret=True)
        outs.append(oc)
    for got_o, got_pool in ((jnp.concatenate(outs, axis=1), pool), one):
        assert distance(np.asarray(got_o), np.asarray(o)) < KERNEL_TOL
        assert distance(np.asarray(got_pool[1, 3:]),
                        np.asarray(snaps.reshape(2, 64, 16))) < KERNEL_TOL


@pytest.mark.parametrize("T", [16, 64])
def test_a_launch_of_more_rows_than_a_call_takes_is_several_calls_on_one_array(T):
    """``_ROWS_A_CALL`` rows a call (a launch of 32 rows in one call beside
    the latent chunk kernel never returned on the chip): the calls share
    the slot array, each row keeps to its own slots; fresh rows and rows
    that resume lie in one call."""
    B = kda._ROWS_A_CALL + 3
    q, k, v, g, beta, S0 = _kda_inputs(B, T, 2, 16, 6)
    fresh = jnp.arange(B) % 3 == 1
    snap = jnp.tile(jnp.asarray([[T, 5]], jnp.int32), (B, 1))
    _, snaps = kda.kda_recurrent_ref(
        q, k, v, g, beta, S0 * ~fresh[:, None, None, None], snap)
    pool = jnp.concatenate([jnp.ones((1, 32, 16)), S0.reshape(B, 32, 16),
                            jnp.ones((2 * B, 32, 16))])[None]
    rows = 1 + jnp.arange(B)
    keep = (1 + B + jnp.arange(2 * B)).reshape(B, 2)
    want = kda.kda_chunk(q, k, v, g, beta, pool, 0, rows, fresh, snap,
                         keep, use_kernel=False)
    got = kda.kda_chunk(q, k, v, g, beta, pool + 0, 0, rows, fresh,
                        snap, keep, use_kernel=True, interpret=True)
    assert distance(np.asarray(got[0]), np.asarray(want[0])) < KERNEL_TOL
    assert distance(np.asarray(got[1]), np.asarray(want[1])) < KERNEL_TOL
    assert distance(np.asarray(got[1][0, 1 + B:]),
                    np.asarray(snaps.reshape(2 * B, 32, 16))) < KERNEL_TOL


def test_the_chunk_kernels_trace_does_not_grow_with_heads_or_rows(model):
    """What set-up pays for the kernel a program: PR 49's blocked form was
    refused for `setup_s` +19.3% in `ling-3.0-flash-ep8.batch-longtail`
    (bound 10%) with a gain of 36% in hand — its body unrolled the heads
    and the block's positions in Python, in each of up to 24 calls of each
    of ~70 prefill programs a set-up traces and lowers, cache or no cache.
    Held here: the body's equations do not grow with the heads (a grid
    step takes ``_HEADS_A_STEP`` of them, batched: the same body at 8
    heads and at the published 32, a smaller one at 2), the positions or
    the rows, and stay under a cap (the walk's body at 32 heads was ~680;
    a snapshot a row costs ~100), and a prefill program of ``ling-tiny``
    holds one call a kda layer and ``_ROWS_A_CALL`` rows, no more."""
    from radixmesh_tpu.models.llama import new_moe_stats, prefill_chunk_paged

    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct

    def body(H, T=64, B=2, d=16, K=3):
        seq = sd((B, T, H, d), f32)
        jaxpr = jax.make_jaxpr(kda.kda_chunk_kernel)(
            seq, seq, seq, seq, sd((B, T, H), f32), sd((1, 9, H * d, d), f32),
            sd((), i32), sd((B,), i32), sd((B,), i32), sd((B, K), i32),
            sd((B, K), i32))
        return max(equations(c.params["jaxpr"])
                   for c in kernel_calls(jaxpr.jaxpr, "radixmesh_kda_chunk"))

    assert body(2) <= body(8) == body(32) <= 900
    assert body(8, T=16) <= body(8) and body(8, T=512) == body(8, B=33)

    cfg, params = model
    shapes = jax.tree.map(lambda x: sd(x.shape, x.dtype), params)
    groups = pool_groups(cfg, 4096, 4, PAGE)
    state = (sd(groups["state_shape"], groups["state_dtype"]),
             sd(groups["conv_state_shape"], cfg.dtype))
    moe = jax.eval_shape(lambda: new_moe_stats(cfg.ep_size > 1, cfg.n_group > 1))
    for rows in (4, 2 * kda._ROWS_A_CALL):
        ints = lambda *s: sd(s, i32)  # noqa: E731
        traced = prefill_chunk_paged.trace(
            shapes, cfg, ints(rows, 64), ints(rows, 64),
            sd(cfg.kv_pool_shape(4096), cfg.dtype), ints(rows, 64),
            ints(rows, 32), ints(rows), page_size=PAGE, logits_at=ints(rows),
            state_pool=state, state_in=ints(rows), snap_at=ints(rows, 3),
            state_out=ints(rows, 3), moe_stats=moe)
        calls = kernel_calls(traced.jaxpr.jaxpr, "radixmesh_kda_chunk")
        assert len(calls) == len(cfg.kda_layers) * -(-rows // kda._ROWS_A_CALL)


def test_the_decode_kernel_updates_the_rows_slots_in_place_and_no_other():
    q, k, v, g, beta, _ = _kda_inputs(3, 1, 4, 16, 3)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 9, 64, 16))
    slots = jnp.asarray([4, 7, 1])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o, new = kda.kda_decode_ref(*args, pool, 1, slots)
    ok, nk = kda.kda_decode(*args, pool + 0, 1, slots, use_kernel=True,
                            interpret=True)
    assert distance(np.asarray(ok), np.asarray(o)) < 1e-6
    assert distance(np.asarray(nk), np.asarray(new)) < 1e-6
    untouched = np.ones((2, 9), bool)
    untouched[1, [4, 7, 1]] = False
    assert np.array_equal(np.asarray(nk)[untouched], np.asarray(pool)[untouched])
    o1, _ = kda.kda_recurrent_ref(
        q, k, v, g, beta, pool[1, slots].reshape(3, 4, 16, 16), jnp.ones((3, 1)))
    assert distance(np.asarray(ok), np.asarray(o1[:, 0])) < 1e-6


@pytest.mark.parametrize("kernel", [False, True])
def test_boundary_copies_move_the_rows_that_want_one(kernel):
    pool = jax.random.normal(jax.random.PRNGKey(4), (3, 8, 64, 16))
    src, dst = jnp.asarray([2, 5, 6]), jnp.asarray([0, 3, 0])
    out = kda.copy_slots(pool + 0, src, dst, use_kernel=kernel, interpret=True)
    assert np.array_equal(out[:, 3], pool[:, 5])
    keep = [1, 2, 4, 5, 6, 7] + ([0] if kernel else [])  # slot 0 is nobody's
    assert np.array_equal(out[:, keep], pool[:, keep])


def test_the_convolution_hands_back_the_inputs_before_each_snapshot():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 10, 24))
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 24))
    state = jax.random.normal(jax.random.PRNGKey(7), (2, 3, 24))
    y, snaps = kda.short_conv(x, w, state, jnp.asarray([[10, 0, 2], [4, 1, 10]]))
    xs = np.concatenate([state, x], axis=1)
    want = sum(np.asarray(w)[j] * xs[:, j:j + 10] for j in range(4))
    assert distance(np.asarray(y), np.asarray(jax.nn.silu(want))) < 1e-6
    assert np.array_equal(snaps[0, 0], x[0, 7:10])
    assert np.array_equal(snaps[0, 1], state[0])
    assert np.array_equal(snaps[0, 2], xs[0, 2:5])
    assert np.array_equal(snaps[1, 1], xs[1, 1:4])
    y1, last = kda.short_conv(x, w, state, None)
    assert np.array_equal(last[:, 0], x[:, 7:10]) and np.array_equal(y1, y)


# ---------------------------------------------------------------------------
# the router's groups
# ---------------------------------------------------------------------------


def _router(seed=0, T=64, H=32, E=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (T, H)), jax.random.normal(ks[1], (H, E)),
            0.02 * jax.random.normal(ks[2], (E,)))


def test_without_groups_the_router_is_todays():
    x, w, b = _router()
    old = route(x, w, 2, True, "sigmoid", b, 2.5)
    new = route(x, w, 2, True, "sigmoid", b, 2.5, n_group=1, topk_group=1)
    assert all(np.array_equal(a, c) for a, c in zip(old, new))
    text = [jax.jit(lambda x, w, b, kw=kw: route(
        x, w, 2, True, "sigmoid", b, 2.5, **kw)).lower(x, w, b).as_text()
        for kw in ({}, {"n_group": 1, "topk_group": 1})]
    assert text[0] == text[1]  # the program it traces today


def test_the_groups_limit_the_choice_and_match_the_reference():
    x, w, b = _router(1)
    ids, weights = route(x, w, 2, True, "sigmoid", b, 2.5, n_group=4, topk_group=2)
    free, _ = route(x, w, 2, True, "sigmoid", b, 2.5)
    assert not np.array_equal(ids, free)  # a reference that ignores groups differs
    lp = {"w_router": w, "router_bias": b}
    kw = dict(top_k=2, norm_topk=True, scaling=2.5)
    r_ids, r_w, scores, raw = ref.route(x, lp, n_group=4, topk_group=2, **kw)
    assert np.array_equal(ids, r_ids) and distance(weights, r_w) < 1e-6
    assert not np.array_equal(ref.route(x, lp, n_group=1, topk_group=1, **kw)[0], ids)
    # two groups kept a token, the chosen experts inside them
    kept = np.isfinite(np.asarray(scores)).reshape(64, 4, 4).all(-1)
    assert (kept.sum(-1) == 2).all()
    assert kept[np.arange(64)[:, None], np.asarray(ids) // 4].all()
    # a group's score is the sum of its TWO best: by hand for one token
    s = np.asarray(jax.nn.sigmoid(x @ w) + b)[0].reshape(4, 4)
    best = np.sort(s, axis=-1)[:, -2:].sum(-1)
    assert set(np.argsort(-best)[:2]) == set(np.flatnonzero(kept[0]))
    # the tool's measure of a choice: 0 for the router's own, positive for
    # an expert it did not choose, by what its group lacks where that is out
    raw = np.asarray(raw)
    assert not ref.shortfall(raw, np.asarray(ids), 4, 2).any()
    out = np.flatnonzero(~kept[0])[0] * 4  # an expert of a group not kept
    lack = ref.shortfall(raw[:1], np.asarray([[out, out + 1]]), 4, 2)
    assert (lack > 0).all() and np.allclose(
        lack, np.sort(best)[-2] - best[out // 4])


def test_ties_between_groups_go_to_the_lower_group():
    w = jnp.zeros((8, 16))
    ids, _ = route(jnp.ones((3, 8)), w, 2, True, "sigmoid", None, 1.0,
                   n_group=4, topk_group=2)
    assert np.array_equal(ids, np.tile([0, 1], (3, 1)))


def test_groups_kept_counts_the_tokens_that_reach_this_share(model):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.hidden))
    lp = {k: v[0] for k, v in params["layers"].items()}
    stacked = {k: params["layers"][k][0] for k in ("w_gate", "w_up", "w_down")}
    kw = dict(k=2, norm_topk=True, site="decode", scoring="sigmoid",
              bias=lp["router_bias"], scale=2.5, n_group=4, topk_group=2,
              use_kernel=False)
    _, ids, stats = moe_ffn(x, lp["w_router"], *stacked.values(), **kw)
    st = dict(zip(GROUP_STATS, map(int, stats)))
    assert st["groups_kept"] == 40 * 2 and st["assignments_elsewhere"] == 0
    # the first of four shares: experts 0-3, one routing group
    share = {k: v[:4] for k, v in stacked.items()}
    _, ids4, stats4 = moe_ffn(x, lp["w_router"], *share.values(), **kw)
    st4 = dict(zip(GROUP_STATS, map(int, stats4)))
    assert np.array_equal(ids, ids4)
    assert st4["assignments"] == int((np.asarray(ids) < 4).sum())
    assert st4["assignments"] + st4["assignments_elsewhere"] == 80
    # a token reaches this chip only when group 0 is among its kept groups
    reach = int((np.asarray(ids) < 4).any(-1).sum())
    assert reach <= st4["groups_kept"] <= 40


def test_the_engine_publishes_groups_kept(model):
    eng = make_engine(model)
    run(eng, prompt_of(30, seed=40))
    stats = read_moe_stats(eng._moe_stats)
    assert stats["prefill"]["groups_kept"] == 30 * 3 * 2  # tokens x layers x kept
    assert stats["decode"]["groups_kept"] == 8 * 3 * 2


# ---------------------------------------------------------------------------
# the kernels' control: a state of lower precision
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("state_dtype", [None, jnp.bfloat16])
def test_the_kernel_check_tells_the_states_precision(state_dtype):
    """``check_kda`` (the chip smoke's kernels phase) is where a state slot
    of lower precision shows on the chip: float32 slots pass its tolerance
    with room, the same states held in bf16 fail it."""
    res = check_kda(heads=4, dim=16, batch=3, chunk=16, interpret=True,
                    state_dtype=state_dtype)
    assert [r["kernel"] for r in res] == list(KDA_CASES)
    assert all(r["tolerance"] == KDA_TOLERANCE for r in res)
    chunk, decode, boundary = res
    if state_dtype is None:
        assert all(r["ok"] for r in res), res
        assert max(chunk["pool_write_diff"], decode["pool_write_diff"]) < KDA_TOLERANCE / 10
    else:
        assert not chunk["ok"] and not decode["ok"], res
        assert min(chunk["pool_write_diff"], decode["pool_write_diff"]) > 10 * KDA_TOLERANCE


# ---------------------------------------------------------------------------
# the expert shares add up
# ---------------------------------------------------------------------------


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up(model):
    """Four shares of the 16 experts (one routing group each): their routed
    parts plus the shared expert counted once are the uncut layer."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 24, cfg.hidden))
    lp = {k: v[1] for k, v in params["layers"].items()}
    whole, _ = _ffn(params, lp, 2, x, cfg, "prefill", use_kernel=False)
    shared = (jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])) @ lp["ws_down"]
    parts = []
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        sliced = {"layers": params["layers"] | {
            k: params["layers"][k][:, held] for k in ("w_gate", "w_up", "w_down")}}
        y, (ids, stats) = _ffn(
            sliced, lp, 2, x, cfg.replace(ep_size=4, ep_rank=rank), "prefill",
            use_kernel=False)
        parts.append(y - shared)
    assert distance(np.asarray(sum(parts) + shared), np.asarray(whole)) < 1e-5
    assert distance(np.asarray(parts[0]), np.asarray(whole - shared)) > 0.1
    # and the reference given the same share computes the same part
    flat = {k: v for k, v in lp.items()}
    flat |= {k: params["layers"][k][1][:4] for k in ("w_gate", "w_up", "w_down")}
    ids, w, *_ = ref.route(x[0], flat, top_k=2, norm_topk=True, scaling=2.5,
                           n_group=4, topk_group=2)
    assert distance(np.asarray(ref._experts(x[0], flat, ids, w, 0)),
                    np.asarray(parts[0][0])) < 1e-5


# ---------------------------------------------------------------------------
# what is refused, and what the config refuses
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change, message", [
    ({"kda_heads": 0}, "kda layers need"),
    ({"kv_lora_rank": 0, "q_lora_rank": 0}, "latent full_attention"),
    ({"layer_types": ("kda",) * 4}, "latent full_attention"),
    ({"n_group": 3}, "group-limited"),
    ({"topk_group": 5}, "group-limited"),
    ({"n_group": 4, "topk_group": 1, "n_experts_per_tok": 5}, "group-limited"),
    ({"router_scoring": "softmax"}, "group-limited"),
])
def test_the_config_refuses_what_the_program_does_not_run(change, message):
    with pytest.raises(ValueError, match=message):
        get_config("ling-tiny", **change)


def test_only_a_kda_config_takes_kda_sizes():
    with pytest.raises(ValueError, match="kda_heads"):
        ModelConfig.tiny().replace(kda_heads=4)
    # latent attention without a bottleneck needs no kda layers
    cfg = get_config("glm-4.7-flash-tiny", q_lora_rank=0)
    params = init_params(cfg.replace(dtype=jnp.float32), jax.random.PRNGKey(0))
    assert "wq" in params["layers"] and "q_norm" not in params["layers"]

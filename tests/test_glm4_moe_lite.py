"""GLM-4.7-Flash (``glm4_moe_lite``) on the served path against the plain
float32 reference (``benchmarks/reference/glm4_moe_lite.py``: attention NOT
absorbed, K and V derived for every token, no cache): the tiny preset (a
dense layer then two sparse ones, 8 sigmoid-routed experts top-2 beside a
shared one, 4 heads of 16 + 8 over a 32 + 8 latent row), float32 program,
CPU.

Dense prefill, chunked paged prefill, prefill + decode steps through the
paged latent cache and a prefix served from the radix tree each have to
equal the reference's FULL forward pass over prompt + generated tokens —
logits, not tokens."""

import inspect
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as ref
from radixmesh_tpu.cache import radix_tree
from radixmesh_tpu.engine import SamplingParams
from radixmesh_tpu.engine.request import RequestState
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    UnsupportedForModel,
    _attn_out,
    _ffn,
    _latent_qkv,
    decode_step,
    prefill_forward,
    read_moe_stats,
)
from radixmesh_tpu.ops.kernel_check import TOLERANCE, _err
from radixmesh_tpu.ops.mla_attention import (
    decode_groups,
    latent_chunk_attention,
    latent_decode_attention,
    latent_prefill_attention,
    mla_chunk_kernel,
    mla_decode_kernel,
)
from radixmesh_tpu.ops.moe import route
from radixmesh_tpu.ops.rope import rope_frequencies
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec, logits_from
from tests.model_conformance import distance as rel

pytestmark = pytest.mark.quick

SPEC = ModelSpec(
    preset="glm-4.7-flash-tiny", seed=31, reference=logits_from(ref.forward),
    engine=dict(page_size=4, num_slots=1024, max_batch=4, max_seq_len=256,
                prefill_chunk=16),
    # 7: inside two pages; 16: a chunk; 53: chunks of 16 with a ragged end
    lengths=(7, 16, 53),
    says=("a latent (MLA) cache row",),
)
make_engine, prompt_of, check_against_reference = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.check)
PAGE, N_NEW, TOL = SPEC.engine["page_size"], SPEC.n_new, SPEC.tol


@pytest.mark.parametrize("n_tail", [5, 21])
def test_prefix_served_from_the_tree_is_read_in_pages(model, n_tail):
    """A second request whose prompt is mostly in the radix tree: its
    latent prefix is read where it lies (the chunk path, whatever the
    tail's length — as every configuration's is, PR 36), and the counters
    say how many rows that was."""
    cfg, _ = model
    eng = make_engine(model, prefill_chunk=16)
    shared = prompt_of(41, seed=1)
    eng.generate([shared + prompt_of(3, seed=2)], SamplingParams(max_new_tokens=2))
    assert eng._last_prefill_path == "dense"  # nothing cached: one launch
    before = dict(eng.attn_window)
    prompt = shared + prompt_of(n_tail, seed=3)
    req = eng.add_request(
        prompt, SamplingParams(max_new_tokens=N_NEW, return_logits=True))
    while eng.has_work():
        eng.step()
    assert eng._last_prefill_path == "chunk"
    assert req.prefix_len == 40  # whole pages of the 41 shared tokens
    check_against_reference(model, req, prompt)
    chunks = range(0, n_tail + 1, 16)  # chunk starts behind the prefix
    assert eng.attn_window["latent_prefix_tokens"] - before[
        "latent_prefix_tokens"] == sum(40 + c for c in chunks) * cfg.n_layers
    ctx = [len(prompt) + 1 + i for i in range(N_NEW - 1)]
    assert eng.attn_window["latent_tokens_read"] - before[
        "latent_tokens_read"] == sum(ctx) * cfg.n_layers


def test_batched_rows_and_counters(model):
    cfg, _ = model
    eng = make_engine(model)
    prompts = [prompt_of(n, seed=9 + n) for n in (5, 25, 38)]
    reqs = [eng.add_request(p, SamplingParams(
        max_new_tokens=N_NEW, return_logits=i != 0)) for i, p in enumerate(prompts)]
    while eng.has_work():
        eng.step()
    assert reqs[0].logit_rows == [] and reqs[0].expert_rows == []
    for req, p in zip(reqs[1:], prompts[1:]):
        check_against_reference(model, req, p)
    moe = read_moe_stats(eng._moe_stats)
    steps, sparse = eng.stats.decode_steps, cfg.n_layers - cfg.first_k_dense
    assert moe["decode"]["layer_launches"] == steps * sparse
    # the shared expert is no assignment: 3 live rows x top-2 x 2 sparse layers
    assert moe["decode"]["assignments"] == 3 * 2 * sparse * steps
    assert moe["prefill"]["assignments"] == sum(map(len, prompts)) * 2 * sparse


def test_the_pool_row_is_what_the_model_states(model):
    """One plane, one head, the latent row (stored in whole 128-lane
    tiles); the radix tree holds slot indices and needed no edit."""
    cfg, params = model
    assert cfg.kv_row == (1, 1, 128)  # 32 + 8 elements, zeros up to 128
    assert cfg.kv_pool_shape(64) == (1, cfg.n_layers, 1, 64, 128)
    eng = make_engine(model)
    assert eng.pool.kv.shape == cfg.kv_pool_shape(1024) and eng.pool.planes == 1
    assert type(eng.tree) is radix_tree.RadixTree
    src = inspect.getsource(radix_tree)
    assert not any(w in src for w in ("planes", "kv_row", "latent", "head_dim"))
    prompt = prompt_of(24, seed=4)
    eng.generate([prompt], SamplingParams(max_new_tokens=2))
    slots = eng.tree.match_prefix(np.asarray(prompt, np.int32)).indices()
    assert slots.dtype.kind == "i" and len(slots) == 24
    rows = np.asarray(eng.pool.gather(slots))  # [planes, L, n, heads, width]
    assert rows.shape == (1, cfg.n_layers, 24, 1, 128)
    _, new_rows, new_v, _ = prefill_forward(
        params, cfg, jnp.asarray([prompt]), jnp.arange(24)[None],
        jnp.zeros((cfg.n_layers, 1, 0, 1, 128)), jnp.zeros((cfg.n_layers, 1, 0, 1, 128)),
        jnp.zeros((1,), jnp.int32))
    assert new_v is None
    np.testing.assert_allclose(rows[0], np.asarray(new_rows)[:, 0], atol=1e-5)
    assert np.all(rows[..., 40:] == 0)  # the row's tail is zeros


def test_one_model_layer_0_dense_the_rest_sparse(model):
    cfg, params = model
    dense, sparse = params["dense_layers"], params["layers"]
    assert dense["w_gate"].shape == (1, cfg.hidden, cfg.intermediate)
    assert "w_router" not in dense and "ws_gate" not in dense
    assert sparse["w_gate"].shape == (2, cfg.n_experts, cfg.hidden, cfg.moe_intermediate)
    assert sparse["ws_gate"].shape == (2, cfg.hidden, cfg.moe_intermediate)
    assert sparse["router_bias"].shape == (2, cfg.n_experts)
    assert float(jnp.abs(sparse["router_bias"]).min()) > 0  # a non-zero draw
    for stack in (dense, sparse):
        assert stack["wkv_a"].shape[1:] == (cfg.hidden, 32 + 8)
        assert stack["wkv_b"].shape[1:] == (32, cfg.n_heads * (16 + 24))
    # zeroing the dense layer's FFN changes the logits; so does the shared expert's
    toks, pos = jnp.asarray([prompt_of(12, seed=6)]), jnp.arange(12)[None]
    empty = jnp.zeros((cfg.n_layers, 1, 0, 1, 128))
    base = prefill_forward(params, cfg, toks, pos, empty, empty, jnp.zeros((1,), jnp.int32))[0]
    for stack, leaf in (("dense_layers", "w_down"), ("layers", "ws_down")):
        cut = {**params, stack: {**params[stack], leaf: 0 * params[stack][leaf]}}
        other = prefill_forward(cut, cfg, toks, pos, empty, empty, jnp.zeros((1,), jnp.int32))[0]
        assert rel(np.asarray(other), np.asarray(base)) > 1e-3


def test_absorbed_attention_is_the_non_absorbed(model):
    """``q_nope W_k^T`` against the latent row and the row's head mixed
    back through ``W_v``: the reference's attention over derived K and V."""
    cfg, params = model
    lp = {k: v[0] for k, v in params["layers"].items()}
    S = 37
    h = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.hidden), jnp.float32)
    pos = jnp.arange(S)[None]
    inv = rope_frequencies(cfg.rope_dim, cfg.rope_theta)
    q_lat, row = _latent_qkv(lp, h, cfg, pos, inv)
    assert q_lat.shape == (1, S, cfg.n_heads, 128) and row.shape == (1, S, 1, 128)
    o_lat = latent_prefill_attention(
        q_lat, row, pos, jnp.full((1,), S), None, cfg.kv_lora_rank,
        cfg.head_dim ** -0.5)
    mine = _attn_out(lp, o_lat, cfg)[0]
    # the reference's attention block on the same h (its x + attention - x)
    with jax.default_matmul_precision("highest"):
        c_q = ref._rms_norm(h[0] @ lp["wq_a"], lp["q_norm"], cfg.rms_eps)
        q = (c_q @ lp["wq_b"]).reshape(S, cfg.n_heads, 24)
        q = jnp.concatenate([q[..., :16], ref._rope(q[..., 16:], inv)], -1)
        kv = h[0] @ lp["wkv_a"]
        c_kv = ref._rms_norm(kv[:, :32], lp["kv_norm"], cfg.rms_eps)
        k_rope = ref._rope(kv[:, None, 32:], inv)
        up = (c_kv @ lp["wkv_b"]).reshape(S, cfg.n_heads, 16 + 24)
        k = jnp.concatenate(
            [up[..., :16], jnp.broadcast_to(k_rope, (S, cfg.n_heads, 8))], -1)
        theirs = ref._attention(q, k, up[..., 16:]) @ lp["wo"]
    assert rel(np.asarray(mine), np.asarray(theirs)) < TOL


def test_router_chooses_by_score_plus_bias_and_weighs_by_score(model):
    cfg, params = model
    lp = {k: v[1] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.hidden), jnp.float32)
    ids, w = route(x, lp["w_router"], 2, True, "sigmoid", lp["router_bias"], 1.8)
    r_ids, r_w, biased = ref.route(x, lp, None, top_k=2, norm_topk=True, scaling=1.8)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(r_ids))
    np.testing.assert_allclose(np.asarray(w), np.asarray(r_w), rtol=1e-5)
    s = np.asarray(biased - lp["router_bias"])  # the unbiased scores
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.8, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(w), 1.8 * np.take_along_axis(s, np.asarray(ids), 1)
        / np.take_along_axis(s, np.asarray(ids), 1).sum(-1, keepdims=True), rtol=1e-5)
    unbiased = np.argsort(-s, axis=-1, kind="stable")[:, :2]
    assert (np.sort(unbiased, -1) != np.sort(np.asarray(ids), -1)).any(), (
        "the bias draw changes no choice: the test cannot tell s + b from s")
    # the softmax router is what it was
    ids0, w0 = route(x, lp["w_router"], 2, True)
    p = np.asarray(jax.nn.softmax(x @ lp["w_router"], axis=-1))
    np.testing.assert_array_equal(np.asarray(ids0), np.argsort(-p, -1, kind="stable")[:, :2])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sparse_layer_with_its_shared_expert(model, use_kernel):
    cfg, params = model
    lp = {k: v[1] for k, v in params["layers"].items()
          if k not in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, cfg.hidden), jnp.float32)
    y, (ids, stats) = _ffn(params, lp, jnp.int32(2), x, cfg, "prefill",
                           use_kernel=use_kernel, interpret=True)
    full = {k: v[1] for k, v in params["layers"].items()}
    flat = x.reshape(-1, cfg.hidden)
    with jax.default_matmul_precision("highest"):
        e, w, _ = ref.route(flat, full, None, top_k=2, norm_topk=True, scaling=1.8)
        want = ref._experts(flat, full, e, w) + ref._swiglu(
            flat, full["ws_gate"], full["ws_up"], full["ws_down"])
    assert rel(np.asarray(y).reshape(-1, cfg.hidden), np.asarray(want)) < TOL
    np.testing.assert_array_equal(np.asarray(ids).reshape(-1, 2), np.asarray(e))
    assert int(stats[1]) == 2 * 19 * 2  # the shared expert is no assignment


def _pool_case(seed=0, B=4, W=128, vw=32, Hq=4, page=16, P=32, L=2):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(1, L, 1, P, page, W)), jnp.float32)
    pt = np.zeros((B, 8), np.int32)
    pt[0, :5] = [3, 4, 5, 9, 2]  # fragmented
    pt[2, :3] = [10, 11, 12]  # consecutive
    pt[3, :5] = [20, 21, 22, 23, 24]
    return rng, pool, pt


@pytest.mark.parametrize("block_tokens", [16, 32, 512])
def test_decode_kernel_in_interpret_mode(block_tokens):
    """``radixmesh_mla_decode`` against the gather reference: fragmented
    and consecutive pages, an idle row, a row that starts a page, and the
    new row written through the aliased pool."""
    rng, pool, pt = _pool_case()
    lengths = np.array([70, 1, 33, 65], np.int32)
    slots = np.array([2 * 16 + 5, 0, 12 * 16, 24 * 16], np.int32)
    q = jnp.asarray(rng.normal(size=(4, 4, 128)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    want, pool_ref = latent_decode_attention(
        q, row, pool, slots, pt, lengths, 1, 32, 0.2, use_kernel=False)
    got, pool_k = mla_decode_kernel(
        q, row, pool, slots, pt, lengths, 1, v_width=32, scale=0.2,
        block_tokens=block_tokens, interpret=True)
    live = lengths > 1
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pool_k), np.asarray(pool_ref))
    assert got.shape == (4, 4, 32)


def _shared_prefix_case(n_members, shared_pages, contiguous, own=(), seed=0,
                        dtype=jnp.float32, page=16, width=12, W=128, Hq=4):
    """A decode launch in which ``n_members`` rows hold the same
    ``shared_pages`` pages first and pages of their own after, beside a
    row that shares nothing, an empty row (length 0, whose table starts
    with the shared pages all the same) and an idle one (length 1).
    ``own``: tokens a member has past the shared pages, the current one
    included (cycled). ``(kernel inputs, page table, lengths, live rows)``."""
    rng = np.random.default_rng(seed)
    B = n_members + 3
    P = 2 + shared_pages + B * width  # page 0 and the last: nobody's context
    ids = 1 + (np.arange(shared_pages) if contiguous
               else rng.permutation(shared_pages))
    free = iter(range(1 + shared_pages, P - 1))
    pt = np.zeros((B, width), np.int32)
    lengths = np.ones(B, np.int32)
    own = own or (1, page + 3, 2 * page, 7)
    for b in range(B - 1):  # the last row idles
        pt[b] = [next(free) for _ in range(width)]
        if b != n_members:  # row n_members shares nothing
            pt[b, :shared_pages] = ids
        lengths[b] = shared_pages * page + own[b % len(own)]
    lengths[n_members + 1] = 0
    pos = np.maximum(lengths - 1, 0)
    slots = pt[np.arange(B), pos // page] * page + pos % page
    slots[n_members + 1] = (P - 1) * page  # the reference writes it, alone
    x = (
        jnp.asarray(rng.normal(size=(B, Hq, W)), dtype),
        jnp.asarray(rng.normal(size=(B, W)), dtype),
        jnp.asarray(rng.normal(size=(1, 2, 1, P, page, W)), dtype),
        slots.astype(np.int32),
    )
    live = [b for b in range(B) if lengths[b] > 1]
    return x, pt, lengths, live


def _check_grouped(x, pt, lengths, groups, atol=1e-5, block_tokens=32):
    """The grouped launch against the gather reference, against the same
    launch with no groups, and its write through the aliased pool."""
    q, row, pool, slots = x
    kw = dict(v_width=32, scale=0.2, block_tokens=block_tokens, interpret=True)
    want, pool_ref = latent_decode_attention(
        q, row, pool, slots, pt, lengths, 1, 32, 0.2, use_kernel=False)
    got, pool_k = mla_decode_kernel(
        q, row, pool, slots, pt, lengths, 1, jnp.asarray(groups), **kw)
    alone, _ = mla_decode_kernel(q, row, pool, slots, pt, lengths, 1, **kw)
    live = lengths > 1
    for other in (want, alone):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(other, np.float32)[live], atol=atol)
    assert not np.asarray(got)[lengths == 0].any()
    np.testing.assert_array_equal(  # but for the empty row's page, the last
        np.asarray(pool_k)[..., :-1, :, :], np.asarray(pool_ref)[..., :-1, :, :])


@pytest.mark.parametrize("contiguous", [True, False])
@pytest.mark.parametrize("n_members", [1, 2, 6, 7, 13, 17])
def test_decode_kernel_reads_a_shared_prefix_once(n_members, contiguous):
    """Rows that hold the same pages first: one pass over those pages for
    all of their queries (6 and 7 members: two tiles of query rows; 13 and
    17: two and three passes), then each row over its own. A representative
    table of consecutive pages takes the one-descriptor copy, a fragmented
    one a copy a page."""
    x, pt, lengths, live = _shared_prefix_case(n_members, 6, contiguous)
    groups, stats = decode_groups(pt, lengths, live, 16, block_tokens=32)
    assert groups.shape == ((n_members + 3) // 2, 9)  # of the batch alone
    if n_members == 1:
        assert stats == {"groups": 0, "members": 0, "passes": 0,
                         "shared_tokens": 0}
        assert (groups[:, 1:] == -1).all()
    else:
        assert stats == {
            "groups": 1, "members": n_members, "passes": -(-n_members // 8),
            "shared_tokens": (n_members - 1) * 96}
        listed = groups[:, 1:][groups[:, 1:] >= 0]
        assert sorted(listed) == list(range(n_members))  # not the empty row
        assert set(groups[:stats["passes"], 0]) == {6}
    _check_grouped(x, pt, lengths, groups)


def test_shared_length_is_cut_to_whole_blocks():
    """Five pages in common under blocks of two: two blocks are shared,
    the fifth page is each row's own business — whether the host cuts it
    (``decode_groups``) or the kernel is handed the pages uncut."""
    x, pt, lengths, live = _shared_prefix_case(3, 5, True)
    groups, stats = decode_groups(pt, lengths, live, 16, block_tokens=32)
    assert stats["shared_tokens"] == 2 * 64 and groups[0, 0] == 4
    _check_grouped(x, pt, lengths, groups)
    groups[0, 0] = 5
    _check_grouped(x, pt, lengths, groups)


def test_a_member_that_ends_inside_the_shared_pages_caps_the_group():
    """A member's context ends inside what the others share: the group
    shares only the blocks that are whole below it. (Its token lies in a
    shared page; it writes the row that is there already.)"""
    (q, row, pool, slots), pt, lengths, live = _shared_prefix_case(4, 6, False)
    lengths[2] = 3 * 16 + 5  # one whole block of two pages, and a half
    at = lengths[2] - 1
    slots[2] = pt[2, at // 16] * 16 + at % 16
    row = row.at[2].set(pool[0, 1, 0, pt[2, at // 16], at % 16])
    groups, stats = decode_groups(pt, lengths, live, 16, block_tokens=32)
    assert stats["shared_tokens"] == 3 * 32 and groups[0, 0] == 2
    _check_grouped((q, row, pool, slots), pt, lengths, groups)


def test_an_empty_row_listed_in_a_group_reads_nothing():
    """A pass that lists a row of length 0 folds blocks into a query
    nobody reads: the row's own program still does nothing."""
    x, pt, lengths, live = _shared_prefix_case(3, 4, True)
    groups, _ = decode_groups(pt, lengths, live, 16, block_tokens=32)
    assert groups[0, 4] == -1
    groups[0, 4] = 4  # the empty row: its table starts alike, a tile of its own
    _check_grouped(x, pt, lengths, groups)


def test_two_groups_and_the_pool_dtype():
    """Two groups in one launch, bf16 operands as served: within
    ``kernel_check``'s tolerance of the reference."""
    x, pt, lengths, live = _shared_prefix_case(
        5, 4, False, dtype=jnp.bfloat16, seed=3)
    spare = x[2].shape[3] - 2  # the idle row's pages were never handed out
    pt[3:5, :4] = [spare, spare - 2, spare - 1, spare - 3]  # another prefix
    groups, stats = decode_groups(pt, lengths, live, 16, block_tokens=32)
    assert (stats["groups"], stats["members"], stats["passes"]) == (2, 5, 2)
    q, row, pool, slots = x
    want, _ = latent_decode_attention(
        q, row, pool, slots, pt, lengths, 1, 32, 0.2, use_kernel=False)
    got, _ = mla_decode_kernel(
        q, row, pool, slots, pt, lengths, 1, jnp.asarray(groups), v_width=32,
        scale=0.2, block_tokens=32, interpret=True)
    assert _err(got, want, lengths > 1) <= TOLERANCE


@pytest.mark.parametrize("block_tokens", [16, 64])
def test_chunk_kernel_in_interpret_mode(block_tokens):
    """``radixmesh_mla_chunk``: a latent prefix in pages, then the chunk's
    own rows as a dense causal block; a row with no prefix and padding."""
    rng, pool, pt = _pool_case(1)
    C = 16
    prior = np.array([37, 0, 16, 64], np.int32)
    kvlen = prior + np.array([16, 5, 9, 16], np.int32)
    q = jnp.asarray(rng.normal(size=(4, C, 4, 128)), jnp.float32)
    cur = jnp.asarray(rng.normal(size=(4, C, 128)), jnp.float32)
    pos = prior[:, None] + np.arange(C)[None]
    pt = np.pad(pt, ((0, 0), (0, 24)))
    want = latent_chunk_attention(
        q, cur, pool, pt, pos, prior, kvlen, 1, 32, 0.2, use_kernel=False)
    got = mla_chunk_kernel(
        q, cur, pool, pt, prior, kvlen, 1, v_width=32, scale=0.2,
        block_tokens=block_tokens, interpret=True)
    valid = pos < kvlen[:, None]
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(want)[valid], atol=1e-5)


def test_model_steps_with_the_kernels_in_interpret_mode(model):
    """``decode_step`` with ``radixmesh_mla_decode`` interpreted equals the
    reference path's logits (the dispatch inside the entry point)."""
    from unittest import mock

    import radixmesh_tpu.models.llama as llama

    cfg, params = model
    toks = prompt_of(20, seed=5)
    empty = jnp.zeros((cfg.n_layers, 1, 0, 1, 128))
    _, rows, _, _ = prefill_forward(
        params, cfg, jnp.asarray([toks]), jnp.arange(20)[None], empty, empty,
        jnp.zeros((1,), jnp.int32))
    pool = jnp.zeros(cfg.kv_pool_shape(64)).at[0, :, :, 16:36].set(
        jnp.asarray(rows[:, 0]).transpose(0, 2, 1, 3))
    args = (params, cfg, jnp.asarray([7]), pool, jnp.asarray([36]),
            jnp.asarray([[1, 2, 3, 0]]), jnp.asarray([21]))
    want = decode_step.__wrapped__(*args, page_size=16)[0]

    def forced(*a, **kw):
        return latent_decode_attention(*a, **kw, use_kernel=True, interpret=True)

    with mock.patch.object(llama, "latent_decode_attention", forced):
        got = decode_step.__wrapped__(*args, page_size=16)[0]
    assert rel(np.asarray(got), np.asarray(want)) < TOL


def test_rows_on_one_published_prefix_decode_through_one_shared_pass(model):
    """Three requests on one published prefix of two kernel blocks and one
    that shares nothing decode together, through the interpreted kernel:
    the token ids each gives alone; the counters say what was attended,
    what the launch had to read, and what it was spared; the groups follow
    the rows — a member finishes, a newcomer makes the tree split the
    prefix's node under the live rows and shares one block with them."""
    import functools
    from unittest import mock

    import radixmesh_tpu.engine.engine as engine_mod
    import radixmesh_tpu.models.llama as llama

    cfg, _ = model
    BLOCK = 2 * PAGE
    shared = prompt_of(2 * BLOCK, seed=1)
    asked = [(shared + prompt_of(5 + i, seed=10 + i), n)
             for i, n in enumerate((9, 5, 9))]
    asked.append((prompt_of(21, seed=20), 3))  # shares nothing, leaves first
    # splits the node; it joins the step after the one that frees the row
    # (the row's request ends by its length, behind the next launch), so
    # five tokens end it one step before the two long members
    late = (shared[:BLOCK] + prompt_of(9, seed=30), 5)

    def alone(prompt, n):
        eng = make_engine(model)
        req = eng.add_request(prompt, SamplingParams(max_new_tokens=n))
        while eng.has_work():
            eng.step()
        return req.generated

    want = [alone(p, n) for p, n in asked + [late]]

    def forced(q, row, pool, slots, pt, lengths, layer, v_width, scale, groups):
        return mla_decode_kernel(
            q, row, pool, slots, pt, lengths, layer, groups, v_width=v_width,
            scale=scale, block_tokens=BLOCK, interpret=True)

    step = jax.jit(decode_step.__wrapped__,  # traced here, with the kernel
                   static_argnames=("cfg", "page_size", "mesh"))
    eng = make_engine(model, max_batch=4)
    with mock.patch.object(llama, "latent_decode_attention", forced), \
            mock.patch.object(engine_mod, "decode_step", step), \
            mock.patch.object(engine_mod, "decode_groups", functools.partial(
                decode_groups, block_tokens=BLOCK)):
        eng.generate([shared + prompt_of(3, seed=2)],
                     SamplingParams(max_new_tokens=2))  # publishes the prefix
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in asked]
        seen = []
        while eng.has_work():
            if late is not None and reqs[3].state is RequestState.FINISHED:
                reqs.append(eng.add_request(
                    late[0], SamplingParams(max_new_tokens=late[1])))
                late = None
            before = dict(eng.attn_window)
            eng.step()
            aw = eng.attn_window
            moved = {k: aw[k] - before[k] for k in (
                "latent_tokens_attended", "latent_tokens_read",
                "latent_tokens_shared")}
            if not moved["latent_tokens_attended"]:
                continue  # no decode launch in this step
            g = aw["decode_groups"]
            seen.append((g["groups"], g["members"], g["shared_tokens"]))
            assert moved["latent_tokens_shared"] == g["shared_tokens"] * cfg.n_layers
            assert moved["latent_tokens_read"] == (
                moved["latent_tokens_attended"] - moved["latent_tokens_shared"])
            assert g["passes"] == g["groups"] <= 1
    assert [r.generated for r in reqs] == want
    assert all(r.prefix_len == 2 * BLOCK for r in reqs[:3])
    assert reqs[4].prefix_len == BLOCK
    kinds = [k for k, _ in itertools.groupby(seen)]
    assert kinds == [
        (1, 3, 2 * 2 * BLOCK),  # three members on two blocks: two spared them
        (1, 4, 3 * BLOCK),  # the newcomer shares ONE block: one level, so all do
        (1, 3, 2 * BLOCK),  # a member finishes
        (1, 2, 2 * BLOCK),  # the newcomer finishes: two blocks again, for two
    ]


def test_a_latent_row_alone_is_refused_int8_weights():
    """A latent row without experts or a window is refused as well."""
    from radixmesh_tpu.models.llama import unsupported_reason

    dense = get_config("llama3-tiny").replace(
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=24)
    assert unsupported_reason(dense) is None
    with pytest.raises(UnsupportedForModel, match="does not support"):
        init_params(dense, jax.random.PRNGKey(0), weight_quant="int8")


LOWERS_TO = {
    # preset, overrides -> (pool shape of 4096 slots, kernels in, kernels out)
    "qwen2": ("qwen2-7b", dict(n_layers=2, vocab_size=4096),
              (2, 2, 4, 4096, 128), {"radixmesh_decode_fused_mh"},
              {"radixmesh_mla_decode", "radixmesh_moe_gmm_decode"}),
    "mellum2": ("mellum2-12b-a2.5b", dict(
        n_layers=4, vocab_size=4096,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)),
        (2, 4, 4, 4096, 128),
        {"radixmesh_decode_fused_mh", "radixmesh_moe_gmm_decode"},
        {"radixmesh_mla_decode"}),
    "glm": ("glm-4.7-flash", dict(n_layers=2, vocab_size=4096),
            (1, 2, 1, 4096, 640),
            {"radixmesh_mla_decode", "radixmesh_moe_gmm_decode"},
            {"radixmesh_decode_fused_mh", "radixmesh_decode_fused"}),
}


@pytest.mark.parametrize("which", sorted(LOWERS_TO))
def test_decode_lowers_to_the_pool_and_kernels_of_its_config(v5e, which):
    """At published widths, compiled for a v5e with the chip's own
    compilers: a Qwen2 and a Mellum2 step keep the K/V-per-head pool and
    the kernels they had, the latent model takes its own."""
    preset, over, pool_shape, inside, outside = LOWERS_TO[which]
    cfg = get_config(preset, **over)
    assert cfg.kv_pool_shape(4096) == pool_shape
    _, params, sd = abstract_params(cfg, v5e)
    compiled = decode_step.lower(
        params, cfg, sd((8,), jnp.int32), sd(pool_shape, cfg.dtype),
        sd((8,), jnp.int32), sd((8, 64), jnp.int32), sd((8,), jnp.int32), 16,
    ).compile()
    names = kernels_in(compiled)
    assert inside <= names and not (outside & names), names
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= np.prod(pool_shape) * 2  # pool aliased through

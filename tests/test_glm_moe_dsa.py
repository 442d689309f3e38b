"""GLM-5.2 (``glm_moe_dsa``) on the served path against the plain float32
reference (``benchmarks/reference/glm_moe_dsa.py``: attention NOT absorbed,
no cache, the selection as a mask over dense scores): the tiny preset (one
dense layer then five sparse ones, indexers on layers 0 and 4 — full,
shared, shared, shared, full, shared — 4 index heads of 16, the 24 best
tokens attended, 16 experts top-4 beside a shared one), float32 program,
CPU.

Dense prefill, chunked paged prefill, prefill + decode steps through the
paged latent cache and its index-key plane, and a prefix served from the
radix tree each have to equal the reference's FULL forward pass over prompt
+ generated tokens — logits, not tokens — with contexts on both sides of
``index_topk``. The expert share: the parts of all shares add up to the
uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm_moe_dsa as ref
from radixmesh_tpu.engine import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    _ffn,
    decode_step,
    new_moe_stats,
    prefill_forward,
    read_moe_stats,
    unsupported_reason,
)
from radixmesh_tpu.ops import dsa
from radixmesh_tpu.ops.moe import SHARE_STATS, STATS, moe_ffn
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec, logits_from
from tests.model_conformance import distance as rel

pytestmark = pytest.mark.quick


def _the_selection_engages_past_index_topk(eng, want, prompt, **_):
    """What the last position attends in each full layer: all it has up to
    ``index_topk`` (24), the 24 best past it."""
    seen = min(len(prompt) + SPEC.n_new - 1, eng.cfg.index_topk)
    assert want["selected"][-1].tolist() == [seen, seen]


SPEC = ModelSpec(
    preset="glm-5.2-tiny", seed=33, reference=logits_from(ref.forward),
    engine=dict(page_size=4, num_slots=1024, max_batch=4, max_seq_len=256,
                prefill_chunk=16),
    # 7 and 16: the whole context is attended (<= index_topk 24); 40 and 75:
    # the selection engages in the prefill and in every decode step
    lengths=(7, 16, 40, 75),
    says=("learned sparse attention",),
    hooks={"prefill": _the_selection_engages_past_index_topk},
)
make_engine, prompt_of, run, check_against_reference = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.run, SPEC.check)
PAGE, N_NEW, TOL = SPEC.engine["page_size"], SPEC.n_new, SPEC.tol


@pytest.mark.parametrize("n_tail", [5, 21])
def test_index_keys_follow_a_radix_hit(model, n_tail):
    """A second request over a cached prefix: its rows AND its index keys
    come from the tree's slots (one allocator), so it selects, over the
    prefix, what a request that computes everything selects."""
    eng = make_engine(model, prefill_chunk=16)
    shared = prompt_of(49, seed=1)
    eng.generate([shared + prompt_of(3, seed=2)], SamplingParams(max_new_tokens=2))
    prompt = shared + prompt_of(n_tail, seed=3)
    req = run(eng, prompt)
    assert eng._last_prefill_path == "chunk" and req.prefix_len == 48
    check_against_reference(model, req, prompt)
    cold = run(make_engine(model, prefill_chunk=16), prompt)
    assert cold.prefix_len == 0
    # the chunk that held the last prompt position, then the 8 decode steps
    # (the cold request prefilled densely: its selection there was a mask)
    assert [p for p, _ in req.index_rows] == list(range(
        len(prompt) - 1, len(prompt) + N_NEW - 1))
    theirs = dict(cold.index_rows)
    assert sorted(theirs) == [p for p, _ in req.index_rows[1:]]
    for position, mine in req.index_rows[1:]:
        assert mine.shape == (2, 24)
        np.testing.assert_array_equal(
            np.sort(mine, -1), np.sort(theirs[position], -1))


def test_a_shared_layer_attends_its_full_layers_set(model):
    """Layers 1-3 use layer 0's selection and layer 5 layer 4's: replacing
    the SECOND indexer's weights changes no hidden state before layer 4,
    and the reference, which carries one mask from a full layer to the
    shared layers after it, agrees with the program on every logit."""
    cfg, params = model
    toks, n = prompt_of(60, seed=4), 60
    empty = jnp.zeros((cfg.n_layers, 1, 0, 1, 128))
    args = (jnp.asarray([toks]), jnp.arange(n)[None], empty, empty,
            jnp.zeros((1,), jnp.int32))
    base = prefill_forward(params, cfg, *args)
    other = {**params, "indexer": {
        k: v.at[1].set(jnp.roll(v[1], 1, axis=-1)) for k, v in params["indexer"].items()}}
    moved = prefill_forward(other, cfg, *args)
    rows_a, rows_b = np.asarray(base[1]), np.asarray(moved[1])  # [L, 1, n, 1, W]
    np.testing.assert_array_equal(rows_a[:5], rows_b[:5])  # layers 0-4's inputs
    assert np.abs(rows_a[5] - rows_b[5]).max() > 1e-4  # layer 4 selected anew
    assert rel(np.asarray(moved[0][0]), ref.forward(other, cfg, toks)["logits"]) < TOL
    # the index keys: the full layers' only, [L_full, B, S, Di]
    assert base[3].shape == (2, 1, n, cfg.index_head_dim)


def test_a_short_context_selects_every_row(model):
    """Up to ``index_topk`` tokens the model IS the dense latent model:
    the same logits with the indexers' weights replaced."""
    cfg, params = model
    eng = make_engine(model)
    prompt = prompt_of(11, seed=5)
    req = run(eng, prompt)  # 11 + 8 < 24
    # A page table wider than index_topk tokens takes the sparse program
    # (a static choice, by the bucket): it then selects every row it has.
    for position, sel in req.index_rows:
        assert ((sel >= 0).sum(-1) == position + 1).all()
    scrambled = {**params, "indexer": jax.tree.map(
        lambda w: jnp.flip(w, -1) * 3.0, params["indexer"])}
    other = run(make_engine((cfg, scrambled)), prompt)
    # (the same rows in another order: the selection sorts by score)
    assert rel(np.stack(other.logit_rows), np.stack(req.logit_rows)) < TOL
    assert other.generated == req.generated
    sp = eng.attn_window["sparse"]
    assert sp["decode"]["rows_selected"] == sp["decode"]["rows_held"] > 0
    assert sp["prefill"]["index_keys_scored"] == 0  # 16 tokens: the dense path


def test_counters_of_a_long_context(model):
    cfg, _ = model
    eng = make_engine(model, prefill_chunk=16, long_prefill_threshold=0)
    prompt = prompt_of(40, seed=6)
    run(eng, prompt)
    sp = eng.attn_window["sparse"]
    ctx = np.arange(41, 41 + N_NEW - 1)  # the decode steps' contexts
    assert sp["decode"]["rows_held"] == ctx.sum() * cfg.n_layers
    assert sp["decode"]["rows_selected"] == 24 * len(ctx) * cfg.n_layers
    assert sp["decode"]["index_keys_scored"] == ctx.sum() * 2  # two full layers
    pre = np.arange(1, 41)
    assert sp["prefill"]["rows_held"] == pre.sum() * cfg.n_layers
    assert sp["prefill"]["rows_selected"] == np.minimum(pre, 24).sum() * cfg.n_layers


def test_the_pool_holds_two_arrays_under_one_allocator(model):
    cfg, _ = model
    assert cfg.kv_index_shape(64) == (2, 64, 16) and cfg.full_layers == (0, 4)
    assert get_config("glm-4.7-flash-tiny").kv_index_shape(64) is None
    eng = make_engine(model, num_slots=256)
    pool = eng.pool
    assert pool.index.shape == (2, 256, 16) and pool.kv.shape == cfg.kv_pool_shape(256)
    assert pool.index_plane_bytes == 2 * 256 * 16 * 4
    free0 = pool.free_slots
    prompt = prompt_of(30, seed=7)
    eng.generate([prompt], SamplingParams(max_new_tokens=3))
    slots = eng.tree.match_prefix(np.asarray(prompt, np.int32)).indices()
    assert len(slots) == 28  # whole pages
    keys = np.asarray(pool.index)[:, slots]
    rows = np.asarray(pool.kv)[0, :, 0][:, slots]
    assert np.abs(keys).min(-1).max() > 0 and np.abs(rows[..., :40]).max() > 0
    # evicting the prefix frees the slots: both arrays' entries go together
    eng.tree.evict(10_000)
    assert pool.free_slots == free0
    other = prompt_of(30, seed=8)
    eng.generate([other], SamplingParams(max_new_tokens=3))
    again = eng.tree.match_prefix(np.asarray(other, np.int32)).indices()
    assert set(again) & set(slots)  # the same slots, reused
    reused = sorted(set(again) & set(slots))
    assert not np.array_equal(np.asarray(pool.index)[:, reused],
                              keys[:, [list(slots).index(s) for s in reused]])
    with pytest.raises(ValueError, match="index plane"):
        type(pool)(256, 6, 1, 128, page_size=4, planes=1, index_shape=(2, 128, 16))


def test_refusals_name_the_expert_share_and_the_indexer(model):
    cfg, _ = model
    share = cfg.replace(ep_size=4, ep_rank=1)
    msg = unsupported_reason(share, kv_quant="int8")
    assert "an expert share" in msg and "learned sparse attention" in msg
    assert unsupported_reason(share) is None  # bf16, one chip: served
    with pytest.raises(ValueError, match="first of them full"):
        cfg.replace(indexer_types=("shared",) + cfg.indexer_types[1:])
    with pytest.raises(ValueError, match="expert share"):
        cfg.replace(ep_size=3)
    empty = jnp.zeros((cfg.n_layers, 1, 8, 1, 128))
    with pytest.raises(ValueError, match="no cached prefix"):
        prefill_forward(model[1], cfg, jnp.zeros((1, 4), jnp.int32),
                        jnp.arange(8, 12)[None], empty, empty,
                        jnp.full((1,), 8, jnp.int32))


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------


def _sparse_layer(cfg, params, at=1):
    return {k: v[at] for k, v in params["layers"].items()}


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("size", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(model, size, use_kernel):
    """The routed parts of all ``size`` shares, and the shared expert
    counted once, are the uncut layer's output; each share counts its own
    experts' assignments and what went elsewhere."""
    cfg, params = model
    lp = _sparse_layer(cfg, params)
    x = jax.random.normal(jax.random.PRNGKey(3), (37, cfg.hidden), jnp.float32)
    common = dict(k=cfg.n_experts_per_tok, norm_topk=True, site="prefill",
                  scoring="sigmoid", bias=lp["router_bias"], scale=2.5,
                  use_kernel=use_kernel, interpret=True)
    whole, ids, stats = moe_ffn(
        x, lp["w_router"], lp["w_gate"], lp["w_up"], lp["w_down"], **common)
    assert stats.shape == (len(STATS),)  # no share: today's four counters
    held = cfg.n_experts // size
    total, here, away = 0.0, 0, 0
    for rank in range(size):
        cut = slice(rank * held, (rank + 1) * held)
        y, ids_r, st = moe_ffn(
            x, lp["w_router"], lp["w_gate"][cut], lp["w_up"][cut],
            lp["w_down"][cut], first_held=rank * held, **common)
        np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids))
        st = dict(zip(SHARE_STATS, map(int, st))) | (
            {"assignments_elsewhere": 0} if size == 1 else {})
        assert st["assignments"] + st["assignments_elsewhere"] == 37 * 4
        assert st["experts_hit"] <= held
        total, here, away = total + y, here + st["assignments"], away + st[
            "assignments_elsewhere"]
    assert here == 37 * 4 and away == 37 * 4 * (size - 1)
    assert rel(np.asarray(total), np.asarray(whole)) < TOL
    with jax.default_matmul_precision("highest"):
        e, w, _ = ref.route(x, lp, None, top_k=4, norm_topk=True, scaling=2.5)
        want = ref._experts(x, lp, e, w, 0)
    assert rel(np.asarray(whole), np.asarray(want)) < TOL


def test_a_share_serves_and_counts(model):
    """``ep_size`` 4, rank 1: the engine holds 4 of 16 experts a layer,
    serves through the same entry points and equals the reference given
    the same share; ``moe`` counts held experts and the rest."""
    cfg, params = model
    share = cfg.replace(ep_size=4, ep_rank=1)
    cut = {**params, "layers": {
        k: (v[:, 4:8] if k in ("w_gate", "w_up", "w_down") else v)
        for k, v in params["layers"].items()}}
    assert init_params(share, jax.random.PRNGKey(0))["layers"]["w_gate"].shape == (
        5, 4, cfg.hidden, cfg.moe_intermediate)
    eng = make_engine((share, cut), prefill_chunk=16)
    prompt = prompt_of(33, seed=9)
    req = run(eng, prompt)
    check_against_reference((share, cut), req, prompt)
    moe = read_moe_stats(eng._moe_stats)
    sparse, steps = cfg.n_layers - 1, eng.stats.decode_steps
    d = moe["decode"]
    assert d["assignments"] + d["assignments_elsewhere"] == 4 * sparse * steps
    assert 0 < d["assignments"] < d["assignments_elsewhere"]
    assert eng._moe_stats.shape == (2, len(SHARE_STATS), 2)
    assert new_moe_stats().shape == (2, len(STATS), 2)
    # the shared expert is computed by every share: once here
    lp = {k: v[0] for k, v in cut["layers"].items()
          if k not in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 5, cfg.hidden), jnp.float32)
    y, _ = _ffn(cut, lp, jnp.int32(1), x, share, "prefill")
    no_shared = {**lp, "ws_down": 0 * lp["ws_down"]}
    y0, _ = _ffn(cut, no_shared, jnp.int32(1), x, share, "prefill")
    assert rel(np.asarray(y - y0), np.asarray(
        ref._swiglu(x[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"]))) < TOL


# ---------------------------------------------------------------------------
# the kernels, interpreted, against their references
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("groups, queries", [(3, 1), (1, 8), (2, 32)])
def test_index_kernel_in_interpret_mode(groups, queries):
    rng = np.random.default_rng(groups)
    pages = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(groups, queries, 4, 16)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(groups, queries, 4)), jnp.float32)
    pt = jnp.asarray(rng.permutation(64)[:groups * 16].reshape(groups, 16), jnp.int32)
    prior = jnp.asarray(rng.integers(0, 60, size=groups), jnp.int32)
    kw = dict(site="chunk")
    got = dsa.index_scores(qi, wi, pages, pt, prior, 1, use_kernel=True,
                           interpret=True, **kw)
    want = dsa.index_scores(qi, wi, pages, pt, prior, 1, use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    seen = np.arange(64)[None, None] < np.asarray(prior)[:, None, None]
    assert (np.asarray(got)[~np.broadcast_to(seen, got.shape)] == dsa.NEG).all()


@pytest.mark.parametrize("groups, queries, own", [(3, 1, 1), (1, 16, 16), (2, 8, 8)])
def test_sparse_attention_kernel_in_interpret_mode(groups, queries, own):
    rng = np.random.default_rng(own)
    pool = jnp.asarray(rng.normal(size=(1, 3, 1, 64, 4, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(groups, queries, 4, 128)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(groups, own, 128)), jnp.float32)

    def bias(*shape):
        return jnp.where(jnp.asarray(rng.random(shape) < 0.7), 0.0, dsa.NEG
                         ).astype(jnp.float32)

    sel = dsa.Selection(
        jnp.asarray(rng.integers(0, 256, size=(groups, queries, 24)), jnp.int32),
        bias(groups, queries, 24), bias(groups, queries, own),
        jnp.zeros((groups, queries, 24), jnp.int32))
    got = dsa.sparse_attention(q, rows, sel, pool, 2, 32, 0.3, site="chunk",
                               use_kernel=True, interpret=True)
    want = dsa.sparse_attention(q, rows, sel, pool, 2, 32, 0.3, site="chunk",
                                use_kernel=False)
    assert got.shape == (groups, queries, 4, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_sparse_decode_kernel_writes_the_new_row():
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.normal(size=(1, 3, 1, 64, 4, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
    sel = dsa.Selection(
        jnp.asarray(rng.integers(0, 256, size=(3, 1, 24)), jnp.int32),
        jnp.zeros((3, 1, 24)), jnp.zeros((3, 1, 1)), jnp.zeros((3, 1, 24), jnp.int32))
    slots = jnp.asarray([7, 100, 201])
    got = dsa.decode_attend(q, row, sel, pool, slots, 1, 32, 0.3,
                            use_kernel=True, interpret=True)
    want = dsa.decode_attend(q, row, sel, pool, slots, 1, 32, 0.3, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(
        np.asarray(got[1])[0, 1, 0, np.asarray(slots) // 4, np.asarray(slots) % 4],
        np.asarray(row))


@pytest.mark.parametrize("rows, width, k, ties", [
    (5, 300, 24, False), (7, 1000, 64, True), (3, 8200, 2048, False),
    (4, 129, 24, True), (2, 50, 24, False)])
def test_both_selections_pick_the_same_set(rows, width, k, ties):
    """The selection without a sort (a k-th value by bisection, ties in
    index order, a compaction by 128-score segment) against ``lax.top_k``:
    the same tags, whatever the ties, with fewer visible scores than k,
    and at a width that is no multiple of a segment."""
    rng = np.random.default_rng(width)
    scores = rng.normal(size=(rows, width)).astype(np.float32)
    if ties:
        scores = np.round(scores * 2) / 2
    visible = rng.integers(0, width + 1, size=rows)
    visible[0] = min(10, width)
    for r in range(rows):
        scores[r, visible[r]:] = dsa.NEG
    tags = jnp.asarray(np.arange(width, dtype=np.int32)[None].repeat(rows, 0) * 3 + 1)
    k = min(k, width)
    a, seen_a = dsa._pick_by_sort(jnp.asarray(scores), tags, k)
    b, seen_b = dsa._pick_by_threshold(jnp.asarray(scores), tags, k)
    for r in range(rows):
        assert int(seen_a[r].sum()) == int(seen_b[r].sum()) == min(k, visible[r])
        assert set(np.asarray(a)[r][np.asarray(seen_a)[r]].tolist()) == set(
            np.asarray(b)[r][np.asarray(seen_b)[r]].tolist())


@pytest.mark.parametrize("path", ["dense", "chunk"])
def test_the_model_with_the_selection_that_does_not_sort(model, path, monkeypatch):
    """The tiny model's rows are short and take ``lax.top_k``; forced onto
    the long rows' selection it gives the same logits."""
    monkeypatch.setattr(dsa, "_THRESHOLD_FROM", 0)
    jax.clear_caches()
    try:
        eng = make_engine(
            model, prefill_chunk=16,
            long_prefill_threshold=1024 if path == "dense" else 0)
        prompt = prompt_of(75, seed=11)
        check_against_reference(model, run(eng, prompt), prompt)
    finally:
        jax.clear_caches()


def test_selection_is_exact_and_ties_go_to_the_lower_position():
    scores = jnp.asarray([[[5.0, 1.0, 1.0, 1.0, dsa.NEG, dsa.NEG, dsa.NEG, dsa.NEG]]])
    own = jnp.asarray([[[1.0, 7.0]]])
    pt = jnp.asarray([[3, 9]])
    sel = dsa.select(scores, own, pt, jnp.asarray([4]), 4, 3)
    # 7 (own 1), 5 (position 0), then the first of the four 1.0s: position 1
    assert sorted(np.asarray(sel.positions)[0, 0].tolist()) == [0, 1, 5]
    assert (np.asarray(sel.own_bias)[0, 0] == 0).tolist() == [False, True]
    picked = np.asarray(sel.slots)[0, 0][np.asarray(sel.bias)[0, 0] == 0]
    assert sorted(picked.tolist()) == [12, 13]  # page 3, offsets 0 and 1
    few = dsa.select(scores, own, pt, jnp.asarray([4]), 4, 24)  # fewer than k
    assert (np.asarray(few.positions) >= 0).sum() == 6
    mask = dsa.dense_selection_mask(scores[0], 2)
    assert np.asarray(mask)[0].tolist() == [True, True] + [False] * 6


# ---------------------------------------------------------------------------
# what the entry points lower to, at published widths
# ---------------------------------------------------------------------------


SPARSE = {"radixmesh_dsa_index_decode", "radixmesh_mla_sparse_decode",
          "radixmesh_dsa_select", "radixmesh_dsa_gather"}


@pytest.mark.parametrize("pages, inside, outside", [
    (64, {"radixmesh_mla_decode"}, SPARSE),  # 1 024 tokens: every row attended
    (256, SPARSE, {"radixmesh_mla_decode"}),  # 4 096: selected rows only
])
def test_decode_lowers_to_dense_or_sparse_by_the_context(v5e, pages, inside, outside):
    """At published widths, compiled for a v5e with the chip's own
    compilers: a page table within ``index_topk`` tokens takes the dense
    latent kernel unchanged (and writes the index keys), a wider one the
    index, selection, gather and sparse-attention pieces; both pools are
    aliased through; the expert layer holds its 16 of 256."""
    cfg = get_config("glm-5.2").replace(
        n_layers=3, first_k_dense=1, indexer_types=("full", "shared", "full"),
        vocab_size=4096, ep_size=16)
    assert cfg.kv_pool_shape(4096) == (1, 3, 1, 4096, 640)
    assert cfg.kv_index_shape(4096) == (2, 4096, 128)
    shapes, params, sd = abstract_params(cfg, v5e)
    assert shapes["layers"]["w_gate"].shape == (2, 16, 6144, 2048)
    assert shapes["layers"]["w_router"].shape == (2, 6144, 256)
    assert shapes["indexer"]["wq"].shape == (2, 2048, 32 * 128)
    compiled = decode_step.lower(
        params, cfg, sd((8,), jnp.int32), sd(cfg.kv_pool_shape(4096), cfg.dtype),
        sd((8,), jnp.int32), sd((8, pages), jnp.int32), sd((8,), jnp.int32), 16,
        index_pool=sd(cfg.kv_index_shape(4096), cfg.dtype),
    ).compile()
    names = kernels_in(compiled)
    assert inside <= names and not (outside & names), names
    assert "radixmesh_moe_gmm_decode" in names
    mem = compiled.memory_analysis()
    both = (np.prod(cfg.kv_pool_shape(4096)) + np.prod(cfg.kv_index_shape(4096))) * 2
    assert mem.alias_size_in_bytes >= both


def test_chunk_lowers_to_the_sparse_pieces(v5e):
    """The prefill chunk over a prefix in pages, at published widths:
    Mosaic accepts the chunk variants of both kernels (32 queries x 32
    index heads a program; one query's 2 048 gathered rows a program) and
    the index keys of the chunk land in the aliased index pool."""
    from radixmesh_tpu.models.llama import prefill_chunk_paged

    cfg = get_config("glm-5.2").replace(
        n_layers=2, first_k_dense=1, indexer_types=("full", "shared"),
        vocab_size=4096, ep_size=16)
    _, params, sd = abstract_params(cfg, v5e)
    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((2, 64), jnp.int32), sd((2, 64), jnp.int32),
        sd(cfg.kv_pool_shape(8192), cfg.dtype), sd((2, 64), jnp.int32),
        sd((2, 256), jnp.int32), sd((2,), jnp.int32), page_size=16,
        logits_at=sd((2,), jnp.int32),
        index_pool=sd(cfg.kv_index_shape(8192), cfg.dtype),
    ).compile()
    names = kernels_in(compiled)
    assert {"radixmesh_dsa_index_chunk", "radixmesh_mla_sparse_chunk",
            "radixmesh_dsa_select", "radixmesh_dsa_gather",
            "radixmesh_moe_gmm_prefill"} <= names, names
    assert "radixmesh_mla_chunk" not in names
    # Rows and index keys enter their pools by whole pages: no copy of
    # either (a scatter on the slot axis relayouts both; PR 34).
    held = {",".join(map(str, shape)) for shape in (
        cfg.kv_pool_shape(8192), cfg.kv_index_shape(8192))}
    assert not held & copied_shapes(compiled), held & copied_shapes(compiled)



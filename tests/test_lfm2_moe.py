"""LFM2-24B-A2B (``lfm2_moe``) on the served path against the plain float32
reference (``benchmarks/reference/lfm2_moe.py``: the whole sequence
convolved at once, no cache, no state): the tiny preset (three periods of
conv, conv, attention, conv — two dense-FFN layers, a scanned run of two
periods, a left-over of two layers — 8 experts top-2, 4 / 2 heads of 64
paired into one 128-wide pool row, 3 taps), float32 program, CPU, page 16.

Dense prefill, chunked paged prefill and prefill + decode through the pool
and the state slots each have to equal the reference's FULL forward pass
over prompt + generated tokens — logits, not tokens — and so does every way
a request reaches a state: the same prompt again, the next turn of a
session, a branch inside a shared prefix, a split above and below a
snapshot, a state evicted under its rows, a preempted request's second
life. A zeroed state has to FAIL the same tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as ref
from radixmesh_tpu.cache.radix_tree import RadixTree
from radixmesh_tpu.engine import SamplingParams
from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    UnsupportedForModel,
    _mixed_runs,
    _own_lanes,
    _pair_heads,
    decode_step,
    prefill_chunk_paged,
    prefill_forward,
    unsupported_reason,
)
from radixmesh_tpu.ops.attention import attend_decode_ref, kernel_shape_ok
from radixmesh_tpu.ops.moe import moe_ffn
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec, logits_from
from tests.model_conformance import distance as rel

pytestmark = pytest.mark.quick


def _rows_of_the_attention_layers_alone(eng, **_):
    """Rows in the 3 attention layers only, two heads to a 128-lane row."""
    cfg = eng.cfg
    assert eng.pool.kv.shape == (2, 3, 1, 4096, 128) == cfg.kv_pool_shape(4096)
    assert eng.pool.state.shape == cfg.state_shape(
        cfg.default_state_slots(4096, 4)) == (9, 256, 2, 128)


def _slot_0_and_the_two_snapshots(eng, **_):
    assert eng.pool.state_slots_used == 3


SPEC = ModelSpec(
    preset="lfm2-tiny", seed=39, reference=logits_from(ref.forward),
    engine=dict(page_size=16, num_slots=4096, max_batch=4, max_seq_len=1024,
                prefill_chunk=64),
    # 7: inside one page; 48: a page multiple; 100: chunks of 64 with a ragged end
    lengths=(7, 48, 100),
    # padded rows: each row's state is taken at its own true end
    ragged=(5, 37, 64, 90),
    forget={"state": lambda at: dict(forget_at=at)},
    says=("gated-convolution layers with a recurrent state",),
    hooks={"prefill": _rows_of_the_attention_layers_alone,
           "before_eviction": _slot_0_and_the_two_snapshots,
           "preempted": _slot_0_and_the_two_snapshots},
)
make_engine, prompt_of, run, check_against_reference = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.run, SPEC.check)
PAGE, TOL = SPEC.engine["page_size"], SPEC.tol


# ---------------------------------------------------------------------------
# a snapshot and the tree: splits, and slots that run out
# ---------------------------------------------------------------------------


def test_slots_run_out_and_the_tree_gives_way(model):
    """More sessions than state slots: admission evicts snapshots of
    finished requests instead of failing, and a request that cannot have a
    snapshot slot goes without."""
    eng = make_engine(model, max_batch=1)
    assert eng.pool.state.shape[1] == 256
    # all but five slots are somebody else's: a row's two and three snapshots
    eng.pool.state_allocator.reserve(np.arange(6, 256, dtype=np.int32))
    for i in range(6):
        p = prompt_of(40, seed=100 + i)
        out = eng.generate([p], SamplingParams(max_new_tokens=2))
        assert len(out[0]) == 2
    assert eng.tree.state_evictions >= 3
    assert eng.pool.state_slots_used <= 256
    # a working and a boundary slot and nothing to evict: the snapshots
    # are skipped, the request is served all the same
    eng.tree.evict_states(1000)
    alloc = eng.pool.state_allocator
    free = np.flatnonzero(~alloc.is_allocated(np.arange(256))).astype(np.int32)
    alloc.reserve(free[2:])
    eng.generate([prompt_of(40, seed=200)], SamplingParams(max_new_tokens=2))
    assert eng.state_stats["snapshots_skipped"] == 1  # both cuts are 32
    assert eng.pool.state_slots_used == 254
    assert eng.tree.match_prefix(
        np.asarray(prompt_of(40, seed=200), np.int32)).states == []


# ---------------------------------------------------------------------------
# the tree's handle
# ---------------------------------------------------------------------------


def test_tree_states_attach_split_and_free():
    freed_rows, freed_states = [], []
    tree = RadixTree(page_size=4, on_free=freed_rows.append,
                     on_free_state=freed_states.append)
    key = np.arange(24, dtype=np.int32)
    tree.insert(key, np.arange(100, 124, dtype=np.int32))
    assert tree.attach_state(key, 7) and not tree.attach_state(key, 8)
    assert not tree.attach_state(np.arange(28, dtype=np.int32), 9)  # not held
    assert tree.attach_state(key[:8], 5)  # splits the node at 8
    m = tree.match_prefix(key)
    assert m.states == [(8, 5), (24, 7)] and m.length == 24
    assert m.state_at_or_before(23) == (8, 5)
    assert m.state_at_or_before(7) == (0, None)
    part = tree.match_prefix(np.concatenate([key[:16], key[:4]]))
    assert part.length == 16 and part.states == [(8, 5)]
    # the split at 16 left [16, 24) with the state, [8, 16) with none
    assert tree.match_prefix(key).states == [(8, 5), (24, 7)]
    tree.evict(8)  # the leaf [16, 24) goes, and its state with it
    assert freed_states == [7]
    tree.reset()
    assert sorted(freed_states) == [5, 7]
    plain = RadixTree(page_size=4)
    plain.insert(key, np.arange(24, dtype=np.int32))
    assert plain.match_prefix(key).states == []


def test_state_eviction_is_lru_and_skips_locked_nodes():
    freed = []
    clock = iter(range(1000))
    tree = RadixTree(page_size=1, on_free_state=freed.append,
                     time_fn=lambda: next(clock))
    keys = [np.asarray([i, 50 + i, 60 + i], np.int32) for i in range(4)]
    for i, k in enumerate(keys):
        tree.insert(k, np.arange(3, dtype=np.int32) + 10 * i)
        assert tree.attach_state(k, 10 + i)
    tree.match_prefix(keys[0])  # touched last
    locked = tree.match_prefix(keys[1]).last_node
    tree.inc_lock_ref(locked)
    assert tree.evict_states(2) == 2
    assert freed == [12, 13]  # 0 was touched, 1 is locked
    assert tree.evict_states(5) == 1 and freed == [12, 13, 10]


# ---------------------------------------------------------------------------
# two heads in one pool row
# ---------------------------------------------------------------------------


def test_paired_heads_equal_per_head_attention(model):
    """The wide-row kernels' inputs against plain per-head attention: the
    same scores (a query is zero in its partner's lanes), each head's own
    lanes of the output, the logits' factor passed in."""
    cfg = model[0]
    rng = np.random.default_rng(0)
    B, ctx, pages = 3, 37, 4
    q = jnp.asarray(rng.normal(size=(B, 1, 4, 64)), jnp.float32)
    k_all = jnp.asarray(rng.normal(size=(pages * PAGE, 2, 64)), jnp.float32)
    v_all = jnp.asarray(rng.normal(size=(pages * PAGE, 2, 64)), jnp.float32)
    pt = jnp.broadcast_to(jnp.arange(pages), (B, pages))
    lengths = jnp.asarray([ctx, 20, 1])
    per_head = attend_decode_ref(
        q[:, 0], k_all.reshape(pages, PAGE, 2, 64).transpose(2, 0, 1, 3),
        v_all.reshape(pages, PAGE, 2, 64).transpose(2, 0, 1, 3), pt, lengths)
    qw, _, _ = _pair_heads(cfg, q, q[:, :, :2], q[:, :, :2])
    _, kw, vw = _pair_heads(cfg, k_all[None, :, :0], k_all[None], v_all[None])
    assert qw.shape == (B, 1, 4, 128) and kw.shape == (1, pages * PAGE, 1, 128)
    wide = attend_decode_ref(
        qw[:, 0], kw[0].reshape(pages, PAGE, 1, 128).transpose(2, 0, 1, 3),
        vw[0].reshape(pages, PAGE, 1, 128).transpose(2, 0, 1, 3), pt, lengths,
        scale=64 ** -0.5)
    own = _own_lanes(cfg, wide[:, None])[:, 0]
    assert rel(np.asarray(own), np.asarray(per_head)) < 1e-6


def test_paired_kernels_in_interpret_mode(model):
    """The fused decode kernel and the chunk kernel on the 128-wide row
    with the passed factor, interpreted, against the jnp path."""
    from radixmesh_tpu.ops.attention import attend_chunk_hybrid
    from radixmesh_tpu.ops.paged_attention import (
        paged_chunk_attention_kernel,
        paged_decode_fused_kernel,
    )

    rng = np.random.default_rng(1)
    B, pages, P = 2, 32, 64
    kv = jnp.asarray(rng.normal(size=(2, 1, 1, P, PAGE, 128)), jnp.float32)
    pt = jnp.asarray(rng.permutation(P).reshape(B, pages), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 4, 128)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, 1, 128)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, 1, 128)), jnp.float32)
    lengths = jnp.asarray([70, 200], jnp.int32)
    slots = jnp.asarray([int(pt[b, (int(lengths[b]) - 1) // PAGE]) * PAGE
                         + (int(lengths[b]) - 1) % PAGE for b in range(B)], jnp.int32)
    scale = 64 ** -0.5
    got, pages_out = paged_decode_fused_kernel(
        q, kn, vn, kv, slots, pt, lengths, 0, interpret=True, scale=scale)
    want_pages = kv.at[0, 0, :, slots // PAGE, slots % PAGE].set(kn)
    want_pages = want_pages.at[1, 0, :, slots // PAGE, slots % PAGE].set(vn)
    want = attend_decode_ref(
        q, want_pages[0, 0], want_pages[1, 0], pt, lengths, scale=scale)
    assert rel(np.asarray(got), np.asarray(want)) < 1e-5
    np.testing.assert_array_equal(np.asarray(pages_out), np.asarray(want_pages))
    C = 16
    qc = jnp.asarray(rng.normal(size=(B, C, 4, 128)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, C, 1, 128)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, C, 1, 128)), jnp.float32)
    prior = jnp.asarray([48, 160], jnp.int32)
    pos = prior[:, None] + jnp.arange(C)
    got = paged_chunk_attention_kernel(
        qc, kc, vc, kv, pt, prior, prior + C, 0, interpret=True, scale=scale)
    want = attend_chunk_hybrid(
        qc, kc, vc, kv, pt, pos, prior, prior + C, 0, kv_block_pages=32,
        scale=scale)
    assert rel(np.asarray(got), np.asarray(want)) < 1e-5


def test_the_unpaired_layout_computes_the_same(model):
    """``pair_kv_heads`` changes where rows lie, not what is computed."""
    cfg, params = model
    prompt = prompt_of(60, seed=17)
    paired = run(make_engine(model), prompt)
    plain_cfg = cfg.replace(pair_kv_heads=False)
    assert plain_cfg.kv_pool_shape(64) == (2, 3, 2, 64, 64)
    plain = run(make_engine((plain_cfg, params)), prompt)
    assert rel(np.stack(paired.logit_rows), np.stack(plain.logit_rows)) < TOL
    assert kernel_shape_ok(cfg.kv_row[2]) and not kernel_shape_ok(cfg.head_dim)


# ---------------------------------------------------------------------------
# the expert share (a case of PR 33's test, at this layer's numbers)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size", [1, 2, 8])
def test_the_shares_add_up_to_the_uncut_layer(model, size):
    cfg, params = model
    lp = {k: v[1] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (37, cfg.hidden), jnp.float32)
    common = dict(k=cfg.n_experts_per_tok, norm_topk=True, site="prefill",
                  scoring="sigmoid", bias=lp["router_bias"], scale=1.0,
                  use_kernel=False)
    whole, ids, _ = moe_ffn(
        x, lp["w_router"], lp["w_gate"], lp["w_up"], lp["w_down"], **common)
    held = cfg.n_experts // size
    total = 0.0
    for rank in range(size):
        cut = slice(rank * held, (rank + 1) * held)
        y, ids_r, _ = moe_ffn(
            x, lp["w_router"], lp["w_gate"][cut], lp["w_up"][cut],
            lp["w_down"][cut], first_held=rank * held, **common)
        np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids))
        total = total + y
    assert rel(np.asarray(total), np.asarray(whole)) < TOL
    with jax.default_matmul_precision("highest"):
        e, w, _ = ref.route(x, lp, top_k=2, norm_topk=True, scaling=1.0)
        want = ref._experts(x, lp, e, w, 0)
    assert rel(np.asarray(whole), np.asarray(want)) < TOL


def test_a_share_serves_through_the_same_path(model):
    cfg, params = model
    share = cfg.replace(ep_size=4, ep_rank=1)
    cut = {**params, "layers": {
        k: (v[:, 2:4] if k in ("w_gate", "w_up", "w_down") else v)
        for k, v in params["layers"].items()}}
    prompt = prompt_of(40, seed=18)
    req = run(make_engine((share, cut)), prompt)
    check_against_reference((share, cut), req, prompt)


# ---------------------------------------------------------------------------
# what is refused, by name
# ---------------------------------------------------------------------------


def test_a_prefix_is_moved_or_advertised_nowhere_without_its_state(model):
    cfg, params = model
    for option, name in (
        ("mesh_cache", "prefix ring"),
        ("stream_publish_tokens", "mid-decode publishing"),
        ("disaggregated", "disaggregated prefill/decode"),
        ("hf_checkpoint", "HuggingFace checkpoint"),
    ):
        msg = unsupported_reason(cfg, **{option: True})
        assert name in msg and "recurrent state" in msg, msg
    assert unsupported_reason(cfg) is None  # bf16, one chip: served
    with pytest.raises(UnsupportedForModel, match="mid-decode publishing"):
        make_engine(model, stream_publish_tokens=4)
    with pytest.raises(UnsupportedForModel, match="prefix ring"):
        make_engine(model, mesh=object())
    assert unsupported_reason(get_config("qwen2-tiny"), mesh_cache=True) is None
    empty = jnp.zeros((3, 1, 16, 1, 128))
    with pytest.raises(ValueError, match="no cached prefix"):
        prefill_forward(params, cfg, jnp.zeros((1, 4), jnp.int32),
                        jnp.arange(16, 20)[None], empty, empty,
                        jnp.full((1,), 16, jnp.int32),
                        snap_at=jnp.zeros((1, 3), jnp.int32))


def test_the_config_refuses_what_it_cannot_run():
    cfg = get_config("lfm2-tiny")
    with pytest.raises(ValueError, match="conv_L_cache >= 2"):
        cfg.replace(conv_L_cache=1)
    with pytest.raises(ValueError, match="whole 128-lane rows"):
        cfg.replace(hidden=96)
    with pytest.raises(ValueError, match="conv or\\s+full_attention|as conv"):
        cfg.replace(layer_types=("conv", "sliding_attention") * 6)
    with pytest.raises(ValueError, match="names no full_attention layer"):
        cfg.replace(layer_types=("conv",) * 12)
    with pytest.raises(ValueError, match="belongs to a config with conv layers"):
        get_config("qwen2-tiny").replace(conv_L_cache=3)
    with pytest.raises(ValueError, match="head_dim == 64"):
        get_config("qwen2-tiny").replace(pair_kv_heads=True)
    with pytest.raises(ValueError, match="qk_norm's weights"):
        get_config("qwen2-tiny").replace(qk_norm=True)
    with pytest.raises(ValueError, match="first_k_dense"):
        cfg.replace(first_k_dense=13)
    with pytest.raises(ValueError, match="no latent attention"):
        cfg.replace(qkv_bias=True)
    # num_dense_layers may end anywhere: the runs follow it
    assert _mixed_runs(cfg) == [(0, 2, 1), (2, 4, 2), (10, 2, 1)]
    assert _mixed_runs(cfg.replace(first_k_dense=0)) == [(0, 4, 3)]
    assert _mixed_runs(cfg.replace(first_k_dense=3)) == [
        (0, 3, 1), (3, 4, 2), (11, 1, 1)]
    big = get_config("lfm2-24b-a2b-ep8")
    assert _mixed_runs(big) == [(0, 2, 1), (2, 4, 9), (38, 2, 1)]
    assert len(big.conv_layers) == 30 and big.attn_layers == tuple(range(2, 40, 4))
    assert big.kv_pool_shape(196608) == (2, 10, 4, 196608, 128)
    assert big.state_shape(2048) == (30, 2048, 32, 128)
    assert big.default_state_slots(196608, 32) == 2048
    assert big.held_experts == 8


# ---------------------------------------------------------------------------
# what the entry points lower to, at published widths
# ---------------------------------------------------------------------------


def _published(v5e, periods=3):
    """Published widths, ``periods`` periods deep (a scanned run of two
    periods when 3), a small vocabulary, the pool and the state slots:
    shapes only."""
    cfg = get_config("lfm2-24b-a2b-ep8").replace(
        n_layers=4 * periods, layer_types=("conv", "conv", "full_attention",
                                           "conv") * periods, vocab_size=4096)
    shapes, params, sd = abstract_params(cfg, v5e)
    assert shapes["layers"]["w_gate"].shape == (4 * periods - 2, 8, 2048, 1536)
    assert shapes["layers"]["w_router"].shape == (4 * periods - 2, 2048, 64)
    assert shapes["conv"]["in_proj"].shape == (3 * periods, 2048, 6144)
    assert shapes["attn"]["wk"].shape == (periods, 2048, 512)
    pools = dict(kv=sd(cfg.kv_pool_shape(8192), cfg.dtype),
                 state_pool=sd(cfg.state_shape(256), cfg.dtype))
    return cfg, params, sd, pools


def test_decode_lowers_for_a_v5e_at_published_widths(v5e):
    """The fused decode kernel on four wide heads (no jnp attention), the
    expert kernel on its 8 of 64, the pool AND the state slots aliased
    through, neither copied."""
    cfg, params, sd, pools = _published(v5e)
    compiled = decode_step.lower(
        params, cfg, sd((8,), jnp.int32), pools["kv"],
        sd((8,), jnp.int32), sd((8, 64), jnp.int32), sd((8,), jnp.int32), 16,
        state_pool=pools["state_pool"], state_slots=sd((2, 8), jnp.int32),
    ).compile()
    names = kernels_in(compiled)
    assert {"radixmesh_decode_fused_mh", "radixmesh_moe_gmm_decode"} <= names, names
    aliased_and_uncopied(compiled, pools.values())


def test_chunk_lowers_for_a_v5e_at_published_widths(v5e):
    cfg, params, sd, pools = _published(v5e)
    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((2, 256), jnp.int32), sd((2, 256), jnp.int32),
        pools["kv"], sd((2, 256), jnp.int32),
        sd((2, 256), jnp.int32), sd((2,), jnp.int32), page_size=16,
        logits_at=sd((2,), jnp.int32), state_pool=pools["state_pool"],
        state_in=sd((2,), jnp.int32), snap_at=sd((2, 3), jnp.int32),
        state_out=sd((2, 3), jnp.int32),
    ).compile()
    names = kernels_in(compiled)
    assert {"radixmesh_chunk_attn", "radixmesh_moe_gmm_prefill"} <= names, names
    aliased_and_uncopied(compiled, pools.values())


_LOWER_DECODE = """
import hashlib, jax, jax.numpy as jnp
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import decode_step
cfg = get_config("lfm2-tiny")
params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
z = lambda *s: jnp.zeros(s, jnp.int32)
low = decode_step.lower(
    params, cfg, z(4), jnp.zeros(cfg.kv_pool_shape(256), cfg.dtype), z(4),
    z(4, 8), z(4) + 1, 16, state_pool=jnp.zeros(cfg.state_shape(8), cfg.dtype),
    state_slots=z(2, 4))
print(hashlib.md5(low.as_text().encode()).hexdigest())
"""


def test_the_lowering_does_not_depend_on_the_string_hash_seed():
    """The persistent compile cache keys on the lowered text: a join
    traced in a set's iteration order made every server process compile
    its programs anew (24 programs, ~160 s of a set-up, PR 39)."""
    import os
    import subprocess
    import sys

    def lowered(hash_seed):
        env = {**os.environ, "PYTHONHASHSEED": hash_seed, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run([sys.executable, "-c", _LOWER_DECODE], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()[-1]

    assert len({lowered(s) for s in ("1", "4", "7")}) == 1

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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as ref
from radixmesh_tpu.cache import radix_tree
from radixmesh_tpu.engine import Engine, SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    UnsupportedForModel,
    _attn_out,
    _ffn,
    _latent_qkv,
    convert_hf_state_dict,
    decode_step,
    prefill_forward,
    read_moe_stats,
)
from radixmesh_tpu.ops.mla_attention import (
    latent_chunk_attention,
    latent_decode_attention,
    latent_prefill_attention,
    mla_chunk_kernel,
    mla_decode_kernel,
)
from radixmesh_tpu.ops.moe import route
from radixmesh_tpu.ops.rope import rope_frequencies
from tests.test_mellum2 import REFUSED, _mesh  # the same options, by name

pytestmark = pytest.mark.quick

PAGE = 4
N_NEW = 9  # one token from the prefill, 8 decode steps through the pool
TOL = 1e-4  # float32 against float32: other orders of the same sums


@pytest.fixture(scope="module")
def model():
    cfg = get_config("glm-4.7-flash-tiny").replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(31))


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 1024)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 160)
    return Engine(cfg, params, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng([31, seed]).integers(0, 512, n).tolist()


def rel(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


def check_against_reference(model, req, prompt):
    """The request's kept logits rows and expert ids against the
    reference's full forward pass over prompt + generated tokens."""
    cfg, params = model
    out = req.generated
    assert len(out) == N_NEW and len(req.logit_rows) == N_NEW
    seq = list(prompt) + out[:-1]
    r = ref.forward(params, cfg, seq, logits_from=len(prompt) - 1)
    got = np.stack(req.logit_rows)
    assert got.dtype == np.float32 and got.shape == r["logits"].shape
    assert rel(got, r["logits"]) < TOL
    assert [int(x) for x in got.argmax(-1)] == out  # greedy: what was sampled
    seen = set()
    for start, ids in req.expert_rows:  # every computed position's choice
        n = ids.shape[0]
        # the sparse layers alone: layer 0 is dense and chooses nothing
        assert ids.shape[1:] == (cfg.n_layers - 1, cfg.n_experts_per_tok)
        np.testing.assert_array_equal(
            np.sort(ids, -1), np.sort(r["expert_ids"][start:start + n], -1))
        seen.update(range(start, start + n))
    assert seen == set(range(req.prefix_len, len(seq)))


@pytest.mark.parametrize("n_prompt", [7, 16, 53])
@pytest.mark.parametrize("path", ["dense", "chunk"])
def test_prefill_then_decode_through_the_latent_cache(model, path, n_prompt):
    eng = make_engine(
        model, prefill_chunk=16,
        long_prefill_threshold=1024 if path == "dense" else 0,
    )
    prompt = prompt_of(n_prompt)
    req = eng.add_request(
        prompt, SamplingParams(max_new_tokens=N_NEW, return_logits=True))
    while eng.has_work():
        eng.step()
    assert eng._last_prefill_path == path
    check_against_reference(model, req, prompt)


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


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_by_name(model, what):
    kw, name = REFUSED[what]
    with pytest.raises(UnsupportedForModel, match=name) as e:
        make_engine(model, **(kw() if callable(kw) else kw))
    assert "a latent (MLA) cache row" in str(e.value)


@pytest.mark.parametrize("what", ["pp", "disagg_decode", "disagg_prefill",
                                  "init_int8", "hf_checkpoint", "sp_prefill",
                                  "latent_alone"])
def test_other_entries_refuse_by_name(model, what):
    from radixmesh_tpu.engine.disagg import DecodeWorker, PrefillWorker
    from radixmesh_tpu.models.llama import prefill_forward_sp, unsupported_reason

    cfg, params = model
    with pytest.raises(UnsupportedForModel) as e:
        if what == "pp":
            from jax.sharding import Mesh

            make_engine(model, device_mesh=Mesh(
                np.asarray(jax.devices()[:2]).reshape(2), ("pp",)))
        elif what == "disagg_decode":
            DecodeWorker(make_engine(model))
        elif what == "disagg_prefill":
            PrefillWorker(cfg, params, num_slots=256, page_size=PAGE,
                          max_batch=2, max_seq_len=64).prefill_handoff([1, 2, 3])
        elif what == "init_int8":
            init_params(cfg, jax.random.PRNGKey(0), weight_quant="int8")
        elif what == "hf_checkpoint":
            convert_hf_state_dict(cfg, {})
        elif what == "sp_prefill":
            prefill_forward_sp(params, cfg, jnp.zeros((1, 8), jnp.int32),
                               jnp.arange(8)[None], _mesh(sp=2))
        else:
            # a latent row alone (no experts, no window) is refused as well
            dense = get_config("llama3-tiny").replace(
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
                qk_rope_head_dim=8, v_head_dim=24)
            assert unsupported_reason(dense) is None
            init_params(dense, jax.random.PRNGKey(0), weight_quant="int8")
    assert "does not support" in str(e.value)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


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
    import re

    from radixmesh_tpu.models.llama import _init_params

    preset, over, pool_shape, inside, outside = LOWERS_TO[which]
    cfg = get_config(preset, **over)
    assert cfg.kv_pool_shape(4096) == pool_shape

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    compiled = decode_step.lower(
        params, cfg, sd((8,), jnp.int32), sd(pool_shape, cfg.dtype),
        sd((8,), jnp.int32), sd((8, 64), jnp.int32), sd((8,), jnp.int32), 16,
    ).compile()
    names = set(re.findall(r"radixmesh_[a-z_]+[a-z]", compiled.as_text()))
    assert inside <= names and not (outside & names), names
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= np.prod(pool_shape) * 2  # pool aliased through

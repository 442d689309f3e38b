"""AI21-Jamba2-3B (``models/jamba.py``) at toy widths on the CPU: Mamba-1
layers with normed delta / B / C as a KIND of the by-kind body, beside plain
multi-query attention layers without a rotary, through the three entry
points, the state slots (the recurrent part in float32, the convolution's
inputs), the pool and the radix tree's snapshots against the plain float32
reference (``benchmarks/reference/jamba.py``) — logits, not tokens — on every
prompt path, at the page of 16 and at this model's page of 128; the paged
kernels over a page table wider than one of their blocks at that page; what
must NOT agree (the controls); the sizes pinned; which ``mamba`` layouts run
and which are refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import jamba as ref
from radixmesh_tpu.engine.engine import pool_groups
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    ModelConfig,
    _mixed_runs,
    decode_step,
    prefill_chunk_paged,
    prefill_forward,
)
from radixmesh_tpu.ops.attention import attend_chunk_hybrid, attend_decode_ref
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec

pytestmark = pytest.mark.quick


def reference(params, cfg, seq, logits_from, **control):
    return ref.forward(params, cfg, seq, at=np.arange(logits_from, len(seq)),
                       **control)


def _the_pool_and_the_slots(eng, n_prompt, path, **_):
    """K and V of the two attention layers alone in the pool; the recurrent
    part float32 and the convolution's inputs beside it in the state slots."""
    cfg, page = eng.cfg, eng.page_size
    assert eng._last_prefill_path == ("chunk" if n_prompt == 300 else path)
    assert eng.pool.kv.shape == (2, 2, 1, 8192, 64)
    assert eng.pool.state.shape[0] == 6 and eng.pool.state.dtype == jnp.float32
    assert eng.pool.state.shape[2:] == (16, 512)
    assert eng.pool.state_conv.shape[2:] == (3 * 512 // 128, 128)
    assert eng.pool.state_slot_bytes == cfg.state_slot_bytes() == 6 * 512 * (
        16 * 4 + 3 * 4)
    aw = eng.attn_window
    assert aw["decode_context_tokens"] == sum(
        n_prompt + i for i in range(1, SPEC.n_new))
    assert aw["table_pages_max"] >= -(-(n_prompt + SPEC.n_new) // page)


SPEC = ModelSpec(
    preset="jamba-tiny", seed=55, reference=reference,
    engine=dict(page_size=16, num_slots=8192, max_batch=4, max_seq_len=2048,
                prefill_chunk=64),
    # 7: inside one page; 48: a multiple of 16; 150 and 300: chunks of 64
    # carrying the state (300: three pages of 128); and at this model's page
    lengths=(7, 48, 150, 300), pages=(128,),
    # bf16 weights, activations, K/V and convolution inputs against the
    # float32 reference at toy widths (8 layers of 256): readings 0.012-0.031
    # over the paths below; a bf16 recurrent state reads the same here (nine
    # positions of decay do not tell it apart at these widths: the chip's
    # control does)
    band_bf16=0.06,
    # without the three inner norms, with int8 weights, with the recurrent
    # state held in bf16: each further off than float32 rounding by orders
    parts={"inner_norms": (dict(no_inner_norms=True), 30),
           "int8_weights": (dict(weight_map=ref.int8_weights), 30),
           "f32_state": (dict(state_dtype=jnp.bfloat16), 30)},
    parts_prompt=150,
    forget={"state": lambda at: dict(forget_state_at=at)},
    forget_is_exact=("state",),
    says=("state-space layers with a recurrent state",),
    omits=("cross-decoder",),  # the other layout's
    hooks={"prefill": _the_pool_and_the_slots},
)
make_engine, prompt_of, run, check = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.run, SPEC.check)


# ---------------------------------------------------------------------------
# the state slots under pressure
# ---------------------------------------------------------------------------


def test_a_full_state_group_gives_up_snapshots_and_serves_on(model):
    """Five slots a row and the scratch slot: sessions past that evict the
    tree's snapshots in order, and a turn whose snapshot went computes its
    prefix again — the result is the reference's either way."""
    eng = make_engine(model, max_batch=2, num_slots=2048)
    assert eng.pool.state.shape[1] == 1 + 5 * 2 and eng._ordered_evict
    prompts = [prompt_of(40 + 16 * i, seed=40 + i) for i in range(8)]
    for p in prompts:
        run(eng, p, 3)
    req = run(eng, prompts[0])
    check(model, req, prompts[0])
    assert eng.pool.state_slots_used <= eng.pool.state.shape[1]


@pytest.mark.parametrize("page, resumed", [(16, 224), (128, 128)])
def test_a_live_rows_prompt_snapshot_is_kept_until_it_completes_a_page(
        model, page, resumed):
    """Pressure on the state slots gives up a live row's own prompt snapshot
    early BECAUSE its release leaves a deeper one — which holds only once a
    decode step of the row has completed a page. At a page of 128 a reply
    of 30 tokens never does: the snapshot is the session's resume point and
    stays (before PR 55 turn 2 computed its whole prefix again)."""
    eng = make_engine(model, page)
    p1 = prompt_of(200, seed=51)
    t1 = eng.add_request(p1, SamplingParams(max_new_tokens=30))
    for _ in range(6):
        eng.step()
    assert t1.state_handed and not t1.state_boundary_len
    assert eng._evict_states(8) == 0  # nothing else holds a state
    for _ in range(12):  # at a page of 16 position 207 completes one
        eng.step()
    assert (t1.state_boundary_len > 0) == (page == 16)
    assert eng._evict_states(8) == (1 if page == 16 else 0)
    while eng.has_work():
        eng.step()
    p2 = p1 + t1.generated + prompt_of(20, seed=52)
    t2 = run(eng, p2)
    assert t2.prefix_len == resumed and eng.state_stats["recomputed_tokens"] == 0
    check(model, t2, p2)


# ---------------------------------------------------------------------------
# what must not agree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["dense", "chunk", "again", "turn"])
def test_bf16_stays_inside_its_band(path):
    """The configuration's own precision on every path, against the float32
    reference of the same (bf16-drawn) weights."""
    model = SPEC.build(dtype=jnp.bfloat16)
    eng = make_engine(model, long_prefill_threshold=1024 if path == "dense" else 0)
    prompt = prompt_of(150, seed=22)
    req = run(eng, prompt)
    if path == "again":
        req = run(eng, prompt)
        assert req.prefix_len == 144
    elif path == "turn":
        prompt = prompt + req.generated + prompt_of(9, seed=23)
        req = run(eng, prompt)
        assert req.prefix_len == 144
    check(model, req, prompt, tol=SPEC.band_bf16)


# ---------------------------------------------------------------------------
# the sizes, pinned
# ---------------------------------------------------------------------------


def test_the_published_model_counts_3029_m_parameters():
    cfg = get_config("jamba2-3b")
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 3_029_337_472
    mamba = sum(int(np.prod(a.shape[1:]))
                for a in jax.tree_util.tree_leaves(shapes["mamba"]))
    attn = sum(int(np.prod(a.shape[1:]))
               for a in jax.tree_util.tree_leaves(shapes["attn"]))
    ffn = 3 * 2560 * 8192 + 2 * 2560  # the SwiGLU and the layer's two norms
    assert round((mamba + ffn) / 1e6, 2) == 104.16
    assert round((attn + ffn) / 1e6, 2) == 76.68
    assert cfg.layer_types.count("mamba") == 26
    assert cfg.attn_layers == (7, 21) and not cfg.use_rope and cfg.tie_embeddings
    assert _mixed_runs(cfg) == [(0, 14, 2)]  # two traced units of 14 layers


def test_a_snapshot_is_9_318_400_bytes_and_the_slots_go_by_bytes():
    cfg = get_config("jamba2-3b")
    assert cfg.state_slot_bytes() == 9_318_400 == 26 * 5120 * (16 * 4 + 3 * 2)
    assert cfg.state_shape(321) == (26, 321, 16, 5120)
    assert cfg.conv_state_shape(321) == (26, 321, 120, 128)
    assert cfg.state_dtype == jnp.float32
    assert cfg.kv_pool_shape(2 ** 21) == (2, 2, 1, 2 ** 21, 128)  # 1 KiB a token
    # 2 M rows of 1 KiB are 2 GiB: a quarter of that buys 57 snapshots, and
    # the five a batch row and the scratch slot hold
    assert cfg.default_state_slots(2 ** 21, 64) == 321
    groups = pool_groups(cfg, 2 ** 21, 64, 128)
    assert groups["window_shape"] is None
    assert groups["state_shape"][1] == groups["conv_state_shape"][1] == 321


# ---------------------------------------------------------------------------
# a page table wider than one kernel block, at this model's page
# ---------------------------------------------------------------------------


def test_the_paged_kernels_at_page_128_over_several_blocks():
    """One KV head under 20 query heads, pages of 128: the decode kernel's
    block is 4 pages (512 tokens), the chunk kernel's likewise; rows of 5
    and 11 pages cross two and three of them, on scattered page ids."""
    from radixmesh_tpu.ops.paged_attention import (
        decode_block_tokens,
        paged_chunk_attention_kernel,
        paged_decode_fused_kernel,
    )

    page, B, pages, P, Hq, D = 128, 2, 12, 32, 20, 128
    assert decode_block_tokens(1, page, D, jnp.bfloat16, 1024) == 512
    rng = np.random.default_rng(55)
    kv = jnp.asarray(rng.normal(size=(2, 2, 1, P, page, D)), jnp.float32)
    pt = jnp.asarray(rng.permutation(P)[:B * pages].reshape(B, pages), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, 1, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, 1, D)), jnp.float32)
    lengths = jnp.asarray([600, 1400], jnp.int32)
    slots = jnp.asarray([int(pt[b, (int(lengths[b]) - 1) // page]) * page
                         + (int(lengths[b]) - 1) % page for b in range(B)], jnp.int32)

    def rel(a, b):
        return float(np.abs(a - b).max() / np.abs(b).max())

    got, pages_out = paged_decode_fused_kernel(
        q, kn, vn, kv, slots, pt, lengths, 1, interpret=True)
    want_pages = kv.at[0, 1, :, slots // page, slots % page].set(kn)
    want_pages = want_pages.at[1, 1, :, slots // page, slots % page].set(vn)
    want = attend_decode_ref(q, want_pages[0, 1], want_pages[1, 1], pt, lengths)
    assert rel(np.asarray(got), np.asarray(want)) < 1e-5
    np.testing.assert_array_equal(np.asarray(pages_out), np.asarray(want_pages))
    C = 64
    qc = jnp.asarray(rng.normal(size=(B, C, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, C, 1, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, C, 1, D)), jnp.float32)
    prior = jnp.asarray([512, 1300], jnp.int32)
    pos = prior[:, None] + jnp.arange(C)
    got = paged_chunk_attention_kernel(
        qc, kc, vc, kv, pt, prior, prior + C, 1, interpret=True)
    want = attend_chunk_hybrid(
        qc, kc, vc, kv, pt, pos, prior, prior + C, 1, kv_block_pages=4)
    assert rel(np.asarray(got), np.asarray(want)) < 1e-5


def test_the_engine_serves_a_table_wider_than_a_block_at_page_128(model):
    """1 100 tokens at page 128 are 9 pages, a 16-page table: three blocks
    of 512 tokens in both kernels' geometry, through chunked prefill, the
    tree and decode."""
    eng = make_engine(model, 128, num_slots=16384, max_seq_len=4096,
                      prefill_chunk=256)
    prompt = prompt_of(1100, seed=31)
    req = run(eng, prompt, 5)
    check(model, req, prompt, 5)
    again = run(eng, prompt + prompt_of(40, seed=32), 5)
    assert again.prefix_len == 1024
    check(model, again, prompt + prompt_of(40, seed=32), 5)
    assert eng.attn_window["table_pages_max"] >= 16


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def _scopes(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add(str(eqn.source_info.name_stack))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _scopes(inner, found)
    return found


@pytest.mark.parametrize("program", ["prefill", "chunk", "decode"])
def test_rm_ssm_and_rm_full_attn_are_in_all_three_programs(model, program):
    cfg, params = model
    eng = make_engine(model, num_slots=256, max_batch=2)
    B, S = 2, 16
    tokens = jnp.zeros((B, S), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    pool = eng.pool
    state = (pool.state, pool.state_conv)
    pt = jnp.zeros((B, 4), jnp.int32)
    if program == "prefill":
        empty = jnp.zeros((2, B, 0, 1, 64), cfg.dtype)
        jaxpr = jax.make_jaxpr(lambda: prefill_forward(
            params, cfg, tokens, positions, empty, empty, jnp.zeros((B,), jnp.int32),
            snap_at=jnp.zeros((B, 3), jnp.int32)))()
    elif program == "chunk":
        jaxpr = jax.make_jaxpr(lambda: prefill_chunk_paged(
            params, cfg, tokens, positions, pool.kv, jnp.zeros((B, S), jnp.int32),
            pt, jnp.full((B,), S, jnp.int32), page_size=16, kv_block_pages=4,
            state_pool=state, state_in=jnp.zeros((B,), jnp.int32),
            snap_at=jnp.zeros((B, 3), jnp.int32),
            state_out=jnp.zeros((B, 3), jnp.int32)))()
    else:
        jaxpr = jax.make_jaxpr(lambda: decode_step(
            params, cfg, tokens[:, 0], pool.kv, jnp.zeros((B,), jnp.int32), pt,
            jnp.full((B,), 2, jnp.int32), page_size=16, state_pool=state,
            state_slots=jnp.zeros((2, B), jnp.int32)))()
    names = " ".join(_scopes(jaxpr.jaxpr, set()))
    assert "rm_ssm" in names and "rm_full_attn" in names


# ---------------------------------------------------------------------------
# which layouts run, what is refused
# ---------------------------------------------------------------------------


def test_the_decoder_hybrid_decoder_is_still_accepted():
    cfg = get_config("phi4flash-tiny")
    assert cfg.has_ssm and cfg.cross_decoder and not cfg.carried_state
    assert not cfg.own_stacks and len(cfg.window_layers) == 2
    tiny = get_config("jamba-tiny")
    assert tiny.has_ssm and not tiny.cross_decoder and tiny.carried_state
    assert tiny.own_stacks and tiny.window_layers == () and tiny.matrix_state is None
    assert tiny.attn_layers == (1, 5)


@pytest.mark.parametrize("change, message", [
    (dict(layer_types=("mamba",) * 8), "interleaved with plain GQA"),
    (dict(layer_types=("mamba", "sliding_attention") * 4, sliding_window=32),
     "exactly one full_attention"),
    (dict(layer_types=("mamba", "full_attention", "gmu", "cross_attention") * 2),
     "exactly one full_attention"),
    (dict(layer_types=("mamba", "full_attention") * 3 + ("mamba", "conv")),
     "must name 8 layers"),
    (dict(qkv_bias=True), "interleaved with plain GQA"),
    (dict(n_experts=8, n_experts_per_tok=2, moe_intermediate=64),
     "interleaved with plain GQA"),
    (dict(layer_norm=True), "interleaved with plain GQA"),
    (dict(diff_attention=True), "exactly one full_attention"),
    (dict(ssm_dt_rank=0), "ssm_dt_rank"),
    (dict(hidden=200, ssm_expand=1), "128-lane rows"),
])
def test_every_other_mamba_layout_is_refused_and_told_what_runs(change, message):
    with pytest.raises(ValueError, match="mamba|layer_types") as e:
        get_config("jamba-tiny").replace(**change)
    assert message in str(e.value), str(e.value)


def test_the_inner_norms_belong_to_the_interleaved_layout():
    with pytest.raises(ValueError, match="ssm_inner_norms"):
        get_config("phi4flash-tiny").replace(ssm_inner_norms=True)
    plain = get_config("jamba-tiny").replace(ssm_inner_norms=False)
    params = init_params(plain, jax.random.PRNGKey(0))
    assert "dt_norm" not in params["mamba"]


def test_a_config_without_mamba_layers_takes_no_ssm_sizes():
    cfg = ModelConfig.tiny()
    with pytest.raises(ValueError, match="interleaved with plain GQA"):
        cfg.replace(ssm_state=16, ssm_conv=4, ssm_dt_rank=8,
                    layer_types=("full_attention",) * cfg.n_layers)

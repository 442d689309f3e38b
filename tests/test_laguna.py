"""Laguna-XS.2 (``models/laguna.py``) at toy widths on the CPU: the three
entry points, the pool's two groups of rows and the radix tree's resume
points that are a window tail and nothing else, against the plain float32
reference (``benchmarks/reference/laguna.py``) — logits, not tokens — and
what must NOT agree (the controls), what is refused, what the programs
lower to for a v5e at published widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from radixmesh_tpu.engine.engine import pool_groups
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    ModelConfig,
    _init_params,
    _layer_kinds,
    _mixed_runs,
    decode_step,
    new_moe_stats,
    prefill_chunk_paged,
    prefill_forward,
)
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec, logits_from

pytestmark = pytest.mark.quick

WINDOW = 16


def _both_groups_and_no_state(eng, req, **_):
    """The two full layers' rows in the pool, the three window layers' in
    the window group; no state array, and a tree that tracks tails alone;
    the expert ids of the four sparse layers ride the logits hook."""
    assert eng.pool.kv.shape == (2, 2, 2, 4096, 32)
    assert eng.pool.window.shape == (2, 3, 2, eng.pool.window_slots, 32)
    assert eng.pool.state is None and eng.state_stats is None
    assert eng.tree.on_free_state is None and eng.tree.on_free_tail is not None
    assert req.expert_rows[0][1].shape[1:] == (4, 2)


def _what_the_two_kinds_read(eng, **_):
    """Two full layers read every context token of every step (contexts 51
    to 139), three window layers at most a window of them."""
    aw = eng.attn_window
    assert aw["full_tokens_read"] == 2 * sum(range(51, 140))
    assert 0 < aw["window_tokens_read"] <= 3 * 89 * WINDOW
    assert aw["window_tokens_skipped"] > aw["window_tokens_read"]
    assert "shared_kv_tokens_read" not in aw


def _two_resumes_four_tails(eng):
    assert eng.tail_stats["resumes"] == 2 and eng.tail_stats["snapshots"] >= 4


SPEC = ModelSpec(
    preset="laguna-tiny", seed=45, reference=logits_from(ref.forward),
    engine=dict(page_size=16, num_slots=4096, max_batch=4, max_seq_len=512,
                prefill_chunk=64),
    # 7: inside one page; 48: a page multiple past the window; 100: chunks of
    # 64 that cut the window (16) and the tail at 96
    lengths=(7, 48, 100),
    # the gate, the half-rotated head and each kind's own head count (which
    # kv head a query head reads) each matter
    parts={piece: (dict(without=(piece,)), 100)
           for piece in ("gate", "partial_rotary", "head_groups")},
    says=("attention kinds of two shapes",
          "a window group of rows resumed from a tail"),
    hooks={"prefill": _both_groups_and_no_state,
           "long_decode": _what_the_two_kinds_read,
           "next_turn": _two_resumes_four_tails},
)
make_engine, prompt_of, run, check = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.run, SPEC.check)
PAGE, N_NEW = SPEC.engine["page_size"], SPEC.n_new


# ---------------------------------------------------------------------------
# every way a resume point (a tail and nothing else) is reached
# ---------------------------------------------------------------------------


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
# the reference itself
# ---------------------------------------------------------------------------


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


def _published(v5e):
    """Published widths and the cut's depth, a small vocabulary and 16 of
    the 256 experts: shapes only."""
    cfg = get_config("laguna-xs.2-l5").replace(
        vocab_size=4096, n_experts=16, n_experts_per_tok=8)
    _, params, sd = abstract_params(cfg, v5e)
    pools = dict(kv=sd(cfg.kv_pool_shape(8192), cfg.dtype),
                 window_pool=sd(cfg.window_pool_shape(4096), cfg.dtype))
    return cfg, params, sd, pools


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
    names = kernels_in(compiled)
    assert {"radixmesh_decode_fused_mh", "radixmesh_moe_gmm_decode"} <= names, names
    aliased_and_uncopied(compiled, pools.values())


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
    names = kernels_in(compiled)
    assert {"radixmesh_chunk_attn", "radixmesh_moe_gmm_prefill"} <= names, names
    aliased_and_uncopied(compiled, pools.values())


def test_the_counters_have_one_shape_for_this_model():
    assert new_moe_stats().shape == (2, 4, 2)
    assert isinstance(get_config("laguna-tiny"), ModelConfig)

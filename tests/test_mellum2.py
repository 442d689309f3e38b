"""Mellum2 on the served path against the plain float32 reference
(``benchmarks/reference/mellum2.py``): the tiny preset (one period of
sliding x3 + full, 8 experts top-2, window 16), float32 program, CPU.

Dense prefill, chunked paged prefill and prefill + 16 decode steps through
the pool each have to equal the reference's FULL forward pass over prompt +
generated tokens, at prompt lengths below, at and above the window, and
with a prefix served from the radix tree."""

import base64
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum2 as ref
from radixmesh_tpu.engine import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import (
    UnsupportedForModel,
    _bump_moe_stats,
    new_moe_stats,
    prefill_chunk_paged,
    read_moe_stats,
)
from radixmesh_tpu.ops.moe import moe_ffn
from tests.model_conformance import *  # noqa: F401,F403  (the shared cases)
from tests.model_conformance import ModelSpec, logits_from
from tests.model_conformance import distance as rel

pytestmark = pytest.mark.quick

WINDOW = 16

SPEC = ModelSpec(
    preset="mellum2-tiny", seed=27, reference=logits_from(ref.forward),
    engine=dict(page_size=4, num_slots=1024, max_batch=4, max_seq_len=256,
                prefill_chunk=16),
    # below, at, one past, and well past the window (and past a chunk of 16)
    lengths=(WINDOW - 7, WINDOW, WINDOW + 1, 3 * WINDOW + 5),
    n_new=17,  # one token from the prefill, 16 decode steps through the pool
    says=("sparse experts and window attention",),
)
make_engine, prompt_of, check_against_reference = (
    SPEC.make_engine, SPEC.prompt_of, SPEC.check)
PAGE, N_NEW, TOL = SPEC.engine["page_size"], SPEC.n_new, SPEC.tol


def test_the_window_is_the_presets(model):
    assert model[0].sliding_window == WINDOW


def test_batched_rows_and_counters(model):
    """Rows of different lengths in one decode launch (window start per
    row), a request that did not ask keeps nothing, and the counters."""
    cfg, _ = model
    eng = make_engine(model)
    prompts = [prompt_of(n, seed=9 + n) for n in (5, WINDOW + 9, 2 * WINDOW + 3)]
    reqs = [eng.add_request(p, SamplingParams(
        max_new_tokens=N_NEW, return_logits=i != 0)) for i, p in enumerate(prompts)]
    while eng.has_work():
        eng.step()
    assert reqs[0].logit_rows == [] and reqs[0].expert_rows == []
    for req, p in zip(reqs[1:], prompts[1:]):
        check_against_reference(model, req, p)
    moe = read_moe_stats(eng._moe_stats)
    steps = eng.stats.decode_steps
    assert moe["decode"]["layer_launches"] == steps * cfg.n_layers
    # idle batch rows stay out: 3 live rows x top-2 x 4 layers a step
    assert moe["decode"]["assignments"] == 3 * 2 * cfg.n_layers * steps
    assert 0 < moe["decode"]["experts_hit"] <= 6 * cfg.n_layers * steps
    assert moe["prefill"]["assignments"] == sum(map(len, prompts)) * 2 * cfg.n_layers
    aw = eng.attn_window
    ctx = [len(p) + 1 + i for p in prompts for i in range(N_NEW - 1)]
    assert aw["window_tokens_read"] == 3 * sum(min(c, WINDOW) for c in ctx)
    assert aw["window_tokens_skipped"] == 3 * sum(max(c - WINDOW, 0) for c in ctx)


@pytest.mark.parametrize("n_prompt", [WINDOW - 3, 2 * WINDOW + 7])
def test_kernels_in_interpret_mode(model, n_prompt):
    """The Pallas paths (chunk attention with a window start, the grouped
    expert product) in interpret mode: two chunks against the pool equal
    the reference."""
    cfg, params = model
    cfg = cfg.replace(head_dim=128, n_heads=2, n_kv_heads=1)  # a tileable head
    params = init_params(cfg, jax.random.PRNGKey(5))
    prompt = np.asarray(prompt_of(n_prompt, seed=4), np.int32)
    C, slots_n = 32, 256
    pool = jnp.zeros((2, cfg.n_layers, cfg.n_kv_heads, slots_n, cfg.head_dim),
                     jnp.float32)
    pt = jnp.arange(32, dtype=jnp.int32)[None]  # pages 0.. in order
    rows = []
    for c0 in range(0, n_prompt, C):
        nv = min(C, n_prompt - c0)
        toks = np.zeros((1, C), np.int32)
        toks[0, :nv] = prompt[c0:c0 + nv]
        pos = (c0 + np.arange(C, dtype=np.int32))[None]
        sl = np.where(np.arange(C) < nv, c0 + np.arange(C), slots_n - 1)[None]
        logits, pool, aux = prefill_chunk_paged(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos), pool,
            jnp.asarray(sl, jnp.int32), pt, jnp.asarray([c0 + nv], jnp.int32),
            page_size=PAGE, kv_block_pages=8, use_kernel=True, interpret=True,
        )
        rows.append(np.asarray(logits[0, :nv]))
    r = ref.forward(params, cfg, prompt)
    assert rel(np.concatenate(rows), r["logits"]) < TOL
    assert read_moe_stats(aux["moe_stats"])["prefill"]["assignments"] == (
        min(C, n_prompt - c0) * 2 * cfg.n_layers)


@pytest.mark.parametrize("fuse_heads", [True, False])
def test_decode_kernel_skips_pages_before_the_window(fuse_heads):
    """The fused decode kernel with a window start, interpret mode,
    against the jnp oracle: rows shorter than, at and past the window, a
    full-attention call (window 0) through the same executable, and
    poisoned pages before the window that a kernel reading them would
    carry into its result."""
    from radixmesh_tpu.ops.attention import paged_decode_attention

    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, page, P = 4, 4, 2, 128, 16, 128
    window = 40
    lengths = np.asarray([1, 17, 40, 370], np.int32)
    kv = rng.standard_normal((2, 1, Hkv, P, page, D)).astype(np.float32)
    pt = rng.permutation(P)[: B * 24].reshape(B, 24).astype(np.int32)
    slots = np.asarray(
        [pt[b, (n - 1) // page] * page + (n - 1) % page
         for b, n in enumerate(lengths)], np.int32)
    q = rng.standard_normal((B, Hq, D)).astype(np.float32)
    kn = rng.standard_normal((B, Hkv, D)).astype(np.float32)
    vn = rng.standard_normal((B, Hkv, D)).astype(np.float32)
    from radixmesh_tpu.ops.paged_attention import paged_decode_fused_kernel

    for w in (window, 0):
        want, want_pool = paged_decode_attention(
            q, kn, vn, jnp.asarray(kv), slots, pt, lengths, 0,
            use_kernel=False, window=jnp.int32(w))
        poisoned = kv.copy()
        if w:  # blocks (8 pages) wholly before row 3's window: not fetched
            first_page = (370 - window) // page
            assert first_page // 8 * 8 == 16
            poisoned[:, :, :, pt[3, :16]] = np.nan
        got, got_pool = paged_decode_fused_kernel(
            jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(poisoned), slots, pt, lengths, 0, interpret=True,
            fuse_heads=fuse_heads, window=jnp.int32(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        if not w:
            np.testing.assert_array_equal(np.asarray(got_pool),
                                          np.asarray(want_pool))


@pytest.mark.parametrize("window", [40, 0])
def test_chunk_kernel_window(window):
    """The chunk kernel with a window, interpret mode, against the jnp
    path: masks per query, and the page loop of each query block starts
    at its first query's window (earlier blocks poisoned, never read)."""
    from radixmesh_tpu.ops.attention import attend_chunk_hybrid
    from radixmesh_tpu.ops.paged_attention import paged_chunk_attention_kernel

    rng = np.random.default_rng(1)
    B, C, Hq, Hkv, D, page, P = 2, 32, 4, 2, 128, 16, 64
    prior = np.asarray([200, 16], np.int32)
    kv = rng.standard_normal((2, 1, Hkv, P, page, D)).astype(np.float32)
    pt = rng.permutation(P)[: B * 16].reshape(B, 16).astype(np.int32)
    q = rng.standard_normal((B, C, Hq, D)).astype(np.float32)
    kc = rng.standard_normal((B, C, Hkv, D)).astype(np.float32)
    vc = rng.standard_normal((B, C, Hkv, D)).astype(np.float32)
    pos = prior[:, None] + np.arange(C, dtype=np.int32)[None]
    kvlen = prior + np.asarray([C, 20], np.int32)
    want = attend_chunk_hybrid(
        q, kc, vc, jnp.asarray(kv), pt, pos, prior, kvlen, 0,
        kv_block_pages=2, window=jnp.int32(window))
    poisoned = kv.copy()
    if window:  # row 0's first query sees keys from 161 on: blocks of 32
        poisoned[:, :, :, pt[0, :10]] = np.nan
    got = paged_chunk_attention_kernel(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(poisoned),
        pt, prior, kvlen, 0, pages_per_block=2, q_block=8, interpret=True,
        window=jnp.int32(window))
    live = np.arange(C)[None, :] < (kvlen - prior)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_expert_layer_alone(model, use_kernel):
    cfg, params = model
    lp = {k: v[1] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (37, cfg.hidden), jnp.float32)
    valid = jnp.arange(37) < 30
    y, ids, stats = moe_ffn(
        x, lp["w_router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        k=cfg.n_experts_per_tok, norm_topk=cfg.norm_topk_prob, site="decode",
        valid=valid, use_kernel=use_kernel, interpret=True,
    )
    want, want_ids = ref.expert_layer(x, lp, top_k=cfg.n_experts_per_tok)
    assert rel(np.asarray(y), np.asarray(want)) < TOL
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    load = np.bincount(np.asarray(ids)[:30].ravel(), minlength=cfg.n_experts)
    assert [int(s) for s in stats] == [1, 60, int((load > 0).sum()), int(load.max())]


def test_counters_carry_past_32_bits():
    stats = new_moe_stats().at[0, 1, 0].set(jnp.uint32(2**32 - 3))
    stats = _bump_moe_stats(stats, "decode", jnp.asarray([1, 10, 0, 0], jnp.int32))
    got = read_moe_stats(stats)
    assert got["decode"]["assignments"] == 2**32 + 7
    assert got["decode"]["layer_launches"] == 1
    assert got["prefill"]["assignments"] == 0


def test_yarn_table_is_transformers():
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    from radixmesh_tpu.models.llama import _layer_tables

    cfg = get_config("mellum2-12b-a2.5b")
    rp = dict(dict(cfg.rope_by_type)["full_attention"])

    class HF:
        rope_theta = rp["rope_theta"]
        head_dim = cfg.head_dim
        hidden_size = cfg.hidden
        num_attention_heads = cfg.n_heads
        max_position_embeddings = 131072
        rope_scaling = {k: v for k, v in rp.items() if k != "rope_theta"}

    inv, factor = rope_utils._compute_yarn_parameters(HF(), "cpu")
    tables = _layer_tables(cfg)
    kinds = np.asarray(cfg.layer_types)
    full = np.asarray(tables["inv_freq"])[kinds == "full_attention"]
    np.testing.assert_allclose(full, np.broadcast_to(inv.numpy(), full.shape),
                               rtol=1e-6)
    assert factor == 1.2772588722239782
    np.testing.assert_allclose(
        np.asarray(tables["rope_scale"]),
        np.where(kinds == "full_attention", np.float32(factor), 1.0))
    np.testing.assert_array_equal(
        np.asarray(tables["window"]),
        np.where(kinds == "sliding_attention", 1024, 0))
    ref_inv, ref_factor = ref.rope_table(cfg.head_dim, rp)
    np.testing.assert_allclose(ref_inv, inv.numpy(), rtol=1e-6)
    assert ref_factor == factor
    sliding = np.asarray(tables["inv_freq"])[kinds == "sliding_attention"]
    np.testing.assert_allclose(
        sliding[0], 500000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)
    assert list(kinds[:4]) == ["sliding_attention"] * 3 + ["full_attention"]


def test_refusal_names_what_was_asked_and_no_launch_count(model):
    """Every model decodes one step a launch now: the refusal says what
    the model serves with and nothing about multi-step decode."""
    with pytest.raises(UnsupportedForModel) as e:
        make_engine(model, kv_quant="int8", spec_decode_tokens=2)
    msg = str(e.value)
    assert "an int8 KV pool" in msg and "speculative decode" in msg
    assert msg.endswith("one chip, bf16 weights and KV (ROADMAP D6 / R4)")
    assert "multi-step" not in msg and "a launch" not in msg


def _post(base, body, stream=False):
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        if not stream:
            return json.loads(resp.read())
        events = [json.loads(line[6:]) for line in resp.read().decode().split("\n")
                  if line.startswith("data: ")]
        return events[-1]


def _unpack(d):
    return np.frombuffer(base64.b64decode(d["b64"]), d["dtype"]).reshape(d["shape"])


@pytest.mark.parametrize("stream", [False, True])
def test_return_logits_reply(model, stream):
    from radixmesh_tpu.server.http_frontend import ServingFrontend

    cfg, params = model
    fe = ServingFrontend(make_engine(model), host="127.0.0.1", port=0)
    try:
        base = f"http://127.0.0.1:{fe.port}"
        prompt = prompt_of(WINDOW + 6, seed=8)
        body = {"input_ids": prompt, "max_tokens": 5, "stream": stream}
        plain = _post(base, body, stream)
        assert "logits" not in plain and "expert_ids" not in plain
        final = _post(base, {**body, "return_logits": True}, stream)
        assert final["output_ids"] == plain["output_ids"]
        logits = _unpack(final["logits"])
        assert logits.dtype == np.float32 and logits.shape == (5, cfg.vocab_size)
        assert logits.argmax(-1).tolist() == final["output_ids"]
        seq = prompt + final["output_ids"][:-1]
        r = ref.forward(params, cfg, seq, logits_from=len(prompt) - 1)
        assert rel(logits, r["logits"]) < TOL
        runs = final["expert_ids"]  # the second pass reads a cached prefix
        assert runs[0]["start"] >= PAGE and _unpack(runs[0]).shape[1:] == (
            cfg.n_layers, cfg.n_experts_per_tok)
        with urllib.request.urlopen(base + "/debug/state", timeout=30) as resp:
            state = json.loads(resp.read())
        assert state["moe"]["decode"]["assignments"] > 0
        assert state["moe"]["prefill"]["layer_launches"] >= 2 * cfg.n_layers
        assert state["attn"]["window_tokens_skipped"] > 0
    finally:
        fe.close()

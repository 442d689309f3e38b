"""A chunk launch as wide as the weights it streams (PR 54).

``prefill_chunk`` is the minimum chunk; how wide a launch may be is read
from the engine's weights (``prefill_plan.chunk_width``: the power of two
that holds ``prefill_cost``'s floor, within a wave's tokens and what the
chunk kernels admit from shapes alone). On the CPU reference path: a cold
prompt of 2 304 tokens prefilled as ``[1, 2048] + [1, 256]`` gives the
tokens and logits of ``[1, 512] x 4 + [1, 256]`` — with experts under
GQA and a window (whose tail is cut inside the wide launch), with a
latent cache row, and with a state whose snapshot falls inside the wide
launch — and an engine holds the width it
reads, never a launch over ``prefill_wave_tokens``. The planner alone:
``tests/test_prefill_plan.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.prefill_plan import chunk_width
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.ops import mla_attention, paged_attention

pytestmark = pytest.mark.quick

PAGE = 16
N_OUT = 4
TOL = 1e-4  # tests/test_prefill_paths.py's: float32 against float32
# case: (preset, new tokens of the second turn)
CASES = {
    "gqa-window-experts": ("mellum2-tiny", 2304),
    "latent-experts": ("glm-4.7-flash-tiny", 2304),
    "conv-state": ("lfm2-tiny", 2304),
    # the last launch holds 2 040 tokens: the prompt's page-aligned cut,
    # where the state's snapshot is left, lies 2 032 tokens INSIDE it
    "conv-state-cut-inside": ("lfm2-tiny", 4088),
}
# (floor, launch) of a dense model, whose tail goes alone under a chunk of
# 512, and of Mellum2, whose floor a chunk of 2 048 holds
NARROW, WIDE = (512, (120.0, 35.0)), (2048, (1167.0, 228.0))
SHAPES = {
    ("wide", 2304): [(1, 2048), (1, 256)],
    ("narrow", 2304): [(1, 512)] * 4 + [(1, 256)],
    ("wide", 4088): [(1, 2048)] * 2,
    ("narrow", 4088): [(1, 512)] * 8,
}


@functools.cache
def model_of(preset):
    cfg = get_config(preset).replace(dtype=jnp.float32, max_seq_len=8192)
    return cfg, init_params(cfg, jax.random.PRNGKey(54))


def make_engine(model, width, cost, **kw):
    cfg, params = model
    eng = Engine(cfg, params, num_slots=8192, page_size=PAGE, max_batch=4,
                 max_seq_len=8192, prefill_chunk=512, long_prefill_threshold=0,
                 **kw)
    eng._chunk_width, eng.prefill_cost = width, cost
    return eng


def tokens_of(n, seed):
    return np.random.default_rng([54, seed]).integers(1, 512, n).tolist()


def run(eng, prompt, n_out=N_OUT):
    """The finished request, and the launches that prefilled it."""
    req = eng.add_request(
        prompt, SamplingParams(max_new_tokens=n_out, return_logits=True))
    eng.step()
    shapes = list(eng._wave_shapes)
    while eng.has_work():
        eng.step()
    return req, shapes


def rel(a, b):
    a, b = np.stack(a), np.stack(b)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("case", CASES)
def test_a_wide_launch_gives_what_its_narrow_chunks_give(case):
    """A first turn of 100 tokens and 30 of reply leaves the tree 128 of
    them; the second turn resumes there and brings its new tokens cold.
    Wide: ``[1, 2048] + [1, 256]``; narrow: four chunks of 512 and one of
    256. Same tokens, same logits. Where the model carries a state the
    wide launch leaves the snapshots its chunks would have (the prompt's
    page-aligned cut: in the last case inside the second wide launch), and
    a third request resumes from that cut to the same tokens."""
    preset, new = CASES[case]
    model = model_of(preset)
    first = tokens_of(100, seed=1)
    got = {}
    for name, (width, cost) in {"narrow": NARROW, "wide": WIDE}.items():
        eng = make_engine(model, width, cost)
        assert eng.prefill_width == width
        turn, _ = run(eng, first, n_out=30)
        prompt = first + turn.generated + tokens_of(new - 2, seed=2)
        before = dict(eng.prefill, by_chunk=dict(eng.prefill["by_chunk"]))
        req, shapes = run(eng, prompt)
        assert req.prefix_len == 128 and eng._last_prefill_path == "chunk"
        assert shapes == SHAPES[name, new]
        p = eng.prefill
        assert p["tokens_new"] - before["tokens_new"] == new
        assert p["launches"] - before["launches"] == len(shapes)
        assert sum(p["by_chunk"].values()) == p["launches"]
        below = p["launches_below_floor"] - before["launches_below_floor"]
        assert below == sum(b * c < cost[0] for b, c in shapes)
        third = None
        if eng.state_stats is not None:
            cut = len(prompt) // PAGE * PAGE
            assert 128 < cut <= 128 + new
            third, _ = run(eng, prompt[:cut + 3] + tokens_of(20, seed=3))
            assert third.prefix_len == cut  # resumed from the snapshot there
        got[name] = (req, third)
    (narrow, n3), (wide, w3) = got["narrow"], got["wide"]
    assert wide.generated == narrow.generated and len(wide.generated) == N_OUT
    assert rel(wide.logit_rows, narrow.logit_rows) < TOL
    if n3 is not None:
        assert w3.generated == n3.generated
        assert rel(w3.logit_rows, n3.logit_rows) < TOL
    assert model[0].n_experts  # every case routes a wide launch's tokens


def test_an_engine_reads_its_width_from_its_weights(planned_width):
    """A float32 toy model streams 4 bytes a parameter: a floor of ~480
    tokens whatever its size, so a chunk of 16 widens to 512 — within the
    wave's tokens as they are NOW (the SLO ladder narrows a wave), never
    under the chunk; a pipeline keeps the chunk."""
    cfg = get_config("llama3-tiny").replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, num_slots=1024, page_size=4, max_batch=4,
                 max_seq_len=256, prefill_chunk=16)
    floor = eng.prefill_cost[0]
    assert 256 < floor < 512
    assert eng.prefill_width == chunk_width(16, floor, 4096) == 512
    eng.prefill_wave_tokens = 64
    assert eng.prefill_width == 64
    eng.prefill_wave_tokens = 8
    assert eng.prefill_width == 16
    # 40 tokens over a cached page: half their bucket, and the tail alone
    # (chunks of 16 would be three launches)
    eng.prefill_wave_tokens = 4096
    shared = tokens_of(8, seed=5)
    run(eng, shared + tokens_of(3, seed=6), n_out=2)
    _, shapes = run(eng, shared + tokens_of(40, seed=7), n_out=2)
    assert shapes == [(1, 32), (1, 16)]
    assert eng.prefill["by_chunk"][32] == 1
    assert sum(eng.prefill["by_chunk"].values()) == eng.prefill["launches"]


@pytest.mark.parametrize("preset,widens", [
    ("mellum2-tiny", True), ("glm-4.7-flash-tiny", True),
    ("glm-5.2-tiny", True), ("lfm2-tiny", False), ("laguna-tiny", False),
    ("ling-tiny", False), ("nemotron-tiny", False), ("phi4flash-tiny", False),
])
def test_a_model_that_resumes_keeps_the_chunk(planned_width, preset, widens):
    """A model with a state or a window tail launches several rows under
    ONE table width, so every bucket shares the ``[B, chunk]`` programs a
    burst of short rows compiled (a turn that resumes 2 k tokens back
    rides them); it keeps the chunk. The others widen to their floor."""
    cfg = get_config(preset)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), num_slots=4096,
                 page_size=PAGE, max_batch=4, max_seq_len=1024, prefill_chunk=64)
    assert eng._resumes != widens
    assert (eng.prefill_width > 64) == widens
    if widens:
        assert eng.prefill_width == chunk_width(
            64, eng.prefill_cost[0], 4096, eng._chunk_admits) >= 256


# Hkv, group, D of the cells' GQA models; (chunk: admitted) by the chunk
# kernel's own VMEM arithmetic at a 2 048-page table
GQA = {
    "qwen2-7b": (4, 7, 128, {512: True, 1024: True, 2048: True, 4096: False}),
    "mellum2": (4, 8, 128, {512: True, 1024: True, 2048: True, 4096: False}),
    "lfm2": (8, 4, 64, {512: True, 1024: True, 2048: True, 4096: False}),
    "laguna": (8, 8, 128, {512: True, 1024: True, 2048: False}),
    "nemotron": (2, 16, 128, {1024: True, 2048: True, 4096: True}),
}


@pytest.mark.parametrize("name", GQA)
def test_the_chunk_kernel_admits_a_width_by_its_own_vmem_arithmetic(name):
    """``paged_attention.chunk_admits``: the chunk's own K and V are held
    double-buffered beside the prefix block, and what is left must hold a
    program of 64 query rows (half an MXU tile: where a wider chunk was
    still seen to pay on the chip)."""
    hkv, group, d, want = GQA[name]
    for chunk, admitted in want.items():
        got = paged_attention.chunk_admits(
            chunk, group, hkv, PAGE, d, jnp.bfloat16, jnp.bfloat16, 2048)
        rows = group * paged_attention.chunk_query_tokens(
            chunk, group, hkv, PAGE, d, jnp.bfloat16, jnp.bfloat16, 2048)
        assert got == admitted == (rows >= 64), (chunk, rows)
    assert paged_attention.chunk_admits(  # a whole toy chunk in one program
        16, 2, 2, 4, 16, jnp.float32, jnp.float32, 64)


@pytest.mark.parametrize("heads,admitted", [
    (20, {1024: True, 2048: True, 4096: True}),  # GLM-4.7-Flash
    (32, {1024: True, 2048: True, 4096: False}),  # Ling-3.0-flash
    (64, {1024: True, 2048: True, 4096: False}),  # GLM-5.2
])
def test_the_latent_chunk_kernel_admits_what_its_vmem_limit_holds(
        heads, admitted):
    for chunk, want in admitted.items():
        assert mla_attention.chunk_admits(chunk, heads, 640, 512, 2) == want

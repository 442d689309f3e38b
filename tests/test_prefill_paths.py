"""Which prefill launch a request takes, and the launch's shape (PR 36).

A lone request with a cached prefix is a chunk group of one — the prefix
is read where it lies, in pages — and a lone request with nothing cached
keeps the dense single launch; both give the tokens a cold prefill gives.
The shape of a one-row launch is a pure function of the power-of-two
buckets of its lengths (``prefill_launch_shape``), so two requests of one
bucket pair never meet two programs: the benchmark's warm-up sends one
request a bucket pair (``benchmarks/run.py::census_cover``). Since PR 47
a request's launches are the planner's (``plan_prefill``: a tail may
take chunks of its own), still a function of its new tokens alone:
``tests/test_prefill_plan.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench  # the warm-up's cover (imports no jax)
from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.prefill_plan import (
    KV_BLOCK_PAGES as _KV_BLOCK_PAGES,
    plan_prefill,
    pow2_at_least as _pow2_at_least,
    prefill_launch_shape,
)
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.models.llama import prefill_chunk_paged
from radixmesh_tpu.obs.tracing import ProcessAccount

pytestmark = pytest.mark.quick

PAGE = 4
CHUNK = 16
N_OUT = 6
MODELS = {"dense-gqa": "llama3-tiny", "window-experts": "mellum2-tiny"}


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    cfg = get_config(MODELS[request.param]).replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(36))


def make_engine(model, **kw):
    cfg, params = model
    return Engine(cfg, params, num_slots=2048, page_size=PAGE, max_batch=4,
                  max_seq_len=256, prefill_chunk=CHUNK, **kw)


def tokens_of(n, seed):
    return np.random.default_rng([36, seed]).integers(1, 512, n).tolist()


def run(eng, prompt, n_out=N_OUT):
    req = eng.add_request(
        prompt, SamplingParams(max_new_tokens=n_out, return_logits=True))
    while eng.has_work():
        eng.step()
    return req


# ----------------------------------------------------------------------
# (a) the path
# ----------------------------------------------------------------------


@pytest.mark.parametrize("threshold", [1024, 0])
def test_a_lone_request_takes_the_path_its_prefix_gives_it(model, threshold):
    """``reuse > 0`` → ``chunk`` whatever ``long_prefill_threshold`` says;
    ``reuse == 0`` → ``dense`` up to the threshold, as before."""
    eng = make_engine(model, long_prefill_threshold=threshold)
    shared = tokens_of(24, seed=1)
    cold = run(eng, shared + tokens_of(3, seed=2))
    assert cold.prefix_len == 0
    assert eng._last_prefill_path == ("dense" if threshold else "chunk")
    before = dict(eng.prefill["by_path"])
    warm = run(eng, shared + tokens_of(5, seed=3))
    assert warm.prefix_len == 24
    assert eng._last_prefill_path == "chunk"
    assert eng.prefill["by_path"]["chunk"] == before["chunk"] + 1
    assert eng.prefill["by_path"]["dense"] == before["dense"]
    assert eng._wave_shapes == [(1, 16)]  # one row, one chunk of the 16-token floor


def test_the_dense_path_holds_no_prefix_code():
    """What ISSUE 36 took out of ``_prefill_dense`` stays out."""
    import inspect

    src = inspect.getsource(Engine._prefill_dense)
    for gone in ("pool.gather", "p_b", ".at[", "prefix_slots", "reuse"):
        assert gone not in src, gone


# ----------------------------------------------------------------------
# (b) the tokens: a prefix read in pages == the same prompt prefilled cold
# ----------------------------------------------------------------------

# shared tokens (24: the prefix ends on a page edge, 27: inside a page;
# the tree serves whole pages, 24 either way) x tail: new-token counts of
# 1, a page - 1, a page, a page + 1, one past a chunk, one past two chunks
CASES = [(24, 1), (24, 3), (24, 4), (24, 5), (24, 17), (24, 33),
         (27, 1), (27, 2), (27, 14), (27, 30)]


@pytest.mark.parametrize("shared_len,tail_len", CASES)
def test_prefix_read_in_pages_equals_a_cold_prefill(model, shared_len, tail_len):
    seed = 100 * shared_len + tail_len
    shared = tokens_of(shared_len, seed=seed)
    prompt = shared + tokens_of(tail_len, seed=seed + 1)
    eng = make_engine(model)
    run(eng, shared + tokens_of(2, seed=seed + 2), n_out=2)
    warm = run(eng, prompt)
    assert warm.prefix_len == 24 and eng._last_prefill_path == "chunk"
    cold_eng = make_engine(model)
    cold = run(cold_eng, prompt)
    assert cold.prefix_len == 0 and cold_eng._last_prefill_path == "dense"
    assert warm.generated == cold.generated and len(cold.generated) == N_OUT
    a, b = np.stack(warm.logit_rows), np.stack(cold.logit_rows)
    assert float(np.abs(a - b).max() / np.abs(b).max()) < 1e-4


# ----------------------------------------------------------------------
# (c) the shape function: pure, and closed over bucket pairs
# ----------------------------------------------------------------------


def _pair(new, reuse, page):  # census_cover's key of a prefill program
    return bench._pow2(new, 8), bench._pow2(reuse, page)


# name: (chunk, page, max_pages, new-token grid, cached-prefix grid,
#        distinct one-row shapes)
GRIDS = {
    # the chat cell: user turns 32-1 024 on a 32 grid, contexts to 8 k
    "chat": (512, 16, 2048, range(32, 1025, 32), range(16, 8193, 16), 5),
    # every length of a small engine, prefix 0 (a long cold prompt) too
    "small": (16, 4, 64, range(1, 65), range(0, 193, 4), 1),
    "floor": (64, 16, 24, range(1, 130), range(0, 257, 16), 3),
}


@pytest.mark.parametrize("grid", GRIDS)
def test_a_bucket_pair_has_exactly_one_one_row_shape(grid):
    chunk, page, max_pages, news, reuses, n_shapes = GRIDS[grid]
    kw = dict(chunk=chunk, page_size=page, max_pages=max_pages)
    by_pair: dict = {}
    census = [(reuse, new) for new in news for reuse in reuses]
    for reuse, new in census:
        shape = prefill_launch_shape(1, new, reuse + new, **kw)
        assert shape == prefill_launch_shape(1, new, reuse + new, **kw)
        by_pair.setdefault(_pair(new, reuse, page), set()).add(shape)
    assert all(len(s) == 1 for s in by_pair.values())
    shapes = set().union(*by_pair.values())
    # so the requests the benchmark's warm-up sends reach every one of them
    assert shapes == {prefill_launch_shape(1, new, reuse + new, **kw)
                      for reuse, new in bench.census_cover(census, page)}
    # a later PR that widens the set of one-row programs fails HERE
    assert len(shapes) == n_shapes
    width = _pow2_at_least(max_pages, floor=_KV_BLOCK_PAGES)
    assert {(b, w) for b, _, w in shapes} == {(1, width)}
    assert {c for _, c, _ in shapes} <= {
        _pow2_at_least(min(n, chunk), floor=16) for n in news}
    # PR 47: a lone request's launches are the planner's — the same set of
    # programs (a tail's chunks are the smaller members of it), whatever
    # the model's cost, and again reached by the warm-up's cover.
    for cost in ((120.0, 35.0), (1300.0, 280.0), (float("inf"), 35.0)):
        def launched(reuse, new):
            return {(p.B, p.C, p.pages) for p in plan_prefill(
                [(new, reuse)], floor_tokens=cost[0], launch_tokens=cost[1], **kw)}
        planned = set().union(*(launched(*pair) for pair in census))
        assert planned <= shapes
        assert planned == set().union(
            *(launched(*pair) for pair in bench.census_cover(census, page)))


@pytest.mark.parametrize("rows,width_selects", [(1, True), (2, False), (5, False),
                                                 (8, True)])
def test_groups_and_selecting_widths_keep_the_longest_rows_pages(rows, width_selects):
    """A group's width, and a one-row width where the width itself engages
    learned sparse attention, are what they were: the longest row's pages
    rounded up to a power of two (at least one attention block) — the
    box's, and under the planner (PR 47) every launch's that holds
    several rows, or one where the width selects."""
    for new, total in [(5, 9), (32, 1136), (32, 2064), (700, 5000), (9000, 9000)]:
        kw = dict(chunk=512, page_size=16, max_pages=2048,
                  width_selects=width_selects)
        B, C, maxp = prefill_launch_shape(rows, new, total, **kw)
        assert B == _pow2_at_least(rows, floor=1)
        assert C == _pow2_at_least(min(new, 512), floor=16)
        assert maxp == _pow2_at_least(-(-total // 16), floor=_KV_BLOCK_PAGES)
        wave = [(new, total - new)] + [(max(1, new - i), 0) for i in range(1, rows)]
        for cost in ((120.0, 35.0), (1300.0, 280.0)):
            for p in plan_prefill(wave, floor_tokens=cost[0],
                                  launch_tokens=cost[1], **kw):
                assert p.C <= C and p.B <= B
                assert p.pages == (maxp if p.B > 1 or width_selects else 2048)


# ----------------------------------------------------------------------
# (d) one bucket pair, two exact lengths: nothing compiles between them
# ----------------------------------------------------------------------

# (cached prefix, new tokens) twice, one bucket pair each time. In the
# second pair the totals lie either side of 32 pages: the width that a
# table cut to the exact total would double at.
SAME_PAIR = {
    "short": ((20, 5), (28, 7)),
    "across_a_width": ((100, 9), (124, 15)),
    "two_chunks": ((40, 17), (60, 31)),
}


@pytest.mark.parametrize("case", SAME_PAIR)
def test_two_lengths_of_one_bucket_pair_share_every_program(model, case):
    first, second = SAME_PAIR[case]
    assert _pair(first[1], first[0], PAGE) == _pair(second[1], second[0], PAGE)
    eng = make_engine(model)
    doc = tokens_of(160, seed=7)
    run(eng, doc, n_out=2)  # the tree holds the document
    acct = ProcessAccount(eng.spans).install()

    def admission_compiles(n0):  # what compiled since, under the admission
        snap = acct.snapshot()["compiles"]
        fresh = snap["last"][-(snap["n"] - n0):] if snap["n"] > n0 else []
        return [c["fun_name"] for c in fresh if c["span"] in (
            "rm.admit", "rm.prefill.group", "rm.first_token.sync")]

    try:
        seen = []
        for k, (reuse, new) in enumerate((first, second)):
            n0 = acct.snapshot()["compiles"]["n"]
            req = run(eng, doc[:reuse] + tokens_of(new, seed=8 + k), n_out=2)
            assert req.prefix_len == reuse and eng._last_prefill_path == "chunk"
            seen.append((prefill_chunk_paged._cache_size(), admission_compiles(n0)))
    finally:
        acct.uninstall()
    (size_1, _), (size_2, compiled_2) = seen
    assert size_2 == size_1  # the jit met no new shape
    assert compiled_2 == []  # and the admission compiled nothing at all


# ----------------------------------------------------------------------
# (d) a grouped wave through the chunk kernel itself (interpreted)
# ----------------------------------------------------------------------


def test_a_grouped_wave_through_the_kernel_equals_its_rows_alone(
        model, monkeypatch):
    """Three requests admitted as ONE chunk group over a cached document,
    their launches lowered through the Pallas chunk kernel (interpreted):
    rows of 33, 40 and 64 new tokens (one size bucket) ride three chunks of
    16 in a launch of four rows, and the row still live a fourth of one
    row (the planner, PR 47), so the launches hold query blocks with no
    token — the fourth row, on the scratch slot, and the third chunk of
    the two shorter rows — which the kernel answers with zeros. Every row's
    tokens equal what it generates alone on the reference path, and
    ``attn.chunk_query_blocks*`` equal the host's arithmetic on the rows'
    lengths."""
    import functools

    from radixmesh_tpu.engine import engine as engine_mod

    doc = tokens_of(24, seed=43)
    news = (33, 40, 64)
    prompts = [doc + tokens_of(n, seed=50 + n) for n in news]
    alone = []
    for p in prompts:
        eng = make_engine(model)
        run(eng, doc + tokens_of(2, seed=49), n_out=2)
        alone.append(run(eng, p).generated)

    monkeypatch.setattr(
        engine_mod, "prefill_chunk_paged", functools.partial(
            prefill_chunk_paged, use_kernel=True, interpret=True))
    eng = make_engine(model)
    run(eng, doc + tokens_of(2, seed=49), n_out=2)
    before = dict(eng.attn_window)
    waves = eng.prefill["waves"]
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=N_OUT))
            for p in prompts]
    while eng.has_work():
        eng.step()
    assert eng.prefill["waves"] == waves + 1 and eng._last_prefill_path == "chunk"
    assert [r.generated for r in reqs] == alone
    # A program holds the whole 16-token chunk of a row (16 x G rows <=
    # 1 024): 4 rows x 3 chunks and 1 row x 1, less the chunks a row has
    # tokens in.
    blocks = eng.attn_window["chunk_query_blocks"] - before["chunk_query_blocks"]
    skipped = (eng.attn_window["chunk_query_blocks_skipped"]
               - before["chunk_query_blocks_skipped"])
    assert blocks == 4 * 3 + 1
    assert skipped == blocks - sum(-(-n // CHUNK) for n in news) == 3

"""The wave planner as a pure function (PR 47, engine/prefill_plan.py).

Over the length grids of every traffic file the benchmark has, under the
cost of every model that serves one, and waves of 1-32 rows: a plan
covers each row's new tokens exactly once and in order; its launches are
members of a closed shape set that the test enumerates and pins by count;
it launches no more tokens than the one box of the rule before; a launch
of one row is a launch the same request gets when it is admitted alone
(what the warm-up's census cover compiles), a launch of several rows has
the box's chunk and width (what its bursts compile); and a cached-prefix
wave on a model with experts is left whole."""

import json
import pathlib

import numpy as np
import pytest

from radixmesh_tpu.engine.prefill_plan import (
    KV_BLOCK_PAGES,
    boxed_tokens,
    chunk_width,
    launch_cost,
    plan_prefill,
    pow2_at_least,
    prefill_launch_shape,
    subwaves,
)

pytestmark = pytest.mark.quick

TRAFFIC = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "traffic"
CHUNK, PAGE, WAVE_TOKENS = 512, 16, 4096

# (streamed GB, G parameters a token meets) of the models the cells serve,
# from ``Engine.prefill_cost``'s arithmetic on their weights: what the
# planner weighs is their ratio (``launch_cost``).
MODELS = {
    "qwen2-7b-w8a16": (7.08, 7.07),
    "mellum2-12b-l12": (10.48, 1.08),
    "glm-4.7-flash-l8": (9.70, 0.89),
    "glm-5.2-ep16": (10.76, 1.87),
    "lfm2-24b-a2b-ep8": (7.52, 1.07),
    "phi-4-mini-flash": (7.71, 3.85),
    "laguna-xs.2-l5": (7.33, 0.54),
}
# cell: (traffic, model, max_seq_len, width_selects, one_width,
#        closed shapes pinned)
CELLS = {
    "qwen2-7b.batch-unshared": ("batch-unshared", "qwen2-7b-w8a16", 32768, False, False, 16),
    "qwen2-7b.chat-sessions": ("chat-sessions", "qwen2-7b-w8a16", 32768, False, False, 106),
    "mellum2-12b-l12.batch-longtail": ("batch-longtail", "mellum2-12b-l12", 32768, False, False, 25),
    "glm-4.7-flash-l8.batch-longtail": ("batch-longtail", "glm-4.7-flash-l8", 65536, False, False, 25),
    "glm-4.7-flash-l8.docs-hotset": ("docs-hotset", "glm-4.7-flash-l8", 65536, False, False, 35),
    "glm-5.2-ep16.repo-hotset": ("repo-hotset", "glm-5.2-ep16", 65536, True, False, 20),
    "lfm2-24b-a2b-ep8.agent-sessions": ("agent-sessions", "lfm2-24b-a2b-ep8", 32768, False, True, 24),
    "phi-4-mini-flash.reasoning-sessions": ("reasoning-sessions", "phi-4-mini-flash", 32768, False, True, 20),
    "laguna-xs.2-l5.agent-sessions": ("agent-sessions", "laguna-xs.2-l5", 32768, False, True, 24),
}


def grid(spec):
    if spec is None:
        return [0]
    return list(range(spec["min"], spec["max"] + 1, spec["quantum"]))


def cell(name):
    traffic, model, max_seq, width_selects, one_width, pinned = CELLS[name]
    params = json.loads((TRAFFIC / f"{traffic}.json").read_text())["params"]
    news = grid(params["turn_tokens"])
    # A cached prefix: a system prompt, and (sessions) a few turns of history.
    turns = params["turns"].get("max", 1)
    cached = sorted({s + k * (max(news) + params["max_tokens"]["max"])
                     for s in grid(params.get("system_len"))
                     for k in range(min(turns, 3))})
    gb, g_met = MODELS[model]
    floor_tokens, launch_tokens = launch_cost(gb * 1e9, 2 * g_met * 1e9)
    kw = dict(chunk=CHUNK, page_size=PAGE, max_pages=max_seq // PAGE,
              floor_tokens=floor_tokens, launch_tokens=launch_tokens,
              width_selects=width_selects, one_width=one_width)
    return news, cached, kw, pinned


def closed_shapes(news, cached, kw):
    """Every ``(B, C, width)`` a plan may launch for rows off these grids:
    several rows at the chunk of a bucket of new tokens under a table of a
    width some total takes, a power of two of them up to the sub-wave's
    slice; one row at any power-of-two chunk up to its bucket's, under the
    lone width (where the width selects: under any)."""
    shape_kw = {k: kw[k] for k in ("chunk", "page_size", "max_pages",
                                   "width_selects", "one_width")}
    several, widths, lone = set(), set(), set()
    for n in news:
        for c in cached:
            _, c0, width = prefill_launch_shape(2, n, n + c, **shape_kw)
            widths.add(width)
            b = 2
            while b <= max(2, WAVE_TOKENS // c0):
                several.add((b, c0, width))
                b *= 2
            lone_width = prefill_launch_shape(1, n, n + c, **shape_kw)[2]
            chunk = 16
            while chunk <= c0:
                lone.add((1, chunk, lone_width))
                chunk *= 2
    if kw["width_selects"]:  # a seat of one takes the sub-wave's width
        lone = {(1, c, w) for _, c, _ in lone for w in widths}
    return several | lone


def waves(news, cached, seed):
    rng = np.random.default_rng([47, seed])
    for n in range(1, 33):
        for _ in range(12):
            rows = [(int(rng.choice(news)), int(rng.choice(cached)))
                    for _ in range(n)]
            for sub in subwaves([r[0] for r in rows], chunk=CHUNK,
                                wave_tokens=WAVE_TOKENS):
                yield [rows[i] for i in sub]


def covered(rows, plan):
    """Each row's new tokens exactly once, in order, never past a chunk."""
    at = [0] * len(rows)
    for launch in plan:
        assert len(set(launch.rows)) == len(launch.rows) <= launch.B
        for row, start, n in zip(launch.rows, launch.starts, launch.lens):
            assert start == at[row] and 0 < n <= launch.C
            at[row] += n
    return at == [new for new, _ in rows]


@pytest.mark.parametrize("name", CELLS)
def test_a_plan_covers_its_rows_inside_a_closed_shape_set(name):
    news, cached, kw, pinned = cell(name)
    allowed = closed_shapes(news, cached, kw)
    # a later PR that widens the set of prefill programs fails HERE
    assert len(allowed) == pinned
    for rows in waves(news, cached, seed=1):
        plan = plan_prefill(rows, **kw)
        assert covered(rows, plan)
        shapes = {(launch.B, launch.C, launch.pages) for launch in plan}
        assert shapes <= allowed, (rows, shapes - allowed)
        launched = sum(launch.B * launch.C for launch in plan)
        assert launched <= boxed_tokens([n for n, _ in rows], chunk=CHUNK)


@pytest.mark.parametrize("name", CELLS)
def test_what_a_wave_launches_the_warm_up_has_compiled(name):
    """One row: a launch of the row's own plan when it is admitted alone,
    at the same place (every length is sent alone: ``census_cover``).
    Several rows: the box's chunk and width, and no more rows than the
    box (bursts of alike rows)."""
    news, cached, kw, _ = cell(name)
    shape_kw = {k: kw[k] for k in ("chunk", "page_size", "max_pages",
                                   "width_selects", "one_width")}
    for rows in waves(news, cached, seed=2):
        box = prefill_launch_shape(
            len(rows), max(n for n, _ in rows), max(n + c for n, c in rows),
            **shape_kw)
        for launch in plan_prefill(rows, **kw):
            if launch.B > 1:
                assert (launch.C, launch.pages) == box[1:] and launch.B <= box[0]
                continue
            (row,), (start,) = launch.rows, launch.starts
            alone = plan_prefill([rows[row]], **kw)
            if not kw["width_selects"]:
                assert launch.pages == alone[0].pages
            assert (start, launch.C) in {(a.starts[0], a.C) for a in alone}


@pytest.mark.parametrize("name,rows", [
    *(("glm-4.7-flash-l8.docs-hotset", n) for n in (1, 2, 3, 5, 8)),
    # (five rows of the repo geometry are four and one: three padded rows
    # of 256 cost its model more than the 692 tokens a launch is worth)
    *(("glm-5.2-ep16.repo-hotset", n) for n in (1, 2, 3, 4, 8)),
])
def test_a_cached_prefix_wave_on_experts_is_left_whole(name, rows):
    """64-256 new tokens on 16 k-49 k of context, every launch streaming
    every expert: a launch is its fixed cost, and the plan is the box."""
    news, cached, kw, _ = cell(name)
    rng = np.random.default_rng([47, 3, rows])
    for _ in range(50):
        bucket = int(rng.choice([64, 128, 256]))
        alike = [n for n in news if pow2_at_least(n, 16) == bucket]
        wave = [(int(rng.choice(alike)), int(rng.choice(cached)))
                for _ in range(rows)]
        plan = plan_prefill(wave, **kw)
        assert len(plan) == 1 and covered(wave, plan)
        assert plan[0].B * plan[0].C == boxed_tokens(
            [n for n, _ in wave], chunk=CHUNK)


def test_the_batch_cell_launches_the_tokens_it_has():
    """What the issue is for: waves of ~8 unshared prompts of 256-1 024
    tokens pad 39% as one box, and under 10% as planned launches."""
    news, cached, kw, _ = cell("qwen2-7b.batch-unshared")
    rng = np.random.default_rng([47, 4])
    new = launched = boxed = launches = subs = 0
    for _ in range(400):
        rows = [(int(rng.choice(news)), 0) for _ in range(8)]
        for sub in subwaves([r[0] for r in rows], chunk=CHUNK,
                            wave_tokens=WAVE_TOKENS):
            plan = plan_prefill([rows[i] for i in sub], **kw)
            new += sum(rows[i][0] for i in sub)
            launched += sum(launch.B * launch.C for launch in plan)
            boxed += boxed_tokens([rows[i][0] for i in sub], chunk=CHUNK,
                                  dense_up_to=1024)
            launches += len(plan)
            subs += 1
    assert 0.37 < 1 - new / boxed < 0.42
    assert 1 - new / launched < 0.10
    assert launches / subs < 8


def test_five_alike_rows_are_four_and_one_and_a_tail_goes_alone():
    kw = dict(chunk=512, page_size=16, max_pages=2048,
              floor_tokens=120.0, launch_tokens=35.0)
    five = plan_prefill([(512, 0)] * 5, **kw)
    assert [(p.B, p.C, p.pages) for p in five] == [(4, 512, 32), (1, 512, 2048)]
    # 544 new tokens are 512 + 32, not 1 024; a cached prefix changes nothing
    for cached in (0, 4096):
        lone = plan_prefill([(544, cached)], **kw)
        assert [(p.B, p.C, p.starts, p.lens) for p in lone] == [
            (1, 512, (0,), (512,)), (1, 32, (512,), (32,))]
    # rows of two chunks: three are two and one, the second level launches
    # only the row still whole, and the tails go alone (288 = 256 + 32)
    ragged = plan_prefill([(1024, 0), (800, 0), (544, 0)], **kw)
    assert [(p.B, p.C, p.rows, p.lens) for p in ragged] == [
        (2, 512, (0, 1), (512, 512)),
        (1, 512, (2,), (512,)),
        (1, 512, (0,), (512,)),
        (1, 256, (1,), (256,)),
        (1, 32, (1,), (32,)),
        (1, 32, (2,), (32,)),
    ]


def test_a_launch_bound_by_its_weights_keeps_the_box():
    """``floor_tokens`` beyond the box (a pipeline schedule: infinite): the
    fewest launches, each the box's shape with the rows still live."""
    kw = dict(chunk=512, page_size=16, max_pages=2048,
              floor_tokens=float("inf"), launch_tokens=35.0)
    plan = plan_prefill([(1024, 0), (800, 0), (544, 0)], **kw)
    assert [(p.B, p.C, p.rows) for p in plan] == [
        (4, 512, (0, 1, 2)), (4, 512, (0, 1, 2))]
    assert KV_BLOCK_PAGES == 32 and plan[0].pages == 64


# ----------------------------------------------------------------------
# PR 54: the chunk's width comes from the same cost
# ----------------------------------------------------------------------

# What ``Engine.prefill_cost`` reads for the two models the cold-prompt
# cells of PR 50 and PR 53 added (same arithmetic as ``MODELS``).
MODELS |= {
    "ling-3.0-flash-ep8": (5.63, 0.586),
    "nemotron-3-nano-ep8": (4.71, 0.782),
}
LONGTAIL = json.loads((TRAFFIC / "batch-longtail.json").read_text())["params"]
CELL_FILES = TRAFFIC.parent / "cells"


def cost_of(model):
    gb, g_met = MODELS[model]
    return launch_cost(gb * 1e9, 2 * g_met * 1e9)


def admissions(seed, n_admissions=60, cached=(0, 0, 0, 512, 4096)):
    """Seeded admissions of 1-16 rows of (new tokens, cached tokens): new
    tokens log-normal around 700 on a 16 grid up to 8 192 — every bucket,
    tails of every size."""
    rng = np.random.default_rng([54, seed])
    for _ in range(n_admissions):
        n = int(rng.integers(1, 17))
        news = np.clip(np.exp(rng.normal(np.log(700), 1.0, n)), 16, 8192)
        yield [(int(round(x / 16)) * 16, int(rng.choice(cached))) for x in news]


def planned(rows, width, cost, **shape):
    """``(sub-wave rows, its plan)`` of one admission, as ``Engine._admit``
    and ``_prefill_group`` draw them."""
    wide = {} if width == CHUNK else {"width": width}  # the parent has none
    kw = dict(chunk=CHUNK, page_size=PAGE, max_pages=2048,
              floor_tokens=cost[0], launch_tokens=cost[1], **shape, **wide)
    for sub in subwaves([n for n, _ in rows], chunk=CHUNK,
                        wave_tokens=WAVE_TOKENS, **wide):
        yield sub, plan_prefill([rows[i] for i in sub], **kw)


# sha256 of the launches of ``admissions(seed)`` under the planner of the
# parent commit (5d3249c: ``subwaves`` and ``plan_prefill`` at chunk 512),
# and how many they are.
GOLDEN = {
    ("qwen2-7b-w8a16", 0): ("bc26678024e1a3d8", 1077),
    ("qwen2-7b-w8a16", 1): ("50fe86cc1653e190", 1051),
    ("qwen2-7b-w8a16", 2): ("1f0de84f14d58481", 1151),
    ("qwen2-7b-w8a16", 3): ("1ed0d4ffed85f952", 1178),
    ("phi-4-mini-flash", 0): ("a6c64c90dd2b3f98", 894),
    ("phi-4-mini-flash", 1): ("e2afc836ddf7f1cb", 867),
    ("phi-4-mini-flash", 2): ("af64727c8ad3f6a6", 950),
    ("phi-4-mini-flash", 3): ("83b28215b0f63417", 975),
}


def digest(model, seed, width):
    import hashlib

    launches = [
        [sub, [list(map(list, (p.rows, p.starts, p.lens))) + [p.B, p.C, p.pages]
               for p in plan]]
        for rows in admissions(seed)
        for sub, plan in planned(rows, width, cost_of(model))]
    text = json.dumps(launches, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()[:16], sum(
        len(plan) for _, plan in launches)


@pytest.mark.parametrize("model,seed", GOLDEN)
def test_a_floor_under_the_chunk_keeps_the_plans_of_the_parent(model, seed):
    """Qwen2-7B int8 (floor 120) and Phi-4 (241): the width is the chunk,
    and every sub-wave of every admission is launched as it was, launch
    for launch."""
    cost = cost_of(model)
    assert cost[0] < CHUNK
    width = chunk_width(CHUNK, cost[0], WAVE_TOKENS)
    assert width == CHUNK
    assert digest(model, seed, width) == GOLDEN[model, seed]


@pytest.mark.parametrize("chunk,floor,wave_tokens,admits,width", [
    (512, 120.1, 4096, None, 512),
    (512, 241.0, 4096, None, 512),
    (512, 692.0, 4096, None, 1024),
    (512, 845.0, 4096, None, 1024),
    (512, 1167.0, 4096, None, 2048),
    (512, 1633.0, 4096, None, 2048),
    (512, 1167.0, 4096, lambda w: w <= 1024, 1024),  # a kernel refuses 2 048
    (512, 1633.0, 4096, lambda w: False, 512),  # and never under the chunk
    (512, 5000.0, 4096, None, 4096),  # within a wave's tokens
    (512, 1167.0, 1024, None, 1024),
    (512, 1167.0, 3000, None, 2048),  # a wave that is no power of two
    (512, 1167.0, 256, None, 512),
    (512, float("inf"), 4096, None, 4096),
    (16, 481.0, 4096, None, 512),  # a toy chunk under a real floor
    (2048, 120.0, 4096, None, 2048),
])
def test_the_width_holds_the_floor_within_a_wave_and_the_kernels(
        chunk, floor, wave_tokens, admits, width):
    kw = {} if admits is None else {"admits": admits}
    assert chunk_width(chunk, floor, wave_tokens, **kw) == width


@pytest.mark.parametrize("model,seed", [
    (m, s) for m in ("lfm2-24b-a2b-ep8", "mellum2-12b-l12", "laguna-xs.2-l5")
    for s in range(3)])
def test_a_wide_plan_fills_its_launches_within_a_wave(model, seed):
    """Floors of 845, 1 167 and 1 633: no launch holds more than a wave's
    tokens, every row's tokens are launched exactly once and in order, a
    row's first launch holds no padded token of its own (its bucket's
    chunk is one every row of the bucket fills), a row with more than a
    whole width of tokens left is never sent under the floor, and buckets
    up to twice the chunk are planned as they were."""
    cost = cost_of(model)
    width = chunk_width(CHUNK, cost[0], WAVE_TOKENS)
    assert width == {"lfm2-24b-a2b-ep8": 1024}.get(model, 2048) >= cost[0]
    wide = narrow = 0
    for rows in admissions(seed):
        before = {tuple(sub): plan for sub, plan in planned(rows, CHUNK, cost)}
        for sub, plan in planned(rows, width, cost):
            mine = [rows[i] for i in sub]
            assert covered(mine, plan)
            if max(n for n, _ in mine) <= 2 * CHUNK:
                assert plan == before[tuple(sub)]
            for launch in plan:
                assert launch.B * launch.C <= WAVE_TOKENS
                left = [mine[r][0] - s for r, s in zip(launch.rows, launch.starts)]
                if max(left) > width:
                    assert launch.C == width and launch.B * launch.C >= cost[0]
                if launch.starts[0] == 0 and max(n for n, _ in mine) > CHUNK:
                    assert set(launch.lens) == {launch.C}
            wide += len(plan)
        narrow += sum(map(len, before.values()))
    assert wide < 0.9 * narrow  # and it is fewer launches


def _programs(rows, width, cost, one_width, threshold=1024):
    """The prefill programs one cold admission reaches, as ``_admit``
    chooses them: a lone prompt bound by the weights it streams takes
    ``prefill_forward`` at its bucket, everything else the plan's
    launches (``planned`` gives an engine 2 048 pages: a lone row's)."""
    out = set()
    for sub, plan in planned(rows, width, cost, one_width=one_width):
        n = rows[sub[0]][0]
        if len(sub) == 1 and n <= threshold and pow2_at_least(n) <= cost[0]:
            out.add(("dense", pow2_at_least(n)))
        else:
            out |= {("chunk", p.B, p.C, p.pages) for p in plan}
    return out


# cell: (model, its chunk programs take one width whatever the rows)
LONGTAIL_CELLS = {
    "mellum2-12b-l12.batch-longtail": ("mellum2-12b-l12", False),
    "glm-4.7-flash-l8.batch-longtail": ("glm-4.7-flash-l8", False),
    "ling-3.0-flash-ep8.batch-longtail": ("ling-3.0-flash-ep8", True),
}


@pytest.mark.parametrize("width", [1024, 2048])
@pytest.mark.parametrize("name", LONGTAIL_CELLS)
def test_a_wide_longtail_wave_launches_what_the_warm_up_compiled(name, width):
    """Shape closure at the widths the three cold-prompt cells may take
    (2 048 by their floors; 1 024 where a kernel refuses that): whatever
    a mixed admission of the cell's own length distribution launches, the
    cell's warm-up has compiled — its census cover (every length alone)
    and its bursts (``n`` alike rows behind a blocker), read from the cell
    file as ``benchmarks/run.py::warm_shapes`` reads it — and the programs
    are no more than today's and five (a model whose launches all take
    one table width shared ``[B, 512]`` between its buckets: Ling)."""
    from benchmarks.generators.sessions import draw

    model, one_width = LONGTAIL_CELLS[name]
    cost = cost_of(model)
    assert chunk_width(CHUNK, cost[0], WAVE_TOKENS) == 2048
    warm = json.loads((CELL_FILES / f"{name}.json").read_text())["warmup"]
    lengths = grid(LONGTAIL["turn_tokens"])
    as_list = lambda x: x if isinstance(x, list) else [x]
    bursts = [[(t, 0)] * n for b in warm["bursts"]
              for n in as_list(b["n"]) for t in as_list(b["new_tokens"])]
    blockers = [[(b["blocker"], 0)] for b in warm["bursts"]]

    def compiled(w):
        return set().union(*(
            _programs(rows, w, cost, one_width)
            for rows in [[(n, 0)] for n in lengths] + bursts + blockers))

    have = compiled(width)
    assert len(have) <= len(compiled(CHUNK)) + 5
    rng = np.random.default_rng([54, 3])
    for _ in range(400):
        n = int(rng.integers(1, 13))
        rows = [(int(t), 0) for t in draw(LONGTAIL["turn_tokens"], rng, n)]
        launched = _programs(rows, width, cost, one_width)
        assert launched <= have, (rows, launched - have)

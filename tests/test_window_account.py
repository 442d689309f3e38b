"""The window's own account (``obs/tracing.py``; ``/debug/state``
``engine.prefill``, ``engine.slow_steps``, ``process``): what the prefill
launch site counts, which steps are recorded as slow, and what the
process's ``gc`` / ``jax.monitoring`` hooks count. No case waits on the
wall clock: the slow-step cases hand the span book a clock of their own."""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from radixmesh_tpu.engine.engine import _SLOW_STEP_RING, _SLOW_STEP_S, Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.obs.tracing import (
    _CACHE_RETRIEVAL,
    _KINDS,
    ProcessAccount,
    SpanBook,
)

PAGE = 4


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, name, **kw):
    cfg, params = model
    return Engine(cfg, params, num_slots=1024, page_size=PAGE, max_batch=4,
                  max_seq_len=256, name=name, **kw)


def _run(eng, *prompts, new=3):
    reqs = [eng.add_request(list(p), SamplingParams(max_new_tokens=new))
            for p in prompts]
    while eng.has_work():
        eng.step()
    return reqs


# name: (prompts of the wave under test, the prefix the tree holds for
# each of them, path)
SHARED = list(range(100, 120))
WAVES = {
    "dense_cold": ([range(1, 21)], 0, "dense"),
    # a lone request over a cached prefix is a group of one (PR 36)
    "lone_prefix": ([SHARED[:16] + [7, 8, 9, 10, 11]], 16, "chunk"),
    "chunk_cold": ([range(1, 11), range(30, 43)], 0, "chunk"),
    "chunk_prefix": ([SHARED[:16] + [7, 8, 9], SHARED[:16] + [5, 6, 7, 8, 9, 10]],
                     16, "chunk"),
}


@pytest.mark.parametrize("case", WAVES)
def test_prefill_launch_site_counts_its_work(model, case):
    prompts, cached, path = WAVES[case]
    eng = _engine(model, f"acct-{case}")
    if cached:
        _run(eng, SHARED)  # publishes the prefix
    before = {**eng.prefill, "by_path": dict(eng.prefill["by_path"]),
              "by_chunk": dict(eng.prefill["by_chunk"])}
    admit_before = eng.spans.s["rm.admit"]
    reqs = _run(eng, *prompts)
    assert all(r.prefix_len == cached for r in reqs)
    p = eng.prefill
    new = sum(len(list(q)) - cached for q in prompts)
    assert p["waves"] - before["waves"] == 1  # one admission wave
    assert p["rows"] - before["rows"] == len(prompts)
    assert p["tokens_new"] - before["tokens_new"] == new
    launched = p["tokens_launched"] - before["tokens_launched"]
    assert launched == sum(b * c for b, c in eng._wave_shapes) >= new
    # every launch is counted under its chunk, and under the floor or not
    launches = p["launches"] - before["launches"]
    assert launches == len(eng._wave_shapes) == sum(
        n - before["by_chunk"].get(c, 0) for c, n in p["by_chunk"].items())
    assert set(p["by_chunk"]) >= {c for _, c in eng._wave_shapes}
    below = p["launches_below_floor"] - before["launches_below_floor"]
    assert below == sum(b * c < eng.prefill_cost[0] for b, c in eng._wave_shapes)
    assert {k: v - before["by_path"][k] for k, v in p["by_path"].items()} == {
        "dense": int(path == "dense"), "chunk": int(path == "chunk"), "sp": 0}
    assert eng._last_prefill_path == path
    wall = p["wall_s"] - before["wall_s"]
    assert 0.0 < wall <= eng.spans.s["rm.admit"] - admit_before


class _Clock:
    """Moves 0.1 ms a reading, and by ``stall[name]`` seconds at the first
    reading made while span ``name`` is the innermost open one."""

    def __init__(self, book):
        self.book, self.t, self.stall = book, 1000.0, {}

    def __call__(self):
        self.t += 1e-4 + self.stall.pop(self.book.innermost, 0.0)
        return self.t


@pytest.fixture
def clocked(model):
    eng = _engine(model, "acct-slow")
    _run(eng, range(1, 17))  # compiles; real clock
    eng.spans.clock = clock = _Clock(eng.spans)
    assert eng.slow_steps["n"] == 0 and eng.slow_steps["threshold_s"] == _SLOW_STEP_S
    return eng, clock


def test_long_decode_side_span_is_one_record_named_first(clocked):
    eng, clock = clocked
    req = eng.add_request(list(range(40, 56)), SamplingParams(max_new_tokens=6))
    eng.step()  # admits, prefills, first decode launch
    assert eng.slow_steps["n"] == 0
    clock.stall["rm.decode.sync"] = 5.0
    eng.step()
    slow = eng.slow_steps
    assert slow["n"] == 1 and slow["s"] == pytest.approx(5.0, abs=0.05)
    (rec,) = slow["last"]
    assert rec["spans"][0] == ["rm.decode.sync", pytest.approx(5.0, abs=0.01)]
    assert rec["step_s"] == pytest.approx(5.0, abs=0.05) == rec["excess_s"]
    assert rec["launch"] == "decode" and rec["rows"] == 1 and rec["waiting"] == 0
    assert rec["prefill_tokens_launched"] == 0 and "gc" not in rec
    while eng.has_work():
        eng.step()
    assert slow["n"] == 1 and len(req.output_tokens) == 6


def test_step_long_by_its_own_prefill_at_the_running_rate_is_none(clocked):
    eng, clock = clocked
    # Re-base the running rate on this clock: 16 launched tokens in 0.5 s.
    eng.prefill.update(tokens_launched=0, wall_s=0.0)
    clock.stall["rm.first_token.sync"] = 0.5
    _run(eng, range(200, 216))
    assert eng.prefill["tokens_launched"] == 16 and eng.slow_steps["n"] == 0
    # 64 tokens at that rate are 2 s: a long step, and no slow one.
    clock.stall["rm.first_token.sync"] = 2.0
    _run(eng, range(300, 364))
    assert eng.prefill["tokens_launched"] == 80 and eng.slow_steps["n"] == 0
    # The same 2 s behind a 16-token wave are 1.5 s over its share.
    clock.stall["rm.first_token.sync"] = 2.0
    _run(eng, range(400, 416))
    slow = eng.slow_steps
    assert slow["n"] == 1 and 1.0 < slow["s"] < 2.0
    rec = slow["last"][0]
    assert [n for n, _ in rec["spans"][:2]] == ["rm.admit", "rm.first_token.sync"]
    assert rec["prefill_tokens_launched"] == 16


def test_ring_is_bounded_and_counts_only_grow(clocked):
    eng, clock = clocked
    eng.add_request(list(range(40, 56)), SamplingParams(max_new_tokens=_SLOW_STEP_RING + 12))
    eng.step()
    seen = []
    while eng.has_work():
        clock.stall["rm.decode.dispatch"] = 1.5
        eng.step()
        seen.append((eng.slow_steps["n"], eng.slow_steps["s"]))
    assert seen == sorted(seen) and seen[-1][0] > _SLOW_STEP_RING
    assert len(eng.slow_steps["last"]) == _SLOW_STEP_RING
    assert [r["at_s"] for r in eng.slow_steps["last"]] == sorted(
        r["at_s"] for r in eng.slow_steps["last"])


def test_gc_callback_counts_by_generation_and_leaves_no_trace():
    found = list(gc.callbacks)
    acct = ProcessAccount().install()
    try:
        assert acct.install() is acct and len(gc.callbacks) == len(found) + 1
        before = acct.snapshot()["gc"]
        gc.collect()
        after = acct.snapshot()["gc"]
    finally:
        acct.uninstall()
    assert gc.callbacks == found
    assert after["2"]["n"] == before["2"]["n"] + 1
    assert after["2"]["s"] >= before["2"]["s"] >= 0.0
    assert after["2"]["max_s"] > 0.0 and acct.gc_n >= 1 and acct.gc_s > 0.0
    n = acct.gc_n
    gc.collect()  # uninstalled: counts nothing
    assert acct.gc_n == n
    acct.uninstall()  # twice is harmless


@pytest.mark.parametrize("where", ["other_thread", "engine_thread_in_span"])
def test_compile_listener_names_the_program_and_where_it_ran(where):
    book = SpanBook()
    acct = ProcessAccount(book).install()

    def window_account_probe(x):
        return x * 3 + (1 if where == "other_thread" else 2)

    try:
        before = acct.snapshot()["compiles"]
        if where == "other_thread":
            jax.jit(window_account_probe)(jnp.arange(5)).block_until_ready()
            want = threading.current_thread().name
        else:
            with book.step():
                with book.span("rm.decode.dispatch"):
                    jax.jit(window_account_probe)(jnp.arange(5)).block_until_ready()
            want = "rm.decode.dispatch"
        after = acct.snapshot()["compiles"]
    finally:
        acct.uninstall()
    assert after["n"] >= before["n"] + 1 and after["s"] > before["s"]
    last = after["last"][-1]
    assert "window_account_probe" in last["fun_name"]
    assert last["span"] == want and last["seconds"] > 0.0
    assert type(last["cache_hit"]) is bool and after["cache_hits"] <= after["n"]
    assert len(after["last"]) <= ProcessAccount.RING
    jax.jit(lambda x: x - 7)(jnp.arange(3))  # uninstalled: counts nothing
    assert acct.snapshot()["compiles"]["n"] == after["n"]


# ---- the set-up's account: three kinds of interval, outermost only --------

TRACE, LOWER, BACKEND = _KINDS  # the events, in the order a program has them
_OLD_KEYS = {"n", "s", "cache_hits", "last"}


def _opened(acct, event, name):
    acct._on_open(event, 0.0, fun_name=name)


def _closed(acct, event, name, seconds):
    acct._on_duration(event, seconds, fun_name=name)


# name: (what JAX reports, in order: ("open" | "close" | "hit", event, name,
# seconds), and what ``compiles`` must read afterwards)
NESTINGS = {
    "trace_in_trace": (
        [("open", TRACE, "outer"), ("open", TRACE, "inner"),
         ("close", TRACE, "inner", 1.0), ("close", TRACE, "outer", 3.0)],
        {"trace_s": 3.0, "lower_s": 0.0, "s": 0.0, "n": 0, "programs": 1},
    ),
    "trace_in_lowering": (
        [("open", LOWER, "jit(outer)"), ("open", TRACE, "less"),
         ("close", TRACE, "less", 0.5), ("close", LOWER, "jit(outer)", 2.0)],
        {"trace_s": 0.0, "lower_s": 2.0, "s": 0.0, "n": 0, "programs": 1},
    ),
    # ensure_compile_time_eval: the compilation keeps its place in n and s,
    # and the trace around it gives those seconds up
    "compile_in_trace": (
        [("open", TRACE, "outer"), ("open", BACKEND, "jit(iota)"),
         ("close", BACKEND, "jit(iota)", 0.5), ("close", TRACE, "outer", 2.0)],
        {"trace_s": 1.5, "lower_s": 0.0, "s": 0.5, "n": 1, "programs": 2},
    ),
    "all_three_then_a_cache_hit": (
        [("open", TRACE, "outer"), ("close", TRACE, "outer", 0.25),
         ("open", LOWER, "jit(outer)"), ("close", LOWER, "jit(outer)", 0.5),
         ("open", BACKEND, "jit(outer)"), ("hit",),
         ("close", BACKEND, "jit(outer)", 0.125)],
        {"trace_s": 0.25, "lower_s": 0.5, "s": 0.125, "n": 1, "programs": 1, "cache_hits": 1, "cache_load_s": 0.125},
    ),
    "installed_inside_a_compilation": (
        [("close", BACKEND, "jit(outer)", 0.75)],
        {"trace_s": 0.0, "lower_s": 0.0, "s": 0.75, "n": 1, "programs": 1, "cache_hits": 0, "cache_load_s": 0.0},
    ),
}


@pytest.mark.parametrize("case", NESTINGS)
def test_a_second_goes_to_the_outermost_interval_and_to_nothing_else(case):
    events, want = NESTINGS[case]
    acct = ProcessAccount()  # not installed: the listeners are called by hand
    for kind, *rest in events:
        if kind == "open":
            _opened(acct, *rest)
        elif kind == "close":
            _closed(acct, *rest)
        else:
            acct._on_duration(_CACHE_RETRIEVAL, 0.01)
    c = acct.snapshot()["compiles"]
    assert {k: c[k] for k in want} == want
    assert _OLD_KEYS <= set(c) and len(c["last"]) == c["n"]
    # both tables sum to the totals; "outer" and "jit(outer)" are one key
    for table in ("by_program", "by_span"):
        rows = c[table].values()
        assert sum(r["n"] for r in rows) == c["n"]
        assert sum(r["backend_s"] for r in rows) == c["s"]
        assert sum(r["trace_s"] for r in rows) == c["trace_s"]
        assert sum(r["lower_s"] for r in rows) == c["lower_s"]
    assert "outer" in c["by_program"] and "jit(outer)" not in c["by_program"]
    assert set(c["by_span"]) == {threading.current_thread().name}
    if case == "all_three_then_a_cache_hit":
        assert c["by_program"]["outer"] == {
            "n": 1, "trace_s": 0.25, "lower_s": 0.5, "backend_s": 0.125,
            "cache_hits": 1, "first_at_s": c["by_program"]["outer"]["first_at_s"],
            "max_s": 0.5, "hits": 0, "load_s": 0.0}
        assert {k: c["last"][0][k] for k in ("seconds", "trace_s", "lower_s",
                                             "cache_hit")} == {
            "seconds": 0.125, "trace_s": 0.25, "lower_s": 0.5, "cache_hit": True}


def test_stored_programs_have_a_block_and_a_row_of_their_own():
    """What ``engine/programs.py`` reports: a load is no compilation (``n``,
    ``trace_s``, ``last`` stay), and still has a row and a ``first_at_s``."""
    acct = ProcessAccount()
    empty = {"hits": 0, "misses": 0, "load_s": 0.0, "write_s": 0.0, "bytes": 0,
             "refused": {}}
    assert acct.snapshot()["compiles"]["stored"] == empty
    _opened(acct, BACKEND, "jit(decode_step)")  # the first start: built, written
    _closed(acct, BACKEND, "jit(decode_step)", 2.0)
    acct.program_missed("decode_step", 0.25, 1000)
    acct.program_loaded("decode_step", 0.5, 1000)  # the next start, same account
    acct.program_loaded("prefill_chunk_paged", 0.125, 500)
    acct.program_refused("corrupt")
    acct.program_refused("corrupt")
    acct.program_refused("mesh")
    c = acct.snapshot()["compiles"]
    assert c["stored"] == {"hits": 2, "misses": 1, "load_s": 0.625, "write_s": 0.25,
                           "bytes": 2500, "refused": {"corrupt": 2, "mesh": 1}}
    assert (c["n"], c["s"], c["trace_s"], len(c["last"]), c["programs"]) == (
        1, 2.0, 0.0, 1, 2)
    loaded = c["by_program"]["prefill_chunk_paged"]
    assert loaded == {"n": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                      "cache_hits": 0, "max_s": 0.0, "hits": 1, "load_s": 0.125,
                      "first_at_s": loaded["first_at_s"]}
    built = c["by_program"]["decode_step"]
    assert (built["n"], built["backend_s"], built["hits"], built["load_s"]) == (
        1, 2.0, 1, 0.5)
    c["stored"]["refused"]["mesh"] = 7  # a snapshot is a copy
    assert acct.snapshot()["compiles"]["stored"]["refused"]["mesh"] == 1


def test_nested_jits_in_a_scan_are_one_program_counted_once():
    acct = ProcessAccount().install()

    @jax.jit
    def setup_account_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def setup_account_outer(x):
        def body(c, _):
            return setup_account_inner(c) + 1, None

        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.arange(8.0)  # an eager program of its own, before the clock
    x.block_until_ready()
    try:
        before = acct.snapshot()["compiles"]
        t0 = time.monotonic()
        setup_account_outer(x).block_until_ready()
        wall = time.monotonic() - t0
        after = acct.snapshot()["compiles"]
    finally:
        acct.uninstall()
    row = after["by_program"]["setup_account_outer"]
    assert "setup_account_inner" not in after["by_program"]
    assert row["n"] == 1 and after["n"] == before["n"] + 1
    assert min(row["trace_s"], row["lower_s"], row["backend_s"]) > 0.0
    assert row["trace_s"] + row["lower_s"] + row["backend_s"] <= wall
    assert row["max_s"] == max(row["trace_s"], row["lower_s"], row["backend_s"])
    last = after["last"][-1]
    assert (last["trace_s"], last["lower_s"], last["seconds"]) == tuple(
        round(row[k], 6) for k in ("trace_s", "lower_s", "backend_s"))


@pytest.mark.parametrize("where", ["engine_thread_in_span", "named_thread"])
def test_by_span_is_where_the_outermost_interval_opened(where):
    book = SpanBook()
    acct = ProcessAccount(book).install()

    def setup_account_by_span(x):
        return x * 5 - (1 if where == "named_thread" else 2)

    def compile_it():
        jax.jit(setup_account_by_span)(jnp.arange(5)).block_until_ready()

    try:
        if where == "named_thread":
            t = threading.Thread(target=compile_it, name="setup-acct-worker")
            t.start()
            t.join(timeout=120)
            assert not t.is_alive()
            want = "setup-acct-worker"
        else:
            with book.step():
                with book.span("rm.prefill.group"):
                    compile_it()
            want = "rm.prefill.group"
        c = acct.snapshot()["compiles"]
    finally:
        acct.uninstall()
    program = c["by_program"]["setup_account_by_span"]
    span = c["by_span"][want]
    assert span["n"] == program["n"] == 1
    assert all(span[k] == program[k] > 0.0
               for k in ("trace_s", "lower_s", "backend_s"))
    assert c["last"][-1]["span"] == want


def test_the_257th_distinct_name_goes_to_other():
    acct = ProcessAccount()
    for i in range(ProcessAccount.TABLE + 2):
        _opened(acct, BACKEND, f"jit(shape_{i})")
        _closed(acct, BACKEND, f"jit(shape_{i})", 0.5)
    _opened(acct, TRACE, "shape_0")  # a name the table has keeps its row
    _closed(acct, TRACE, "shape_0", 0.25)
    c = acct.snapshot()["compiles"]
    assert c["programs"] == len(c["by_program"]) == ProcessAccount.TABLE + 1
    assert c["by_program"]["(other)"]["n"] == 2
    assert c["by_program"]["(other)"]["backend_s"] == 1.0
    assert c["by_program"]["shape_0"]["trace_s"] == 0.25
    assert f"shape_{ProcessAccount.TABLE}" not in c["by_program"]
    assert sum(r["n"] for r in c["by_program"].values()) == c["n"]


def test_phases_are_recorded_in_order_and_ready_is_their_last_edge():
    acct = ProcessAccount()
    assert acct.snapshot()["startup"] == {
        "phases": [], "ready_at_s": None, "compiling_s": 0.0}
    acct.started("imports")
    with acct.phase("devices"):  # a program of the start-up's own: 1.75 s
        _opened(acct, LOWER, "jit(_init_params)")
        _closed(acct, LOWER, "jit(_init_params)", 0.5)
        _opened(acct, BACKEND, "jit(_init_params)")
        _closed(acct, BACKEND, "jit(_init_params)", 1.25)
    with acct.phase("params"):
        time.sleep(0.01)
    startup = acct.snapshot()["startup"]
    assert [name for name, _ in startup["phases"]] == ["imports", "devices", "params"]
    seconds = [s for _, s in startup["phases"]]
    assert seconds[0] > 0.0 and seconds[2] >= 0.01  # imports: the process's age
    assert startup["ready_at_s"] >= sum(seconds) and startup["compiling_s"] == 1.75
    assert acct.snapshot()["startup"]["ready_at_s"] == startup["ready_at_s"]


@pytest.mark.parametrize("proc", ["missing", "clocks_disagree"])
def test_without_a_usable_proc_the_age_is_since_import(proc, monkeypatch):
    import builtins
    import io

    from radixmesh_tpu.obs import tracing

    files = {"/proc/self/stat": "7 (a b) S " + " ".join(["0"] * 18) + " 99999999999 0",
             "/proc/uptime": "12.5 3.0"}  # started long after the "boot"
    real_open = builtins.open

    def fake_open(path, *a, **kw):
        if str(path).startswith("/proc/"):
            if proc == "missing":
                raise FileNotFoundError(path)
            return io.StringIO(files[path])
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", fake_open)
    before = tracing.since_import()
    age = tracing._process_age()
    assert before <= age <= tracing.since_import()
    acct = ProcessAccount()
    acct.started("imports")
    startup = acct.snapshot()["startup"]
    assert 0.0 <= startup["phases"][0][1] <= startup["ready_at_s"] <= (
        tracing.since_import() + 1e-3)


def test_uninstall_leaves_no_listener_behind():
    from jax._src import monitoring  # the public module has no getters

    found = (list(gc.callbacks), monitoring.get_event_duration_listeners(),
             monitoring.get_scalar_listeners())
    acct = ProcessAccount().install()
    try:
        assert len(monitoring.get_scalar_listeners()) == len(found[2]) + 1
        assert len(monitoring.get_event_duration_listeners()) == len(found[1]) + 1
    finally:
        acct.uninstall()
    assert (list(gc.callbacks), monitoring.get_event_duration_listeners(),
            monitoring.get_scalar_listeners()) == found
    acct.uninstall()  # twice is harmless


def test_slow_step_names_the_tracing_and_lowering_inside_it(model):
    acct = ProcessAccount()  # its listeners are called by the clock below
    eng = _engine(model, "acct-slow-trace", process_account=acct)
    _run(eng, range(1, 17))  # compiles; real clock

    class Clock(_Clock):  # a box shape traced where the stall is
        def __call__(self):
            if self.book.innermost in self.stall:
                for event, name, s in ((TRACE, "box", 1.25), (LOWER, "jit(box)", 0.5),
                                       (BACKEND, "jit(box)", 0.125)):
                    _opened(acct, event, name)
                    _closed(acct, event, name, s)
                acct.program_loaded("stored_box", 0.75, 10)  # and one loaded
            return super().__call__()

    eng.spans.clock = clock = Clock(eng.spans)
    eng.add_request(list(range(40, 56)), SamplingParams(max_new_tokens=4))
    eng.step()
    assert eng.slow_steps["n"] == 0
    clock.stall["rm.decode.dispatch"] = 2.7
    eng.step()
    (rec,) = eng.slow_steps["last"]
    assert rec["compiles"] == {"n": 1, "s": 0.125, "trace_s": 1.25, "lower_s": 0.5,
                               "loads": 1, "load_s": 0.75}
    assert acct.snapshot()["compiles"]["by_span"]["rm.decode.dispatch"]["n"] == 1
    while eng.has_work():
        eng.step()

"""The window's own account (``obs/tracing.py``; ``/debug/state``
``engine.prefill``, ``engine.slow_steps``, ``process``): what the prefill
launch site counts, which steps are recorded as slow, and what the
process's ``gc`` / ``jax.monitoring`` hooks count. No case waits on the
wall clock: the slow-step cases hand the span book a clock of their own."""

import gc
import threading

import jax
import jax.numpy as jnp
import pytest

from radixmesh_tpu.engine.engine import _SLOW_STEP_RING, _SLOW_STEP_S, Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.obs.tracing import ProcessAccount, SpanBook

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
    before = {**eng.prefill, "by_path": dict(eng.prefill["by_path"])}
    admit_before = eng.spans.s["rm.admit"]
    reqs = _run(eng, *prompts)
    assert all(r.prefix_len == cached for r in reqs)
    p = eng.prefill
    new = sum(len(list(q)) - cached for q in prompts)
    assert p["waves"] - before["waves"] == 1  # one admission wave
    assert p["rows"] - before["rows"] == len(prompts)
    assert p["tokens_new"] - before["tokens_new"] == new
    launched = p["tokens_launched"] - before["tokens_launched"]
    assert launched == eng._wave_padded >= new
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

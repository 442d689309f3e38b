"""The program's host spans (``rm.*`` through ``obs.tracing.annotate`` =
``jax.profiler.TraceAnnotation``) and its two always-on counters.

A tiny model (each of two kinds) served through ``ServingFrontend`` with
a profile directory: a ``/debug/profile`` capture taken during a few requests holds
every span name, nested as ``Engine.step`` documents them, on the engine
thread's line; ``rm.http.emit`` on the stream handlers' lines; nothing of
the python tracer unless ``?python=1``. Without a capture the same
requests leave no trace file. ``/debug/state`` counts every admission's
queue wait once, the tree's host seconds, the sampler's launches by
the executable they ran, and every decode token's deferred emission."""

import glob
import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.server.http_frontend import ServingFrontend
from radixmesh_tpu.slo.control import SLOConfig

ENGINE_SPANS = {
    # name: parent (None = top level of the engine thread's line)
    "rm.runner.pre_step": None,
    "rm.slo.pump": "rm.runner.pre_step",
    "rm.engine.step": None,
    "rm.admit": "rm.engine.step",
    "rm.tree.match": "rm.admit",
    "rm.prefill.dense": "rm.admit",
    "rm.prefill.group": "rm.admit",
    "rm.first_token.sync": "rm.admit",
    "rm.decode.build": "rm.engine.step",
    "rm.decode.dispatch": "rm.engine.step",
    "rm.decode.sample": "rm.engine.step",
    "rm.decode.emit": "rm.engine.step",  # the step BEFORE's tokens, behind this launch
    "rm.decode.sync": "rm.engine.step",
    "rm.decode.consume": "rm.engine.step",
    "rm.tree.publish": None,  # under rm.decode.consume at finish, a prefill at install
}


def _stream(base, ids, n):
    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps({"input_ids": ids, "max_tokens": n, "stream": True,
                         "stop_token_ids": []}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read()


def _get(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def _lines(logdir):
    """``[(line events)]`` of plane /host:CPU: ``(name, start, end)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events])
    return out


@pytest.fixture(scope="module", params=["llama3-tiny", "mellum2-tiny"])
def served(request, tmp_path_factory):
    """A served tiny model (a dense one; one with experts and window
    layers); the traffic of one round (a lone streamed request: dense
    prefill; three requests put in the queue together: one batched
    prefill) is run once to compile, then under each capture."""
    cfg = get_config(request.param)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), num_slots=1024,
                 page_size=4, max_batch=4, name=f"host-spans-{request.param}")
    profile_dir = str(tmp_path_factory.mktemp("profile"))
    f = ServingFrontend(eng, port=0, profile_dir=profile_dir, slo=SLOConfig())
    base = f"http://127.0.0.1:{f.port}"
    rounds = iter(range(1, 100))

    def traffic():
        k = 1000 * next(rounds)
        _stream(base, list(range(k, k + 20)), 6)
        with f.runner._lock:  # one admission wave -> rm.prefill.group
            reqs = [eng.add_request(list(range(k + 100 * i, k + 100 * i + 20)),
                                    SamplingParams(max_new_tokens=6))
                    for i in (1, 2, 3)]
        f.runner._wake.set()
        for r in reqs:
            f.runner.wait(r, timeout=60)
        return 4  # requests sent

    def staggered(*lengths):
        """Requests admitted in one wave that end at different steps."""
        k = 1000 * next(rounds)
        with f.runner._lock:
            reqs = [eng.add_request(list(range(k + 100 * i, k + 100 * i + 20)),
                                    SamplingParams(max_new_tokens=n))
                    for i, n in enumerate(lengths, 1)]
        f.runner._wake.set()
        for r in reqs:
            f.runner.wait(r, timeout=60)

    def stopping():
        """Traffic in which a stop id ends a request: two requests
        admitted in one wave, of which one samples its stop id — learned
        here, by a run of the same prompt to its length — in the step
        before the other's last."""
        while True:
            k = 1000 * next(rounds)
            # ids inside the vocabulary: the reply then varies
            ids = np.random.default_rng(k).integers(
                1, cfg.vocab_size, 20).tolist()
            with f.runner._lock:
                probe = eng.add_request(ids, SamplingParams(max_new_tokens=6))
            f.runner._wake.set()
            f.runner.wait(probe, timeout=60)
            out = list(probe.output_tokens)
            # not its first decode step: that one is built in the gap
            cut = next((i for i in range(2, 6) if out[i] not in out[:i]), None)
            if cut is not None:
                break

        def run():
            with f.runner._lock:
                reqs = [
                    eng.add_request(ids, SamplingParams(
                        max_new_tokens=6, stop_token_ids=(out[cut],))),
                    eng.add_request(list(range(k + 100, k + 120)),
                                    SamplingParams(max_new_tokens=cut + 2)),
                ]
            f.runner._wake.set()
            for r in reqs:
                f.runner.wait(r, timeout=60)
            assert reqs[0].output_tokens == out[:cut]

        return run

    def capture(query="", more=None):
        got = {}
        t = threading.Thread(target=lambda: got.update(
            _get(base + "/debug/profile?seconds=1.5" + query)))
        t.start()
        time.sleep(0.3)
        traffic()
        if more is not None:
            more()
        t.join()
        return _lines(got["logdir"])

    traffic()  # compiles
    staggered(3, 4, 6)
    stopping()()
    yield {"frontend": f, "engine": eng, "base": base, "traffic": traffic,
           "staggered": staggered, "stopping": stopping, "capture": capture,
           "profile_dir": profile_dir}
    f.close()


def test_no_capture_leaves_no_trace_and_counts_every_admission(served):
    eng, base = served["engine"], served["base"]
    files = lambda: sorted(  # noqa: E731
        p for p, _, fs in os.walk(served["profile_dir"]) for _ in fs)
    before_files = files()

    def state():
        # A waiter wakes on ``state = FINISHED``, before that step has
        # counted and published the request; /debug/state takes no lock,
        # the step does.
        with served["frontend"].runner._lock:
            return _get(base + "/debug/state")

    a = state()
    n = served["traffic"]()
    b = state()
    assert files() == before_files  # spans without a capture write nothing
    wa, wb = a["engine"]["admission_wait"], b["engine"]["admission_wait"]
    assert wb["count"] - wa["count"] == n  # each admitted request once
    assert sum(wb["counts"]) == wb["count"] == eng.admission_wait["count"]
    assert len(wb["counts"]) == len(wb["le_ms"]) + 1
    assert 0.0 < wb["sum_s"] - wa["sum_s"] < 5.0
    ta, tb = a["cache"]["tree_host"], b["cache"]["tree_host"]
    assert tb["matches"] - ta["matches"] == n  # one walk per admission
    # one publish when a prompt is installed, one when the request finishes
    finished = b["engine"]["finished"] - a["engine"]["finished"]
    assert finished == n and tb["publishes"] - ta["publishes"] == 2 * n
    assert tb["match_s"] > ta["match_s"] and tb["publish_s"] > ta["publish_s"]
    # engine.sampler: the traffic is greedy, so every decode step's launch
    # and every first-token launch (the lone request's, the wave's) ran
    # the argmax alone.
    sa, sb = a["engine"]["sampler"], b["engine"]["sampler"]
    assert sb == eng.sampler and sb["drawing_launches"] == 0
    steps = b["engine"]["decode_steps"] - a["engine"]["decode_steps"]
    assert steps > 0
    assert sb["argmax_launches"] - sa["argmax_launches"] >= steps + 2
    # engine.emit: every decode token emitted once (5 a request: the
    # first token is woken at its own site), all queues empty between
    # rounds, and most flushes behind a decode launch.
    ea, eb = a["engine"]["emit"], b["engine"]["emit"]
    assert eb == eng.emit and eb["tokens"] - ea["tokens"] == 5 * n
    assert not eng._emit_rows and not eng._emit_steps
    assert eb["flushes_under_decode"] - ea["flushes_under_decode"] >= steps - 4


def test_capture_holds_every_span_nested_as_documented(served):
    # besides the round's traffic, three requests of which two end by
    # their length one step apart, beside one that goes on; and two of
    # which one samples its stop id one step before the other's last
    stop_traffic = served["stopping"]()
    lines = served["capture"](
        more=lambda: (served["staggered"](3, 4, 6), stop_traffic()))
    engine = max(lines, key=lambda evs: sum(n == "rm.engine.step" for n, _, _ in evs))
    spans = [e for e in engine if e[0].startswith("rm.")]
    names = {n for n, _, _ in spans}
    assert set(ENGINE_SPANS) <= names, set(ENGINE_SPANS) - names
    assert "rm.http.emit" not in names

    def inside(child, parent):
        return any(p[0] == parent and p[1] <= child[1] and child[2] <= p[2]
                   for p in spans)

    # The capture may begin INSIDE a step (a waiter wakes on its request's
    # finish, before the step that finished it has flushed its emission:
    # the round before is still in its last step when this capture starts,
    # in 3 runs of 5), and may end inside one. The step's own annotation,
    # open across the capture's edge, is not in the capture; its children
    # are. So what lies before the first step the capture holds, or after
    # the last, is a cut step's, and is held to no parent.
    steps = [s for s in spans if s[0] == "rm.engine.step"]
    first, last = min(s[1] for s in steps), max(s[2] for s in steps)

    def cut(span):
        return span[2] <= first or last <= span[1]

    def under_step(name):
        while name is not None and name != "rm.engine.step":
            name = ENGINE_SPANS[name]
        return name is not None

    for name, parent in ENGINE_SPANS.items():
        if parent is not None:
            mine = [s for s in spans if s[0] == name
                    and not (under_step(name) and cut(s))]
            assert mine and all(inside(s, parent) for s in mine), (name, parent)
    publishes = [s for s in spans if s[0] == "rm.tree.publish" and not cut(s)]
    assert any(inside(s, "rm.decode.consume") for s in publishes)
    assert all(inside(s, "rm.engine.step") for s in publishes)
    # A decode step's phases follow each other inside one step, in one of
    # two orders (as on the chip). FAST, between two plain launches: the
    # launch goes out with inputs built behind the launch before it;
    # behind it run the step BEFORE's advance, the next launch's build and
    # the step before's emission; then the wait. A step that takes a row
    # to its length cap beside rows that go on is such a step: the next
    # launch is built without that row, and the row's release and tree
    # publish are in the NEXT step's consume. SETTLED, the order every
    # step had before (here: built in the gap because a row that sampled
    # its stop id had left, and ending the last row): nothing is built
    # ahead and the advance follows the wait.
    orders, deferred_finishes = [], 0
    for step in (p for p in spans if p[0] == "rm.engine.step"):
        phases = sorted((s for s in spans if s[0].startswith("rm.decode.")
                         and step[1] <= s[1] and s[2] <= step[2]),
                        key=lambda s: s[1])
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        order = [s[0].rsplit(".", 1)[1] for s in phases]
        fast = ["dispatch", "sample", "consume", "build", "emit", "sync"]
        if order == fast and any(
                s[0] == "rm.tree.publish" and phases[2][1] <= s[1]
                and s[2] <= phases[2][2] for s in spans):
            deferred_finishes += 1
        if order:
            orders.append(order)
            # whatever the order, one dispatch, the sampler behind it, the
            # wait behind the sampler, and no wake-up between dispatch and
            # sampler
            assert order.count("dispatch") == order.count("sync") == 1
            i = order.index("dispatch")
            assert order[i + 1] == "sample" and "sync" in order[i + 2:]
    assert ["dispatch", "sample", "consume", "build", "emit", "sync"] in orders
    # the requests of 3 and of 4 tokens were released behind the launch
    # after their last: between its dispatch and its sync
    assert deferred_finishes >= 2
    # the last live row ends: nothing to build, the step settles, and its
    # own tokens are emitted at its end (no launch follows to hide them)
    assert ["dispatch", "sample", "consume", "emit", "sync", "consume",
            "emit"] in orders
    # a stop id is known only after the sync: built ahead, settled all the
    # same; the launch after it is built in the gap, and ends the last row
    assert ["dispatch", "sample", "consume", "build", "emit", "sync",
            "consume"] in orders
    assert ["build", "dispatch", "sample", "emit", "sync", "consume",
            "emit"] in orders
    # a request's first decode step: built in the gap, and again ahead
    assert ["build", "dispatch", "sample", "build", "sync"] in orders
    # the stream handler's spans are on another line
    emit_lines = [evs for evs in lines if any(n == "rm.http.emit" for n, _, _ in evs)]
    assert emit_lines and all(evs is not engine for evs in emit_lines)
    # python tracer off: no frame events ("$file:line fn") anywhere
    assert not any(n.startswith("$") for evs in lines for n, _, _ in evs)


def test_python_tracer_only_on_request(served):
    lines = served["capture"]("&python=1")
    assert any(n.startswith("$") for evs in lines for n, _, _ in evs)
    assert any(n == "rm.engine.step" for evs in lines for n, _, _ in evs)


def test_debug_state_serves_the_span_book_and_the_prefill_account(served):
    """``engine.spans``: the fifteen engine-thread names and
    ``outside_step``, counted with no capture running (that none leaves a
    trace file is the test above's); ``cache.tree_host``
    is a view of two of them; ``engine.prefill`` counts the round's two
    admission waves; ``process`` only where an entry point installed it."""
    base = served["base"]

    def state():  # between two steps: /debug/state takes no lock
        with served["frontend"].runner._lock:
            return _get(base + "/debug/state")

    a = state()
    n = served["traffic"]()
    b = state()
    sa, sb = a["engine"]["spans"], b["engine"]["spans"]
    assert list(sb) == [*ENGINE_SPANS, "outside_step"] and len(sb) == 16
    assert all(set(v) == {"n", "s"} for v in sb.values())
    d = {k: (sb[k]["n"] - sa[k]["n"], sb[k]["s"] - sa[k]["s"]) for k in sb}
    assert all(dn >= 0 and ds >= 0.0 for dn, ds in d.values())
    steps = d["rm.engine.step"][0]
    assert steps > 0 and d["rm.admit"][0] == steps  # one a step
    assert d["outside_step"][0] in (steps, steps - 1)  # a gap before each step
    assert d["rm.runner.pre_step"][0] >= steps and d["rm.slo.pump"][0] >= steps
    assert d["rm.tree.match"][0] == n and d["rm.tree.publish"][0] == 2 * n
    assert d["rm.prefill.dense"][0] == 1 and d["rm.prefill.group"][0] == 1
    assert d["rm.first_token.sync"][0] == 2
    decode = b["engine"]["decode_steps"] - a["engine"]["decode_steps"]
    assert d["rm.decode.dispatch"][0] == d["rm.decode.sync"][0] == decode
    # nested spans take no more than what holds them
    assert d["rm.admit"][1] <= d["rm.engine.step"][1]
    assert (d["rm.prefill.dense"][1] + d["rm.prefill.group"][1]
            + d["rm.first_token.sync"][1] + d["rm.tree.match"][1]) <= d["rm.admit"][1]
    th = b["cache"]["tree_host"]
    assert th == {"matches": sb["rm.tree.match"]["n"], "match_s": sb["rm.tree.match"]["s"],
                  "publishes": sb["rm.tree.publish"]["n"],
                  "publish_s": sb["rm.tree.publish"]["s"],
                  "publish_key_tokens": served["engine"].tree_publish["key_tokens"],
                  "publish_walked_tokens":
                      served["engine"].tree_publish["walked_tokens"]}
    assert 0 < th["publish_walked_tokens"] <= th["publish_key_tokens"]
    pa, pb = a["engine"]["prefill"], b["engine"]["prefill"]
    assert pb["waves"] - pa["waves"] == 2 and pb["rows"] - pa["rows"] == n
    assert pb["tokens_new"] - pa["tokens_new"] == 20 * n  # nothing shared
    assert pb["tokens_launched"] - pa["tokens_launched"] >= 20 * n
    assert pb["by_path"]["dense"] - pa["by_path"]["dense"] == 1
    assert pb["by_path"]["chunk"] - pa["by_path"]["chunk"] == 1
    # every launch under its chunk (JSON keys are strings), and counted
    # under the floor of ``prefill_cost`` or not (PR 54)
    assert list(pb)[:5] == ["waves", "rows", "launches",
                            "launches_below_floor", "by_chunk"]
    assert sum(pb["by_chunk"].values()) == pb["launches"] > pa["launches"]
    assert all(int(c) & (int(c) - 1) == 0 for c in pb["by_chunk"])
    assert 0 <= pb["launches_below_floor"] <= pb["launches"]
    assert 0.0 < pb["wall_s"] - pa["wall_s"] <= d["rm.admit"][1]
    slow = b["engine"]["slow_steps"]
    assert slow["threshold_s"] == 1.0 and slow["n"] == len(slow["last"]) <= 32
    assert "process" not in b  # gc / jax.monitoring hooks: launch.py serve's


class _Ticks:
    """A clock that reads 1, 2, 3, ... seconds."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_book_span_adds_to_its_name_and_to_no_other():
    from radixmesh_tpu.obs.tracing import ENGINE_SPANS as NAMES, SpanBook

    assert NAMES == tuple(ENGINE_SPANS)  # the table above, in its order
    book = SpanBook(clock=_Ticks())
    zero = book.snapshot()
    assert list(zero) == [*ENGINE_SPANS, "outside_step"]
    assert all(v == {"n": 0, "s": 0.0} for v in zero.values())
    for _ in range(3):
        with book.span("rm.decode.build") as span:
            assert book.innermost == "rm.decode.build"
        assert span.seconds == 1.0 and book.innermost is None
    got = book.snapshot()
    assert got.pop("rm.decode.build") == {"n": 3, "s": 3.0}
    assert all(v == {"n": 0, "s": 0.0} for v in got.values())
    with book.span("rm.not.seeded"):  # a name the book was not seeded with
        pass
    assert book.snapshot()["rm.not.seeded"] == {"n": 1, "s": 1.0}


@pytest.mark.parametrize("raises", [False, True], ids=["exit", "exception"])
def test_book_nesting_restores_the_innermost_name(raises):
    from radixmesh_tpu.obs.tracing import SpanBook

    book = SpanBook(clock=_Ticks())
    try:
        with book.span("rm.admit"):
            with book.span("rm.tree.match"):
                assert book.innermost == "rm.tree.match"
            assert book.innermost == "rm.admit"
            with book.span("rm.prefill.dense"):
                if raises:
                    raise KeyError("a prefill that fails")
            assert book.innermost == "rm.admit"
    except KeyError:
        assert raises
    assert book.innermost is None
    got = book.snapshot()
    assert got["rm.tree.match"] == {"n": 1, "s": 1.0}
    assert got["rm.prefill.dense"] == {"n": 1, "s": 1.0}
    assert got["rm.admit"] == {"n": 1, "s": 5.0}  # readings 1..6: holds both


def test_outside_step_adds_up_between_two_steps():
    from radixmesh_tpu.obs.tracing import SpanBook

    book = SpanBook(clock=_Ticks())
    with book.step() as first:  # readings 1, 2
        assert book.innermost == "rm.engine.step"
    assert book.snapshot()["outside_step"] == {"n": 0, "s": 0.0}  # no step before
    with book.span("rm.runner.pre_step"):  # readings 3, 4: between two steps
        pass
    with book.step() as second:  # readings 5, 8
        with book.span("rm.admit"):  # 6, 7
            pass
    with book.step():  # 9, 10: straight after
        pass
    got = book.snapshot()
    assert got["outside_step"] == {"n": 2, "s": 3.0 + 1.0}
    assert got["rm.engine.step"] == {"n": 3, "s": 1.0 + 3.0 + 1.0}
    assert first.seconds == 1.0 and second.seconds == 3.0
    assert book.thread == threading.get_ident()
    # a step keeps the book's seconds at its entry: its own spans, largest first
    assert book.top_since(second.entry) == [["rm.admit", 1.0]]
    assert book.top_since(first.entry, k=1) == [["rm.runner.pre_step", 1.0]]


def test_readmission_after_preemption_waits_from_its_requeue_time():
    cfg = ModelConfig.tiny()
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), num_slots=512,
                 page_size=4, max_batch=2, max_seq_len=128, name="host-spans-2")
    req = eng.add_request(list(range(1, 20)), SamplingParams(max_new_tokens=8))
    req.submit_time -= 100.0  # its first life queued for 100 s
    for _ in range(3):
        eng.step()
    aw = eng.admission_wait
    assert aw["count"] == 1 and aw["counts"][-1] == 1  # beyond the last edge
    assert aw["sum_s"] == pytest.approx(100.0, abs=1.0)
    eng._preempt(req)
    while eng.has_work():
        eng.step()
    assert len(req.output_tokens) == 8
    assert aw["count"] == 2 and sum(aw["counts"]) == 2
    assert aw["counts"][-1] == 1  # the second wait is not the first one again
    assert aw["sum_s"] == pytest.approx(100.0, abs=1.0)
    assert eng.tree_host["matches"] == 2
    assert eng.tree_host["publishes"] == 4  # install, preempt, install, finish


def test_recorded_fast_steps_keep_the_clock_check_and_name_their_idle_time():
    """Three decode steps recorded on the chip with the fast hand-over
    (``fixtures/host_slice_handover.json``): the benchmark's reader
    (``benchmarks/harness/hostspans.py``) still finds host spans and
    device modules causally ordered — the launch is sent after the sync
    of the step before, not before it — every idle nanosecond has a name,
    and what the device waits out is the sync's tail, the dispatch and the
    hand-over, no longer the build or the advance."""
    from benchmarks.harness import hostspans

    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "host_slice_handover.json")) as fh:
        raw = json.load(fh)
    lines = [[tuple(e) for e in evs] for evs in raw["host"]]
    ops = [tuple(e) for e in raw["device"]["XLA Ops"]]
    ok = hostspans.causality(lines, [tuple(e) for e in raw["device"]["XLA Modules"]])
    assert ok["steps_checked"] == 3 and ok["clocks_shared"]
    assert 100 < ok["dispatch_to_device_us"][0] <= ok["dispatch_to_device_us"][1] < 2000
    assert 100 < ok["sampler_to_sync_us"][0] <= ok["sampler_to_sync_us"][1] < 3000
    engine = hostspans.engine_line(lines)
    steps = sorted((e for e in engine if e[0] == "rm.engine.step"), key=lambda e: e[1])
    assert len(steps) == 3
    for _, start, dur in steps:
        inside = sorted((e for e in engine if e[0].startswith("rm.decode.")
                         and start <= e[1] and e[1] + e[2] <= start + dur),
                        key=lambda e: e[1])
        assert [e[0].rsplit(".", 1)[1] for e in inside] == [
            "dispatch", "sample", "consume", "build", "emit", "sync"]
    got = hostspans.attribute(lines, ops)
    idle = sum(b - a for a, b in hostspans.idle_intervals(ops)) / 1e9
    assert got["steps"] == 3
    assert sum(v for _, v in got["by_span"]) == pytest.approx(idle, rel=1e-9)
    assert got["unattributed_s"] == 0.0  # the slice starts and ends on a span
    by = dict(got["by_span"])
    assert by.get("rm.decode.build", 0.0) + by.get("rm.decode.emit", 0.0) < 0.02 * idle
    exposed = by["rm.decode.sync"] + by["rm.decode.dispatch"]
    assert exposed > 0.5 * idle and idle / 3 < 3e-3  # under 3 ms a step

"""Device-idle time named by the engine thread's spans
(``harness/hostspans.py``) on a hand-made capture with hand-computed
answers (``fixtures/host_spans.json``: two decode steps, one stream
handler, one device; nanoseconds), the p95-from-buckets arithmetic of the
admission-wait counter, and the ``--trace 2`` command on the CPU: its last
line carries end-to-end and per-layer metrics, the end-to-end part by the
code path of ``--trace 0``."""
import json
import os
import sys

import pytest

from benchmarks.harness import hostspans, registry
from benchmarks.tests.test_by_name import REPO, run_cmd

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "host_spans.json")
RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "host_slice_chip.json")


@pytest.fixture
def cap():
    with open(FIXTURE) as fh:
        raw = json.load(fh)
    lines = [[tuple(e) for e in evs] for evs in raw["host"]]
    dev = {ln: [tuple(e) for e in evs] for ln, evs in raw["device"].items()}
    return lines, dev


def test_innermost_span_wins_and_segments_tile_the_line(cap):
    lines, _ = cap
    segs = hostspans.flatten(hostspans.engine_line(lines))
    assert segs[0][0] == 0 and segs[-1][1] == 2000
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))  # no hole, no overlap
    at = {s[0]: (s[2], s[3]) for s in segs}
    assert at[120] == ("rm.tree.match", True)  # inside rm.admit inside rm.engine.step
    assert at[150] == ("rm.admit", True)  # back to the parent
    assert at[900] == ("rm.tree.publish", True)
    assert at[10] == (None, False)  # between pre_step and the step
    assert at[0] == ("rm.runner.pre_step", False)


def test_idle_time_by_span_on_the_hand_made_capture(cap):
    lines, dev = cap
    got = hostspans.attribute(lines, dev["XLA Ops"])
    ns = {k: round(v * 1e9) for k, v in got["by_span"]}
    # Idle intervals of the device: (-150, 140), (160, 350), (780, 1450),
    # (1900, 2100) = 1350 ns. (160, 350) splits across rm.admit 40 /
    # rm.decode.build 100 / rm.decode.dispatch 50; (1000, 1200) holds no
    # engine span and is overlapped by rm.http.emit for 50 of its 200.
    assert ns == {
        "unattributed": 150 + 100,  # before the first span, after the last
        "rm.runner.pre_step": 10 + 10,
        "outside_step.other": 90 + 150 + 90,
        "outside_step.http_emit": 50,
        "rm.engine.step": 10 + 10 + 100 + 10,  # self time only
        "rm.admit": 10 + 40,
        "rm.tree.match": 20,  # not rm.admit's: innermost wins
        "rm.decode.build": 100,
        "rm.decode.dispatch": 50 + 50,
        "rm.decode.sync": 20 + 50,
        "rm.decode.consume": 100 + 40 + 40,
        "rm.tree.publish": 50,
    }
    assert got["steps"] == 2
    assert got["idle_s"] == pytest.approx(1350e-9)
    assert got["in_step_s"] == pytest.approx(700e-9)
    assert got["outside_step_s"] == pytest.approx(400e-9)
    assert got["unattributed_s"] == pytest.approx(250e-9)
    assert [k for k, _ in got["by_span"]][0] == "outside_step.other"  # longest first


def test_causality_margins_and_a_shifted_clock(cap):
    lines, dev = cap
    ok = hostspans.causality(lines, dev["XLA Modules"])
    assert ok["steps_checked"] == 2 and ok["clocks_shared"]
    assert ok["dispatch_to_device_us"] == [pytest.approx(0.05), pytest.approx(0.05)]
    # the sampler module of an admission (1320) is not a step's: device order decides
    assert ok["sampler_to_sync_us"] == [pytest.approx(0.02), pytest.approx(0.05)]
    early = [(n, s - 100, d) for n, s, d in dev["XLA Modules"]]
    bad = hostspans.causality(lines, early)
    assert not bad["clocks_shared"] and bad["dispatch_to_device_us"][0] == pytest.approx(-0.05)
    # the device's tracer outlives the host's: a step whose dispatch the
    # capture does not hold is no step of it (seen on the chip, PR 25)
    end = max(s + d for evs in lines for _, s, d in evs)
    late = dev["XLA Modules"] + [("jit_decode_step(1)", end + 5000, 800),
                                 ("jit_sample_tokens(2)", end + 5800, 100)]
    assert hostspans.causality(lines, late) == ok


def test_recorded_chip_slice():
    """Two decode steps recorded on the chip (batch cell, PR 25): the idle
    time is conserved, all of it has a name, the consume loop holds most of
    it, and host spans and device modules are causally ordered."""
    with open(RECORDED) as fh:
        raw = json.load(fh)
    lines = [[tuple(e) for e in evs] for evs in raw["host"]]
    ops = [tuple(e) for e in raw["device"]["XLA Ops"]]
    got = hostspans.attribute(lines, ops)
    idle = sum(b - a for a, b in hostspans.idle_intervals(ops)) / 1e9
    assert got["steps"] == 2
    assert sum(v for _, v in got["by_span"]) == pytest.approx(idle, rel=1e-9)
    assert got["in_step_s"] + got["outside_step_s"] + got["unattributed_s"] == \
        pytest.approx(idle, rel=1e-9)
    assert got["unattributed_s"] == 0.0  # the slice starts and ends on a span
    assert got["by_span"][0][0] == "rm.decode.consume"
    assert got["by_span"][0][1] > 0.5 * idle
    seen = {k for k, _ in got["by_span"]}
    assert {"rm.decode.build", "rm.decode.dispatch", "rm.decode.sync", "rm.admit",
            "outside_step.other", "outside_step.http_emit"} <= seen
    ok = hostspans.causality(lines, [tuple(e) for e in raw["device"]["XLA Modules"]])
    assert ok["steps_checked"] == 2 and ok["clocks_shared"]
    assert 100 < ok["dispatch_to_device_us"][0] <= ok["dispatch_to_device_us"][1] < 2000
    assert 100 < ok["sampler_to_sync_us"][0] <= ok["sampler_to_sync_us"][1] < 3000


def test_a_capture_without_the_spans_reads_nothing(cap):
    _, dev = cap
    assert hostspans.attribute([], dev["XLA Ops"]) is None
    assert hostspans.attribute([[("rm.http.emit", 0, 5)]], dev["XLA Ops"]) is None
    assert hostspans.causality([], dev["XLA Modules"]) is None


def test_readers_divide_by_the_steps_of_the_capture(cap, monkeypatch):
    lines, dev = cap
    got = hostspans.attribute(lines, dev["XLA Ops"])
    monkeypatch.setattr(hostspans, "of_cell", lambda ctx: got)
    read = lambda name: registry.load_module("layer_metrics", name).read({})  # noqa: E731
    assert read("step_host_exposed_ms") == pytest.approx(350e-6)
    assert read("between_steps_idle_ms.batch") == pytest.approx(200e-6)
    assert read("idle_attributed_share") == pytest.approx(100 * 1100 / 1350)
    monkeypatch.setattr(hostspans, "of_cell", lambda ctx: None)  # the parent's program
    assert read("step_host_exposed_ms.batch") is None
    assert read("idle_attributed_share.batch") is None


def test_p95_from_buckets():
    p95 = registry.load_module("layer_metrics", "admission_wait_p95_ms").p95_upper_edge
    le = [1, 2, 5, 10]
    assert p95(le, [0, 0, 0, 0, 0]) is None
    assert p95(le, [100, 0, 0, 0, 0]) == 1.0
    assert p95(le, [94, 0, 6, 0, 0]) == 5.0  # rank 95 of 100 lies in (2, 5]
    assert p95(le, [95, 0, 5, 0, 0]) == 1.0  # rank 95 is the last of the first bucket
    assert p95(le, [19, 1, 0, 0, 0]) == 1.0  # rank ceil(19.0) = 19 of 20
    assert p95(le, [18, 1, 1, 0, 0]) == 2.0
    assert p95(le, [1, 0, 0, 0, 9]) == 10.0  # overflow: reads the last edge


def test_counter_readers_take_window_deltas_and_skip_a_program_without_them():
    le = [1, 2, 5]
    def snap(count, sum_s, counts, match_s, publish_s, finished):
        return {"state": {"engine": {"finished": finished, "admission_wait": {
            "count": count, "sum_s": sum_s, "le_ms": le, "counts": counts}},
            "cache": {"tree_host": {"matches": 0, "match_s": match_s,
                                    "publishes": 0, "publish_s": publish_s}}}}
    ctx = {"before": snap(10, 1.0, [10, 0, 0, 0], 0.5, 0.5, 7),
           "after": snap(30, 1.04, [10, 19, 1, 0], 0.52, 0.54, 17)}
    read = lambda name: registry.load_module("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("admission_wait_mean_ms") == pytest.approx(2.0)  # 40 ms over 20
    assert read("admission_wait_p95_ms") == 2.0  # rank 19 of 20 in (1, 2]
    assert read("tree_host_ms_per_request") == pytest.approx(6.0)  # 60 ms over 10
    old = {"state": {"engine": {"finished": 1}, "cache": {}}}
    ctx = {"before": old, "after": old}
    assert read("admission_wait_mean_ms") is None
    assert read("admission_wait_p95_ms") is None
    assert read("tree_host_ms_per_request") is None


def test_end_to_end_part_is_one_function_for_trace_0_and_trace_2():
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import inspect

    import run

    e2e = [{"name": "itl_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"},
           {"name": "tokens_per_s", "unit": "tokens/s"}]
    got = run.end_to_end(e2e, {"itl_p95_ms": 7.0, "tokens_per_s": None}, 12.5)
    assert got == {"itl_p95_ms": {"value": 7.0, "unit": "ms"},
                   "setup_s": {"value": 12.5, "unit": "s"}}
    src = inspect.getsource(run.run)
    assert src.count("end_to_end(r[\"end_to_end\"], summary, setup_s)") == 1
    # ... and the summary it is given is made once, from the frozen records
    assert src.count("ledger.summarize(") == 1


@pytest.mark.parametrize("cell", ["qwen2-7b.chat-sessions", "qwen2-7b.batch-unshared"])
def test_trace_2_rehearsal_prints_both_kinds_of_metric(cell):
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "2147484001", "--seconds", "4", "--trace", "2", "--rehearse-cpu"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600)
    assert rc == 0, err[-2000:]
    lines = [json.loads(ln) for ln in out.strip().splitlines()]
    result = lines[-1]
    man = registry.resolve(cell)
    e2e = {m["name"] for m in man["end_to_end"]}
    assert e2e <= set(result["metrics"]), result["metrics"].keys()
    # per-layer metrics that need no device plane are there too
    counters = {"qwen2-7b.chat-sessions": {"prefix_hit_share", "admission_wait_mean_ms",
                                           "admission_wait_p95_ms",
                                           "tree_host_ms_per_request"},
                "qwen2-7b.batch-unshared": {"decode_rows_per_step", "preemptions"}}[cell]
    assert counters <= set(result["metrics"]), result["metrics"].keys()
    assert set(result["device"]) >= {"busy_s", "window_s", "memory_peak_bytes"}
    assert "breakdown" in result
    # the end-to-end values are the window's, as --trace 0 computes them
    window = next(ln for ln in lines if ln.get("diag") == "window")
    for name in e2e - {"setup_s"}:
        assert result["metrics"][name]["value"] == window[name]
    timeline = next(ln for ln in lines if ln.get("diag") == "timeline")
    cap = timeline["capture"]
    assert cap["began_after_window_s"] >= timeline["drained_s"]
    assert len(cap["backlog_start_end"]) == 2 and not cap["arrivals_had_stopped"]
    # reduced, then deleted
    assert not os.path.exists(os.path.join(REPO, "benchmarks", ".work", cell, "profile"))

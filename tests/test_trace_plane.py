"""Request-flight tracing plane (``obs/trace_plane.py``): flight-recorder
semantics (bounded, drop-oldest, one-branch no-op when off), the span
instrumentation threaded through the serving stack, and the Chrome
trace-event artifact contract (``validate_trace``)."""

import json
import time

import jax
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.obs.trace_plane import (
    FlightRecorder,
    get_recorder,
    set_recorder,
    validate_trace,
    write_trace,
)
from radixmesh_tpu.workload import MultiTurnWorkload, run_engine_workload

pytestmark = pytest.mark.quick


def _tiny_engine(name: str, mesh=None, **kw) -> Engine:
    cfg = ModelConfig.tiny()
    return Engine(
        cfg,
        init_params(cfg, jax.random.PRNGKey(0)),
        num_slots=512,
        page_size=4,
        max_batch=2,
        name=name,
        mesh=mesh,
        **kw,
    )


class TestFlightRecorder:
    def test_capacity_bound_and_drop_oldest(self):
        rec = FlightRecorder(capacity=16, sample=1.0)
        for i in range(100):
            rec.event("lane", f"e{i}", float(i), 0.5)
        assert len(rec) == 16
        assert rec.dropped == 84
        assert rec.recorded == 100
        # Drop-OLDEST: the survivors are the freshest spans.
        names = [s.name for s in rec.snapshot()]
        assert names == [f"e{i}" for i in range(84, 100)]
        assert len(rec.drain()) == 16
        assert len(rec) == 0

    def test_disabled_recorder_returns_none_and_records_nothing(self):
        rec = FlightRecorder(capacity=16, sample=0.0)
        assert rec.trace("req:1") is None
        rec.event("lane", "e", 0.0, 1.0)
        assert len(rec) == 0 and rec.recorded == 0

    def test_partial_sampling_mixes_traced_and_untraced(self):
        rec = FlightRecorder(capacity=1024, sample=0.5)
        got = [rec.trace("req") is not None for _ in range(200)]
        assert any(got) and not all(got)

    def test_span_context_manager_measures(self):
        rec = FlightRecorder(capacity=8, sample=1.0)
        ctx = rec.trace("req:1")
        with ctx.span("work", x=1):
            time.sleep(0.01)
        (span,) = rec.snapshot()
        assert span.name == "work" and span.dur >= 0.01
        assert span.trace_id == ctx.trace_id and span.args == {"x": 1}

    def test_chrome_trace_schema_validates(self):
        rec = FlightRecorder(capacity=64, sample=1.0)
        for i in range(10):
            rec.event(f"lane{i % 3}", "e", float(10 - i), 0.25, k=i)
        obj = rec.chrome_trace()
        assert validate_trace(obj) == []
        # Round-trips through JSON (the /debug/trace body).
        assert validate_trace(json.loads(json.dumps(obj))) == []
        names = {
            ev["args"]["name"]
            for ev in obj["traceEvents"]
            if ev["ph"] == "M"
        }
        assert names == {"lane0", "lane1", "lane2"}


class TestNoOpGuard:
    def test_disabled_tracing_allocates_no_spans(self, monkeypatch):
        """Acceptance: with sampling off, the per-step hot path takes the
        no-op branch — zero Span allocations, zero recorder writes — for
        a full serve (admission, prefill, decode, publish)."""
        calls = {"record": 0}
        orig = FlightRecorder._record

        def spy(self, span):
            calls["record"] += 1
            return orig(self, span)

        monkeypatch.setattr(FlightRecorder, "_record", spy)
        eng = _tiny_engine("trace-off")
        reqs = [eng.add_request(list(range(1, 16))) for _ in range(3)]
        for _ in range(200):
            if not eng.has_work():
                break
            eng.step()
        assert all(r.trace is None for r in reqs)
        assert calls["record"] == 0

    def test_enabled_tracing_attaches_context(self):
        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        eng = _tiny_engine("trace-on")
        req = eng.add_request(list(range(1, 16)))
        assert req.trace is not None
        for _ in range(200):
            if not eng.has_work():
                break
            eng.step()
        names = {s.name for s in get_recorder().snapshot()}
        assert {"prefix_match", "admission_wait", "prefill_wave",
                "decode_chunk", "publish", "first_token"} <= names


class TestEngineWorkloadTrace:
    def test_workload_trace_has_request_span_tree_and_ring_lag(self, tmp_path):
        """Acceptance: a CPU engine workload run with tracing enabled
        produces Chrome trace JSON containing, for at least one request,
        spans for admission wait, prefill wave, decode chunk, publish —
        and ring replication-lag spans from the mesh leg."""
        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig

        set_recorder(FlightRecorder(capacity=1 << 15, sample=1.0))
        InprocHub.reset_default()
        prefill, decode = ["p0"], ["d0"]
        nodes = []
        try:
            for addr in prefill + decode:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=[],
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.05,
                    gc_interval_s=30.0,
                )
                nodes.append(MeshCache(cfg, pool=None).start())
            for n in nodes:
                assert n.wait_ready(timeout=10)
            eng = _tiny_engine("trace-mesh", mesh=nodes[0])
            wl = MultiTurnWorkload(
                n_conversations=2, n_turns=2, system_len=8,
                user_len=4, gen_len=4, vocab_size=256,
            )
            report = run_engine_workload(eng, wl)
            assert report["requests"] == 4
            # Replication lag is recorded on d0's receive path; give the
            # ring a moment to lap.
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if any(
                    s.name == "replication_lag"
                    for s in get_recorder().snapshot()
                ):
                    break
                time.sleep(0.02)
            path = str(tmp_path / "trace.json")
            assert write_trace(path) > 0
        finally:
            for n in nodes:
                n.close()
            InprocHub.reset_default()

        with open(path) as fh:
            obj = json.load(fh)
        assert validate_trace(obj) == []
        by_trace: dict[int, set] = {}
        lag_spans = []
        for ev in obj["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            args = ev.get("args") or {}
            tid = args.get("trace_id")
            if tid:
                by_trace.setdefault(tid, set()).add(ev["name"])
            if ev["name"] == "replication_lag":
                lag_spans.append(ev)
        want = {"admission_wait", "prefill_wave", "decode_chunk", "publish"}
        assert any(want <= names for names in by_trace.values()), (
            "no request carried the full span tree",
            {t: sorted(n) for t, n in by_trace.items()},
        )
        assert lag_spans, "no ring replication-lag spans recorded"
        assert all(ev["dur"] >= 0 for ev in lag_spans)

    def test_workload_emits_trace_artifact_inline(self, tmp_path):
        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        eng = _tiny_engine("trace-artifact")
        wl = MultiTurnWorkload(
            n_conversations=1, n_turns=2, system_len=8,
            user_len=4, gen_len=4, vocab_size=256,
        )
        path = str(tmp_path / "wl_trace.json")
        report = run_engine_workload(eng, wl, trace_path=path)
        assert report["trace_artifact"] == path
        assert report["trace_spans"] > 0
        with open(path) as fh:
            assert validate_trace(json.load(fh)) == []


class TestSLOQueueSpan:
    def test_slo_dispatch_records_queue_span(self):
        from radixmesh_tpu.slo import SLOConfig
        from radixmesh_tpu.slo.runner import SLORunner

        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        eng = _tiny_engine("trace-slo")
        runner = SLORunner(eng, SLOConfig()).start()
        try:
            req = runner.submit(list(range(1, 12)))
            runner.wait(req, timeout=60)
            names = {s.name for s in get_recorder().snapshot()}
            assert "slo_queue" in names
        finally:
            runner.close()


class TestDisaggSpans:
    def test_handoff_records_pack_and_write_spans(self):
        from radixmesh_tpu.engine.disagg import DecodeWorker, PrefillWorker

        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        cfg = ModelConfig.tiny()
        params = init_params(cfg, jax.random.PRNGKey(0))
        pw = PrefillWorker(
            cfg, params, num_slots=256, page_size=4, max_batch=1,
            name="trace-pw",
        )
        dw = DecodeWorker(
            Engine(cfg, params, num_slots=256, page_size=4, max_batch=1,
                   name="trace-dw")
        )
        pkt = pw.prefill_handoff(list(range(1, 14)))
        dw.submit(pkt)
        dw.run_until_drained()
        names = {s.name for s in get_recorder().snapshot()}
        assert {"disagg_handoff_pack", "disagg_handoff_receive",
                "disagg_kv_write"} <= names

    def test_fractional_sampling_follows_packet_traced_bit(self):
        """Under 0<sample<1 the decode side must follow the prefill
        node's coin flip (HandoffPacket.traced + force), not flip its
        own — else cross-node timelines come apart probabilistically."""
        from radixmesh_tpu.engine.disagg import (
            HandoffPacket,
            pack_handoff,
            unpack_handoff,
        )
        import numpy as np

        pkt = HandoffPacket(
            prompt=np.arange(1, 9, dtype=np.int32),
            first_token=3,
            kv=np.zeros((2, 1, 8, 1, 2), dtype=np.float32),
            traced=True,
        )
        rt = unpack_handoff(pack_handoff(pkt))
        assert rt.traced is True  # the bit survives the wire
        # force=True skips the coin (a ~0 sample would lose every flip)
        # but NOT the off switch.
        rec = FlightRecorder(capacity=8, sample=1e-9)
        assert rec.trace("req:1", force=True) is not None
        assert FlightRecorder(capacity=8, sample=0.0).trace(
            "req:1", force=True
        ) is None


class TestLaunchTraceFlags:
    def _args(self, **kw):
        import argparse

        base = dict(trace_capacity=64, trace_sample=None, trace_dir=None)
        base.update(kw)
        return argparse.Namespace(**base)

    def test_trace_dir_alone_implies_full_sampling(self):
        from radixmesh_tpu.launch import _configure_tracing

        _configure_tracing(self._args(trace_dir="/tmp/x"))
        assert get_recorder().sample == 1.0

    def test_explicit_zero_sample_wins_over_trace_dir(self):
        from radixmesh_tpu.launch import _configure_tracing

        before = get_recorder()
        _configure_tracing(self._args(trace_dir="/tmp/x", trace_sample=0.0))
        # Recorder untouched: the operator said off, so off.
        assert get_recorder() is before and not get_recorder().enabled

    def test_unset_everything_stays_disabled(self):
        from radixmesh_tpu.launch import _configure_tracing

        before = get_recorder()
        _configure_tracing(self._args())
        assert get_recorder() is before and not get_recorder().enabled

    def test_dump_skipped_when_tracing_explicitly_off(self, tmp_path):
        import logging
        import os

        from radixmesh_tpu.launch import _configure_tracing, _dump_trace

        args = self._args(trace_dir=str(tmp_path / "t"), trace_sample=0.0)
        _configure_tracing(args)
        _dump_trace(args, logging.getLogger("t"))
        # No empty junk artifact that reads as "a trace was captured".
        assert not os.path.exists(args.trace_dir)


class TestCrossNodeStitching:
    """PR 9: 64-bit globally-unique trace ids that cross the wire, per-
    span node attribution, and the merge path that folds many nodes'
    exports into ONE Perfetto document with one process-track per node
    and clock-offset correction."""

    def test_new_trace_ids_are_unique_and_nonzero(self):
        from radixmesh_tpu.obs.trace_plane import new_trace_id

        ids = {new_trace_id() for _ in range(2000)}
        assert len(ids) == 2000
        assert all(0 < i < (1 << 64) for i in ids)

    def test_trace_id_adoption_implies_force(self):
        """A receiver handed an upstream id must keep it (the stitch
        contract) and must not re-flip the sampling coin — the id's
        existence IS the upstream decision."""
        rec = FlightRecorder(capacity=8, sample=1e-9, node="n1")
        ctx = rec.trace("req:7", trace_id=0xABCDE)
        assert ctx is not None and ctx.trace_id == 0xABCDE
        # The off switch still wins (tracing disabled = no spans, ever).
        assert FlightRecorder(capacity=8, sample=0.0).trace(
            "req:7", trace_id=0xABCDE
        ) is None

    def test_spans_carry_node_labels(self):
        rec = FlightRecorder(capacity=8, sample=1.0, node="default-node")
        rec.trace("req:1").add("a", 0.0, 0.1)
        rec.trace("req:2", node="other-node").add("b", 0.0, 0.1)
        rec.event("lane", "c", 0.0, 0.1)
        nodes = [s.node for s in rec.snapshot()]
        assert nodes == ["default-node", "other-node", "default-node"]

    def test_event_with_trace_id_skips_coin_flip(self):
        rec = FlightRecorder(capacity=64, sample=1e-9)
        for _ in range(20):
            rec.event("lane", "lag", 0.0, 0.1, trace_id=0x77)
        assert len(rec) == 20
        assert all(s.trace_id == 0x77 for s in rec.snapshot())

    def test_merge_one_pid_per_node_with_clock_offsets(self):
        """Two exports with different wall offsets (two processes) plus
        a per-node skew estimate: the merged doc carries one process
        track per node, process_name metadata, and validates against
        the trace artifact contract."""
        from radixmesh_tpu.obs.trace_plane import stitch_traces

        a = FlightRecorder(capacity=8, sample=1.0, node="prefill@0")
        b = FlightRecorder(capacity=8, sample=1.0, node="decode@1")
        a.trace("req:1", trace_id=5).add("publish", 1.0, 0.2)
        b.event("ring:decode@1", "replication_lag", 1.1, 0.1, trace_id=5)
        ea, eb = a.export_spans(), b.export_spans()
        eb["wall_offset"] += 3.0  # a second process's clock base
        doc = stitch_traces([ea, eb], clock_offsets={"decode@1": 3.0})
        assert validate_trace(doc) == []
        procs = {
            ev["args"]["name"]
            for ev in doc["traceEvents"]
            if ev["ph"] == "M" and ev["name"] == "process_name"
        }
        assert procs == {"prefill@0", "decode@1"}
        pids = {
            ev["pid"] for ev in doc["traceEvents"] if ev.get("ph") == "X"
        }
        assert len(pids) == 2
        # Both spans stitch under the SAME trace id.
        tids = {
            ev["args"]["trace_id"]
            for ev in doc["traceEvents"]
            if ev.get("ph") == "X"
        }
        assert len(tids) == 1
        # The offset correction cancelled decode@1's +3s base: the two
        # spans sit ~0.1s apart, not ~3s.
        xs = sorted(
            ev["ts"] for ev in doc["traceEvents"] if ev.get("ph") == "X"
        )
        assert xs[1] - xs[0] < 1e6  # microseconds

    def test_single_inproc_export_groups_by_span_node(self):
        """In-process multi-node harnesses share ONE recorder: the
        stitcher must split tracks by each SPAN's node label."""
        from radixmesh_tpu.obs.trace_plane import stitch_traces

        rec = FlightRecorder(capacity=16, sample=1.0, node="edge")
        for node in ("edge", "prefill@0", "decode@1"):
            rec.event("lane", "e", 1.0, 0.1, trace_id=9, node=node)
        doc = stitch_traces([rec.export_spans()])
        procs = {
            ev["args"]["name"]
            for ev in doc["traceEvents"]
            if ev["ph"] == "M" and ev["name"] == "process_name"
        }
        assert procs == {"edge", "prefill@0", "decode@1"}


class TestNoOpGuardNewCallSites:
    """The PR 2 invariant — sampling off means zero span allocations and
    zero recorder writes — re-proven at the PR 9 call sites: the oplog
    receive path (trace trailer handling) and the engine wave paths
    (step accounting's seam)."""

    def test_oplog_receive_with_trace_trailer_records_nothing_when_off(
        self, monkeypatch
    ):
        import numpy as np

        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.cache.oplog import Oplog, OplogType, serialize
        from radixmesh_tpu.config import MeshConfig

        calls = {"record": 0, "event": 0}
        orig = FlightRecorder._record
        monkeypatch.setattr(
            FlightRecorder,
            "_record",
            lambda self, span: (calls.__setitem__(
                "record", calls["record"] + 1
            ), orig(self, span))[1],
        )
        set_recorder(FlightRecorder(capacity=64, sample=0.0))
        mesh = MeshCache(MeshConfig(
            prefill_nodes=["p0", "p1"], decode_nodes=[], router_nodes=[],
            local_addr="p0", protocol="inproc",
        ))
        try:
            frame = serialize(Oplog(
                op_type=OplogType.INSERT, origin_rank=1, logic_id=1, ttl=2,
                key=np.arange(1, 5, dtype=np.int32),
                value=np.arange(4, dtype=np.int32),
                value_rank=1, ts=time.time(),
                trace_id=0xBEEF,  # trailer present; receiver must no-op
            ))
            mesh.oplog_received(frame)
            assert mesh.tree.match_prefix(
                np.arange(1, 5, dtype=np.int32)
            ).length == 4  # the apply happened
            assert calls["record"] == 0  # ...with zero recorder writes
        finally:
            mesh.close()

    def test_wave_paths_with_accounting_off_touch_no_recorder(
        self, monkeypatch
    ):
        """Default engines (step_accounting off) keep the wave hot paths
        at one `is not None` branch: a full serve with sampling off
        makes zero recorder writes and allocates no StepAccounting."""
        calls = {"record": 0}
        orig = FlightRecorder._record
        monkeypatch.setattr(
            FlightRecorder,
            "_record",
            lambda self, span: (calls.__setitem__(
                "record", calls["record"] + 1
            ), orig(self, span))[1],
        )
        set_recorder(FlightRecorder(capacity=64, sample=0.0))
        eng = _tiny_engine("waves-off")
        assert eng.step_acct is None
        eng.add_request(list(range(1, 16)))
        for _ in range(200):
            if not eng.has_work():
                break
            eng.step()
        assert calls["record"] == 0

    def test_mesh_insert_without_trace_id_records_nothing(self):
        import numpy as np

        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.config import MeshConfig

        set_recorder(FlightRecorder(capacity=64, sample=1.0))
        mesh = MeshCache(MeshConfig(
            prefill_nodes=["p0", "p1"], decode_nodes=[], router_nodes=[],
            local_addr="p0", protocol="inproc",
        ))
        try:
            mesh.insert(
                np.arange(1, 5, dtype=np.int32),
                np.arange(4, dtype=np.int32),
            )
            names = {s.name for s in get_recorder().snapshot()}
            assert "mesh_publish" not in names  # untraced insert: no anchor
            mesh.insert(
                np.arange(1, 5, dtype=np.int32),
                np.arange(4, dtype=np.int32),
                trace_id=0x123,
            )
            spans = [
                s for s in get_recorder().snapshot()
                if s.name == "mesh_publish"
            ]
            assert spans and spans[0].trace_id == 0x123
            assert spans[0].node == "prefill@0"
        finally:
            mesh.close()


class TestStepAccounting:
    """obs/step_plane.py unit math + the engine seam (leg (c) of the
    observability tentpole)."""

    def test_note_wave_math(self):
        from radixmesh_tpu.obs.step_plane import StepAccounting

        acct = StepAccounting("unit", n_params=1_000_000, peak_tflops=1.0)
        # 500 real of 1000 launched tokens in 1 ms on a 1 TFLOP/s peak:
        # 2e6 FLOPs/token * 500 / (1e12 * 1e-3) = 1e9/1e9 = 1.0e0... no:
        # 2*1e6*500 = 1e9 FLOPs over 1e9 peak-FLOP budget -> MFU 1.0.
        mfu = acct.note_wave("prefill", 500, 1000, 1e-3)
        assert mfu == pytest.approx(1.0)
        rep = acct.report()
        assert rep["prefill"]["waves"] == 1
        assert rep["prefill"]["pad_fraction"] == pytest.approx(0.5)
        assert rep["prefill"]["mfu"] == pytest.approx(1.0)
        assert rep["decode"]["waves"] == 0
        with pytest.raises(ValueError):
            acct.note_wave("warp", 1, 1, 1.0)

    def test_peak_lookup_knows_the_device_or_raises(self, monkeypatch):
        """A CPU gets the nominal figure, a listed TPU its published
        peak, and an accelerator the table does not know is an error —
        not a made-up 1 TFLOP/s every MFU reading would be scaled by."""
        import jax

        from radixmesh_tpu.obs.step_plane import detect_peak_tflops

        assert detect_peak_tflops() == 1.0  # this process: cpu

        class Dev:
            platform = "tpu"
            device_kind = "TPU v5 lite"

        monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
        assert detect_peak_tflops() == 197.0
        Dev.device_kind = "TPU v9x"
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            detect_peak_tflops()

    def test_engine_reports_prefill_and_decode_waves(self):
        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        eng = _tiny_engine("steps-on", step_accounting=True, peak_tflops=1.0)
        eng.generate([list(range(1, 14)), list(range(1, 10))])
        rep = eng.step_acct.report()
        for kind in ("prefill", "decode"):
            assert rep[kind]["waves"] > 0, rep
            assert rep[kind]["mfu"] > 0
            assert 0.0 <= rep[kind]["pad_fraction"] < 1.0
        # The step_wave spans landed on the engine's step lane.
        lanes = {s.lane for s in get_recorder().snapshot()}
        assert "step:steps-on" in lanes


class TestTraceArtifactSchema:
    """The flight-recorder trace artifact (workload
    trace_path emissions) stays machine-loadable: valid JSON object, a
    traceEvents list, numeric ts/dur, monotonic ts within each lane."""

    def _trace(self) -> dict:
        from radixmesh_tpu.obs.trace_plane import FlightRecorder

        rec = FlightRecorder(capacity=256, sample=1.0)
        ctx = rec.trace("req:1")
        ctx.add("admission_wait", 1.0, 0.01)
        ctx.add("prefill_wave", 1.01, 0.2, wave_rows=2)
        ctx.add("decode_chunk", 1.21, 0.05, k_steps=8)
        ctx.add("publish", 1.26, 0.002)
        rec.event("ring:decode@1", "replication_lag", 1.27, 0.003,
                  origin_rank=0)
        return json.loads(json.dumps(rec.chrome_trace()))

    def test_recorder_export_validates(self):
        assert validate_trace(self._trace()) == []

    def test_violations_are_named(self):
        obj = self._trace()
        xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        xs[0]["ts"] = -5.0           # negative timestamp
        xs[1]["dur"] = "fast"        # non-numeric duration
        del xs[2]["tid"]             # no lane
        problems = "\n".join(validate_trace(obj))
        assert "ts invalid" in problems
        assert "dur invalid" in problems
        assert "tid missing" in problems

    def test_ts_regression_within_lane_is_flagged(self):
        obj = self._trace()
        xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        same_lane = [e for e in xs if e["tid"] == xs[0]["tid"]]
        assert len(same_lane) >= 2
        same_lane[-1]["ts"] = 0.0  # jump backwards in its lane
        assert any(
            "regresses within tid" in p for p in validate_trace(obj)
        )

    def test_not_an_object_and_missing_events(self):
        assert validate_trace([1, 2]) == ["artifact is not a JSON object"]
        assert validate_trace({"x": 1}) == [
            "traceEvents missing or not a list"
        ]

"""HTTP serving + routing frontends (the layer above the reference's
router that its repo explicitly leaves out — SURVEY §1 L5) and the CLI
launcher's argument surface."""

import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.models.llama import ModelConfig, init_params
from radixmesh_tpu.server.http_frontend import RouterFrontend, ServingFrontend


def _post(url: str, obj: dict, timeout=60):
    req = urllib.request.Request(
        url,
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url: str, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


@pytest.fixture(scope="module")
def frontend():
    cfg = ModelConfig.tiny()
    eng = Engine(
        cfg,
        init_params(cfg, jax.random.PRNGKey(0)),
        num_slots=512,
        page_size=4,
        max_batch=2,
        name="http-test",
    )
    f = ServingFrontend(eng, port=0)
    yield f
    f.close()


class TestServingFrontend:
    def test_generate(self, frontend):
        status, out = _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": list(range(1, 20)), "max_tokens": 8},
        )
        assert status == 200
        assert len(out["output_ids"]) >= 1
        assert out["cached_tokens"] == 0

    def test_generate_hits_cache_on_revisit(self, frontend):
        prompt = list(range(40, 80))
        _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": prompt, "max_tokens": 4},
        )
        status, out = _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": prompt, "max_tokens": 4},
        )
        assert status == 200
        assert out["cached_tokens"] > 0

    def test_generate_deterministic_greedy(self, frontend):
        prompt = list(range(90, 120))
        outs = [
            _post(
                f"http://127.0.0.1:{frontend.port}/generate",
                {"input_ids": prompt, "max_tokens": 6, "temperature": 0.0},
            )[1]["output_ids"]
            for _ in range(2)
        ]
        assert outs[0] == outs[1]

    def test_streaming_sse(self, frontend):
        req = urllib.request.Request(
            f"http://127.0.0.1:{frontend.port}/generate",
            data=json.dumps(
                {"input_ids": list(range(1, 16)), "max_tokens": 5, "stream": True}
            ).encode(),
            method="POST",
        )
        tokens, done = [], None
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.headers["Content-Type"].startswith("text/event-stream")
            for line in r:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                evt = json.loads(line[len("data: "):])
                if evt.get("done"):
                    done = evt
                    break
                tokens.append(evt["token"])
        assert done is not None
        assert done["output_ids"] == tokens
        # the prefix the tree served, as the non-streaming reply has it
        assert type(done["cached_tokens"]) is int and 0 <= done["cached_tokens"] < 15

    def test_bad_request(self, frontend):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(
                f"http://127.0.0.1:{frontend.port}/generate",
                {"input_ids": "not a list"},
            )
        assert e.value.code == 400

    @pytest.mark.parametrize("ids", [
        [1, 2, True], [1, 2.0, 3], [1, "2"], [1, None], [[1], 2], (1, 2), "12",
    ], ids=repr)
    def test_input_ids_are_a_list_of_ints_and_nothing_else(self, ids):
        from radixmesh_tpu.server.http_frontend import _ids_from_body

        with pytest.raises(ValueError, match="list of ints"):
            _ids_from_body({"input_ids": ids}, None, "server")
        good = list(range(5))
        assert _ids_from_body({"input_ids": good}, None, "server") is good
        assert _ids_from_body({"input_ids": []}, None, "server") == []

    def test_healthz_stats_metrics(self, frontend):
        status, _ = _get(f"http://127.0.0.1:{frontend.port}/healthz")
        assert status == 200
        status, body = _get(f"http://127.0.0.1:{frontend.port}/stats")
        assert status == 200 and b"hit_rate" in body
        # The module-scoped engine bound its counters to an earlier test's
        # registry (conftest isolates registries per test), so only check
        # the endpoint serves a well-formed exposition here; counter
        # presence is covered by test_metrics.py.
        status, body = _get(f"http://127.0.0.1:{frontend.port}/metrics")
        assert status == 200

    def test_concurrent_requests(self, frontend):
        import concurrent.futures as cf

        prompts = [list(range(s, s + 12)) for s in (1, 50, 100, 150)]
        with cf.ThreadPoolExecutor(4) as ex:
            results = list(
                ex.map(
                    lambda p: _post(
                        f"http://127.0.0.1:{frontend.port}/generate",
                        {"input_ids": p, "max_tokens": 4},
                    )[1]["output_ids"],
                    prompts,
                )
            )
        assert all(len(r) >= 1 for r in results)

    def test_a_burst_of_callers_connects_whole(self, frontend):
        """A closed loop of 128 callers, or a warm-up burst of 64, connects
        at once. Behind socketserver's backlog of 5 the kernel dropped
        handshakes and reset a connection in a few hundred a second later;
        the server asks for a backlog the callers fit in."""
        import threading

        assert frontend._server.request_queue_size >= 256
        statuses = []

        def caller(k):
            try:
                status, body = _post(
                    f"http://127.0.0.1:{frontend.port}/generate",
                    {"input_ids": list(range(k, k + 9)), "max_tokens": 1})
                statuses.append(status if body["output_ids"] else "empty")
            except OSError as e:  # URLError, ConnectionResetError
                statuses.append(repr(e))

        for _ in range(2):
            threads = [threading.Thread(target=caller, args=(k,))
                       for k in range(96)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert statuses == [200] * 192, [s for s in statuses if s != 200]


class TestRouterFrontend:
    def test_route_endpoint(self):
        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig, NodeRole
        from radixmesh_tpu.cache.kv_pool import PagedKVPool
        from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter
        import time

        InprocHub.reset_default()
        prefill, decode, router = ["p0"], ["d0"], ["r0"]
        nodes = []
        try:
            for addr in prefill + decode + router:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.05,
                    gc_interval_s=30.0,
                )
                pool = (
                    None
                    if cfg.local_role is NodeRole.ROUTER
                    else PagedKVPool(
                        num_slots=64, num_layers=1, num_kv_heads=1, head_dim=2
                    )
                )
                nodes.append(MeshCache(cfg, pool=pool).start())
            for n in nodes:
                assert n.wait_ready(timeout=10)
            p0 = nodes[0]
            slots = p0.pool.alloc(3)
            p0.insert([7, 8, 9], slots)
            rnode = nodes[2]
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if getattr(rnode.match_prefix([7, 8, 9]), "prefill_rank", -1) == 0:
                    break
                time.sleep(0.01)
            car = CacheAwareRouter(rnode, rnode.cfg)
            car.finish_warm_up()
            f = RouterFrontend(car, port=0)
            try:
                status, out = _post(
                    f"http://127.0.0.1:{f.port}/route", {"input_ids": [7, 8, 9, 10]}
                )
                assert status == 200
                assert out["prefill_addr"] == "p0"
                assert out["prefill_cache_hit"] is True
                assert out["match_len"] == 3
                # Cold key falls back to the hash ring.
                status, out = _post(
                    f"http://127.0.0.1:{f.port}/route", {"input_ids": [999, 998]}
                )
                assert out["prefill_addr"] == "p0"  # only node
                assert out["prefill_cache_hit"] is False
            finally:
                f.close()
        finally:
            for n in nodes:
                n.close()
            InprocHub.reset_default()


class TestLaunchCLI:
    def test_parser_surface(self):
        from radixmesh_tpu.launch import main

        with pytest.raises(SystemExit):
            main(["--help"])
        with pytest.raises(SystemExit):
            main([])  # command required

    def test_node_requires_config(self):
        from radixmesh_tpu.launch import main

        with pytest.raises(SystemExit):
            main(["node"])


class TestProfileEndpoint:
    def test_profile_captures_trace(self, tmp_path):
        import os

        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=64, page_size=4, max_batch=1, name="http-prof")
        f = ServingFrontend(eng, port=0, profile_dir=str(tmp_path))
        try:
            code, body = _post(
                f"http://127.0.0.1:{f.port}/profile", {"seconds": 0.2}
            )
            assert code == 200, body
            assert body["logdir"].startswith(str(tmp_path))
            files = [x for _, _, fs in os.walk(body["logdir"]) for x in fs]
            assert files, "no trace artifacts written"
        finally:
            f.close()

    def test_profile_disabled_and_bad_duration(self, tmp_path, frontend):
        import urllib.error

        # fixture frontend has no profile_dir → 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"http://127.0.0.1:{frontend.port}/profile", {"seconds": 1})
        assert ei.value.code == 403
        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=64, page_size=4, max_batch=1, name="http-prof2")
        f = ServingFrontend(eng, port=0, profile_dir=str(tmp_path))
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://127.0.0.1:{f.port}/profile", {"seconds": -1})
            assert ei.value.code == 400
        finally:
            f.close()


    def test_missing_profiler_is_an_error_not_an_empty_capture(
        self, tmp_path, monkeypatch
    ):
        """A profiler that cannot start answers 500 with the message — it
        used to be swallowed into a 200 with nothing captured."""
        import jax.profiler

        def broken(_logdir, **_kw):
            raise RuntimeError("profiler backend missing")

        monkeypatch.setattr(jax.profiler, "trace", broken)
        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=64, page_size=4, max_batch=1, name="http-prof3")
        f = ServingFrontend(eng, port=0, profile_dir=str(tmp_path))
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://127.0.0.1:{f.port}/profile", {"seconds": 0.1})
            assert ei.value.code == 500
            assert "profiler backend missing" in ei.value.read().decode()
        finally:
            f.close()


class TestStepFailure:
    """A step that raises fails its requests with the error and is
    counted; it is not retried in a loop while the clients time out."""

    def _frontend(self, name):
        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=256, page_size=4, max_batch=2, name=name)
        return eng, ServingFrontend(eng, port=0)

    def test_prefill_failure_answers_500_and_serving_continues(
        self, monkeypatch
    ):
        eng, f = self._frontend("http-fail")
        base = f"http://127.0.0.1:{f.port}"
        real = eng._prefill_dense
        free0 = eng.pool.free_slots
        calls = []

        def refused(*a, **k):
            calls.append(1)
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        try:
            monkeypatch.setattr(eng, "_prefill_dense", refused)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/generate",
                      {"input_ids": list(range(1, 20)), "max_tokens": 4})
            assert ei.value.code == 500
            body = json.loads(ei.value.read())
            assert body["failed"] is True
            assert "Mosaic failed to compile" in body["error"]
            assert calls == [1], "the failing step was retried"
            state = json.loads(_get(base + "/debug/state")[1])
            assert state["engine"]["step_failures"] == 1
            assert "Mosaic failed" in state["engine"]["last_step_error"]
            # The member was mid-admission (out of `waiting`, not yet in a
            # row): its pages and prefix lock came back.
            assert eng.pool.free_slots == free0
            assert not eng.has_work()
            metrics = _get(base + "/metrics")[1].decode()
            assert (
                'radixmesh_engine_step_failures_total{engine="http-fail"} 1'
                in metrics
            )
            # The next request gets a fresh step.
            monkeypatch.setattr(eng, "_prefill_dense", real)
            status, out = _post(
                base + "/generate",
                {"input_ids": list(range(1, 20)), "max_tokens": 4},
            )
            assert status == 200 and len(out["output_ids"]) == 4
        finally:
            f.close()

    def test_decode_failure_fails_every_running_stream(self, monkeypatch):
        from radixmesh_tpu.engine.request import RequestState, SamplingParams

        eng, f = self._frontend("http-fail2")
        try:
            def boom(*a, **k):
                raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")

            monkeypatch.setattr(eng, "_decode_once", boom)
            reqs = [
                f.runner.submit(list(range(i, i + 12)),
                                SamplingParams(max_new_tokens=8))
                for i in (1, 40)
            ]
            for req in reqs:
                f.runner.wait(req, timeout=30.0)
                assert req.state is RequestState.FINISHED
                assert req.error == "MemoryError: RESOURCE_EXHAUSTED: out of HBM"
            assert f.runner.step_failures >= 1
        finally:
            f.close()


class TestGracefulShutdown:
    def test_close_cancels_stragglers(self):
        """Waiters must not hang on requests the stopped scheduler will
        never step again: close() cancels them, so wait() returns with
        partial output and the cancelled flag set."""
        import time

        from radixmesh_tpu.engine.request import RequestState, SamplingParams

        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=256, page_size=4, max_batch=1, name="http-drain")
        f = ServingFrontend(eng, port=0)
        req = f.runner.submit([1, 2, 3], SamplingParams(max_new_tokens=500))
        time.sleep(2.0)  # let some decoding happen
        f.close(drain_s=0.1)  # too short to finish 500 tokens
        assert len(req.generated) < 500, (
            "host decoded 500 tokens in 2s; raise max_new_tokens"
        )
        assert req.state is RequestState.FINISHED
        assert req.cancelled
        # wait() must return promptly instead of hanging on a request the
        # stopped scheduler would never finish.
        f.runner.wait(req, timeout=1.0)
        # And late submits are refused rather than stranded.
        import pytest as _pytest

        with _pytest.raises(RuntimeError):
            f.runner.submit([4, 5], SamplingParams(max_new_tokens=2))


class TestConcurrentClients:
    def test_parallel_submit_cancel_storm(self):
        """Concurrent clients through the REAL HTTP layer — /generate
        (some streaming via SSE) racing /cancel: every request must get a
        response (no stranded handler, no dropped connection) and the
        engine must drain."""
        import threading
        import time as _time

        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=512, page_size=4, max_batch=3, name="http-conc")
        f = ServingFrontend(eng, port=0)
        errors: list = []
        rids: list = []

        def client(i):
            rng = np.random.default_rng(100 + i)  # per-thread: race-free + replayable
            try:
                n = int(rng.integers(3, 12))
                prompt = rng.integers(1, cfg.vocab_size, n).tolist()
                body = {
                    "input_ids": prompt,
                    "max_tokens": int(rng.integers(2, 10)),
                }
                if i % 4 == 1:
                    body["stream"] = True
                if i % 3 == 0:
                    # Race a cancel against the in-flight generate from a
                    # second connection (rids are assigned sequentially).
                    def late_cancel():
                        _time.sleep(0.05)
                        try:
                            _post(
                                f"http://127.0.0.1:{f.port}/cancel",
                                {"rid": i},
                            )
                        except Exception:  # noqa: BLE001 — unknown rid etc.
                            pass

                    threading.Thread(target=late_cancel).start()
                if body.get("stream"):
                    import urllib.request

                    req = urllib.request.Request(
                        f"http://127.0.0.1:{f.port}/generate",
                        data=json.dumps(body).encode(),
                        method="POST",
                    )
                    with urllib.request.urlopen(req, timeout=120) as r:
                        data = r.read().decode()  # consume the SSE stream
                    assert "done" in data
                    rids.append(i)
                else:
                    code, resp = _post(
                        f"http://127.0.0.1:{f.port}/generate", body, timeout=120
                    )
                    assert code == 200
                    rids.append(resp["rid"])
            except Exception as e:  # noqa: BLE001
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        try:
            assert not errors, errors
            assert len(rids) == 12
            assert not any(t.is_alive() for t in threads), "stranded client"
            deadline = _time.monotonic() + 30
            while eng.has_work() and _time.monotonic() < deadline:
                _time.sleep(0.05)
            assert not eng.has_work()
        finally:
            f.close(drain_s=0.5)


class TestDeferredEmissionStream:
    """A decode token's wake-up runs behind the NEXT launch
    (``Engine._flush_emit``) and is never held across a wait: neither a
    prefill's sync, nor an engine that has no further step to run, nor the
    0.5 s fallback of ``Handler._stream``."""

    def test_tokens_arrive_without_the_fallback(self):
        import threading
        import time as _time

        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=1024, page_size=4, max_batch=2, name="http-emit")
        f = ServingFrontend(eng, port=0)
        base = f"http://127.0.0.1:{f.port}"
        stall_s, limit_s = 0.8, 0.3
        stamps: list[float] = []  # the step stamp of each decode token of A
        slow = threading.Event()
        advance, finalize = eng._advance_token, eng._finalize_first_tokens

        def spy(req, row, slot, token, stamp):
            if req.prompt[0] == 1:
                stamps.append(stamp)
            return advance(req, row, slot, token, stamp)

        def slow_finalize(pending):
            # A long prefill: its sync comes AFTER the flush site in _admit.
            if slow.is_set():
                _time.sleep(stall_s)
            return finalize(pending)

        eng._advance_token = spy
        eng._finalize_first_tokens = slow_finalize

        def round_(n_a):
            """Stream A; once four of its tokens are here, post B, whose
            prefill then runs between two of A's decode steps."""
            arrivals, out = [], {}
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"input_ids": list(range(1, 17)), "max_tokens": n_a,
                                 "stream": True, "stop_token_ids": []}).encode(),
                method="POST",
            )
            b = threading.Thread(target=lambda: out.update(_post(
                base + "/generate",
                {"input_ids": list(range(300, 312)), "max_tokens": 2,
                 "stop_token_ids": []})[1]))
            with urllib.request.urlopen(req, timeout=60) as r:
                for line in r:
                    line = line.decode().strip()
                    if not line.startswith("data: "):
                        continue
                    evt = json.loads(line[len("data: "):])
                    arrivals.append(_time.monotonic())
                    if evt.get("done"):
                        out["a"] = evt["output_ids"]
                        break
                    if len(arrivals) == 4:
                        b.start()
            b.join(timeout=60)
            assert not b.is_alive()
            return arrivals, out

        def state():
            with f.runner._lock:  # /debug/state takes no lock, a step does
                return json.loads(_get(base + "/debug/state")[1])["engine"]

        try:
            round_(200)  # compiles every shape of a cold round ...
            round_(200)  # ... and of one whose prompts the tree serves
            stamps.clear()
            before = state()
            slow.set()
            arrivals, out = round_(200)
            after = state()
        finally:
            f.close()
        n = len(out["a"])
        assert n == 200 and len(arrivals) == n + 1 and len(stamps) == n - 1
        # No token of A waited out B's prefill, nor a fallback: each arrived
        # within the limit of the step that made it (the first token is
        # woken at its own site; decode token i carries stamps[i - 1]) ...
        late = [t - s for t, s in zip(arrivals[1:n], stamps)]
        assert max(late) < limit_s, sorted(late)[-3:]
        # ... though that prefill really stood between two of them.
        gaps = [b - a for a, b in zip(arrivals, arrivals[1:])]
        assert max(gaps) > 0.9 * stall_s
        # The last tokens and the done event of a request whose engine then
        # has nothing left to run: at once, through the finish.
        assert arrivals[n] - stamps[-1] < limit_s
        # engine.emit counts every decode token once and names where each
        # flush ran: behind decode launches, behind B's prefill, and at the
        # end of the steps that left no row.
        d = {k: after["emit"][k] - before["emit"][k] for k in after["emit"]}
        assert d["tokens"] == (n - 1) + (len(out["output_ids"]) - 1)
        assert d["flushes_under_prefill"] == 1
        assert d["flushes_exposed"] >= 1
        assert d["flushes_under_decode"] >= n - 4
        assert not eng._emit_rows and not eng._emit_steps


class TestStreamEmitter:
    """The decode tokens of every SSE stream are written by ONE thread
    (``_StreamEmitter``): a step's rows wake it once, not a handler each.
    A stream whose socket does not take a write at once goes back to its
    handler for good, with what is still to go."""

    @pytest.mark.parametrize("tokens", [[0], [7, 151_643, 12], []])
    def test_events_are_the_json_form(self, tokens):
        from radixmesh_tpu.server.http_frontend import _token_events

        want = "".join(f"data: {json.dumps({'token': t})}\n\n" for t in tokens)
        assert _token_events(tokens) == want.encode()

    def test_one_thread_writes_the_streams(self, monkeypatch):
        import threading

        from radixmesh_tpu.server import http_frontend

        cfg = ModelConfig.tiny()
        eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                     num_slots=1024, page_size=4, max_batch=4, name="http-one")
        f = ServingFrontend(eng, port=0)
        wrote: dict[str, int] = {}
        events = http_frontend._token_events

        def spy(tokens):
            name = threading.current_thread().name
            wrote[name] = wrote.get(name, 0) + len(tokens)
            return events(tokens)

        monkeypatch.setattr(http_frontend, "_token_events", spy)
        outs: dict[int, list] = {}

        def client(i):
            req = urllib.request.Request(
                f"http://127.0.0.1:{f.port}/generate",
                data=json.dumps({"input_ids": list(range(1 + i, 13 + i)),
                                 "max_tokens": 40, "stream": True,
                                 "stop_token_ids": []}).encode(),
                method="POST")
            got = []
            with urllib.request.urlopen(req, timeout=60) as r:
                for line in r:
                    line = line.decode().strip()
                    if line.startswith("data: "):
                        got.append(json.loads(line[len("data: "):]))
            outs[i] = got

        try:
            threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            f.close()
        for got in outs.values():
            *tokens, done = got
            assert done["done"] and len(done["output_ids"]) == 40
            assert [e["token"] for e in tokens] == done["output_ids"]
        assert len(outs) == 4
        # the handlers wrote at most what a finish left them
        assert wrote["http-emit"] >= 4 * 40 - 4 * 2
        assert sum(wrote.values()) == 4 * 40
        assert not f.emitter._streams

    def test_a_reader_that_falls_behind_gets_its_handler_back(self):
        import socket

        from radixmesh_tpu.engine.request import Request, SamplingParams
        from radixmesh_tpu.server.http_frontend import (
            _SseStream, _StreamEmitter, _token_events)

        a, b = socket.socketpair()
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        req = Request(prompt=np.arange(4), sampling=SamplingParams())
        em = _StreamEmitter()
        stream = _SseStream(req, a)
        em.add(stream)
        assert req.on_progress is not None
        req.output_tokens.extend(range(3))
        stream.pump()
        assert stream.emitter_owns and stream.sent == 3
        req.output_tokens.extend(range(3, 200_000))  # more than the socket takes
        stream.pump()
        assert not stream.emitter_owns and stream.rest
        assert req.on_progress is None  # its waiters are told again
        req.output_tokens.append(5)
        stream.pump()  # no longer the emitter's: nothing is written or counted
        assert stream.sent == 200_000
        em.remove(stream)
        em.close()
        b.settimeout(5)
        got = bytearray()
        want = _token_events(range(200_000))
        while len(got) + len(stream.rest) < len(want):
            got += b.recv(1 << 20)
        assert bytes(got) + stream.rest == want
        a.close()
        b.close()


class TestSLOFrontend:
    """The overload control plane surfaced over HTTP: tenant field,
    429 + Retry-After on rate limit, /stats slo section."""

    def _frontend(self, **tenant_kw):
        from radixmesh_tpu.slo import SLOConfig, TenantConfig

        cfg = ModelConfig.tiny()
        eng = Engine(
            cfg,
            init_params(cfg, jax.random.PRNGKey(2)),
            num_slots=512,
            page_size=4,
            max_batch=2,
            name="http-slo-test",
        )
        slo = SLOConfig(
            tenants={"free": TenantConfig(**tenant_kw)} if tenant_kw else {}
        )
        return ServingFrontend(eng, port=0, slo=slo)

    def test_generate_with_tenant_and_stats(self):
        f = self._frontend()
        try:
            status, out = _post(
                f"http://127.0.0.1:{f.port}/generate",
                {"input_ids": list(range(1, 16)), "max_tokens": 4,
                 "tenant": "pro", "ttft_deadline_ms": 60_000},
            )
            assert status == 200
            assert len(out["output_ids"]) >= 1
            status, body = _get(f"http://127.0.0.1:{f.port}/stats")
            slo = json.loads(body)["slo"]
            assert slo["total_admitted"] == 1 and slo["total_shed"] == 0
            assert "pro" in slo["tenants"]
        finally:
            f.close(drain_s=0.5)

    def test_rate_limit_answers_429_with_retry_after(self):
        # Bucket covers one 15-token prompt; near-zero refill.
        f = self._frontend(rate_tokens_per_s=0.1, burst_tokens=16)
        try:
            status, _ = _post(
                f"http://127.0.0.1:{f.port}/generate",
                {"input_ids": list(range(1, 16)), "max_tokens": 2,
                 "tenant": "free"},
            )
            assert status == 200
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(
                    f"http://127.0.0.1:{f.port}/generate",
                    {"input_ids": list(range(1, 16)), "max_tokens": 2,
                     "tenant": "free"},
                )
            err = exc.value
            assert err.code == 429
            assert int(err.headers["Retry-After"]) >= 1
            payload = json.loads(err.read())
            assert payload["shed"] and payload["reason"] == "rate_limited"
        finally:
            f.close(drain_s=0.5)

    def test_plain_frontend_ignores_slo_fields(self, frontend):
        # No control plane: tenant/deadline fields are accepted and
        # ignored (no tenants exist to enforce them against).
        status, out = _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": list(range(200, 220)), "max_tokens": 2,
             "tenant": "whoever", "ttft_deadline_ms": 1},
        )
        assert status == 200
        assert len(out["output_ids"]) >= 1


class TestDebugEndpoints:
    """The tracing plane's HTTP surfaces: /debug/trace (flight-recorder
    drain as Chrome trace JSON), /debug/requests (in-flight table),
    /debug/state (node snapshot) — well-formed JSON on both frontend
    variants, including under concurrent load."""

    def test_debug_state_shape(self, frontend):
        status, body = _get(f"http://127.0.0.1:{frontend.port}/debug/state")
        assert status == 200
        state = json.loads(body)
        assert state["engine"]["max_batch"] == 2
        assert state["pool"]["num_slots"] == 512
        assert state["pool"]["free_slots"] <= state["pool"]["num_slots"]
        assert "trace" in state and state["trace"]["capacity"] > 0
        # The hardware the node's numbers came from, as JAX reports it.
        dev = jax.devices()[0]
        assert state["device"] == {
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "count": jax.device_count(),
            "memory": [],  # the CPU backend keeps no memory counters
        }
        assert state["engine"]["step_failures"] == 0

    def test_debug_state_names_the_decode_launch(self, frontend):
        """``decode_dispatch``: what chip_smoke.py reads to prove the
        kernel served. On a CPU the step ran ``decode_step``'s jnp
        attention ("dense"); the sizes are the last plain step's."""
        base = f"http://127.0.0.1:{frontend.port}"
        prompt = list(range(40, 51))
        _post(base + "/generate", {"input_ids": prompt, "max_tokens": 4})
        d = json.loads(_get(base + "/debug/state")[1])["decode_dispatch"]
        assert d == {
            "path": "dense", "batch": 1, "bucket": 1,
            "max_len": len(prompt) + 2,  # the fourth token's step
        }

    def test_debug_requests_table(self, frontend):
        _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": list(range(300, 320)), "max_tokens": 2},
        )
        status, body = _get(f"http://127.0.0.1:{frontend.port}/debug/requests")
        assert status == 200
        table = json.loads(body)
        assert "requests" in table and isinstance(table["requests"], list)
        # Finished requests leave the table; it reports only live state.
        assert table["waiting"] == 0

    def test_debug_tokens_serves_the_token_plane(self, frontend):
        _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": list(range(310, 330)), "max_tokens": 4},
        )
        status, body = _get(f"http://127.0.0.1:{frontend.port}/debug/tokens")
        assert status == 200
        out = json.loads(body)
        tl = out["timeline"]
        assert tl["capacity"] > 0
        assert set(tl["stalls"].keys()) <= {
            "restore_park", "prefill_convoy", "rebalance_handoff",
            "spec_verify_miss", "scheduler_wait",
        }
        assert out["goodput"]["useful_tokens"] >= 4
        assert isinstance(out["spec"], dict)
        # ?limit= caps the recent tail; a bad limit is a 400, not a 500.
        status, body = _get(
            f"http://127.0.0.1:{frontend.port}/debug/tokens?limit=1"
        )
        assert len(json.loads(body)["timeline"]["recent"]) <= 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"http://127.0.0.1:{frontend.port}/debug/tokens?limit=zap")
        assert ei.value.code == 400

    def test_debug_tokens_404_when_plane_disabled(self):
        cfg = ModelConfig.tiny()
        eng = Engine(
            cfg, init_params(cfg, jax.random.PRNGKey(0)),
            num_slots=64, page_size=4, max_batch=1, name="notl",
            token_timeline_capacity=0,
        )
        f = ServingFrontend(eng, port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{f.port}/debug/tokens")
            assert ei.value.code == 404
        finally:
            f.close()

    def test_debug_trace_drains_chrome_json(self, frontend):
        from radixmesh_tpu.obs.trace_plane import (
            FlightRecorder,
            set_recorder,
            validate_trace,
        )

        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        status, out = _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {"input_ids": list(range(400, 430)), "max_tokens": 3},
        )
        assert status == 200
        status, body = _get(f"http://127.0.0.1:{frontend.port}/debug/trace")
        assert status == 200
        obj = json.loads(body)
        assert validate_trace(obj) == []
        names = {
            ev["name"] for ev in obj["traceEvents"] if ev.get("ph") == "X"
        }
        assert {"admission_wait", "prefill_wave", "decode_chunk",
                "publish", "http_request"} <= names
        # Default GET is a read-only snapshot (a peek must not destroy
        # the post-mortem); ?drain=1 opts into consuming the buffer.
        status, body2 = _get(f"http://127.0.0.1:{frontend.port}/debug/trace")
        assert status == 200
        obj2 = json.loads(body2)
        assert len(obj2["traceEvents"]) >= len(obj["traceEvents"])
        status, _ = _get(
            f"http://127.0.0.1:{frontend.port}/debug/trace?drain=1"
        )
        assert status == 200
        status, body3 = _get(f"http://127.0.0.1:{frontend.port}/debug/trace")
        obj3 = json.loads(body3)
        assert len(obj3["traceEvents"]) < len(obj["traceEvents"])

    def test_debug_endpoints_under_concurrent_load(self, frontend):
        import concurrent.futures as cf

        from radixmesh_tpu.obs.trace_plane import (
            FlightRecorder,
            set_recorder,
        )

        set_recorder(FlightRecorder(capacity=2048, sample=1.0))
        paths = ("/debug/trace", "/debug/requests", "/debug/state")

        def gen(i):
            return _post(
                f"http://127.0.0.1:{frontend.port}/generate",
                {"input_ids": list(range(i, i + 10)), "max_tokens": 3},
                timeout=120,
            )[0]

        def dbg(i):
            status, body = _get(
                f"http://127.0.0.1:{frontend.port}{paths[i % 3]}"
            )
            json.loads(body)  # must be well-formed under racing drains
            return status

        with cf.ThreadPoolExecutor(8) as ex:
            gens = [ex.submit(gen, 500 + 16 * i) for i in range(4)]
            dbgs = [ex.submit(dbg, i) for i in range(12)]
            assert all(f.result() == 200 for f in gens + dbgs)

    def test_router_debug_endpoints_concurrent(self):
        import concurrent.futures as cf

        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig, NodeRole
        from radixmesh_tpu.cache.kv_pool import PagedKVPool
        from radixmesh_tpu.obs.trace_plane import (
            FlightRecorder,
            set_recorder,
            validate_trace,
        )
        from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter

        set_recorder(FlightRecorder(capacity=4096, sample=1.0))
        InprocHub.reset_default()
        prefill, decode, router = ["p0"], ["d0"], ["r0"]
        nodes = []
        try:
            for addr in prefill + decode + router:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.05,
                    gc_interval_s=30.0,
                )
                pool = (
                    None
                    if cfg.local_role is NodeRole.ROUTER
                    else PagedKVPool(
                        num_slots=64, num_layers=1, num_kv_heads=1, head_dim=2
                    )
                )
                nodes.append(MeshCache(cfg, pool=pool).start())
            for n in nodes:
                assert n.wait_ready(timeout=10)
            car = CacheAwareRouter(nodes[2], nodes[2].cfg)
            car.finish_warm_up()
            f = RouterFrontend(car, port=0)
            try:
                def route(i):
                    return _post(
                        f"http://127.0.0.1:{f.port}/route",
                        {"input_ids": [i, i + 1, i + 2]},
                    )[0]

                def dbg(path):
                    status, body = _get(f"http://127.0.0.1:{f.port}{path}")
                    return status, json.loads(body)

                with cf.ThreadPoolExecutor(6) as ex:
                    routes = [ex.submit(route, i) for i in range(8)]
                    assert all(r.result() == 200 for r in routes)
                status, state = dbg("/debug/state")
                assert status == 200
                assert state["router"]["warm_up"] is False
                assert state["membership"]["role"] == "router"
                assert sorted(state["router"]["alive"]["prefill"]) == ["p0"]
                status, table = dbg("/debug/requests")
                assert status == 200 and table["requests"] == []
                status, trace = dbg("/debug/trace")
                assert status == 200
                assert validate_trace(trace) == []
                route_spans = [
                    ev for ev in trace["traceEvents"]
                    if ev.get("ph") == "X" and ev["name"] == "route"
                ]
                assert len(route_spans) >= 8
            finally:
                f.close()
        finally:
            for n in nodes:
                n.close()
            InprocHub.reset_default()


@pytest.mark.quick
class TestClusterEndpoints:
    """Fleet telemetry surfaces (PR 3): /cluster/health and
    /cluster/telemetry on BOTH frontends."""

    def test_serving_frontend_without_mesh(self, frontend):
        for path in ("/cluster/health", "/cluster/telemetry"):
            status, body = _get(f"http://127.0.0.1:{frontend.port}{path}")
            assert status == 200
            out = json.loads(body)
            assert out["nodes"] == {} and "note" in out

    def test_router_frontend_serves_fleet_view(self):
        import time

        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig, NodeRole
        from radixmesh_tpu.obs.fleet_plane import FleetPlane
        from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter

        InprocHub.reset_default()
        prefill, decode, router = ["p0"], ["d0"], ["r0"]
        nodes = []
        try:
            for addr in prefill + decode + router:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.05,
                    gc_interval_s=30.0,
                )
                nodes.append(MeshCache(cfg, pool=None).start())
            for n in nodes:
                assert n.wait_ready(timeout=10)
            planes = [
                FleetPlane(n, interval_s=0.1)
                for n in nodes
                if n.role is not NodeRole.ROUTER
            ]
            for p in planes:
                p.publish_once()
            rnode = nodes[2]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if len(rnode.fleet.digests()) == 2:
                    break
                time.sleep(0.01)
            car = CacheAwareRouter(rnode, rnode.cfg, health_aware=True)
            car.finish_warm_up()
            f = RouterFrontend(car, port=0)
            try:
                status, body = _get(
                    f"http://127.0.0.1:{f.port}/cluster/telemetry"
                )
                assert status == 200
                tel = json.loads(body)
                assert set(tel["nodes"]) == {"0", "1"}
                assert tel["convergence"]["converged"] is True
                d = tel["nodes"]["0"]
                assert d["role"] == "prefill"
                assert len(d["fingerprint"]) == 16  # hex-encoded 64-bit
                assert tel["self"]["role"] == "router"

                status, body = _get(
                    f"http://127.0.0.1:{f.port}/cluster/health"
                )
                assert status == 200
                health = json.loads(body)
                assert health["min_score"] == 1.0
                assert set(health["nodes"]) == {"0", "1"}
                assert health["nodes"]["0"]["score"] == 1.0
                assert health["convergence"]["max_convergence_age_s"] == 0.0
            finally:
                f.close()
        finally:
            for n in nodes:
                n.close()
            InprocHub.reset_default()


class TestStitchingSurfaces:
    """PR 9 cross-node stitching HTTP seams: /generate adopts an
    upstream trace id (resume/hedge re-routes stitch under the
    originating request), and /debug/trace?format=spans serves the raw
    per-node span export the collector feeds to stitch_traces."""

    def test_generate_adopts_trace_id_and_spans_export(self, frontend):
        from radixmesh_tpu.obs.trace_plane import (
            FlightRecorder,
            set_recorder,
            stitch_traces,
            validate_trace,
        )

        set_recorder(FlightRecorder(capacity=4096, sample=1.0, node="serve"))
        status, out = _post(
            f"http://127.0.0.1:{frontend.port}/generate",
            {
                "input_ids": list(range(700, 720)),
                "max_tokens": 2,
                "trace_id": "0x00dead00beef0001",
            },
        )
        assert status == 200
        status, body = _get(
            f"http://127.0.0.1:{frontend.port}/debug/trace?format=spans"
        )
        assert status == 200
        export = json.loads(body)
        assert export["node"] == "serve"
        assert isinstance(export["wall_offset"], float)
        adopted = [
            s for s in export["spans"]
            if s["trace_id"] == "0x00dead00beef0001"
        ]
        assert adopted, "no span adopted the upstream trace id"
        assert {"prefill_wave", "publish"} <= {s["name"] for s in adopted}
        # The export stitches into a valid single-process document.
        assert validate_trace(stitch_traces([export])) == []

    def test_generate_rejects_bad_trace_id(self, frontend):
        for bad in (0, "soup"):
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(
                    f"http://127.0.0.1:{frontend.port}/generate",
                    {"input_ids": [1, 2, 3], "max_tokens": 1,
                     "trace_id": bad},
                )
            assert e.value.code == 400


class TestTimeseriesAndBlackboxEndpoints:
    """PR 13 surfaces: GET /debug/timeseries (cursor pagination, both
    frontends) + POST /admin/blackbox, hammered while the sampler
    writes and while a drain-triggered black-box flush runs against
    the lifecycle plane lock (the satellite-3 concurrency contract)."""

    def test_timeseries_serves_rings_and_paginates(self, frontend):
        status, body = _get(
            f"http://127.0.0.1:{frontend.port}/debug/timeseries"
            "?family=radixmesh_history&limit=50"
        )
        assert status == 200
        page = json.loads(body)
        assert page["interval_s"] == 1.0
        # The self-accounting series exist from the first sample on.
        deadline = 50
        while not page["series"] and deadline:
            deadline -= 1
            import time as _t

            _t.sleep(0.1)
            page = json.loads(_get(
                f"http://127.0.0.1:{frontend.port}/debug/timeseries"
                "?family=radixmesh_history&limit=50"
            )[1])
        assert any(
            n.startswith("radixmesh_history_samples_total")
            for n in page["series"]
        )
        # Cursor round-trip: the next page starts past this one.
        status, body2 = _get(
            f"http://127.0.0.1:{frontend.port}/debug/timeseries"
            f"?since={page['next_since']}"
        )
        page2 = json.loads(body2)
        assert page2["since"] == page["next_since"]

    def test_timeseries_rejects_bad_cursor(self, frontend):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(
                f"http://127.0.0.1:{frontend.port}/debug/timeseries"
                "?since=banana"
            )
        assert ei.value.code == 400

    def test_disabled_history_404s(self):
        cfg = ModelConfig.tiny()
        eng = Engine(
            cfg, init_params(cfg, jax.random.PRNGKey(0)),
            num_slots=64, page_size=4, max_batch=1, name="nohist",
        )
        f = ServingFrontend(eng, port=0, history_interval_s=0.0)
        try:
            assert f.history is None
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{f.port}/debug/timeseries")
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://127.0.0.1:{f.port}/admin/blackbox", {})
            assert ei.value.code == 404
        finally:
            f.close()

    def test_admin_blackbox_flushes_a_final(self, tmp_path):
        cfg = ModelConfig.tiny()
        eng = Engine(
            cfg, init_params(cfg, jax.random.PRNGKey(0)),
            num_slots=64, page_size=4, max_batch=1, name="bb-http",
        )
        f = ServingFrontend(
            eng, port=0, history_interval_s=0.05,
            blackbox_dir=str(tmp_path),
        )
        try:
            status, res = _post(
                f"http://127.0.0.1:{f.port}/admin/blackbox", {}
            )
            assert status == 200
            assert res["flushed"] is True
            assert res["cause"] == "admin"
            import os

            assert os.path.isfile(res["path"])
            with open(res["path"]) as fh:
                final = json.load(fh)
            # The final carries the /debug/state snapshot and the live
            # doctor verdict alongside the history.
            assert final["state"]["engine"]["name"] == "bb-http"
            assert "findings" in final["doctor"]
        finally:
            f.close()

    def test_timeseries_hammered_under_sampler_and_drain_flush(
        self, tmp_path
    ):
        """The satellite-3 race: /debug/timeseries paginating from many
        threads WHILE the 20ms sampler writes the rings, WHILE requests
        generate, and WHILE a lifecycle drain (holding the plane lock)
        runs its black-box flush — no deadlock, no malformed page."""
        import concurrent.futures as cf

        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig
        from radixmesh_tpu.policy.lifecycle import (
            LifecycleConfig,
            LifecyclePlane,
            LifecycleState,
        )

        InprocHub.reset_default()
        cfg = ModelConfig.tiny()
        eng = Engine(
            cfg, init_params(cfg, jax.random.PRNGKey(0)),
            num_slots=512, page_size=4, max_batch=2, name="bb-drain",
        )
        mesh_nodes = []
        f = None
        lc = None
        try:
            for addr in ("hp0", "hd0"):
                mcfg = MeshConfig(
                    prefill_nodes=["hp0"],
                    decode_nodes=["hd0"],
                    router_nodes=[],
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.1,
                    failure_timeout_s=60.0,
                )
                mesh_nodes.append(MeshCache(mcfg, pool=None).start())
            for n in mesh_nodes:
                assert n.wait_ready(timeout=30)
            f = ServingFrontend(
                eng, port=0, history_interval_s=0.02,
                blackbox_dir=str(tmp_path),
            )
            lc = LifecyclePlane(
                mesh_nodes[0],
                runner=f.runner,
                blackbox=f.blackbox,
                cfg=LifecycleConfig(drain_timeout_s=10.0),
            )
            f.lifecycle = lc

            def gen(i):
                try:
                    return _post(
                        f"http://127.0.0.1:{f.port}/generate",
                        {"input_ids": list(range(i, i + 8)),
                         "max_tokens": 2},
                        timeout=60,
                    )[0]
                except urllib.error.HTTPError as e:
                    return e.code  # drain shed mid-storm is legal

            def ts(i):
                since = -1
                for _ in range(4):
                    status, body = _get(
                        f"http://127.0.0.1:{f.port}/debug/timeseries"
                        f"?since={since}&limit=200"
                    )
                    page = json.loads(body)  # well-formed under races
                    since = page["next_since"]
                return status

            def drain():
                return lc.drain(deadline_s=10.0)

            with cf.ThreadPoolExecutor(10) as ex:
                gens = [ex.submit(gen, 100 + 16 * i) for i in range(3)]
                pages = [ex.submit(ts, i) for i in range(6)]
                dr = ex.submit(drain)
                stats = dr.result(timeout=60)
                assert stats["blackbox"] is not None
                assert all(p.result(timeout=60) == 200 for p in pages)
                assert all(
                    g.result(timeout=120) in (200, 503) for g in gens
                )
            assert lc.state is LifecycleState.LEFT
            # The drain's flush landed as a complete final artifact.
            from radixmesh_tpu.obs.blackbox import load_blackbox

            dump = load_blackbox(str(tmp_path))
            assert "drain" in dump["causes"]
            assert dump["unclean"] is False
        finally:
            if lc is not None:
                lc.close()
            if f is not None:
                f.close()
            for n in mesh_nodes:
                n.close()
            InprocHub.reset_default()

    def test_router_frontend_serves_timeseries(self):
        from radixmesh_tpu.cache.mesh_cache import MeshCache
        from radixmesh_tpu.comm.inproc import InprocHub
        from radixmesh_tpu.config import MeshConfig
        from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter

        InprocHub.reset_default()
        prefill, decode, router = ["tp0"], ["td0"], ["tr0"]
        nodes = []
        rf = None
        try:
            for addr in prefill + decode + router:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.1,
                    failure_timeout_s=60.0,
                )
                nodes.append(MeshCache(cfg, pool=None).start())
            for n in nodes:
                assert n.wait_ready(timeout=30)
            r = CacheAwareRouter(nodes[-1], nodes[-1].cfg)
            rf = RouterFrontend(r, port=0, history_interval_s=0.02)
            deadline = 100
            page = {}
            while deadline:
                deadline -= 1
                status, body = _get(
                    f"http://127.0.0.1:{rf.port}/debug/timeseries"
                )
                page = json.loads(body)
                if page["series"]:
                    break
                import time as _t

                _t.sleep(0.05)
            assert status == 200
            assert any(
                n.startswith("radixmesh_") for n in page["series"]
            )
        finally:
            if rf is not None:
                rf.close()
            for n in nodes:
                n.close()
            InprocHub.reset_default()

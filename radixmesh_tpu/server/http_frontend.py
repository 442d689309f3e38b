"""HTTP serving frontend over the engine + cache-aware router.

The reference stops at the cache layer — "serving frontend (not in repo)"
is the explicit seam above its router (SURVEY §1 L5). This module supplies
that frontend with stdlib-only HTTP (no framework dependency):

- :class:`ServingFrontend` (prefill/decode nodes): ``POST /generate``
  (token-ids in, token-ids out; optional SSE streaming), ``GET /metrics``
  (Prometheus exposition from ``obs/metrics.py``), ``GET /healthz``,
  ``GET /stats`` (engine hit-rate/TTFT snapshot).
- :class:`RouterFrontend` (router node): ``POST /route`` → the prefill +
  decode addresses holding the longest cached prefix
  (``router/cache_aware_router.py``), plus the same health/metrics.
- Debug surfaces on BOTH frontends (``obs/trace_plane.py``):
  ``GET /debug/trace`` serves the flight recorder as Chrome trace-event
  JSON (load in Perfetto; read-only — ``?drain=1`` consumes the buffer),
  ``GET /debug/requests`` is the in-flight
  request table with per-phase elapsed times, ``GET /debug/state`` is a
  point-in-time node snapshot (batch occupancy, pool/cache/host-tier
  fill, membership view, SLO tier, recorder stats), and
  ``GET /debug/timeseries`` is the history axis (``obs/timeseries.py``):
  cursor-paginated bounded rings of every metric family + derived plane,
  ``GET /debug/tokens`` is the token-level speed plane
  (``obs/token_timeline.py``): the per-token ITL ring with stall-cause
  attribution, the speculation ledger, and the goodput decomposition,
  with ``POST /admin/blackbox`` flushing the crash-surviving dump
  (``obs/blackbox.py``).

Threading model: the engine is single-threaded by design (host-side tree
mutation between device steps, SURVEY §7 hard part (c)); an
:class:`EngineRunner` thread owns it exclusively, stepping while work
exists. HTTP handler threads only enqueue requests and wait for their
completion — they never touch engine internals. The decode tokens of every
SSE stream are written by one emitter thread (:class:`_StreamEmitter`).

The API is token-ids-native: tokenization is the client's concern (no
tokenizer download in the serving path). A ``transformers`` tokenizer can
be layered client-side.
"""

from __future__ import annotations

import base64
import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Sequence

import numpy as np

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import Request, RequestState, SamplingParams
from radixmesh_tpu.models.llama import read_moe_stats
from radixmesh_tpu.obs.aggregator import FleetAggregator, HttpPeer
from radixmesh_tpu.obs.attribution import ensure_attributor
from radixmesh_tpu.obs.blackbox import BlackBox
from radixmesh_tpu.obs.doctor import MeshDoctor
from radixmesh_tpu.obs.metrics import get_registry
from radixmesh_tpu.obs.step_plane import describe_devices
from radixmesh_tpu.obs.timeseries import TelemetryHistory
from radixmesh_tpu.obs.trace_plane import get_recorder
from radixmesh_tpu.obs.tracing import annotate
from radixmesh_tpu.policy.retry import jittered_retry_after
from radixmesh_tpu.slo.control import RequestShed
from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter
from radixmesh_tpu.utils.logging import get_logger

__all__ = ["EngineRunner", "ServingFrontend", "RouterFrontend"]


class EngineRunner:
    """Exclusive owner of an :class:`Engine`: a single thread steps the
    scheduler while work exists; other threads submit and wait."""

    def __init__(self, engine: Engine):
        self.engine = engine
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._closed = False
        # Graceful drain (policy/lifecycle.py): once set, new submits are
        # refused retriably while in-flight work runs to completion.
        self._draining = False
        self._drain_retry_after_s = 1.0
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="engine-runner"
        )
        self.log = get_logger("engine.runner")
        # Steps that raised (see _fail_step): count + last message, both
        # on /debug/state.
        self.step_failures = 0
        self.last_step_error: str | None = None
        self._m_step_failures = get_registry().counter(
            "radixmesh_engine_step_failures_total",
            "engine steps that raised; each failed its in-flight requests",
            ("engine",),
        ).labels(engine=engine.name)

    def start(self) -> "EngineRunner":
        self._thread.start()
        return self

    def close(self, drain_s: float = 0.0) -> None:
        """Stop the scheduler thread. With ``drain_s`` > 0, give in-flight
        requests that long to finish first (then cancel the stragglers so
        no waiter blocks on a request that will never be stepped again)."""
        if drain_s > 0:
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline:
                with self._lock:
                    if not self.engine.has_work():
                        break
                # meshcheck: ok[sleep-audit] deadline-bounded drain poll;
                # completion is engine.has_work() under the runner lock —
                # no condition crosses the engine seam.
                time.sleep(0.02)
        with self._lock:
            self._closed = True  # reject submits racing the sweep
            self.engine.cancel_all()
        self._stop.set()
        self._wake.set()
        if self._thread.ident is not None:  # never-started runners skip join
            self._thread.join(timeout=5)

    def submit(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        resume_tokens: Sequence[int] | None = None,
        trace_id: int | None = None,
    ) -> Request:
        # Arrival is stamped BEFORE the lock (as SLORunner.submit does):
        # engine.step() runs under it, so a submit landing mid-step waits
        # the step out first — queueing like any other, and the largest
        # part of engine.admission_wait on a busy engine.
        t_arrival = time.monotonic()
        with self._lock:
            if self._closed:
                # After the shutdown cancel sweep nothing steps the engine
                # again; admitting would strand the waiter forever.
                raise RuntimeError("engine runner is shut down")
            if self._draining:
                raise RuntimeError(
                    "node is draining — retry via the router"
                )
            req = self.engine.make_request(
                prompt, sampling, resume_tokens=resume_tokens,
                trace_id=trace_id,
            )
            req.submit_time = t_arrival
            self.engine.enqueue(req)
        self._wake.set()
        return req

    def cancel(self, rid: int) -> bool:
        with self._lock:
            return self.engine.cancel(rid)

    # -- graceful drain (driven by policy/lifecycle.py) ----------------

    def begin_drain(self, retry_after_s: float = 1.0) -> None:
        """Close the admission window: new submits are refused retriably
        (clients re-route via the router) while in-flight work keeps
        stepping. The engine also stops converting PREFETCH hints — a
        restore nobody will be routed here to use must not open tickets
        on a departing node."""
        with self._lock:
            self._draining = True
            self._drain_retry_after_s = retry_after_s
            self.engine.draining = True

    def drain_requeue(self) -> int:
        """Cancel-and-flag every queued and parked-RESTORING request for
        requeue at the router (they have produced nothing; bouncing them
        loses no work). Returns the number flagged."""
        with self._lock:
            return self.engine.drain_requeue_waiting()

    def drain_wait_idle(self, deadline_s: float, poll_s: float = 0.02) -> bool:
        """Let in-flight decodes run to completion, bounded by
        ``deadline_s``; stragglers past it are cancelled (partial output
        returns, flagged). True = everything finished on its own."""
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            with self._lock:
                if not self.engine.has_work():
                    return True
            # meshcheck: ok[sleep-audit] deadline-bounded drain poll
            # (same seam as above: has_work() is the only signal).
            time.sleep(poll_s)
        with self._lock:
            n = self.engine.cancel_all()
        if n:
            self.log.warning(
                "drain deadline (%.1fs): cancelled %d straggler(s)",
                deadline_s, n,
            )
        return False

    def drain_flush(self) -> tuple[int, bool]:
        """Write hot prefixes back to the host tier (fused write-back
        lane) and wait for the arena writes to land — the last step
        before LEAVE, so a warm rejoin finds its working set. Returns
        ``(tokens written back, landed)``: ``landed`` is False when the
        write barrier timed out or an awaited write-back FAILED (its
        arena bytes are untrusted), so the drain must not report a
        durable flush it never got."""
        with self._lock:
            n = self.engine.drain_flush_hot()
        plane = self.engine.kv_transfer
        landed = True
        if plane is not None:
            landed = plane.wait_host_ready()
            if not landed:
                self.log.warning(
                    "drain write-back barrier failed/timed out — hot "
                    "prefixes may not have landed in the host tier"
                )
        return n, landed

    def drain_flush_disk(self) -> tuple[int, bool]:
        """Drain step 5d (policy/lifecycle.py): flush the hot subtrees
        one tier further — host arena → durable disk extents — so the
        working set survives a whole-cell power loss after this node
        leaves. (0, True) without a tier. Run AFTER :meth:`drain_flush`
        so the device flush has landed in the arena first."""
        with self._lock:
            return self.engine.drain_flush_disk()

    def wait(self, req: Request, timeout: float | None = None) -> list[int]:
        """Block until ``req`` finishes; returns its generated tokens.

        Event-driven, not polled: the waiter parks on the request's
        condition (``Request.cond``), which every finish transition
        notifies via ``Request.__setattr__`` — so wake-up latency is the
        notify cost, not a poll quantum, and idle waiters don't spin.
        A coarse 1 s fallback re-check guards against a waiter racing in
        between the state write and the notify."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with req.cond:
            while req.state is not RequestState.FINISHED:
                if deadline is None:
                    remaining = 1.0
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"request {req.rid} not finished in time"
                        )
                req.cond.wait(timeout=min(remaining, 1.0))
        return req.generated

    def tokens_so_far(self, req: Request) -> list[int]:
        # list() under the engine lock is not needed: handler threads only
        # read the append-only list, and a torn read costs one token of
        # staleness, not corruption (CPython list append is atomic).
        return list(req.output_tokens)

    def _pre_step(self) -> None:
        """Subclass hook run each scheduler iteration with the runner
        lock held, before the has-work check (the SLO runner pumps its
        admission queue here)."""

    def _run(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                with self.engine.spans.span("rm.runner.pre_step"):
                    self._pre_step()
                has_work = self.engine.has_work()
                if has_work:
                    try:
                        self.engine.step()
                    except Exception as exc:  # noqa: BLE001 — serving boundary: report to the clients, keep answering
                        self._fail_step(exc)
            if not has_work:
                self._wake.wait(timeout=0.05)
                self._wake.clear()
            else:
                # meshcheck: ok[sleep-audit] a zero-length yield, not a
                # wait: threading.Lock is not fair — looping straight
                # back, this thread re-takes the lock before a submitter
                # parked on it is ever scheduled, and an arrival waits out
                # the whole running batch instead of one step. (In a
                # profiler capture this hand-over is the engine thread's
                # time under no rm.* span; it needs none of its own.)
                time.sleep(0)

    def _fail_step(self, exc: Exception) -> None:
        """A step that raised — a kernel the compiler refuses, a device
        out of memory, a scheduler bug — is not retried: the same work
        would raise again (recompiling each time) while its clients wait
        out a timeout. Count it and finish every request the engine
        holds with the error; requests submitted afterwards get a fresh
        step."""
        msg = f"{type(exc).__name__}: {exc}"
        self.step_failures += 1
        self.last_step_error = msg
        self._m_step_failures.inc()
        n = self.engine.cancel_all(error=msg)
        self.log.exception(
            "engine step failed; failed %d in-flight request(s)", n
        )


def _json_response(handler: BaseHTTPRequestHandler, code: int, obj: dict) -> None:
    body = json.dumps(obj).encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def _read_json(handler: BaseHTTPRequestHandler) -> dict:
    length = int(handler.headers.get("Content-Length", 0))
    if length <= 0 or length > 64 * 1024 * 1024:
        raise ValueError("missing or oversized body")
    obj = json.loads(handler.rfile.read(length))
    if not isinstance(obj, dict):
        raise ValueError("body must be a JSON object")
    return obj


def _ids_from_body(body: dict, tokenizer, who: str) -> list[int]:
    """Token ids from a request body: ``text`` (tokenized server-side)
    or raw ``input_ids``. One implementation for the serving and router
    frontends so validation cannot drift between them."""
    if "text" in body:
        if tokenizer is None:
            raise ValueError(
                f"{who} has no tokenizer (start with --tokenizer); "
                "send input_ids instead"
            )
        if not isinstance(body["text"], str):
            raise ValueError("text must be a string")
        ids = tokenizer.encode(body["text"])
    else:
        ids = body["input_ids"]
    # One pass in C, not an ``isinstance`` a token: a turn on a long
    # document re-sends 33 k-101 k ids, and this thread holds the
    # interpreter the engine thread needs to admit the next request
    # (``bool`` is a type of its own here: True is no token id).
    if not isinstance(ids, list) or not set(map(type, ids)) <= {int}:
        raise ValueError("input_ids must be a list of ints")
    return ids


class _FrontendServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    # The listen backlog (socketserver's default is 5). A closed loop of
    # 128 callers, or a burst of 64, connects at once; past the backlog
    # the kernel drops the handshake and one connection in a few hundred
    # is reset a second later ("Connection reset by peer" before a byte
    # of the request was read: PR 41). The kernel caps it at somaxconn.
    request_queue_size = 1024


def _token_events(tokens: Sequence[int]) -> bytes:
    """The SSE lines of these tokens, as ``json.dumps({"token": t})``
    writes them."""
    return b"".join([b'data: {"token": %d}\n\n' % t for t in tokens])


class _SseStream:
    """One SSE reply: the request, its socket and how many tokens went out.
    The emitter writes while ``emitter_owns``; a write that the socket does
    not take at once (a reader that fell behind) or that fails hands the
    stream to its handler thread for good, with what is still to go in
    ``rest`` — so one slow reader never holds the other streams up."""

    def __init__(self, req: Request, sock):
        self.req, self.sock = req, sock
        self.sent = 0
        self.rest = b""
        self.emitter_owns = True
        self.lock = threading.Lock()

    def pump(self) -> None:
        """Write the tokens that landed since the last call (emitter
        thread; never blocks)."""
        with self.lock:
            tokens = self.req.output_tokens
            n = len(tokens)
            if not self.emitter_owns or n <= self.sent:
                return
            data = _token_events(tokens[self.sent:n])
            self.sent = n
            try:
                done = self.sock.send(data, socket.MSG_DONTWAIT)
            except OSError:  # full (BlockingIOError) or gone: the handler's
                done = 0     # own write waits, or raises where it always did
            if done < len(data):
                self.rest = data[done:]
                self.release()

    def release(self) -> None:
        """From here on the handler thread writes (call with ``lock``)."""
        self.emitter_owns = False
        self.req.on_progress = None
        with self.req.cond:
            self.req.cond.notify_all()


class _StreamEmitter:
    """The one thread that writes the decode tokens of every SSE stream.
    A decode step of B rows announces B tokens at once
    (``Engine._flush_emit``); a handler thread a stream would wake B
    threads, each taking the interpreter two or three times for one line —
    and the engine thread, back from its wait for the device, queues behind
    them for the next launch. Here the step sets one event B times, this
    thread wakes once and writes B sockets; the handlers sleep until their
    request finishes (``Request.cond``) and write the ``done`` event."""

    def __init__(self):
        self._streams: list[_SseStream] = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._closed = False
        self._thread: threading.Thread | None = None

    def add(self, stream: _SseStream) -> None:
        with self._lock:
            self._streams.append(stream)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="http-emit")
                self._thread.start()
        stream.req.on_progress = self._wake.set
        self._wake.set()  # what landed before the hook was set

    def remove(self, stream: _SseStream) -> None:
        with stream.lock:
            if stream.emitter_owns:
                stream.release()
        with self._lock:
            self._streams.remove(stream)

    def close(self) -> None:
        self._closed = True
        self._wake.set()

    def _run(self) -> None:
        while not self._closed:
            if not self._wake.wait(timeout=1.0):
                continue
            self._wake.clear()
            with self._lock:
                streams = list(self._streams)
            with annotate("rm.http.emit"):
                for stream in streams:
                    try:
                        stream.pump()
                    except Exception:  # noqa: BLE001 — this thread serves every stream: one that cannot be written goes back to its handler, which fails alone
                        with stream.lock:
                            if stream.emitter_owns:
                                stream.release()


def _b64_array(a: np.ndarray) -> dict:
    a = np.ascontiguousarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "b64": base64.b64encode(a.tobytes()).decode("ascii")}


def _logits_payload(req: Request) -> dict:
    """What ``"return_logits": true`` adds to a reply's final event: for
    every generated position the float32 logits row the sampler saw
    (``logits``: [n_generated, V], little-endian, base64), and for a model
    with experts the ids every computed position chose (``expert_ids``:
    runs of ``{"start": first position, ...[n, L, k] int32}``; a prefix
    served from the tree has none)."""
    if not req.sampling.return_logits:
        return {}
    rows = (
        np.stack(req.logit_rows)
        if req.logit_rows
        else np.zeros((0, 0), np.float32)
    )
    out = {"logits": _b64_array(rows.astype("<f4"))}
    if req.expert_rows:
        out["expert_ids"] = [
            {"start": start, **_b64_array(ids.astype("<i4"))}
            for start, ids in req.expert_rows
        ]
    if req.index_rows:  # learned sparse attention: [L_full, K] a position
        out["index_positions"] = [
            {"position": position, **_b64_array(sel.astype("<i4"))}
            for position, sel in req.index_rows
        ]
    return out


def _request_row(req: Request, now: float) -> dict:
    """One /debug/requests table row: identity + per-phase elapsed times
    derived from the timestamps the scheduler already stamps."""
    ft = req.first_token_time
    return {
        "rid": req.rid,
        "state": req.state.value,
        "tenant": req.tenant,
        "prompt_tokens": len(req.prompt),
        "output_tokens": len(req.output_tokens),
        "kv_len": req.kv_len,
        "prefix_hit_tokens": req.prefix_len,
        "row": req.row,
        "trace_id": getattr(req.trace, "trace_id", None),
        "elapsed_s": {
            "total": round(now - req.submit_time, 6) if req.submit_time else None,
            "slo_queue": (
                round(req.admit_time - req.submit_time, 6)
                if req.admit_time
                else None
            ),
            "to_first_token": round(ft - req.submit_time, 6) if ft else None,
            "decoding": (
                round(now - ft, 6)
                if ft and req.state is RequestState.RUNNING
                else None
            ),
        },
    }


def _membership_state(mesh) -> dict:
    """Membership/topology block shared by both frontends' /debug/state."""
    return {
        "role": mesh.role.value,
        "rank": mesh.rank,
        "view_epoch": mesh.view.epoch,
        "alive": list(mesh.view.alive),
        "master_rank": mesh.view.master_rank(),
        "successor_rank": mesh._succ_rank,
    }


def _cluster_telemetry(mesh) -> dict:
    """``GET /cluster/telemetry``: every gossiped NodeDigest plus the
    pairwise fingerprint-convergence audit (``obs/fleet_plane.py``).
    Shared by both frontends so fleet tooling can scrape any node."""
    if mesh is None:
        return {"nodes": {}, "note": "no cache mesh attached to this node"}
    snap = mesh.fleet.snapshot()
    if "shard_heat" in snap:
        # Replace the bare fleet heat map with the ownership-enriched
        # report: the hot shard's OWNER SET is the piece only a node
        # holding the ownership map can add (PR 9 heat telemetry).
        snap["shard_heat"] = mesh.shard_heat_report()
    snap["self"] = _membership_state(mesh)
    return snap


def _cluster_health(mesh) -> dict:
    """``GET /cluster/health``: per-node 0..1 health scores with the
    detector reasons that capped them, the fleet-wide convergence
    summary, and the autoscale recommendation (pure policy over the
    same gossiped signals — ``policy/lifecycle.py``) — the page an
    operator (or a workload driver) reads first."""
    if mesh is None:
        return {"nodes": {}, "note": "no cache mesh attached to this node"}
    from radixmesh_tpu.policy.lifecycle import AutoscalePolicy

    health = mesh.fleet.health()
    scores = [h["score"] for h in health.values()]
    return {
        "nodes": {str(r): h for r, h in sorted(health.items())},
        "min_score": min(scores, default=1.0),
        "convergence": mesh.fleet.convergence(),
        "autoscale": AutoscalePolicy().recommend(
            mesh.fleet, alive_ring=len(mesh.view.alive)
        ),
        "self": _membership_state(mesh),
    }


def _debug_timeseries_response(
    handler: BaseHTTPRequestHandler, history
) -> None:
    """Serve the telemetry-history rings (``obs/timeseries.py``) with
    cursor pagination: ``?family=`` prefix-filters series, ``?since=``
    is the sample-sequence cursor from a previous response's
    ``next_since``, ``?limit=`` bounds points per page (cut on a sample
    boundary)."""
    from urllib.parse import parse_qs, urlsplit

    if history is None:
        _json_response(
            handler, 404,
            {"error": "telemetry history disabled "
             "(--telemetry-history-interval 0)"},
        )
        return
    q = parse_qs(urlsplit(handler.path).query)
    try:
        family = q.get("family", [""])[-1] or None
        since = int(q.get("since", ["-1"])[-1])
        limit = int(q.get("limit", ["2000"])[-1])
    except ValueError:
        _json_response(
            handler, 400, {"error": "since/limit must be integers"}
        )
        return
    _json_response(
        handler, 200, history.query(family=family, since=since, limit=limit)
    )


def _cluster_timeseries_response(
    handler: BaseHTTPRequestHandler, aggregator
) -> None:
    """``GET /cluster/timeseries``: the fleet-merged history — every
    peer's rings folded into one node-labeled store (``obs/
    aggregator.py``). Same query surface as ``/debug/timeseries``
    (``family``/``since``/``limit`` cursor pagination), because the
    fleet store IS a :class:`TelemetryHistory` — readers built for one
    node read the fleet unchanged. 404 on nodes that host no
    aggregator (serving nodes; routers started without peers)."""
    from urllib.parse import parse_qs, urlsplit

    if aggregator is None:
        _json_response(
            handler, 404,
            {"error": "no fleet aggregator hosted here — query a router "
             "started with --agg-interval > 0 (serving nodes export "
             "/debug/timeseries only)"},
        )
        return
    q = parse_qs(urlsplit(handler.path).query)
    try:
        family = q.get("family", [""])[-1] or None
        since = int(q.get("since", ["-1"])[-1])
        limit = int(q.get("limit", ["2000"])[-1])
    except ValueError:
        _json_response(
            handler, 400, {"error": "since/limit must be integers"}
        )
        return
    body = aggregator.store.query(family=family, since=since, limit=limit)
    body["aggregator"] = aggregator.stats()
    _json_response(handler, 200, body)


def _cluster_slo_response(handler: BaseHTTPRequestHandler, aggregator) -> None:
    """``GET /cluster/slo``: TRUE fleet percentiles — per-tenant
    p50/p99 TTFT and e2e from merged histogram bucket counts across
    every node (never an average of per-node percentiles), each tail
    quantile carrying its bucket and the freshest trace exemplar that
    landed in it (``obs/aggregator.py::FleetAggregator.fleet_slo``)."""
    if aggregator is None:
        _json_response(
            handler, 404,
            {"error": "no fleet aggregator hosted here — query a router "
             "started with --agg-interval > 0"},
        )
        return
    _json_response(handler, 200, aggregator.fleet_slo())


def _admin_blackbox_response(handler: BaseHTTPRequestHandler, blackbox) -> None:
    """``POST /admin/blackbox``: flush the full black box now (the
    operator's pre-restart snapshot — same artifact the SIGTERM/drain/
    watchdog triggers write)."""
    if blackbox is None:
        _json_response(
            handler, 404,
            {"error": "no black box on this node (start with "
             "--blackbox-dir)"},
        )
        return
    try:
        res = blackbox.flush("admin")
    except OSError as e:
        _json_response(handler, 500, {"error": str(e)})
        return
    _json_response(handler, 200, {"flushed": True, **res})


def _debug_trace_response(handler: BaseHTTPRequestHandler) -> None:
    """Serve the flight recorder as Chrome trace-event JSON. Read-only by
    default — a GET must not destroy the post-mortem a later reader (or
    the --trace-dir exit dump) depends on; ``?drain=1`` opts into
    consuming the buffer (e.g. a collector that polls and archives).
    ``?format=spans`` serves the RAW span export (node label, wall
    offset, span dicts) instead — the per-node body the cross-node
    stitcher (``trace_plane.stitch_traces``) collects from every peer
    to emit one merged Perfetto file."""
    from urllib.parse import parse_qs, urlsplit

    query = parse_qs(urlsplit(handler.path).query)
    # Opt-in must be deliberate: only recognized truthy spellings drain —
    # anything else (?drain=False, typos) stays read-only.
    drain = query.get("drain", ["0"])[-1].lower() in ("1", "true", "yes")
    if query.get("format", [""])[-1].lower() == "spans":
        _json_response(handler, 200, get_recorder().export_spans(drain=drain))
        return
    _json_response(handler, 200, get_recorder().chrome_trace(drain=drain))


def _debug_tokens_response(handler: BaseHTTPRequestHandler, engine) -> None:
    """Serve the token-level speed plane (obs/token_timeline.py): the
    change-compressed per-token ITL ring with stall-cause attribution,
    the per-(tenant, shape, draft-source) speculation ledger, and the
    goodput/waste decomposition. ``?limit=N`` bounds the raw ring tail
    (default 256). 404 when the engine was built with the timeline off
    (``token_timeline_capacity=0``) — absent, not silently empty."""
    from urllib.parse import parse_qs, urlsplit

    tl = getattr(engine, "timeline", None)
    if tl is None:
        _json_response(
            handler, 404,
            {"error": "token timeline disabled on this engine "
             "(token_timeline_capacity=0)"},
        )
        return
    query = parse_qs(urlsplit(handler.path).query)
    try:
        limit = int(query.get("limit", ["256"])[-1])
    except ValueError:
        _json_response(handler, 400, {"error": "limit must be an integer"})
        return
    led = getattr(engine, "spec_ledger", None)
    gp = getattr(engine, "goodput", None)
    acct = getattr(engine, "step_acct", None)
    _json_response(handler, 200, {
        "timeline": tl.snapshot(limit=max(0, limit)),
        "spec": {} if led is None else led.report(),
        "goodput": {} if gp is None else gp.report(step_acct=acct, spec=led),
    })


class ServingFrontend:
    """HTTP API over one serving engine."""

    def __init__(
        self,
        engine: Engine,
        host: str = "127.0.0.1",
        port: int = 0,
        profile_dir: str | None = None,
        tokenizer=None,
        slo=None,
        lifecycle=None,
        history_interval_s: float = 1.0,
        history_capacity: int = 900,
        blackbox_dir: str | None = None,
        blackbox_watchdog_s: float = 0.0,
    ):
        # Membership lifecycle plane (policy/lifecycle.py). With one
        # attached, POST /admin/drain moves the node through DRAINING →
        # LEFT, and drain sheds carry a "router" field pointing clients
        # at the retry path. launch.py wires it after construction (the
        # plane needs this frontend's runner), so handlers read the
        # attribute dynamically.
        self.lifecycle = lifecycle
        # With an SLOConfig, the overload control plane owns admission:
        # /generate grows `tenant`, `ttft_deadline_ms`, `deadline_ms`
        # fields, and overload answers 429/503 + Retry-After instead of
        # unbounded queueing (radixmesh_tpu/slo/). Imported lazily —
        # slo.runner imports this module for EngineRunner.
        if slo is not None:
            from radixmesh_tpu.slo.runner import SLORunner

            self.runner = SLORunner(engine, slo).start()
        else:
            self.runner = EngineRunner(engine).start()
        self.slo_enabled = slo is not None
        self.log = get_logger("http.serve")
        # Pluggable text seam (server/tokenizer.py): with a tokenizer,
        # /generate accepts {"text": ...} and answers with decoded
        # "text"; raw "input_ids" stay first-class either way (the
        # reference's keys are id lists, radix_mesh.py:193).
        self.tokenizer = tokenizer
        # /profile writes ONLY under this operator-configured directory
        # (None = endpoint disabled): a network peer must never choose
        # filesystem paths for the server.
        self.profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        self._profile_seq = 0
        self.emitter = _StreamEmitter()  # the SSE streams' one writer
        frontend = self

        # -- /debug surfaces (flight-recorder + live state) ------------
        # Snapshots are LOCK-FREE on purpose: the runner lock is held
        # across whole engine steps (a jit compile can take seconds), and
        # a debug endpoint that blocks behind it is useless exactly when
        # the node is wedged. list() under the GIL is an atomic snapshot;
        # a torn read costs one request of staleness, not corruption.

        def _debug_requests() -> dict:
            eng = self.runner.engine
            now = time.monotonic()
            waiting = list(eng.waiting)
            restoring = [r for r, _ in list(getattr(eng, "_restoring", ()))]
            running = [r for r in list(eng._rows) if r is not None]
            # Counts derive from the SAME snapshots as the rows, so one
            # response is always internally consistent (the snapshot
            # itself may trail the scheduler by a beat — by design).
            return {
                "requests": [
                    _request_row(r, now)
                    for r in waiting + restoring + running
                ],
                "waiting": len(waiting),
                "restoring": len(restoring),
                "running": len(running),
            }

        def _debug_state() -> dict:
            eng = self.runner.engine
            tree = eng.tree
            state = {
                "engine": {
                    "name": eng.name,
                    "batch_rows_active": sum(
                        1 for r in eng._rows if r is not None
                    ),
                    "max_batch": eng.max_batch,
                    "waiting": len(eng.waiting),
                    "pressure": eng._pressure,
                    "prefills": eng.stats.prefills,
                    "decode_steps": eng.stats.decode_steps,
                    "finished": eng.stats.finished,
                    "preemptions": eng.stats.preemptions,
                    "step_failures": self.runner.step_failures,
                    "last_step_error": self.runner.last_step_error,
                    "hit_rate": round(eng.stats.hit_rate, 4),
                    # Histogram-derived (interpolated within buckets):
                    # bounded-memory estimates over the process lifetime,
                    # unlike the raw per-request sample lists.
                    "p50_ttft_s": round(eng._m_ttft.quantile(0.5), 6),
                    "p99_ttft_s": round(eng._m_ttft.quantile(0.99), 6),
                    "p50_tpot_s": round(eng._m_tpot.quantile(0.5), 6),
                    # Queue wait of every admission (requeue / dispatch /
                    # submission, whichever was last, to the instant a
                    # row was secured): count, sum and bucket counts;
                    # read as a delta between two snapshots.
                    "admission_wait": eng.admission_wait,
                    # Sampler launches by the executable they ran: the
                    # argmax alone, or the one that sorts and draws.
                    "sampler": eng.sampler,
                    # Decode tokens handed to their observers (stream
                    # wake-up, timeline, output counter) and where each
                    # flush ran: behind a dispatched decode launch, behind
                    # a prefill, or with nothing on the device to hide it.
                    "emit": eng.emit,
                    # The hand-over between two decode launches: launches
                    # whose inputs were built behind the launch before
                    # them / in the gap, steps whose per-row advance ran
                    # behind the next launch / with the device waiting,
                    # and what forced the latter.
                    "handover": eng.handover,
                    # Count and host seconds of every rm.* span the engine
                    # thread opened, and ``outside_step``: the seconds
                    # between two steps.
                    "spans": eng.spans.snapshot(),
                    # The admission waves' work: members, uncached prompt
                    # tokens, tokens of the launched shapes, wall seconds
                    # from dispatch to the first-token sync, waves by path.
                    "prefill": eng.prefill,
                    # Steps whose excess over their own prefill passed
                    # ``threshold_s``: count, excess seconds, the last
                    # records (when, top spans, queue, pauses, memory).
                    "slow_steps": {
                        **eng.slow_steps,
                        "last": list(eng.slow_steps["last"]),
                    },
                },
                "pool": {
                    "num_slots": eng.pool.num_slots,
                    "free_slots": eng.pool.free_slots,
                    "page_size": eng.pool.page_size,
                    "quant": eng.pool.quant,
                },
                # The state beside the rows: the index keys of a model
                # with learned sparse attention (0: it has none). write:
                # launches that wrote rows into the pool outside the decode
                # kernels (a dense prefill's, a chunk's), the pages they
                # moved whole, and those of them given only part of
                # their rows (read first, overlaid, written back).
                "kv": {
                    "index_plane_bytes": eng.pool.index_plane_bytes,
                    "write": eng.pool.write_stats,
                },
                "cache": {
                    "evictable_tokens": getattr(tree, "evictable_size_", None),
                    "protected_tokens": getattr(tree, "protected_size_", None),
                    # Host seconds in the tree walk + slot acquisition
                    # (rm.tree.match) and in publish (rm.tree.publish).
                    "tree_host": eng.tree_host,
                },
                # Window layers: context tokens decode launches had to
                # read / could skip (summed over rows x sliding layers).
                "attn": eng.attn_window,
                # The hardware every number on this node came from, with
                # live per-device memory counters (obs/step_plane.py).
                "device": describe_devices(),
                "trace": get_recorder().stats(),
                # Per-bucket trace exemplars (obs/metrics.py): the fleet
                # aggregator's HTTP peer transport reads this section to
                # link fleet-tail buckets back to stitched traces.
                "exemplars": get_registry().exemplars(),
            }
            if eng.process_account is not None:
                # The process's pauses (launch.py serve installs the
                # account): collections by generation, compilations by name.
                state["process"] = eng.process_account.snapshot()
            if eng._moe_stats is not None:
                # The expert layer's counters, by call site: kept on the
                # device, fetched here and nowhere else.
                state["moe"] = read_moe_stats(eng._moe_stats) | {
                    "held_experts": eng.cfg.held_experts}
            if eng.state_stats is not None:
                # The state slots beside the rows (a model whose layers
                # carry a state): how requests resumed, what the tree took.
                state["state"] = eng.state_stats | {
                    "slots_used": eng.pool.state_slots_used,
                    "slots_used_peak": eng.pool.state_used_peak,
                    "evictions": tree.state_evictions,
                    # the rows beside them: one token in every layer that
                    # owns rows, as stored
                    "kv_bytes_per_token": (
                        eng.pool.kv.size // eng.pool.num_slots
                        * eng.pool.kv.dtype.itemsize),
                    "bytes_per_slot": eng.pool.state_slot_bytes,
                }
            if eng.matrix_stats is not None:
                # Layers with a matrix state ("kda": linear attention,
                # "ssd": Mamba-2): what the launches moved of the state
                # slots (Engine.matrix_stats), under the kind's name.
                state[eng.cfg.matrix_state] = dict(eng.matrix_stats)
            if eng.tail_stats is not None:
                # A model whose resume points are window tails alone: how
                # requests resumed, what the tree took and gave up.
                state["tail"] = eng.tail_stats | {
                    "evictions": tree.state_evictions}
            if eng.window_stats is not None:
                # Two groups of rows (a model whose window layers keep
                # their own): each group's slots and the most ever taken,
                # and what the rows and the tree's snapshots hold of the
                # window group.
                pool = eng.pool
                state["kv"]["groups"] = {
                    "full": {"slots": pool.num_slots, "used_peak": pool.used_peak,
                             "bytes_per_token": (pool.kv.size // pool.num_slots
                                                 * pool.kv.dtype.itemsize)},
                    "window": {"slots": pool.window_slots,
                               "used_peak": pool.window_used_peak,
                               "used": pool.window_slots_used,
                               "bytes_per_token": (
                                   pool.window.size // pool.window_slots
                                   * pool.window.dtype.itemsize)},
                }
                state["window"] = dict(eng.window_stats)
            host = getattr(tree, "host", None)
            if host is not None:
                state["host_tier"] = {
                    "num_slots": getattr(host, "num_slots", None),
                    "free_slots": getattr(host, "free_slots", None),
                    "writeback_sweeps": getattr(tree, "wb_sweeps", 0),
                    "writeback_gathers": getattr(tree, "wb_gathers", 0),
                }
            plane = getattr(eng, "kv_transfer", None)
            if plane is not None:
                # Async KV-movement plane (cache/kv_transfer.py): lane
                # queue depths + restore-park state, same lock-free
                # snapshot discipline as the rest of this endpoint.
                state["kv_transfer"] = {
                    **plane.stats(),
                    "restoring_requests": len(
                        getattr(eng, "_restoring", ())
                    ),
                }
            acct = getattr(eng, "step_acct", None)
            if acct is not None:
                # TPU step attribution (obs/step_plane.py): per-wave MFU
                # estimate + pad fraction aggregates.
                state["step_accounting"] = acct.report()
            waves = getattr(eng, "waves", None)
            if waves is not None:
                # Mixed compute waves (engine/waves.py): wave-kind mix,
                # inline-token throughput, and the decode-defer counter
                # against its starvation bound.
                state["waves"] = {
                    **waves.snapshot(),
                    "inline_backlog": len(getattr(eng, "_inline", ())),
                }
            dispatch = getattr(eng, "_last_dispatch", None)
            if dispatch is not None:
                # The last decode launch: the attention it ran (the
                # paged kernel, or "dense" = the jnp reference where the
                # kernel does not lower) and its batch, bucket and
                # longest context. chip_smoke.py proves from it that the
                # kernel served.
                state["decode_dispatch"] = dispatch
            if eng._last_prefill_path is not None:
                # Which prefill launch the last admission wave took
                # (dense / chunk / sp), and the attention a chunk launch
                # runs here: Pallas kernel ("paged") or the jnp reference
                # — the reference must never serve silently.
                state["prefill_dispatch"] = {
                    "path": eng._last_prefill_path,
                    "chunk_attention": eng.chunk_attention,
                }
            if eng.mesh is not None:
                state["membership"] = _membership_state(eng.mesh)
            if self.slo_enabled:
                state["slo"] = self.runner.ctl.snapshot()
            lc = self.lifecycle
            if lc is not None:
                state["lifecycle"] = lc.status()
            return state

        self._debug_requests = _debug_requests
        self._debug_state = _debug_state

        # Diagnosis plane (obs/doctor.py + obs/attribution.py): the
        # attributor installs on the recorder's span-retire hook NOW so
        # phase histograms accumulate from the first traced request;
        # the doctor persists across GETs — its burn-rate windows need
        # continuity — and resolves the attributor at diagnose time
        # through the ensure_* seam (a swapped recorder gets a fresh
        # one).
        ensure_attributor()
        # Telemetry history (obs/timeseries.py): bounded time-series
        # rings over every plane, sampled at a fixed cadence; serves
        # GET /debug/timeseries and feeds the doctor's burn windows.
        # 0 disables (point-in-time-only, the pre-PR-13 behavior).
        self.history = None
        if history_interval_s > 0:
            self.history = TelemetryHistory(
                interval_s=history_interval_s,
                capacity=history_capacity,
                mesh=engine.mesh,
                engine=engine,
                slo=self.runner.ctl if self.slo_enabled else None,
                node=engine.name,
            )
        # Serving nodes never host a fleet aggregator (that's the
        # router/front-door role) — the attribute exists so the
        # /cluster/timeseries and /cluster/slo handlers answer with a
        # uniform pointer instead of a bare 404.
        self.aggregator = None
        self.doctor = MeshDoctor(
            mesh=engine.mesh,
            engine=engine,
            slo=self.runner.ctl if self.slo_enabled else None,
            attributor=ensure_attributor,
            history=self.history,
        )
        # The black box (obs/blackbox.py): crash-surviving dumps of the
        # history + waterfalls + spans + doctor findings + state.
        self.blackbox = None
        if blackbox_dir:
            self.blackbox = BlackBox(
                blackbox_dir,
                history=self.history,
                doctor=self.doctor,
                recorder=get_recorder,
                attributor_fn=ensure_attributor,
                state_fn=_debug_state,
                node=engine.name,
                watchdog_timeout_s=blackbox_watchdog_s,
            )
        if self.history is not None:
            # Started AFTER the black box installed its segment hook, so
            # the very first samples are already crash-durable.
            self.history.start()

        def _run_profile(
            seconds: float, python: bool = False
        ) -> tuple[int, dict]:
            """One ``jax.profiler`` capture window into a fresh numbered
            subdirectory of the operator-configured base dir. Shared by
            POST /profile and GET /debug/profile?seconds=N (the step-
            attribution quickstart's one-liner) so the path policy —
            clients never choose filesystem paths — cannot drift. The
            python tracer is off unless ``python`` (``?python=1`` /
            ``"python": true``): the ``rm.*`` host spans and the device
            planes do not need it, and it is most of a capture's cost."""
            if frontend.profile_dir is None:
                return 403, {"error": "profiling disabled (no --profile-dir)"}
            if not (0.0 < seconds <= 60.0):
                return 400, {"error": "seconds must be in (0, 60]"}
            if not frontend._profile_lock.acquire(blocking=False):
                return 409, {"error": "profile already running"}
            try:
                from radixmesh_tpu.obs.tracing import profile as _profile

                # _profile_lock is held: the seq needs no lock of its
                # own. The timestamp keeps directories unique across
                # server restarts into the same base dir.
                frontend._profile_seq += 1
                logdir = os.path.join(
                    frontend.profile_dir,
                    f"capture-{int(time.time())}-"
                    f"{frontend._profile_seq:04d}",
                )
                stats = frontend.runner.engine.stats
                with _profile(logdir, python_tracer=python):
                    before = stats.decode_steps
                    # meshcheck: ok[sleep-audit] the sleep IS the
                    # requested jax.profiler capture window.
                    time.sleep(seconds)
                    steps = stats.decode_steps - before
            except Exception as e:  # noqa: BLE001 — report, don't kill the handler
                return 500, {"error": str(e)}
            finally:
                frontend._profile_lock.release()
            # decode_steps: what the window covered — a capture of an idle
            # engine is a valid file with nothing to read in it.
            return 200, {
                "profiled_s": seconds, "logdir": logdir, "decode_steps": steps,
            }

        self._run_profile = _run_profile

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route through our logger
                frontend.log.debug(fmt, *args)

            def do_GET(self):
                if self.path == "/healthz":
                    _json_response(self, 200, {"status": "ok"})
                elif self.path == "/metrics":
                    body = get_registry().render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/stats":
                    s = frontend.runner.engine.stats
                    _json_response(
                        self,
                        200,
                        {
                            "hit_rate": s.hit_rate,
                            "p50_ttft_s": s.p50_ttft_s,
                            "prompt_tokens": s.prompt_tokens,
                            "cached_tokens": s.cached_tokens,
                            "generated_tokens": s.generated_tokens,
                            "finished": s.finished,
                            "preemptions": s.preemptions,
                            "spec_proposed": s.spec_proposed,
                            "spec_accepted": s.spec_accepted,
                            **(
                                {"slo": frontend.runner.ctl.snapshot()}
                                if frontend.slo_enabled
                                else {}
                            ),
                        },
                    )
                elif self.path.split("?", 1)[0] == "/debug/trace":
                    # Load the body in Perfetto (ui.perfetto.dev).
                    _debug_trace_response(self)
                elif self.path.split("?", 1)[0] == "/debug/profile":
                    # TPU step attribution leg (c): a bounded
                    # jax.profiler capture window as a one-line GET —
                    # ?seconds=N, default 3 (POST /profile is the
                    # original body-carrying form; both share
                    # _run_profile).
                    from urllib.parse import parse_qs, urlsplit

                    q = parse_qs(urlsplit(self.path).query)
                    try:
                        seconds = float(q.get("seconds", ["3.0"])[-1])
                    except ValueError:
                        _json_response(
                            self, 400, {"error": "seconds must be a number"}
                        )
                        return
                    python = q.get("python", ["0"])[-1] not in ("", "0")
                    code, obj = frontend._run_profile(seconds, python)
                    _json_response(self, code, obj)
                elif self.path == "/debug/requests":
                    _json_response(self, 200, frontend._debug_requests())
                elif self.path == "/debug/state":
                    _json_response(self, 200, frontend._debug_state())
                elif self.path.split("?", 1)[0] == "/debug/timeseries":
                    # Telemetry history (obs/timeseries.py): cursor-
                    # paginated time-series rings over every plane.
                    _debug_timeseries_response(self, frontend.history)
                elif self.path == "/debug/waterfall":
                    # Critical-path attribution (obs/attribution.py):
                    # p50/p99 phase breakdown + per-shape table +
                    # recent per-request waterfalls.
                    _json_response(self, 200, ensure_attributor().report())
                elif self.path.split("?", 1)[0] == "/debug/tokens":
                    # Token-level speed plane (obs/token_timeline.py):
                    # ITL ring + stall causes, speculation ledger,
                    # goodput/waste decomposition.
                    _debug_tokens_response(self, frontend.runner.engine)
                elif self.path == "/cluster/telemetry":
                    body = _cluster_telemetry(frontend.runner.engine.mesh)
                    # Per-shape speculative acceptance (the doctor's
                    # spec-efficiency evidence) — engine-local, so it
                    # rides the serving node's view only.
                    body["spec"] = frontend.runner.engine.spec_report()
                    _json_response(self, 200, body)
                elif self.path == "/cluster/health":
                    _json_response(
                        self, 200, _cluster_health(frontend.runner.engine.mesh)
                    )
                elif self.path == "/cluster/doctor":
                    # The mesh doctor (obs/doctor.py): ranked findings
                    # with pinned evidence over every attached plane.
                    _json_response(self, 200, frontend.doctor.diagnose())
                elif self.path.split("?", 1)[0] == "/cluster/timeseries":
                    _cluster_timeseries_response(self, frontend.aggregator)
                elif self.path == "/cluster/slo":
                    _cluster_slo_response(self, frontend.aggregator)
                else:
                    _json_response(self, 404, {"error": "not found"})

            def do_POST(self):
                if self.path == "/admin/blackbox":
                    _admin_blackbox_response(self, frontend.blackbox)
                    return
                if self.path == "/admin/drain":
                    # Graceful drain (policy/lifecycle.py): kick the
                    # DRAINING → LEFT sequence asynchronously — the
                    # handler must not block for the full drain deadline.
                    lc = frontend.lifecycle
                    if lc is None:
                        _json_response(
                            self, 404,
                            {"error": "no lifecycle plane attached to "
                             "this node (start via launch.py node)"},
                        )
                        return
                    try:
                        length = int(self.headers.get("Content-Length", 0) or 0)
                        body = _read_json(self) if length > 0 else {}
                        deadline = body.get("deadline_s")
                        deadline = None if deadline is None else float(deadline)
                    except (TypeError, ValueError, json.JSONDecodeError) as e:
                        _json_response(self, 400, {"error": str(e)})
                        return
                    accepted = lc.request_drain(deadline_s=deadline)
                    _json_response(
                        self,
                        202 if accepted else 200,
                        {
                            "accepted": accepted,
                            "state": lc.state.value,
                            # Where shed clients should retry.
                            "router": lc.router_hint(),
                            "deadline_s": (
                                deadline
                                if deadline is not None
                                else lc.cfg.drain_timeout_s
                            ),
                        },
                    )
                    return
                if self.path == "/profile":
                    # Capture a device+host trace of live serving into a
                    # server-configured logdir (obs/tracing.py::profile —
                    # exception-safe stop; SURVEY §5: the reference has no
                    # tracing at all). Clients never supply paths; each
                    # capture lands in a fresh numbered subdirectory
                    # (shared _run_profile — GET /debug/profile is the
                    # query-param form of the same capture).
                    try:
                        body = _read_json(self)
                        seconds = float(body.get("seconds", 3.0))
                    except (TypeError, ValueError, json.JSONDecodeError) as e:
                        _json_response(self, 400, {"error": str(e)})
                        return
                    code, obj = frontend._run_profile(
                        seconds, bool(body.get("python", False))
                    )
                    _json_response(self, code, obj)
                    return
                if self.path == "/cancel":
                    try:
                        rid = int(_read_json(self)["rid"])
                    except (KeyError, TypeError, ValueError, json.JSONDecodeError) as e:
                        _json_response(self, 400, {"error": str(e)})
                        return
                    _json_response(
                        self, 200, {"cancelled": frontend.runner.cancel(rid)}
                    )
                    return
                if self.path != "/generate":
                    _json_response(self, 404, {"error": "not found"})
                    return
                try:
                    body = _read_json(self)
                    ids = _ids_from_body(body, frontend.tokenizer, "server")
                    stop_ids = tuple(body.get("stop_token_ids", ()))
                    if (
                        "text" in body
                        and "stop_token_ids" not in body
                        and frontend.tokenizer.eos_id is not None
                    ):
                        # Text callers reasonably expect generation to end
                        # at EOS without knowing the id space; an explicit
                        # (even empty) stop_token_ids opts out.
                        stop_ids = (frontend.tokenizer.eos_id,)
                    seed = body.get("seed")
                    sampling = SamplingParams(
                        temperature=float(body.get("temperature", 0.0)),
                        top_p=float(body.get("top_p", 1.0)),
                        top_k=int(body.get("top_k", 0)),
                        max_new_tokens=int(body.get("max_tokens", 16)),
                        stop_token_ids=stop_ids,
                        seed=None if seed is None else int(seed),
                        return_logits=bool(body.get("return_logits", False)),
                    )
                    # Resume admission (crash recovery): output a prior
                    # life already delivered — replayed through prefill
                    # (near-pure cache hit), never re-emitted.
                    resume_tokens = body.get("resume_tokens")
                    if resume_tokens is not None and (
                        not isinstance(resume_tokens, list)
                        or not all(
                            isinstance(t, int) and not isinstance(t, bool)
                            for t in resume_tokens
                        )
                    ):
                        raise ValueError(
                            "resume_tokens must be a list of ints"
                        )
                    # Cross-node trace stitching (PR 9): a resume/hedge
                    # re-route carries the originating request's 64-bit
                    # trace id (int or hex string) so THIS node's spans
                    # land in the same stitched timeline.
                    trace_id = body.get("trace_id")
                    if trace_id is not None:
                        trace_id = int(str(trace_id), 0)
                        if not 0 < trace_id < (1 << 64):
                            raise ValueError(
                                "trace_id must be a nonzero 64-bit int"
                            )
                    slo_kw = {}
                    if frontend.slo_enabled:
                        # SLO fields (ignored without a control plane —
                        # plain runners have neither tenants nor
                        # deadlines to enforce them with).
                        slo_kw["tenant"] = str(body.get("tenant", "default"))
                        if "ttft_deadline_ms" in body:
                            slo_kw["ttft_deadline_s"] = (
                                float(body["ttft_deadline_ms"]) / 1e3
                            )
                        if "deadline_ms" in body:
                            slo_kw["e2e_deadline_s"] = (
                                float(body["deadline_ms"]) / 1e3
                            )
                except (KeyError, TypeError, ValueError, json.JSONDecodeError) as e:
                    _json_response(self, 400, {"error": str(e)})
                    return
                try:
                    req = frontend.runner.submit(
                        ids, sampling, resume_tokens=resume_tokens,
                        trace_id=trace_id, **slo_kw
                    )
                except RequestShed as e:  # overload control plane refusal
                    # A drain shed points the client at the router: the
                    # fleet still has capacity — just not HERE.
                    drain_hint = (
                        {"router": frontend.lifecycle.router_hint()}
                        if e.reason == "draining"
                        and frontend.lifecycle is not None
                        else {}
                    )
                    if e.retry_after_s is not None:
                        # Retry-After must precede end_headers; build the
                        # response by hand rather than teach
                        # _json_response about extra headers. The
                        # advertised value carries bounded jitter
                        # (policy/retry.py): a thundering herd shed in
                        # one instant must not come back in one instant
                        # against a recovering fleet.
                        retry_s = jittered_retry_after(e.retry_after_s)
                        body_b = json.dumps(
                            {
                                "error": str(e),
                                "shed": True,
                                "reason": e.reason,
                                "retry_after_s": round(retry_s, 4),
                                **drain_hint,
                            }
                        ).encode()
                        self.send_response(e.http_status)
                        self.send_header("Content-Type", "application/json")
                        self.send_header(
                            "Retry-After", str(max(1, int(round(retry_s))))
                        )
                        self.send_header("Content-Length", str(len(body_b)))
                        self.end_headers()
                        self.wfile.write(body_b)
                    else:
                        _json_response(
                            self,
                            e.http_status,
                            {"error": str(e), "shed": True, "reason": e.reason,
                             **drain_hint},
                        )
                    return
                except ValueError as e:  # e.g. prompt too long
                    _json_response(self, 400, {"error": str(e)})
                    return
                except RuntimeError as e:  # submit raced shutdown/drain
                    extra = {}
                    lc = frontend.lifecycle
                    if lc is not None and "draining" in str(e):
                        extra["router"] = lc.router_hint()
                    _json_response(self, 503, {"error": str(e), **extra})
                    return
                if body.get("stream"):
                    self._stream(req)
                    return
                tokens = frontend.runner.wait(
                    req, timeout=float(body.get("timeout", 300.0))
                )
                tr = req.trace
                if tr is not None:
                    # The outermost span of the request's flight: HTTP
                    # submit → response ready (streams record theirs when
                    # the SSE done event flushes).
                    tr.add(
                        "http_request", req.submit_time,
                        time.monotonic() - req.submit_time, cat="http",
                        output_tokens=len(tokens),
                    )
                if req.error is not None:
                    # The engine step serving this request raised
                    # (EngineRunner._fail_step).
                    _json_response(
                        self, 500,
                        {"error": req.error, "failed": True, "rid": req.rid},
                    )
                    return
                if req.shed and not tokens:
                    # Dropped from the SLO queue before any work ran
                    # (dispatch-time deadline check or shutdown flush).
                    _json_response(
                        self,
                        503,
                        {
                            "error": f"request shed ({req.shed_reason})",
                            "shed": True,
                            "reason": req.shed_reason,
                        },
                    )
                    return
                _json_response(
                    self,
                    200,
                    {
                        "output_ids": tokens,
                        "cached_tokens": req.prefix_len,
                        "rid": req.rid,
                        # Resumed requests: the stream continues from
                        # token k — output_ids holds ONLY post-resume
                        # tokens, never a re-emission of the delivered
                        # prefix.
                        **(
                            {"resumed_from": req.resume_offset}
                            if req.resume_offset
                            else {}
                        ),
                        **(
                            {"text": frontend.tokenizer.decode(tokens)}
                            if frontend.tokenizer is not None
                            else {}
                        ),
                        **({"cancelled": True} if req.cancelled else {}),
                        **(
                            {"shed": True, "reason": req.shed_reason}
                            if req.shed
                            else {}
                        ),
                        **_logits_payload(req),
                    },
                )

            def _stream(self, req: Request) -> None:
                """Server-sent events: one ``data:`` line per new token."""
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.end_headers()
                # The emitter thread writes the tokens (_StreamEmitter);
                # this thread sleeps until the request finishes — or until
                # the emitter hands the stream back (a reader that fell
                # behind), and then writes them itself, as below.
                stream = _SseStream(req, self.connection)
                frontend.emitter.add(stream)
                try:
                    with req.cond:
                        while (stream.emitter_owns
                               and req.state is not RequestState.FINISHED):
                            req.cond.wait(timeout=0.5)
                finally:
                    frontend.emitter.remove(stream)
                if stream.rest:
                    self.wfile.write(stream.rest)
                sent = stream.sent
                while True:
                    # One span per wake-up (not per token): encode +
                    # write + flush of whatever landed since the last.
                    with annotate("rm.http.emit"):
                        tokens = req.output_tokens
                        n = len(tokens)
                        if n > sent:
                            self.wfile.write(_token_events(tokens[sent:n]))
                            sent = n
                        self.wfile.flush()
                    if req.state is RequestState.FINISHED:
                        with annotate("rm.http.emit"):
                            final = frontend.runner.tokens_so_far(req)
                            if len(final) > sent:
                                self.wfile.write(_token_events(final[sent:]))
                            done_evt = {
                                "done": True,
                                "output_ids": final,
                                "cached_tokens": req.prefix_len,
                            }
                            if req.resume_offset:
                                done_evt["resumed_from"] = req.resume_offset
                            if frontend.tokenizer is not None:
                                done_evt["text"] = frontend.tokenizer.decode(final)
                            if req.cancelled:
                                done_evt["cancelled"] = True
                            if req.error is not None:
                                done_evt["error"] = req.error
                            done_evt.update(_logits_payload(req))
                            self.wfile.write(
                                f"data: {json.dumps(done_evt)}\n\n".encode()
                            )
                            self.wfile.flush()
                        tr = req.trace
                        if tr is not None:
                            tr.add(
                                "http_request", req.submit_time,
                                time.monotonic() - req.submit_time,
                                cat="http", stream=True,
                                output_tokens=len(final),
                            )
                        return
                    # Park until the next token lands (or the request
                    # finishes) — the engine notifies per token (a decode
                    # token's wake-up comes once the next launch is on
                    # the device, Engine._flush_emit; never after a wait),
                    # so first-token latency is not quantized by a poll
                    # interval. The 0.5 s fallback re-check covers a
                    # notify racing the length read above.
                    with req.cond:
                        if (
                            len(req.output_tokens) <= sent
                            and req.state is not RequestState.FINISHED
                        ):
                            req.cond.wait(timeout=0.5)

        self._server = _FrontendServer((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True, name="http-serve"
        )
        self._thread.start()
        self.log.info("serving frontend on %s:%d", host, self.port)

    def close(self, drain_s: float = 5.0) -> None:
        self._server.shutdown()
        self._server.server_close()
        self.emitter.close()
        if self.blackbox is not None:
            # Graceful shutdown writes one last final (the drain hook
            # may already have written its own; each final is complete
            # and the loader takes the newest).
            self.blackbox.close(flush_cause="shutdown")
        if self.history is not None:
            self.history.close()
        self.runner.close(drain_s=drain_s)


class RouterFrontend:
    """HTTP API over a router node's cache-aware router."""

    def __init__(
        self,
        router: CacheAwareRouter,
        host: str = "127.0.0.1",
        port: int = 0,
        tokenizer=None,
        history_interval_s: float = 1.0,
        history_capacity: int = 900,
        blackbox_dir: str | None = None,
        blackbox_watchdog_s: float = 0.0,
        aggregator_peers: Sequence[tuple] = (),  # (name, base_url[, rank])
        aggregator_interval_s: float = 2.0,
    ):
        self.router = router
        self.log = get_logger("http.route")
        # Routing keys are token ids (the tree's key space, the
        # reference's List[int] contract); with a tokenizer, text clients
        # can route without running tokenization themselves. MUST be the
        # same tokenizer the serving nodes use, or routed prefixes won't
        # line up with cached ones.
        self.tokenizer = tokenizer
        frontend = self

        def _debug_state() -> dict:
            r = self.router
            with r._alive_lock:
                alive = {k: sorted(v) for k, v in r._alive.items()}
            return {
                "router": {
                    "warm_up": r._warm_up,
                    "alive": alive,
                    "estimated_load": {
                        addr: round(r._loads.load(addr), 3)
                        for role_addrs in alive.values()
                        for addr in role_addrs
                    },
                },
                "membership": _membership_state(r.mesh_cache),
                "trace": get_recorder().stats(),
                "exemplars": get_registry().exemplars(),
            }

        self._debug_state = _debug_state

        # Diagnosis plane: a router doctor sees the fleet-facing rules
        # (hot shard, replication lag) — it holds no engine or SLO
        # controller, and ``rules_checked``/``inputs`` in the report
        # say so explicitly.
        ensure_attributor()
        # Telemetry history + black box, same wiring as the serving
        # frontend minus the engine/SLO seams a router doesn't hold:
        # the router's rings are the fleet-facing record (health, heat,
        # skew) — the observer dump the post-mortem doctor reads when a
        # serving node dies without flushing its own.
        node_label = f"router@{router.mesh_cache.rank}"
        self.history = None
        if history_interval_s > 0:
            self.history = TelemetryHistory(
                interval_s=history_interval_s,
                capacity=history_capacity,
                mesh=router.mesh_cache,
                node=node_label,
            )
        # Fleet aggregation (obs/aggregator.py): the router is the
        # front door, so it hosts the collector — cursor-pulling every
        # peer's /debug/timeseries ring into one node-labeled fleet
        # store, served on /cluster/timeseries + /cluster/slo. Started
        # only when peers are configured (launch.py --agg-interval); the
        # doctor gets the aggregator seam either way, so its
        # ``available`` map states the truth.
        self.aggregator = None
        if aggregator_peers:
            self.aggregator = FleetAggregator(
                peers=[
                    # The optional third element is the peer's ring rank
                    # — the telemetry_gap rule needs it to cross-
                    # reference gossip health for its dead-node vs
                    # dead-sampler verdict.
                    HttpPeer(p[0], p[1], rank=p[2] if len(p) > 2 else None)
                    for p in aggregator_peers
                ],
                interval_s=aggregator_interval_s,
                capacity=history_capacity,
                node=node_label,
                registry=get_registry(),
            )
        self.doctor = MeshDoctor(
            mesh=router.mesh_cache, attributor=ensure_attributor,
            history=self.history, aggregator=self.aggregator,
        )
        self.blackbox = None
        if blackbox_dir:
            self.blackbox = BlackBox(
                blackbox_dir,
                history=self.history,
                doctor=self.doctor,
                recorder=get_recorder,
                attributor_fn=ensure_attributor,
                state_fn=_debug_state,
                node=node_label,
                watchdog_timeout_s=blackbox_watchdog_s,
            )
        if self.history is not None:
            self.history.start()
        if self.aggregator is not None:
            self.aggregator.start()

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                frontend.log.debug(fmt, *args)

            def do_GET(self):
                if self.path == "/healthz":
                    _json_response(self, 200, {"status": "ok"})
                elif self.path == "/metrics":
                    body = get_registry().render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path.split("?", 1)[0] == "/debug/trace":
                    _debug_trace_response(self)
                elif self.path == "/debug/requests":
                    # Router nodes hold no request state: routing is one
                    # stateless tree walk per call. The empty table (vs a
                    # 404) keeps fleet-wide debug tooling uniform.
                    _json_response(
                        self, 200,
                        {
                            "requests": [],
                            "note": "router node — see a serving node's "
                            "/debug/requests for in-flight requests",
                        },
                    )
                elif self.path == "/debug/state":
                    _json_response(self, 200, frontend._debug_state())
                elif self.path.split("?", 1)[0] == "/debug/timeseries":
                    _debug_timeseries_response(self, frontend.history)
                elif self.path == "/debug/waterfall":
                    _json_response(self, 200, ensure_attributor().report())
                elif self.path == "/cluster/telemetry":
                    _json_response(
                        self, 200,
                        _cluster_telemetry(frontend.router.mesh_cache),
                    )
                elif self.path == "/cluster/health":
                    _json_response(
                        self, 200, _cluster_health(frontend.router.mesh_cache)
                    )
                elif self.path == "/cluster/doctor":
                    _json_response(self, 200, frontend.doctor.diagnose())
                elif self.path.split("?", 1)[0] == "/cluster/timeseries":
                    _cluster_timeseries_response(self, frontend.aggregator)
                elif self.path == "/cluster/slo":
                    _cluster_slo_response(self, frontend.aggregator)
                else:
                    _json_response(self, 404, {"error": "not found"})

            def do_POST(self):
                if self.path == "/admin/blackbox":
                    _admin_blackbox_response(self, frontend.blackbox)
                    return
                if self.path != "/route":
                    _json_response(self, 404, {"error": "not found"})
                    return
                try:
                    body = _read_json(self)
                    ids = _ids_from_body(body, frontend.tokenizer, "router")
                except (KeyError, ValueError, json.JSONDecodeError) as e:
                    _json_response(self, 400, {"error": str(e)})
                    return
                res = frontend.router.cache_aware_route(ids)
                cfg = frontend.router.config
                _json_response(
                    self,
                    200,
                    {
                        # null address = no node of that role alive right
                        # now (RouteResult contract): caller queues/errors.
                        "prefill_addr": res.prefill_addr,
                        "decode_addr": res.decode_addr,
                        # Where to POST /generate: the routed node's serving
                        # HTTP endpoint (cache port + serve_port_offset).
                        "prefill_serve_addr": cfg.serve_addr(res.prefill_addr),
                        "decode_serve_addr": cfg.serve_addr(res.decode_addr),
                        "prefill_cache_hit": res.prefill_cache_hit,
                        "decode_cache_hit": res.decode_cache_hit,
                        "match_len": res.match_len,
                    },
                )

        self._server = _FrontendServer((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True, name="http-route"
        )
        self._thread.start()
        self.log.info("router frontend on %s:%d", host, self.port)

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self.aggregator is not None:
            # Before the history: a puller sweep racing shutdown must
            # not ingest into a store whose owner is tearing down.
            self.aggregator.close()
        if self.blackbox is not None:
            self.blackbox.close(flush_cause="shutdown")
        if self.history is not None:
            self.history.close()

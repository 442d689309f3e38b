"""SLO-governed engine runner: the control plane wired into serving.

:class:`SLORunner` replaces the plain
:class:`~radixmesh_tpu.server.http_frontend.EngineRunner` when a frontend
is constructed with an :class:`~radixmesh_tpu.slo.control.SLOConfig`. The
request path becomes::

    submit() ── offer() ──► shed (RequestShed, 429/503) ─► client retries
        │
        └► enqueue() into per-tenant WFQ queues
                │ (runner thread, every scheduler iteration)
                ▼
            _pump(): tier knobs → e2e-deadline sweep → weighted-fair
            dispatch into engine.waiting (kept shallow — at most one
            admission wave deep, so the SLO layer owns ordering, the
            engine owns batching) → finalize dispatch-time sheds
                │
                ▼
            engine.step()   (unchanged)

Degradation tier knobs applied here (the controller only decides the
tier): tier ≥1 zeroes ``engine.spec_decode_tokens`` (a wide verify launch
steals exactly the prefill capacity an overload needs back), tier ≥2 caps
each dispatched request's ``max_new_tokens``, tier ≥3 shrinks
``engine.prefill_wave_tokens``. All restore on the way back down.

The engine's ``on_first_token`` hook feeds the controller's service-rate
EWMA and retires dispatched tokens from the backlog estimate — both run
on the runner thread with the runner lock held, like every other engine
mutation.
"""

from __future__ import annotations

import time
from dataclasses import replace as dc_replace
from typing import Sequence

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import Request, RequestState, SamplingParams
from radixmesh_tpu.server.http_frontend import EngineRunner
from radixmesh_tpu.slo.control import (
    SHED_DRAINING,
    SHED_SHUTDOWN,
    OverloadController,
    RequestShed,
    SLOConfig,
)
from radixmesh_tpu.utils.logging import get_logger

__all__ = ["SLORunner"]


class SLORunner(EngineRunner):
    """Exclusive engine owner with the overload control plane in the
    admission path. Drop-in for :class:`EngineRunner`; ``submit`` grows
    tenant/deadline parameters and may raise :class:`RequestShed`."""

    def __init__(
        self,
        engine: Engine,
        slo: SLOConfig | None = None,
        clock=time.monotonic,
    ):
        super().__init__(engine)
        self.ctl = OverloadController(slo, clock=clock)
        self._clock = clock
        self._base_spec = engine.spec_decode_tokens
        self._base_wave = engine.prefill_wave_tokens
        self._applied_tier = 0
        self.log = get_logger("slo.runner")
        engine.on_first_token = self._on_first_token

    # -- engine callback (runner thread, lock held) --------------------

    def _on_first_token(self, req: Request) -> None:
        if req.admit_time > 0:  # dispatched through the SLO queue
            self.ctl.note_first_token(req, self._clock())

    # -- submission path ----------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        tenant: str = "default",
        ttft_deadline_s: float | None = None,
        e2e_deadline_s: float | None = None,
        resume_tokens: Sequence[int] | None = None,
        trace_id: int | None = None,
    ) -> Request:
        # Arrival is STAMPED BEFORE the lock: engine.step() runs under
        # self._lock, so a submit landing mid-step (or mid-jit-compile)
        # waits out the step first — time that is queueing delay like any
        # other and must count against the deadline and measured TTFT,
        # not vanish into an unobserved lock wait.
        t_arrival = self._clock()
        with self._lock:
            if self._closed:
                raise RuntimeError("engine runner is shut down")
            if self._draining:
                # Graceful drain (policy/lifecycle.py): retriable 503 +
                # Retry-After; the frontend's shed body names the router
                # the client should re-route through.
                raise RequestShed(
                    SHED_DRAINING, self._drain_retry_after_s, tenant
                )
            # Validation (length bounds) before admission accounting, so
            # a malformed request can't spend bucket tokens.
            # A resumed request (crash recovery, server/recovery.py)
            # threads its REMAINING deadline budget in as
            # e2e_deadline_s: the edge computed it from the original
            # admission instant, so the second life cannot spend time
            # the first life already used.
            req = self.engine.make_request(
                prompt,
                sampling,
                tenant=tenant,
                ttft_deadline_s=ttft_deadline_s,
                e2e_deadline_s=e2e_deadline_s,
                resume_tokens=resume_tokens,
                trace_id=trace_id,
            )
            req.submit_time = t_arrival
            decision = self.ctl.offer(
                tenant, len(req.prompt), ttft_deadline_s
            )
            if not decision.admitted:
                raise RequestShed(
                    decision.reason, decision.retry_after_s, tenant
                )
            self.ctl.enqueue(req)
        self._wake.set()
        return req

    # -- scheduler loop ------------------------------------------------

    def _pre_step(self) -> None:  # EngineRunner._run hook, lock held
        self._pump()

    def _pump(self) -> None:
        """One control-plane iteration (runner lock held)."""
        now = self._clock()
        tier = self.ctl.update_tier(now)
        if tier != self._applied_tier:
            self._apply_tier(tier)
        self._sweep_e2e_deadlines(now)
        # Keep the engine's own FIFO shallow: dispatch at most one
        # admission wave ahead, so ordering stays with the WFQ and a
        # deadline re-check happens as close to prefill as possible.
        with self.engine.spans.span("rm.slo.pump"):
            while len(self.engine.waiting) < self.engine.max_batch:
                req = self.ctl.pop_ready(now)
                if req is None:
                    break
                if tier >= 2:
                    cap = self.ctl.cfg.tier2_max_new_tokens
                    if req.sampling.max_new_tokens > cap:
                        req.sampling = dc_replace(
                            req.sampling, max_new_tokens=cap
                        )
                req.degradation_tier = tier
                req.admit_time = now
                tr = req.trace
                if tr is not None:
                    # The control-plane leg of the timeline: submit →
                    # WFQ dispatch (engine queue wait is its own span,
                    # recorded at admission).
                    tr.add(
                        "slo_queue", req.submit_time,
                        now - req.submit_time, cat="queue",
                        tenant=req.tenant, tier=tier,
                    )
                self.engine.enqueue(req)
        for req in self.ctl.drain_shed():
            self._finalize_shed(req)

    def _apply_tier(self, tier: int) -> None:
        eng = self.engine
        eng.spec_decode_tokens = 0 if tier >= 1 else self._base_spec
        # Tell the speculation ledger WHY γ went to zero: the doctor's
        # spec_misconfigured rule must distinguish "off by SLO policy"
        # from "mistuned", and the adaptive-γ controller (which clamps
        # to the base γ at draft time) inherits the zero automatically —
        # it never fights the ladder.
        led = getattr(eng, "spec_ledger", None)
        if led is not None:
            led.note_tier(tier)
        eng.prefill_wave_tokens = (
            max(
                eng.prefill_chunk,
                int(self._base_wave * self.ctl.cfg.tier3_wave_factor),
            )
            if tier >= 3
            else self._base_wave
        )
        self.log.info(
            "applied degradation tier %d (spec=%d, wave=%d)",
            tier, eng.spec_decode_tokens, eng.prefill_wave_tokens,
        )
        self._applied_tier = tier

    def _sweep_e2e_deadlines(self, now: float) -> None:
        """Cancel running/queued requests past their end-to-end deadline:
        partial output returns immediately (flagged shed) instead of the
        request holding a batch row past the point anyone is waiting."""
        # Parked-for-restore requests (RESTORING, cache/kv_transfer.py)
        # are deadline-subject like any queued request: a restore that
        # outlives the deadline must not resurrect the request later.
        restoring = [r for r, _ in getattr(self.engine, "_restoring", ())]
        expired = [
            r
            for r in list(self.engine.waiting) + restoring + self.engine._rows
            if r is not None
            and r.e2e_deadline_s is not None
            and now - r.submit_time > r.e2e_deadline_s
        ]
        for req in expired:
            req.shed = True
            req.shed_reason = "e2e_deadline"
            self.engine.cancel(req.rid)
            if req.admit_time > 0:
                # Cancelled before a first token: retire its backlog cost
                # (no-op if the first token already landed).
                self.ctl.note_retired(req, now)

    def _finalize_shed(self, req: Request) -> None:
        """A queued request the controller dropped: surface it to waiters
        exactly like a cancel (FINISHED, no output, flagged)."""
        req.cancelled = True
        req.state = RequestState.FINISHED
        tr = req.trace
        if tr is not None:
            tr.add(
                "slo_shed", req.submit_time,
                self._clock() - req.submit_time, cat="queue",
                tenant=req.tenant, reason=req.shed_reason,
            )

    def cancel(self, rid: int) -> bool:
        with self._lock:
            # Still waiting in the WFQ: the engine has never seen it.
            queued = self.ctl.cancel_queued(rid)
            if queued is not None:
                self._finalize_shed(queued)
                return True
            req = next(
                (r for r in self.engine.waiting if r.rid == rid), None
            ) or next(
                (
                    r
                    for r in self.engine._rows
                    if r is not None and r.rid == rid
                ),
                None,
            ) or next(
                (
                    r
                    for r, _ in getattr(self.engine, "_restoring", ())
                    if r.rid == rid
                ),
                None,
            )
            ok = self.engine.cancel(rid)
            if ok and req is not None and req.admit_time > 0:
                self.ctl.note_retired(req)
            return ok

    def begin_drain(self, retry_after_s: float = 1.0) -> None:
        """Graceful drain with the control plane in the path: close
        admission (new submits shed ``draining``, retriable 503), then
        bounce every WFQ-queued-but-undispatched request back to its
        client the same way — queued work has produced nothing, so the
        router re-places it on a surviving node with zero loss."""
        super().begin_drain(retry_after_s)
        for req in self.ctl.flush(SHED_DRAINING):
            self._finalize_shed(req)

    def close(self, drain_s: float = 0.0) -> None:
        # Close the submit window BEFORE flushing: a submit racing into
        # the gap between flush and the base class's _closed would
        # enqueue a request nothing ever pumps, stranding its waiter.
        with self._lock:
            self._closed = True
        # Queued-but-undispatched requests would otherwise strand their
        # waiters: drop them first, then the engine sweep runs as usual.
        for req in self.ctl.flush(SHED_SHUTDOWN):
            self._finalize_shed(req)
        super().close(drain_s=drain_s)

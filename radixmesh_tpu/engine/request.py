"""Request lifecycle state for the serving engine."""

from __future__ import annotations

import enum
import itertools
import threading
import time
from dataclasses import dataclass, field

import numpy as np

__all__ = ["Request", "RequestState", "SamplingParams"]

_rid_counter = itertools.count()


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0  # 0 = greedy
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    max_new_tokens: int = 16
    stop_token_ids: tuple[int, ...] = ()
    # Per-request sampling seed (None = the engine's global stream).
    # With a seed, a sampled token depends only on (seed, absolute
    # position) whenever every row in the launch is seeded — so a
    # request resurrected on another node after a crash redraws exactly
    # the continuation its first life would have drawn (the recovery
    # plane's replay-determinism contract, server/recovery.py).
    seed: int | None = None
    # The logits hook (POST /generate "return_logits"): keep, for every
    # generated position, the float32 logits row the sampler saw, and for
    # a model with experts the expert ids every computed position chose.
    return_logits: bool = False


class RequestState(enum.Enum):
    QUEUED = "queued"
    # Parked while the async KV plane (cache/kv_transfer.py) restores the
    # request's host-tier prefix into HBM; the engine keeps decoding and
    # re-queues the request when its pages land.
    RESTORING = "restoring"
    RUNNING = "running"
    FINISHED = "finished"


# The declared admission state machine — checked statically against
# every assignment/dispatch site in the package by meshcheck's protocol
# checker (analysis/protocol.py), the same way the lifecycle plane's
# _VALID_TRANSITIONS is. FINISHED is terminal (a resurrection is a NEW
# request, server/recovery.py); QUEUED re-entry covers both the
# restore-complete requeue and mid-decode preemption.
VALID_TRANSITIONS = {
    (RequestState.QUEUED, RequestState.RUNNING),      # dispatch
    (RequestState.QUEUED, RequestState.RESTORING),    # park for staged restore
    (RequestState.QUEUED, RequestState.FINISHED),     # cancel/shed pre-dispatch
    (RequestState.RESTORING, RequestState.QUEUED),    # restore landed: requeue
    (RequestState.RESTORING, RequestState.FINISHED),  # cancel/deadline mid-park
    (RequestState.RUNNING, RequestState.QUEUED),      # preempt (pool pressure)
    (RequestState.RUNNING, RequestState.FINISHED),    # stop/cap/cancel/handoff
}


@dataclass
class Request:
    prompt: np.ndarray  # int32 token ids
    sampling: SamplingParams = field(default_factory=SamplingParams)
    rid: int = field(default_factory=lambda: next(_rid_counter))

    # -- engine-managed state --
    state: RequestState = RequestState.QUEUED
    output_tokens: list[int] = field(default_factory=list)
    row: int = -1  # decode-batch row while RUNNING
    kv_len: int = 0  # tokens whose KV is in the pool
    prefix_len: int = 0  # tokens reused from the radix cache at prefill
    # Behind ``token_slots`` (the property below): a buffer that keeps
    # spare capacity past its first ``_n_slots`` entries, so a decode
    # step's append copies nothing of the row's earlier slots.
    _slots: np.ndarray = field(
        default_factory=lambda: np.empty(0, dtype=np.int32),
        repr=False, compare=False,
    )
    _n_slots: int = field(default=0, repr=False, compare=False)
    # Slots allocated by/for this request (whole pages; superset of the
    # tail of token_slots until handed to the tree or freed).
    own_slots: np.ndarray = field(
        default_factory=lambda: np.empty(0, dtype=np.int32)
    )
    lock_node: object = None  # TreeNode protected while RUNNING
    # ... and how many tokens from the root that node ENDS (a split by
    # another request leaves the lower half its identity and its end):
    # where ``Engine._publish`` starts, everything above being in place.
    lock_end: int = 0
    # ``token_slots[:canon_len]`` are the tree's own slots: the reused
    # prefix at admission, everything up to ``lock_end`` after a publish.
    canon_len: int = 0
    # A model whose layers carry a state beside the rows (state slots of
    # ``cache/kv_pool.py``; 0 = none, slot 0 is nobody's): the row's
    # working slot, the slot that keeps the state as of the last page
    # boundary a decode step completed (``state_boundary_len``: where; 0 =
    # none yet), the slot prefill resumes from (the tree's, borrowed), and
    # the snapshots a prefill wrote for the tree to take at the next
    # publish, ``[(position, slot)]``.
    state_slot: int = 0
    state_boundary: int = 0
    state_boundary_len: int = 0
    state_resume: int = 0
    state_snaps: list = field(default_factory=list)
    # ... and the slots of those the tree took while the request lives: its
    # own resume points until a decode step of its has completed a page
    # (``Engine._evict_states`` keeps them till then).
    state_handed: list = field(default_factory=list)
    # A model with a window group of rows: the pages of that group this
    # request counts on, for logical pages ``w_first ..`` of its sequence
    # in order (the tail of the snapshot it resumed from, then its own);
    # what left the window has been given back from the front.
    w_first: int = 0
    w_pages: list = field(default_factory=list)
    cancelled: bool = False  # aborted by Engine.cancel (output is partial)
    # Set when the engine step serving this request raised: the message
    # the runner fails the request with (server/http_frontend.py).
    error: str | None = None
    # Resume-admission (crash recovery, server/recovery.py): the last
    # ``resume_offset`` tokens of ``prompt`` are output the FIRST life of
    # this request already delivered to the client — replayed through
    # prefill (a near-pure cache hit against the replicated tree) but
    # never re-emitted: ``output_tokens`` holds only post-resume tokens,
    # so an SSE stream continues seamlessly from token k.
    resume_offset: int = 0

    # -- SLO control plane (radixmesh_tpu/slo/) --
    tenant: str = "default"  # rate-limit / fair-share accounting key
    ttft_deadline_s: float | None = None  # relative to submit_time
    e2e_deadline_s: float | None = None  # relative to submit_time
    admit_time: float = 0.0  # SLO queue → engine dispatch instant
    shed: bool = False  # refused or dropped by the control plane
    shed_reason: str = ""
    degradation_tier: int = 0  # tier in force when dispatched
    # Backlog cost retired from the controller (first token OR cancel
    # before one) — whichever side runs first flips it; see
    # OverloadController.note_retired.
    slo_retired: bool = False
    # Tree-based speculative drafting stays enabled only while it pays:
    # cleared the first time the tree has no continuation for this
    # request, so novel generations never re-walk the whole history
    # every launch (the walk is O(context)).
    tree_draft_ok: bool = True
    # Draft-ahead from the mesh (ROADMAP 1a′): the tree's
    # ``draft_ready_epoch`` value this request last peeked at. When a
    # PREFETCH fill or disk promotion lands a continuation AFTER that
    # (tree epoch > this), ``Engine._draft_for`` re-arms ``tree_draft_ok``
    # and peeks again — a remote/disk-resident hit drafts exactly like a
    # native one instead of staying latched off forever.
    draft_epoch: int = 0
    submit_time: float = 0.0
    first_token_time: float = 0.0
    # -- token timeline (radixmesh_tpu/obs/token_timeline.py) --
    # Monotonic stamp of the last emitted token (0 = none yet this
    # life): the inter-token-latency clock. Reset by Engine._preempt so
    # a requeued life's first token reads as TTFT, not a huge gap.
    last_token_time: float = 0.0
    # The logits hook (``SamplingParams.return_logits``): one float32 [V]
    # row per generated token, in order, and ``(first position, ids
    # [n, L, k])`` for each run of positions a launch computed (a prefix
    # served from the tree was computed by an earlier request: no ids).
    logit_rows: list = field(default_factory=list, repr=False, compare=False)
    expert_rows: list = field(default_factory=list, repr=False, compare=False)
    # Learned sparse attention: ``(position, token positions each full
    # layer selected for it [L_full, K], -1 = nothing)`` for every sampled
    # position whose launch selected (the context passed ``index_topk``).
    index_rows: list = field(default_factory=list, repr=False, compare=False)
    # Draft tokens the LAST speculative wave rejected for this row:
    # the spec_verify_miss stall attribution, consumed (zeroed) by
    # Engine._stall_cause.
    spec_miss: int = 0

    # -- request-flight tracing (radixmesh_tpu/obs/trace_plane.py) --
    # TraceContext when this request won the sampling coin flip, else
    # None; every span site guards with one `is not None` branch.
    trace: object = None
    # Stamped by Engine._preempt on requeue: the second admission's
    # queue-wait span starts HERE, not at the original submit — the
    # first life's prefill+decode must not render as queue wait.
    requeue_time: float = 0.0
    # Per-request progress wake (server/http_frontend.py): waiters block
    # on this instead of polling, so streamed first-token latency is not
    # quantized by a poll interval and idle waiters don't spin. Notified
    # by note_progress() — at once for the first token; for a decode
    # token once the engine's next launch is on the device
    # (Engine._flush_emit: behind the launch that follows the token's
    # step, never across a device wait or an idle runner) — and, via
    # __setattr__, at once on ANY transition to
    # FINISHED, so no finish site can strand a waiter.
    cond: threading.Condition = field(
        default_factory=threading.Condition, repr=False, compare=False
    )
    # Who is told of a new token in place of the waiters on ``cond``
    # (None: the waiters are). An SSE stream sets it to its emitter's
    # wake-up (server/http_frontend.py::_StreamEmitter): a launch of 64
    # rows then wakes one thread that writes 64 sockets, not 64 threads
    # that each queue for the interpreter while the engine thread wants it
    # back for the next launch. A finish always notifies ``cond``.
    on_progress: object = field(default=None, repr=False, compare=False)

    def note_progress(self) -> None:
        """Tell whoever follows this request that a new token landed:
        ``on_progress`` where one is set, else every thread blocked on
        ``cond`` (one uncontended lock round-trip). A reader finds
        ``output_tokens``, which may already hold tokens newer than
        the one this call announces: decode tokens are appended when their
        step's rows are advanced (``Engine._consume``) and announced from
        ``Engine._flush_emit``."""
        hook = self.on_progress
        if hook is not None:
            hook()
            return
        with self.cond:
            self.cond.notify_all()

    def __setattr__(self, name, value):
        object.__setattr__(self, name, value)
        if name == "state" and value is RequestState.FINISHED:
            # dict lookup, not attribute access: during dataclass
            # __init__ the state field is assigned before cond exists.
            cond = self.__dict__.get("cond")
            if cond is not None:
                with cond:
                    cond.notify_all()
            # Span-retire funnel (obs/attribution.py): EVERY finish path
            # — stop token, length cap, cancel, shed, drain, deadline —
            # assigns FINISHED exactly here, so this is the one place a
            # traced request's terminal ``request_done`` span (the phase
            # attributor's retire trigger) cannot be missed by a new
            # finish site. One `is not None` branch when untraced (the
            # PR 2 contract); the _retired guard keeps a double
            # transition from double-feeding the attributor.
            tr = self.__dict__.get("trace")
            if (
                tr is not None
                and self.__dict__.get("submit_time")
                and not self.__dict__.get("_retired")
            ):
                object.__setattr__(self, "_retired", True)
                tr.add(
                    "request_done",
                    self.submit_time,
                    time.monotonic() - self.submit_time,
                    cat="scheduler",
                    prompt_tokens=len(self.prompt),
                    output_tokens=len(self.output_tokens),
                    cancelled=bool(self.cancelled),
                    shed=bool(self.shed),
                )

    @property
    def token_slots(self) -> np.ndarray:
        """Slot index per token position ``[kv_len]``: canonical
        (tree-owned) slots over the reused prefix, this request's slots
        after it. A view of exactly that many entries: no reader sees the
        spare capacity. Assigning an array makes it the buffer as it is."""
        return self._slots[: self._n_slots]

    @token_slots.setter
    def token_slots(self, slots: np.ndarray) -> None:
        self._slots, self._n_slots = slots, len(slots)

    def reserve_slots(self, n: int) -> None:
        """Room for ``n`` more slots: one copy now (or at a doubling, should
        the row outgrow it), none at an append."""
        need = self._n_slots + n
        if need > len(self._slots):
            buf = np.empty(max(need, 2 * len(self._slots)), dtype=np.int32)
            buf[: self._n_slots] = self._slots[: self._n_slots]
            self._slots = buf

    def append_slot(self, slot: int) -> None:
        n = self._n_slots
        if n == len(self._slots):
            self.reserve_slots(1)
        self._slots[n] = slot
        self._n_slots = n + 1

    @property
    def next_token(self) -> int:
        """Token to feed on the next decode step."""
        return self.output_tokens[-1]

    @property
    def num_tokens(self) -> int:
        return len(self.prompt) + len(self.output_tokens)

    @property
    def generated(self) -> list[int]:
        return list(self.output_tokens)

    def is_finished_by(self, token: int) -> bool:
        return (
            token in self.sampling.stop_token_ids
            or len(self.output_tokens) >= self.sampling.max_new_tokens
        )

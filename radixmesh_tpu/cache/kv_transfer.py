"""Async KV-movement plane: every bulk KV copy in the system, off the hot path.

PRs 1–3 removed the scheduling, tracing, and telemetry stalls from the
serving loop; the last hot-path stall left standing was KV movement
itself: host→device restores ran inline inside admission
(``HierarchicalCache.match_and_load``), eviction write-back paid one
blocking device→host gather per tree node, and the disaggregated decode
worker placed a whole handoff packet at admission time. Disaggregated
serving systems (DistServe, Mooncake's transfer engine) show that hiding
exactly this class of movement behind compute is where the TTFT/TPOT
wins live. This module is the single owner of those copies — three lanes
over one staged executor:

- **restore** (host tier → HBM): admission splits into a non-blocking
  ``match_prefix`` plus a *staged* restore. The engine parks the request
  in the ``RESTORING`` admission state and keeps decoding; the plane's
  worker thread reads the host arena chunk-by-chunk and starts each
  chunk's host→device transfer (``jnp.asarray`` — async dispatch), and
  the engine applies the cheap pool scatters at its next ``pump()``.
  Only the engine thread ever touches ``pool.kv`` (the donated buffer is
  single-owner by design), so the worker stages *data*, never the pool.
- **write-back** (HBM → host tier): an eviction sweep records its nodes
  and dispatches ONE fused device gather for the whole sweep
  (``host_cache.py``); the blocking device→host materialization + arena
  memcopy run on the worker, off the engine loop.
- **handoff** (prefill → decode): the disagg receive path stages
  ``device_put`` per layer-block on the transport reader thread so
  decode-side placement overlaps the wire receive, and the prefill side
  can stream completed chunks through :meth:`submit_task` while later
  gathers are still materializing (``engine/disagg.py``).

Ordering contract (what makes the lanes composable): the worker queue is
FIFO *except* that write-back items take priority. A node restore can
only be enqueued after its write-back (``host_value`` is set when the
write-back ticket is created), so prioritizing write-backs can only move
an arena write *earlier* than a dependent arena read — never later.
``wait_host_ready()`` gives the synchronous fallback path the same
guarantee before it touches the arena directly.

Restores are also **predictive**: the router sends a fire-and-forget
``PREFETCH`` oplog (``cache/oplog.py``) when it routes a cache hit, and
the target engine funnels it through :meth:`note_hint` → a ticket with
no request attached. Hints are idempotent (pending nodes are joined, not
re-restored), never evict (allocation comes straight from the pool's
free list), never split tree nodes, and are droppable at every stage.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from radixmesh_tpu.obs.metrics import TRANSFER_SECONDS_BUCKETS, get_registry
from radixmesh_tpu.obs.trace_plane import get_recorder
from radixmesh_tpu.utils.logging import get_logger

__all__ = ["KVTransferPlane", "RestoreTicket", "kv_token_bytes"]

_LANES = ("restore", "writeback", "handoff", "spill")


def kv_token_bytes(pool) -> int:
    """Wire/HBM bytes per token-slot of ``pool`` (every plane of the row,
    all layers, plus quant scales when present) — the bytes-counter unit
    for every lane."""
    import jax.numpy as jnp

    per = pool.planes * pool.num_layers * pool.num_kv_heads * pool.head_dim
    n = per * jnp.dtype(pool.dtype).itemsize
    if pool.quant is not None:
        n += 2 * pool.num_layers * pool.num_kv_heads * 4  # f32 scales
    return int(n)


@dataclass
class _RestoreUnit:
    """One host- or disk-resident tree node's restore. Shared between
    tickets (a prefetch hint and a real admission racing on the same
    prefix join the same unit instead of double-restoring). The source
    is EITHER the host arena (``host_slots``) or a durable disk extent
    (``extent``, read + checksum-verified on the worker — a corrupt
    extent fails the unit, never installs)."""

    node: object  # TreeNode
    host_slots: np.ndarray
    dev_slots: np.ndarray
    extent: object = None  # kv_tier.ExtentRef for disk-source units
    n_tokens: int = 0
    refs: int = 0  # tickets referencing this unit
    applied: bool = False
    attached: bool = False  # node.value was actually installed
    locked: bool = False  # holds an eviction lock until refs drain
    failed: bool = False  # worker staging failed: never install
    tickets: list = field(default_factory=list)


class _ExtentUnreadable(Exception):
    """A disk extent failed verification (torn/corrupt/missing): the
    unit degrades — expected under crash/corruption drills, so it logs
    a warning, not a traceback."""


class RestoreTicket:
    """A parked restore: the ordered units one match's host extension
    needs. ``done`` when every unit has been applied (installed into the
    tree, or skipped because it raced/split/detached — the request then
    simply re-matches a shorter hit)."""

    __slots__ = ("units", "anchor", "t0", "auto_release", "released", "tokens")

    def __init__(self, units, anchor, auto_release: bool):
        self.units = units
        self.anchor = anchor
        self.t0 = time.monotonic()
        self.auto_release = auto_release
        self.released = False
        self.tokens = int(sum(u.n_tokens for u in units))

    @property
    def done(self) -> bool:
        return all(u.applied for u in self.units)


@dataclass
class _WritebackTicket:
    """One eviction sweep's fused device→host copy: the gather was
    dispatched on the engine thread (device-side async); the worker
    materializes it and writes the arena."""

    kv: object  # jax.Array [2, L, n_padded, H, D] (pool dtype)
    scales: object | None
    n: int
    host_slots: np.ndarray
    host: object = None  # HostKVStore the arena write targets
    failed: bool = False  # materialization raised: arena bytes untrusted
    done: threading.Event = field(default_factory=threading.Event)


class KVTransferPlane:
    """The staged executor behind all three lanes.

    Threading model: the ENGINE thread owns the tree and ``pool.kv`` —
    it begins restores, dispatches write-back gathers, and applies
    staged scatters at :meth:`pump`. The WORKER thread owns only host
    memory and fresh device arrays (arena reads/writes, ``np.asarray``
    materialization, ``jnp.asarray`` staging, handoff pack/send tasks).
    Transport reader threads may enqueue hints and handoff staging but
    never touch the tree or the pool.
    """

    def __init__(
        self,
        *,
        chunk_tokens: int = 512,
        stage_depth: int = 16,
        max_hints: int = 64,
        name: str = "engine",
    ):
        if chunk_tokens <= 0:
            raise ValueError("chunk_tokens must be positive")
        self.chunk_tokens = int(chunk_tokens)
        self.log = get_logger("kvplane")
        self._lock = threading.Lock()
        # Worker input lanes: write-backs drain first (see module
        # docstring's ordering contract); restores and handoff tasks
        # share the data lane FIFO.
        self._wb_q: deque[_WritebackTicket] = deque()
        self._data_q: deque[tuple] = deque()
        self._work_evt = threading.Event()
        # Double-buffered staging: the worker may run at most
        # ``stage_depth`` chunks ahead of the engine's pump — enough to
        # hide the arena read + H2D latency, bounded so a stalled engine
        # can't accumulate a pool-sized backlog of staged device arrays.
        self._stage_sem = threading.Semaphore(stage_depth)
        self._staged: deque[tuple] = deque()
        self._progress = threading.Event()
        # node.id → in-flight _RestoreUnit (dedupe/join + the host-tier
        # eviction shield — host_cache._evict_host skips pending nodes).
        self._pending_nodes: dict[int, _RestoreUnit] = {}
        # node.id → node with a disk spill in flight (the worker reads
        # its arena slots, so eviction/destage must leave them alone
        # until the extent commits at pump).
        self._pending_spills: dict[int, object] = {}
        # Worker-finished spills awaiting their engine-thread commit
        # (node.disk_value installation happens at pump — only the
        # engine thread mutates the tree).
        self._spilled: deque[tuple] = deque()
        # Arena slot ids whose write-back materialization FAILED: the
        # bytes there were never written, so any node still pointing at
        # them must drop its host copy instead of restoring garbage.
        # Checked (and cleared) lazily on the engine thread via
        # host_slots_ok() before every restore of a node.
        self._poisoned_host: set[int] = set()
        self._tickets: list[RestoreTicket] = []
        self._hints: deque[np.ndarray] = deque(maxlen=max_hints)
        self._stop = threading.Event()
        # Test seam: when set, the worker blocks here before staging each
        # restore chunk — deterministic "restore in flight" windows.
        self.stage_barrier: threading.Event | None = None
        self.hints_seen = 0
        self.hints_joined = 0  # admissions that found a hint's restore in flight

        reg = get_registry()
        lbl = {"plane": name}
        bytes_total = reg.counter(
            "radixmesh_kv_transfer_bytes_total",
            "bulk KV bytes moved by the async plane, by lane",
            ("plane", "lane"),
        )
        seconds = reg.histogram(
            "radixmesh_kv_transfer_seconds",
            "blocking-side duration of one plane operation (arena "
            "read/write, gather materialization, handoff stage), by lane",
            ("plane", "lane"),
            buckets=TRANSFER_SECONDS_BUCKETS,
        )
        depth = reg.gauge(
            "radixmesh_kv_transfer_inflight_tokens",
            "tokens currently queued/staged in the plane, by lane "
            "(the lane queue-depth signal)",
            ("plane", "lane"),
        )
        self._m_bytes = {ln: bytes_total.labels(lane=ln, **lbl) for ln in _LANES}
        self._m_seconds = {ln: seconds.labels(lane=ln, **lbl) for ln in _LANES}
        self._m_depth = {ln: depth.labels(lane=ln, **lbl) for ln in _LANES}
        self._m_restored = reg.counter(
            "radixmesh_kv_transfer_restored_tokens_total",
            "host-tier tokens restored to HBM by the staged lane",
            ("plane",),
        ).labels(**lbl)
        self._m_hints = reg.counter(
            "radixmesh_kv_transfer_prefetch_hints_total",
            "prefetch hints by outcome (started = restore launched, "
            "noop = already device-resident/unknown, joined = an "
            "admission found the hinted restore already in flight, "
            "dropped = hint queue overflow, draining = discarded "
            "because the node is mid-drain)",
            ("plane", "outcome"),
        )
        self._m_hint = {
            o: self._m_hints.labels(outcome=o, **lbl)
            for o in ("started", "noop", "joined", "dropped", "draining")
        }
        self._trace_lane = f"kv:{name}"
        self._worker = threading.Thread(
            target=self._run, daemon=True, name="kv-transfer"
        )
        self._worker.start()

    # ------------------------------------------------------------------
    # lifecycle / introspection
    # ------------------------------------------------------------------

    def close(self) -> None:
        self._stop.set()
        self._work_evt.set()
        self._worker.join(timeout=2)

    def idle(self) -> bool:
        with self._lock:
            return (
                not self._wb_q
                and not self._data_q
                and not self._staged
                and not self._pending_nodes
                and not self._pending_spills
                and not self._spilled
                and not self._tickets
                and not self._hints
            )

    def stats(self) -> dict:
        """Programmatic plane state for ``/debug/state``."""
        with self._lock:
            return {
                "chunk_tokens": self.chunk_tokens,
                "writebacks_queued": len(self._wb_q),
                "restores_queued": len(self._data_q),
                "staged_chunks": len(self._staged),
                "pending_restore_nodes": len(self._pending_nodes),
                "pending_spills": len(self._pending_spills),
                "spills_uncommitted": len(self._spilled),
                "active_tickets": len(self._tickets),
                "hints_queued": len(self._hints),
                "hints_seen": self.hints_seen,
                "hints_joined": self.hints_joined,
            }

    def wait_progress(self, timeout: float = 0.002) -> None:
        """Engine idle-wait: block briefly until the worker stages or
        completes something (avoids a busy spin when the only live work
        is an in-flight restore)."""
        self._progress.wait(timeout)
        self._progress.clear()

    # ------------------------------------------------------------------
    # restore lane (engine thread: begin/pump/finish; worker: staging)
    # ------------------------------------------------------------------

    def is_pending(self, node) -> bool:
        with self._lock:
            return node.id in self._pending_nodes

    def has_engine_work(self) -> bool:
        """True while the plane holds work only the ENGINE thread can
        advance: unconverted hints, staged chunks awaiting their pool
        scatter, or open tickets awaiting release. Folded into
        ``Engine.has_work`` so an otherwise-idle scheduler keeps pumping
        — a PREFETCH hint landing on an idle node must convert NOW (the
        head start is the feature), and a drained engine must not strand
        a hint restore's staged chunks and eviction locks."""
        with self._lock:
            return bool(
                self._hints or self._staged or self._tickets or self._spilled
            )

    def host_slots_ok(self, slots) -> bool:
        """False if any of ``slots`` belongs to a FAILED write-back (its
        arena bytes were never written). Restore paths call this before
        reading the arena; a False answer means the caller must drop the
        node's host copy (``HierarchicalCache._drop_poisoned_host``)
        rather than restore garbage. Slots reported bad are retired from
        the poison set — the caller's drop frees them for reuse, after
        which fresh writes make them trustworthy again."""
        # Racy empty-read is a pure fast path: the sync caller ran
        # wait_host_ready() first (the barrier drains every queued
        # write-back and fails on poison), new poison can only be
        # enqueued by this same engine thread's next sweep, and a
        # non-empty set re-checks under the lock. (No longer needs a
        # guarded-by suppression: _host_slots_poisoned's worker-side
        # read makes the off-lock read a convention, not an outlier.)
        if not self._poisoned_host:
            return True
        with self._lock:
            return self._host_slots_ok_locked(slots)

    def _host_slots_ok_locked(self, slots) -> bool:
        """``host_slots_ok`` body for callers already holding the plane
        lock (``begin_restore``'s unit loop — the lock is NOT reentrant,
        so re-acquiring would deadlock the engine thread the first time
        a write-back ever failed)."""
        if not self._poisoned_host:
            return True
        bad = [int(s) for s in slots if int(s) in self._poisoned_host]
        if not bad:
            return True
        self._poisoned_host.difference_update(bad)
        return False

    def begin_restore(self, tree, match, alloc, auto_release: bool = False):
        """Start (or join) a staged restore of ``match``'s host-tier
        extension. ``alloc(n) -> slots | None`` supplies device slots —
        the engine passes its evict-and-retry allocator, prefetch hints
        pass the pool's plain ``alloc`` (hints must never evict). Units
        already in flight for a node are JOINED, not duplicated — the
        idempotence that makes duplicate hints and hint/admission races
        free. Returns a :class:`RestoreTicket`, or None when there is
        nothing restorable (all device-resident, or no room)."""
        anchor = (
            match.last_node
            if match.last_node is not None and match.last_node is not tree.root
            else None
        )
        # Shield the DEVICE prefix BEFORE any allocation: ``alloc`` may
        # evict for room, and an unlocked anchor (a device leaf whose
        # only descendants are the host nodes being restored) is itself
        # an eviction candidate — its removal would strand and clear the
        # very host subtree this restore is reading (the same hazard the
        # synchronous path locks against first, host_cache.py).
        if anchor is not None:
            tree.inc_lock_ref(anchor)
        units: list[_RestoreUnit] = []
        new_units: list[_RestoreUnit] = []
        joined_hint = False
        disk_tier = getattr(tree, "disk", None)
        with self._lock:
            for node in (
                match.restorable_nodes()
                if hasattr(match, "restorable_nodes")
                else match.host_nodes
            ):
                u = self._pending_nodes.get(node.id)
                if u is not None:
                    u.refs += 1
                    units.append(u)
                    # "Joined a hint" only when the in-flight unit was
                    # started by a PREFETCH ticket — two admissions
                    # sharing a prefix are dedupe, not prefetch credit.
                    joined_hint |= any(t.auto_release for t in u.tickets)
                    continue
                if node.value is not None:
                    break  # raced: already restored
                if node.host_value is not None:
                    if not self._host_slots_ok_locked(node.host_value):
                        # Failed write-back: the arena bytes were never
                        # written — retire the host copy (the check
                        # consumed the poison entry, so the drop must
                        # happen here) and stop; the hit is simply
                        # shorter.
                        tree._drop_poisoned_host(node)
                        break
                    host_slots = np.asarray(node.host_value, dtype=np.int32)
                    n = len(host_slots)
                    dev = alloc(n)
                    if dev is None:
                        break  # no room: the hit is simply shorter
                    u = _RestoreUnit(
                        node, host_slots, dev[:n], n_tokens=n, refs=1
                    )
                elif node.disk_value is not None and disk_tier is not None:
                    # Disk-source unit: the worker reads + verifies the
                    # extent; the checksum is the serve gate.
                    n = len(node.disk_value)
                    dev = alloc(n)
                    if dev is None:
                        break
                    u = _RestoreUnit(
                        node,
                        np.empty(0, dtype=np.int32),
                        dev[:n],
                        extent=node.disk_value,
                        n_tokens=n,
                        refs=1,
                    )
                else:
                    break  # detached mid-walk / tier unreachable
                self._pending_nodes[node.id] = u
                units.append(u)
                new_units.append(u)
            if not units:
                if anchor is not None:
                    tree.dec_lock_ref(anchor)
                return None
            ticket = RestoreTicket(units, anchor=anchor, auto_release=auto_release)
            for u in units:
                u.tickets.append(ticket)
            self._tickets.append(ticket)
            for u in new_units:
                self._data_q.append(("restore", u, tree))
            self._m_depth["restore"].inc(sum(u.n_tokens for u in new_units))
        if joined_hint:
            with self._lock:
                self.hints_joined += 1
            self._m_hint["joined"].inc()
        self._work_evt.set()
        return ticket

    def pump(self, tree) -> bool:
        """ENGINE-THREAD drain of staged restore chunks: dispatch each
        chunk's pool scatter (the only place the plane touches
        ``pool.kv``), install fully-restored nodes into the tree, and
        release completed auto-release tickets. Returns True when any
        progress was made."""
        progress = False
        while True:
            with self._lock:
                if not self._staged:
                    break
                item = self._staged.popleft()
            self._stage_sem.release()
            unit, last, dev_chunk, kv, scales, tree_ref = item
            pool = tree_ref.pool
            if len(dev_chunk):  # empty = a failed unit's poison sentinel
                if scales is not None:
                    pool.write_raw(dev_chunk, kv, scales)
                else:
                    pool.write(dev_chunk, kv[0], kv[1])
            self._m_depth["restore"].dec(len(dev_chunk))
            if last:
                self._apply_unit(tree_ref, unit)
            progress = True
        # Commit worker-finished spills (only the engine thread mutates
        # the tree): install the extent ref when the node is unchanged;
        # a raced node (split/removed/re-sliced since submit) retires
        # the extent instead — the data was valid for the OLD segment,
        # but the ref must map the node exactly.
        while True:
            with self._lock:
                if not self._spilled:
                    break
                node, slots, ref, cause = self._spilled.popleft()
                self._pending_spills.pop(node.id, None)
            disk = getattr(tree, "disk", None)
            unchanged = (
                node.host_value is not None
                and len(node.host_value) == len(slots)
                and np.array_equal(node.host_value, slots)
            )
            if ref is not None:
                if unchanged and node.disk_value is None:
                    node.disk_value = ref
                elif disk is not None:
                    disk.retire(ref)
            elif cause == "poisoned" and unchanged:
                # The spill source itself was a failed write-back's
                # arena slots: the host copy is garbage either way.
                # Retire the poison entries FIRST — the drop frees the
                # slots for reuse, and a stale entry would wrongly
                # condemn the next tenant's freshly-written host copy
                # (the "fresh writes make them trustworthy again"
                # invariant).
                with self._lock:
                    self._poisoned_host.difference_update(
                        int(s) for s in slots
                    )
                tree._drop_poisoned_host(node)
            progress = True
        # Auto-release tickets (prefetch hints, cancelled requests) are
        # finished here; engine-owned tickets are finished by the engine
        # when it re-queues the parked request.
        done_auto = []
        with self._lock:
            for t in self._tickets:
                if t.auto_release and t.done and not t.released:
                    done_auto.append(t)
        for t in done_auto:
            self.finish_ticket(tree, t)
            progress = True
        return progress

    def _apply_unit(self, tree, unit: _RestoreUnit) -> None:
        """Install one fully-scattered unit (engine thread). Nodes that
        were split, detached, or sync-restored since the unit was
        created are skipped and their device slots returned — the
        waiting request just re-matches a shorter hit."""
        node = unit.node
        with self._lock:
            self._pending_nodes.pop(node.id, None)
        if unit.extent is not None:
            raced = (
                unit.failed
                or node.value is not None
                or node.disk_value is not unit.extent
            )
            if unit.failed and node.disk_value is unit.extent:
                disk = getattr(tree, "disk", None)
                if disk is None or not disk.has(unit.extent):
                    # The extent failed VERIFICATION (corrupt/torn —
                    # the tier already dropped the file): clear the
                    # dangling ref so the node degrades to a recompute
                    # instead of re-attempting a restore that can never
                    # verify. A TRANSIENT failure (H2D allocation,
                    # scatter error) leaves the intact extent attached
                    # for the next attempt.
                    node.disk_value = None
        else:
            raced = (
                unit.failed
                or node.host_value is None
                or node.value is not None
                or len(node.host_value) != len(unit.host_slots)
                or not np.array_equal(node.host_value, unit.host_slots)
            )
        if raced:
            tree.pool.free(unit.dev_slots)
        else:
            node.value = unit.dev_slots
            tree.evictable_size_ += len(node.key)
            # Hold the restored node (and through the lock-walk its
            # ancestors) until every ticket that needs it has finished:
            # a just-restored mid-chain node must not be re-evicted
            # before the chunks below it land (device residency stays
            # prefix-closed).
            tree.inc_lock_ref(node)
            unit.attached = True
            unit.locked = True
            n = unit.n_tokens
            self._m_restored.inc(n)
            self._m_bytes["restore"].inc(n * kv_token_bytes(tree.pool))
            if unit.extent is not None:
                # Tier promote accounting (radixmesh_kv_tier_*): the
                # disk copy is KEPT — re-demotion of this node is free.
                disk = getattr(tree, "disk", None)
                if disk is not None:
                    disk.note_promote(unit.extent)
            # Draft-ahead (ROADMAP 1a′): a PREFETCH fill or disk
            # promotion just attached continuation KV this node did not
            # compute natively — bump the tree's draft epoch so
            # Engine._draft_for re-arms tree drafting for in-flight
            # requests whose earlier peek predated this install.
            note = getattr(tree, "note_draft_ready", None)
            if note is not None:
                note()
            # Keep the hicache restore-token series continuous: existing
            # dashboards alert on it, and "plane on" must read as MORE
            # restore activity there, not zero. (The restore-STALL
            # histogram legitimately stays flat — there IS no stall.)
            m = getattr(tree, "_m_restore", None)
            if m is not None:
                m.inc(n)
        unit.applied = True
        rec = get_recorder()
        if rec.enabled:
            rec.event(
                self._trace_lane, "kv_restore", time.monotonic(), 0.0,
                cat="kv", tokens=int(unit.n_tokens),
                source="disk" if unit.extent is not None else "host",
                attached=bool(unit.attached),
            )
        self._progress.set()

    def finish_ticket(self, tree, ticket: RestoreTicket) -> None:
        """Release a DONE ticket's eviction shields (engine thread).
        Units shared with still-running tickets stay locked until the
        last reference drains."""
        if ticket.released:
            return
        ticket.released = True
        with self._lock:
            try:
                self._tickets.remove(ticket)
            except ValueError:
                pass
        if ticket.anchor is not None:
            tree.dec_lock_ref(ticket.anchor)
        for u in ticket.units:
            u.refs -= 1
            if u.refs <= 0 and u.locked:
                u.locked = False
                tree.dec_lock_ref(u.node)

    # ------------------------------------------------------------------
    # write-back lane
    # ------------------------------------------------------------------

    def submit_writeback(self, pool, host, slots: np.ndarray, host_slots: np.ndarray):
        """ENGINE THREAD: dispatch one fused gather for an eviction
        sweep (device-side async — the sweep's slots are captured from
        the current pool buffer before any later scatter can recycle
        them) and queue the blocking materialization + arena write for
        the worker."""
        from radixmesh_tpu.cache.kv_pool import _pad_to_bucket

        slots = np.asarray(slots, dtype=np.int32)
        n = len(slots)
        if n == 0:
            return None
        padded, _ = _pad_to_bucket(slots, [], [])
        kv, scales = pool.gather_raw(padded)
        ticket = _WritebackTicket(
            kv=kv, scales=scales, n=n,
            host_slots=np.asarray(host_slots, dtype=np.int32), host=host,
        )
        with self._lock:
            self._wb_q.append(ticket)
            self._m_depth["writeback"].inc(n)
        self._work_evt.set()
        return ticket

    def wait_host_ready(self, timeout: float = 30.0) -> bool:
        """Block until every write-back queued so far has landed in the
        arena — the synchronous restore fallback's read barrier. Returns
        False on timeout OR if an awaited write-back FAILED (its arena
        bytes are untrusted); callers must then serve the shorter
        device-only hit instead of reading the arena. The staged restore
        path never needs this (worker FIFO + write-back priority give
        the same guarantee for free)."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                pending = list(self._wb_q)
            if not pending:
                return True
            self._work_evt.set()
            if not pending[-1].done.wait(max(0.0, deadline - time.monotonic())):
                return False
            if any(t.failed for t in pending):
                return False

    # ------------------------------------------------------------------
    # spill lane (host tier → durable disk extents, cache/kv_tier.py)
    # ------------------------------------------------------------------

    def spill_pending(self, node) -> bool:
        """True while a disk spill of ``node`` is in flight (its arena
        slots must not be freed or re-destaged until the extent
        commits)."""
        with self._lock:
            return node.id in self._pending_spills

    def submit_spill(self, tree, node, prefix_tokens) -> bool:
        """ENGINE THREAD: queue one host-resident node's demotion to a
        disk extent. The worker reads the arena (after the write-back
        priority drain — so the bytes are the landed ones) and writes
        the checksummed, fsynced extent; the engine's next :meth:`pump`
        installs ``node.disk_value``. Returns False when the node is
        already being spilled/restored or holds no host copy."""
        disk = getattr(tree, "disk", None)
        if disk is None or node.host_value is None:
            return False
        with self._lock:
            if (
                node.id in self._pending_spills
                or node.id in self._pending_nodes
            ):
                return False
            slots = np.asarray(node.host_value, dtype=np.int32).copy()
            seg = np.asarray(node.key, dtype=np.int32).copy()
            self._pending_spills[node.id] = node
            self._data_q.append(
                (
                    "spill",
                    node,
                    np.asarray(prefix_tokens, dtype=np.int32).copy(),
                    seg,
                    slots,
                    tree,
                )
            )
            self._m_depth["spill"].inc(len(slots))
        self._work_evt.set()
        return True

    def _host_slots_poisoned(self, slots) -> bool:
        """Read-only poison check (worker): unlike ``host_slots_ok``
        this does NOT consume the poison entries — the restore path
        still owns the retire-on-read contract. The unlocked empty-read
        fast path shares host_slots_ok's justification."""
        if not self._poisoned_host:
            return False
        with self._lock:
            return any(int(s) in self._poisoned_host for s in slots)

    def _run_spill(self, item) -> None:
        """WORKER: one queued spill — arena read + extent write+fsync.
        Every outcome (committed ref, poisoned source, I/O failure)
        reports back through ``_spilled`` for the engine-thread commit."""
        _, node, prefix, seg, slots, tree = item
        t0 = time.monotonic()
        ref = None
        cause = None
        try:
            if self._host_slots_poisoned(slots):
                cause = "poisoned"
            else:
                kv, scales = tree.host.read(slots)
                ref = tree.disk.write_extent(prefix, seg, kv, scales)
                if ref is None:
                    cause = "io"
        except Exception:  # noqa: BLE001 — a failed spill must not kill the lane
            self.log.exception("disk spill failed; node stays volatile")
            cause = "error"
        dur = time.monotonic() - t0
        self._m_seconds["spill"].observe(dur)
        if ref is not None:
            self._m_bytes["spill"].inc(ref.nbytes)
            rec = get_recorder()
            if rec.enabled:
                rec.event(
                    self._trace_lane, "kv_spill", t0, dur, cat="kv",
                    tokens=int(len(slots)),
                )
        with self._lock:
            self._m_depth["spill"].dec(len(slots))
            self._spilled.append((node, slots, ref, cause))
        tree.disk.drain_retired()
        self._progress.set()

    def spills_idle(self) -> bool:
        """True when no spill is queued, in flight, or awaiting its
        engine-thread commit."""
        with self._lock:
            if self._pending_spills or self._spilled:
                return False
            return not any(it[0] == "spill" for it in self._data_q)

    # ------------------------------------------------------------------
    # handoff lane (disagg pack/send pipelining)
    # ------------------------------------------------------------------

    def submit_task(self, fn) -> None:
        """Queue a handoff-lane closure (gather materialization + pack +
        send for one streamed chunk) on the worker, FIFO with restores."""
        with self._lock:
            self._data_q.append(("task", fn))
        self._work_evt.set()

    def note_handoff(self, n_tokens: int, pool, dur: float) -> None:
        """Account one staged handoff block (called from whichever
        thread staged it — disagg reader threads included)."""
        self._m_bytes["handoff"].inc(n_tokens * kv_token_bytes(pool))
        self._m_seconds["handoff"].observe(dur)
        rec = get_recorder()
        if rec.enabled:
            rec.event(
                self._trace_lane, "kv_handoff_stage",
                time.monotonic() - dur, dur, cat="kv",
                tokens=int(n_tokens),
            )

    # ------------------------------------------------------------------
    # prefetch hints
    # ------------------------------------------------------------------

    def note_hint(self, key: np.ndarray) -> None:
        """Record a PREFETCH hint (any thread — the mesh receive path
        calls this on its transport reader). Bounded drop-oldest: a hint
        is a cache warm-up, losing one costs a restore overlap, never
        correctness."""
        with self._lock:
            self.hints_seen += 1
            if len(self._hints) == self._hints.maxlen:
                self._m_hint["dropped"].inc()
            self._hints.append(np.asarray(key, dtype=np.int32))
        self._progress.set()

    def take_hints(self) -> list[np.ndarray]:
        with self._lock:
            out = list(self._hints)
            self._hints.clear()
        return out

    def count_hint(self, outcome: str) -> None:
        self._m_hint[outcome].inc()

    # ------------------------------------------------------------------
    # worker
    # ------------------------------------------------------------------

    def _take_wb(self) -> _WritebackTicket | None:
        with self._lock:
            return self._wb_q[0] if self._wb_q else None

    def _drain_writebacks(self) -> bool:
        """Process every queued write-back (priority lane). Runs between
        restore chunks too, so a long restore cannot delay the arena
        writes a fallback reader may be waiting on."""
        did = False
        while not self._stop.is_set():
            ticket = self._take_wb()
            if ticket is None:
                return did
            t0 = time.monotonic()
            try:
                kv = np.asarray(ticket.kv)[:, :, : ticket.n]
                scales = (
                    None
                    if ticket.scales is None
                    else np.asarray(ticket.scales)[:, :, : ticket.n]
                )
                self._host_write(ticket, kv, scales)
                dur = time.monotonic() - t0
                self._m_seconds["writeback"].observe(dur)
                self._m_bytes["writeback"].inc(
                    kv.nbytes + (0 if scales is None else scales.nbytes)
                )
                rec = get_recorder()
                if rec.enabled:
                    rec.event(
                        self._trace_lane, "kv_writeback", t0, dur, cat="kv",
                        tokens=int(ticket.n),
                    )
            except Exception:  # noqa: BLE001 — one bad sweep must not kill the lane
                # The ticket is retired FAILED (done still fires so
                # wait_host_ready callers don't hang) — affected arena
                # slots may hold stale bytes, which the synchronous
                # fallback's failed-barrier check treats as unreadable.
                self.log.exception("write-back materialization failed")
                ticket.failed = True
                with self._lock:
                    self._poisoned_host.update(
                        int(s) for s in ticket.host_slots
                    )
            with self._lock:
                if self._wb_q and self._wb_q[0] is ticket:
                    self._wb_q.popleft()
                self._m_depth["writeback"].dec(ticket.n)
            ticket.done.set()
            self._progress.set()
            did = True
        return did

    def _host_write(self, ticket: _WritebackTicket, kv, scales) -> None:
        ticket.host.write(ticket.host_slots, kv, scales)

    def _run(self) -> None:
        import jax.numpy as jnp

        while not self._stop.is_set():
            if self._drain_writebacks():
                continue
            with self._lock:
                item = self._data_q.popleft() if self._data_q else None
            if item is None:
                self._work_evt.wait(timeout=0.1)
                self._work_evt.clear()
                continue
            if item[0] == "task":
                try:
                    item[1]()
                except Exception:  # noqa: BLE001 — a failed send must not kill the lane
                    self.log.exception("handoff task failed")
                continue
            if item[0] == "spill":
                self._run_spill(item)
                continue
            _, unit, tree = item
            host = tree.host
            n = unit.n_tokens
            n_chunks = max(1, -(-n // self.chunk_tokens))
            t0 = time.monotonic()
            staged_upto = 0
            try:
                disk_kv = disk_scales = None
                if unit.extent is not None:
                    # Disk-source unit: ONE verified extent read up
                    # front (checksum is the serve gate — a torn or
                    # flipped extent returns None and the unit degrades
                    # below, never installing a byte of it).
                    payload = tree.disk.read_extent(unit.extent)
                    if payload is None:
                        raise _ExtentUnreadable(unit.extent.path)
                    disk_kv, disk_scales = payload
                for ci in range(n_chunks):
                    # Between chunks: write-backs first (priority), then
                    # the bounded staging window (pump releases slots).
                    self._drain_writebacks()
                    if self.stage_barrier is not None:
                        self.stage_barrier.wait(timeout=10.0)
                    while not self._stop.is_set():
                        if self._stage_sem.acquire(timeout=0.05):
                            break
                        self._drain_writebacks()
                    if self._stop.is_set():
                        return
                    lo = ci * self.chunk_tokens
                    hi = min(n, (ci + 1) * self.chunk_tokens)
                    if unit.extent is not None:
                        kv_np = disk_kv[:, :, lo:hi]
                        scale_np = (
                            None
                            if disk_scales is None
                            else disk_scales[:, :, lo:hi]
                        )
                    else:
                        kv_np, scale_np = host.read(unit.host_slots[lo:hi])
                    # jnp.asarray starts the H2D transfer NOW (async
                    # dispatch); the engine's pump only pays the scatter.
                    kv = jnp.asarray(kv_np)
                    scales = None if scale_np is None else jnp.asarray(scale_np)
                    with self._lock:
                        self._staged.append(
                            (unit, hi == n, unit.dev_slots[lo:hi], kv, scales, tree)
                        )
                    staged_upto = hi
                    self._progress.set()
            except Exception as e:  # noqa: BLE001 — a failed stage must not wedge the ticket
                # Mark the unit poisoned and hand it to the pump as its
                # final "chunk": the engine applies it as raced (slots
                # freed, node left in its source tier — or degraded out
                # of it for an unreadable extent — request re-queued
                # with a shorter hit) instead of parking forever; no
                # partially-written node is ever installed.
                if isinstance(e, _ExtentUnreadable):
                    self.log.warning(
                        "disk restore degraded: %s failed verification", e
                    )
                else:
                    self.log.exception("restore staging failed; degrading unit")
                unit.failed = True
                self._m_depth["restore"].dec(n - staged_upto)
                with self._lock:
                    self._staged.append(
                        (unit, True, unit.dev_slots[:0], None, None, tree)
                    )
                self._progress.set()
            disk = getattr(tree, "disk", None)
            if disk is not None:
                disk.drain_retired()
            self._m_seconds["restore"].observe(time.monotonic() - t0)

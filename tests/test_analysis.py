"""meshcheck: the AST-based static-analysis plane's quick gate.

``test_tree_is_clean`` IS the CI gate: every checker over every product
file, zero unsuppressed findings. The rest of the file proves the gate
means something — each positive-control fixture (a deliberately broken
mini package tree under ``tests/fixtures/analysis/``) must trip its
checker with the right invariant-id and file:line, and the
justification-comment grammar must suppress exactly what it names,
flag what it fails to justify, and rot-proof itself (stale
suppressions are findings).
"""

from __future__ import annotations

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from radixmesh_tpu.analysis import all_checkers
from radixmesh_tpu.analysis import report as analysis_report
from radixmesh_tpu.analysis.controls import (
    default_fixtures_root,
    run_positive_controls,
)
from radixmesh_tpu.analysis.core import SourceIndex, run_checkers

pytestmark = pytest.mark.quick

_REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# THE gate
# ---------------------------------------------------------------------------

def test_tree_is_clean():
    """Every checker, every product file, zero unsuppressed findings.
    (Suppression requires an in-source justification comment; a stale
    or malformed one is itself a finding, so this single assertion also
    pins the excuse ledger.)"""
    from radixmesh_tpu.analysis import check_tree

    result = check_tree()
    assert result.clean, "\n" + result.pretty()


# ---------------------------------------------------------------------------
# positive controls: the checkers still SEE the seeded bug classes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def controls():
    out = run_positive_controls()
    assert out, "no positive-control fixtures found under tests/fixtures/analysis"
    return out


def _tripped(controls, fixture, invariant):
    hits = [
        c for c in controls
        if c.fixture == fixture and c.invariant == invariant
    ]
    assert hits, f"no seeded marker for {invariant} in fixture {fixture!r}"
    missed = [c for c in hits if not c.tripped]
    assert not missed, (
        f"checker went blind: {[(c.file, c.line, c.invariant) for c in missed]}"
    )
    return hits


class TestPositiveControls:
    def test_all_controls_tripped(self, controls):
        missed = [c for c in controls if not c.tripped]
        assert not missed, [
            f"{c.fixture}: {c.invariant} at {c.file}:{c.line}" for c in missed
        ]

    def test_seeded_deadlock_cycle(self, controls):
        """The helper-nested lock cycle — B→A lives behind a call, which
        no grep can see — trips with file:line on the cycle edge."""
        hits = _tripped(controls, "lock_cycle", "lock-order-cycle")
        assert hits[0].file == "engine/engine.py"
        assert hits[0].line > 0

    def test_seeded_aliased_writers(self, controls):
        """Aliased lifecycle write, aliased heat counter, private
        OwnershipMap construction + owner-set poke."""
        _tripped(controls, "single_writer_alias", "single-writer-lifecycle")
        _tripped(controls, "single_writer_alias", "single-writer-heat")
        hits = _tripped(
            controls, "single_writer_alias", "single-writer-ownership"
        )
        assert {c.line for c in hits} == {8, 13}  # construction AND poke

    def test_seeded_override_second_writer(self, controls):
        """PR 14: the rebalance plane's single-writer contract is
        enforced, not aspirational — a private ShardOverrides
        construction AND a .moves poke both trip."""
        hits = _tripped(
            controls, "single_writer_alias", "single-writer-overrides"
        )
        assert len(hits) == 2

    def test_seeded_hotpath_sleep(self, controls):
        """time.sleep two frames below Engine.step — reachable through
        the call graph, invisible to any module-scoped grep."""
        hits = _tripped(controls, "hotpath_sleep", "hotpath-blocking")
        assert hits[0].file == "engine/engine.py"

    def test_seeded_hotpath_file_io(self, controls):
        """PR 15's durable-tier boundary: an extent read (builtin open)
        two frames below Engine.step AND an os.fsync below
        Engine.enqueue both trip — the lint pin that keeps disk I/O on
        the KV-plane worker, never the serving loop."""
        hits = _tripped(controls, "hotpath_file_io", "hotpath-file-io")
        assert len(hits) == 2
        assert all(h.file == "engine/engine.py" for h in hits)

    def test_seeded_unregistered_oplog_kind(self, controls):
        hits = _tripped(controls, "wire_unregistered", "wire-unregistered")
        assert hits[0].file == "cache/oplog.py"

    def test_seeded_unprefixed_metric(self, controls):
        _tripped(controls, "metrics_vocab", "metrics-prefix")
        _tripped(controls, "metrics_vocab", "metrics-unit")
        _tripped(controls, "metrics_vocab", "metrics-literal")

    def test_seeded_send_seam_breaches(self, controls):
        hits = _tripped(controls, "send_seam", "send-seam")
        # Both the raw .send( AND the out-of-seam try_send trip; the
        # _sender_loop try_send in the same fixture does NOT.
        assert len(hits) == 2

    def test_seeded_unjustified_suppression(self, controls):
        """An ok[...] directive with no justification is a finding and
        suppresses nothing (the sleep beneath it still trips)."""
        _tripped(controls, "suppression_grammar", "suppression-grammar")
        _tripped(controls, "suppression_grammar", "sleep-audit")

    def test_seeded_offlock_write_two_helpers_down(self, controls):
        """The guarded-by race: an off-lock write two helper frames
        below its thread root, and a write reachable from two roots
        with no common lock — both grep-invisible."""
        hits = _tripped(controls, "guarded_race", "guarded-by-race")
        assert {c.file for c in hits} == {"cache/plane.py"}
        assert len(hits) == 2  # the helper-nested pop AND the split-lock write

    def test_seeded_thread_escapes(self, controls):
        """A lambda thread target escapes the map (blinding every
        downstream concurrency verdict); a daemonless spawn wedges
        shutdown."""
        _tripped(controls, "thread_escape", "thread-target-unresolved")
        _tripped(controls, "thread_escape", "thread-daemonless")

    def test_seeded_protocol_drift(self, controls):
        """An undeclared LEFT→ACTIVE revival (source known from the
        enclosing compare), a state with no exit edge, and a dispatch
        that silently drops two declared RequestStates."""
        hits = _tripped(
            controls, "protocol_drift", "protocol-undeclared-transition"
        )
        assert hits[0].file == "policy/lifecycle.py"
        _tripped(controls, "protocol_drift", "protocol-no-exit")
        hits = _tripped(controls, "protocol_drift", "protocol-unhandled-state")
        assert hits[0].file == "engine/engine.py"

    def test_seeded_dead_metric(self, controls):
        _tripped(controls, "metrics_vocab", "metrics-dead")


# ---------------------------------------------------------------------------
# suppression grammar, live
# ---------------------------------------------------------------------------

def _run_on(tmp_path: Path, rel: str, source: str):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    index = SourceIndex(tmp_path)
    return run_checkers(index, all_checkers())


class TestSuppressionGrammar:
    def test_justified_suppression_suppresses(self, tmp_path):
        res = _run_on(tmp_path, "utils/poll.py", """\
            import time

            def backoff():
                # meshcheck: ok[sleep-audit] test: bounded retry pacing
                time.sleep(0.1)
            """)
        assert res.clean, res.pretty()
        assert len(res.suppressed) == 1
        finding, sup = res.suppressed[0]
        assert finding.invariant == "sleep-audit"
        assert sup.justification == "test: bounded retry pacing"

    def test_suppression_only_covers_named_invariant(self, tmp_path):
        res = _run_on(tmp_path, "utils/poll.py", """\
            import time

            def backoff(q):
                # meshcheck: ok[timeout-audit] wrong invariant named
                time.sleep(0.1)
            """)
        # The sleep is NOT excused (directive names a different id) and
        # the directive is stale (it excused nothing).
        invs = {f.invariant for f in res.findings}
        assert invs == {"sleep-audit", "stale-suppression"}, res.pretty()

    def test_file_scope_suppression(self, tmp_path):
        res = _run_on(tmp_path, "utils/gen.py", """\
            # meshcheck: file-ok[sleep-audit] test: generator paces by design
            import time

            def a():
                time.sleep(0.1)

            def b():
                time.sleep(0.2)
            """)
        assert res.clean, res.pretty()
        assert len(res.suppressed) == 2

    def test_malformed_directive_is_a_finding(self, tmp_path):
        res = _run_on(tmp_path, "utils/bad.py", """\
            def f():
                # meshcheck: ok[sleep-audit]
                return 1
            """)
        invs = [f.invariant for f in res.findings]
        assert invs == ["suppression-grammar"], res.pretty()

    def test_stale_suppression_is_a_finding(self, tmp_path):
        """The rot-proofing the old grep allowlists did by hand
        (``test_allowlist_entries_still_match``), framework-enforced."""
        res = _run_on(tmp_path, "utils/clean.py", """\
            def f():
                # meshcheck: ok[sleep-audit] excuse with nothing beneath it
                return 1
            """)
        invs = [f.invariant for f in res.findings]
        assert invs == ["stale-suppression"], res.pretty()

    def test_multiline_justification_block_covers_next_statement(self, tmp_path):
        res = _run_on(tmp_path, "utils/poll.py", """\
            import time

            def backoff():
                # meshcheck: ok[sleep-audit] the justification continues
                # onto a second line and still anchors to the statement
                # after the comment block.
                time.sleep(0.1)
            """)
        assert res.clean, res.pretty()


# ---------------------------------------------------------------------------
# grep-invisible cases, live (not via fixtures): the two bug shapes the
# ISSUE names as motivating the AST rewrite
# ---------------------------------------------------------------------------

class TestGrepInvisible:
    def test_helper_nested_lock_cycle_detected(self, tmp_path):
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._state = threading.Lock()
                    self._io = threading.Lock()

                def flush(self):
                    with self._state:
                        self._emit()

                def _emit(self):
                    with self._io:
                        pass

                def reload(self):
                    with self._io:
                        with self._state:
                            pass
            """)
        cycles = [f for f in res.findings if f.invariant == "lock-order-cycle"]
        assert cycles, res.pretty()
        assert "_state" in cycles[0].message and "_io" in cycles[0].message

    def test_nonreentrant_self_deadlock_through_helper(self, tmp_path):
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()

                def get(self, k):
                    with self._lock:
                        return self._slow(k)

                def _slow(self, k):
                    with self._lock:
                        return k
            """)
        invs = {f.invariant for f in res.findings}
        assert "lock-order-reentry" in invs, res.pretty()

    def test_rlock_reentry_is_legal(self, tmp_path):
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.RLock()

                def get(self, k):
                    with self._lock:
                        return self._slow(k)

                def _slow(self, k):
                    with self._lock:
                        return k
            """)
        assert res.clean, res.pretty()

    def test_aliased_lifecycle_write_detected(self, tmp_path):
        res = _run_on(tmp_path, "server/rogue.py", """\
            from radixmesh_tpu.policy.lifecycle import LifecycleState

            def force_active(plane):
                target = LifecycleState.ACTIVE
                plane.state = target
            """)
        hits = [
            f for f in res.findings
            if f.invariant == "single-writer-lifecycle"
        ]
        assert len(hits) == 2, res.pretty()  # the binding AND the store

    def test_lifecycle_comparisons_stay_legal(self, tmp_path):
        res = _run_on(tmp_path, "server/reader.py", """\
            from radixmesh_tpu.policy.lifecycle import LifecycleState

            def is_active(plane):
                draining = plane.state is LifecycleState.DRAINING
                return not draining and (
                    plane.code == LifecycleState.ACTIVE.value
                )
            """)
        assert res.clean, res.pretty()

    def test_setattr_write_detected(self, tmp_path):
        res = _run_on(tmp_path, "server/rogue.py", """\
            from radixmesh_tpu.policy.lifecycle import LifecycleState

            def sneak(plane):
                setattr(plane, "state", LifecycleState.ACTIVE)
            """)
        invs = {f.invariant for f in res.findings}
        assert "single-writer-lifecycle" in invs, res.pretty()

    def test_bare_imported_sleep_detected(self, tmp_path):
        """``from time import sleep; sleep(x)`` must not evade the
        audit the dotted-name match would miss."""
        res = _run_on(tmp_path, "engine/engine.py", """\
            from time import sleep

            class Engine:
                def step(self):
                    sleep(0.25)
            """)
        invs = {f.invariant for f in res.findings}
        assert "hotpath-blocking" in invs, res.pretty()

    def test_block_true_get_is_unbounded(self, tmp_path):
        """``q.get(True)`` passes the block FLAG, not a timeout — it
        parks forever and must trip like a bare get()."""
        res = _run_on(tmp_path, "engine/engine.py", """\
            class Engine:
                def __init__(self, q):
                    self._q = q

                def step(self):
                    return self._q.get(True)

                def drain(self):
                    return self._q.get(block=True)
            """)
        hot = [f for f in res.findings if f.invariant == "hotpath-blocking"]
        audit = [f for f in res.findings if f.invariant == "timeout-audit"]
        assert hot and audit, res.pretty()

    def test_aliased_store_after_nested_binding(self, tmp_path):
        """The alias pass is order-independent: a store that lexically
        follows a binding nested in a deeper block still trips."""
        res = _run_on(tmp_path, "server/rogue.py", """\
            from radixmesh_tpu.policy.lifecycle import LifecycleState

            def force(plane, cond):
                if cond:
                    st = LifecycleState.ACTIVE
                plane.state = st
            """)
        hits = [
            f for f in res.findings
            if f.invariant == "single-writer-lifecycle"
        ]
        assert len(hits) == 2, res.pretty()

    def test_serving_entry_points_still_resolve(self):
        """The hot-path checker's roots are pinned: a rename that
        silently dropped an entry point would hollow out the whole
        call-graph plane while everything stayed green (the same
        rot class stale-suppression guards against, for the checker's
        own config)."""
        import ast

        from radixmesh_tpu.analysis import tree_index
        from radixmesh_tpu.analysis.hot_path import DEFAULT_ENTRY_POINTS

        index = tree_index()
        for rel, qual in DEFAULT_ENTRY_POINTS:
            assert rel in index, f"entry-point module {rel} vanished"
            tree = index.module(rel).tree
            cls, _, meth = qual.partition(".")
            found = any(
                isinstance(n, ast.ClassDef) and n.name == cls
                and any(
                    isinstance(m, ast.FunctionDef) and m.name == meth
                    for m in n.body
                )
                for n in tree.body
            )
            assert found, f"entry point {rel}:{qual} no longer resolves"

    def test_blocking_call_two_frames_down(self, tmp_path):
        """Entry point -> helper -> helper -> unbounded queue get."""
        res = _run_on(tmp_path, "engine/engine.py", """\
            class Engine:
                def __init__(self, q):
                    self._q = q

                def step(self):
                    self._admit()

                def _admit(self):
                    self._take_one()

                def _take_one(self):
                    return self._q.get()
            """)
        hot = [f for f in res.findings if f.invariant == "hotpath-blocking"]
        assert hot, res.pretty()
        assert "Engine.step" in hot[0].message  # the chain is named

    def test_bounded_get_stays_legal(self, tmp_path):
        res = _run_on(tmp_path, "engine/engine.py", """\
            class Engine:
                def __init__(self, q):
                    self._q = q

                def step(self):
                    return self._q.get(timeout=0.05)
            """)
        assert res.clean, res.pretty()


# ---------------------------------------------------------------------------
# concurrency plane, live: the lock-set / thread-root / protocol rules
# on synthetic trees (the shapes the gates must keep legal vs flag)
# ---------------------------------------------------------------------------


class TestGuardedByLive:
    def test_compositional_lock_chain_stays_clean(self, tmp_path):
        """A write three helper frames below the lock acquisition is
        GUARDED — the ambient-set fixpoint follows the chain (the shape
        that would false-positive under naive one-frame analysis:
        oplog_received -> _gc_handle -> _fold -> del)."""
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._pending = {}
                    self._t = threading.Thread(target=self._loop, daemon=True)
                    self._u = threading.Thread(target=self._other, daemon=True)

                def _loop(self):
                    with self._lock:
                        self._handle()

                def _handle(self):
                    self._fold()

                def _fold(self):
                    self._pending["x"] = 1

                def _other(self):
                    with self._lock:
                        self._pending.pop("x", None)
            """)
        assert res.clean, res.pretty()

    def test_single_root_state_never_fires(self, tmp_path):
        """Engine-thread-only fields are allowed to mix locked and
        unlocked access: one non-multi root cannot race itself."""
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0
                    self._t = threading.Thread(target=self._loop, daemon=True)

                def _loop(self):
                    with self._lock:
                        self._n = 1
                    with self._lock:
                        self._n = 2
                    self._n = 3
            """)
        assert res.clean, res.pretty()

    def test_deviant_read_against_unanimous_convention(self, tmp_path):
        """Every access but one holds the guard, a guarded write runs on
        another thread → the deviant read is a read-write race."""
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._map = {}
                    self._t = threading.Thread(target=self._loop, daemon=True)

                def _loop(self):
                    with self._lock:
                        self._map["k"] = 1
                    with self._lock:
                        self._map["j"] = 2

                def snapshot(self):
                    return dict(self._map)
            """)
        hits = [f for f in res.findings if f.invariant == "guarded-by-race"]
        assert hits and "read-write" in hits[0].message, res.pretty()

    def test_volatile_read_idiom_stays_legal(self, tmp_path):
        """TWO lock-free reads break unanimity — the codebase's own
        convention declares the snapshot-read idiom legal here."""
        res = _run_on(tmp_path, "cache/plane.py", """\
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._map = {}
                    self._t = threading.Thread(target=self._loop, daemon=True)

                def _loop(self):
                    with self._lock:
                        self._map["k"] = 1
                    with self._lock:
                        self._map["j"] = 2

                def snapshot(self):
                    return dict(self._map)

                def peek(self):
                    return len(self._map)
            """)
        assert res.clean, res.pretty()

    def test_offlock_write_inside_spawned_closure(self, tmp_path):
        """The hedge-leg shape: a closure handed to Thread runs OFF the
        spawning frame's locks — an off-lock write inside it races the
        guarded writes (review finding: the nested-def skip must not
        blind the checker to spawned closures)."""
        res = _run_on(tmp_path, "server/hedge.py", """\
            import threading

            class Hedger:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._winner = {}
                    self._t = threading.Thread(target=self._loop, daemon=True)

                def _loop(self):
                    with self._lock:
                        self._winner["w"] = 1
                    with self._lock:
                        self._winner["v"] = 2

                def race(self):
                    def leg():
                        self._winner["x"] = 3

                    t = threading.Thread(target=leg, daemon=True)
                    t.start()
            """)
        hits = [f for f in res.findings if f.invariant == "guarded-by-race"]
        assert hits, res.pretty()

    def test_inline_closure_under_lock_stays_clean(self, tmp_path):
        """A closure called INLINE (sort key, local helper) runs on the
        caller's thread under the caller's locks — only spawned
        closures get the empty held set."""
        res = _run_on(tmp_path, "server/sorter.py", """\
            import threading

            class Sorter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._rows = {}
                    self._t = threading.Thread(target=self._loop, daemon=True)
                    self._u = threading.Thread(target=self._loop2, daemon=True)

                def _loop(self):
                    with self._lock:
                        def bump():
                            self._rows["a"] = 1

                        bump()

                def _loop2(self):
                    with self._lock:
                        self._rows["b"] = 2
            """)
        assert res.clean, res.pretty()

    def test_threadsafe_containers_exempt(self, tmp_path):
        """Queue/Event attributes are internally synchronized — method
        calls on them from any thread are not races."""
        res = _run_on(tmp_path, "cache/plane.py", """\
            import queue
            import threading

            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()
                    self._evt = threading.Event()
                    self._t = threading.Thread(target=self._loop, daemon=True)

                def _loop(self):
                    with self._lock:
                        self._q.put_nowait(1)
                    self._evt.set()

                def submit(self):
                    self._q.put_nowait(2)
                    self._evt.clear()
            """)
        assert res.clean, res.pretty()


class TestThreadMapLive:
    def test_declared_roots_still_resolve(self):
        """Same rot-guard as the hot-path entry points: a rename that
        silently dropped a declared root would hollow out the
        concurrency plane while everything stayed green."""
        from radixmesh_tpu.analysis import tree_index
        from radixmesh_tpu.analysis.thread_roots import DECLARED_ROOTS
        from radixmesh_tpu.analysis.callgraph import get_callgraph

        cg = get_callgraph(tree_index())
        for rel, qual, name, _multi in DECLARED_ROOTS:
            assert (rel, qual) in cg.funcs, f"declared root {name} vanished"

    def test_product_tree_thread_map_is_complete(self):
        """The documented long-lived threads all resolve as roots, and
        the map is finding-free (every target resolved, every spawn
        daemon=True)."""
        from radixmesh_tpu.analysis import check_tree, get_thread_map, tree_index

        assert not [
            f for f in check_tree().findings
            if f.invariant in ("thread-target-unresolved", "thread-daemonless")
        ]
        names = {r.name for r in get_thread_map(tree_index()).roots}
        for expected in (
            "mesh-sender", "mesh-owner-sender", "mesh-ticker", "mesh-gc",
            "mesh-housekeeper", "kv-transfer", "repair-plane",
            "lifecycle-plane", "lifecycle-drain", "engine-runner",
            "wire-receive", "engine-loop", "fleet-aggregator",
        ):
            assert expected in names, f"thread root {expected!r} vanished"
        # Per-connection concurrency is modeled: the HTTP handlers and
        # the wire receive path are multi-instance roots.
        tm = get_thread_map(tree_index())
        assert tm.is_multi("wire-receive")
        assert any(r.kind == "handler" and r.multi for r in tm.roots)

    def test_nested_def_target_maps_to_enclosing(self, tmp_path):
        """A closure handed to Thread (the hedge-leg shape) resolves to
        its enclosing frame instead of escaping the map."""
        res = _run_on(tmp_path, "server/hedge.py", """\
            import threading

            class Hedger:
                def race(self):
                    def leg():
                        return 1

                    t = threading.Thread(target=leg, daemon=True)
                    t.start()
            """)
        assert not [
            f for f in res.findings
            if f.invariant == "thread-target-unresolved"
        ], res.pretty()


class TestProtocolLive:
    def test_dispatch_with_else_is_exhaustive(self, tmp_path):
        res = _run_on(tmp_path, "engine/engine.py", """\
            from .request import RequestState

            class Engine:
                def poll(self, req):
                    if req.state is RequestState.QUEUED:
                        return "wait"
                    elif req.state is RequestState.RUNNING:
                        return "go"
                    else:
                        return "done"
            """)
        # No engine/request.py in this tree: the spec module is absent,
        # so nothing fires either way — exhaustiveness needs the enum.
        assert res.clean, res.pretty()

    def test_declared_transition_stays_legal(self, tmp_path):
        (tmp_path / "engine").mkdir(parents=True, exist_ok=True)
        (tmp_path / "engine" / "request.py").write_text(textwrap.dedent("""\
            import enum

            class RequestState(enum.Enum):
                QUEUED = "queued"
                RUNNING = "running"
                FINISHED = "finished"

            VALID_TRANSITIONS = {
                (RequestState.QUEUED, RequestState.RUNNING),
                (RequestState.RUNNING, RequestState.FINISHED),
            }
            """))
        res = _run_on(tmp_path, "engine/engine.py", """\
            from .request import RequestState

            class Engine:
                def finish(self, req):
                    if req.state is RequestState.RUNNING:
                        req.state = RequestState.FINISHED
            """)
        assert res.clean, res.pretty()

    def test_product_request_table_covers_every_live_transition(self):
        """Runtime cross-check of the declared table: every enum member
        participates, FINISHED is terminal, QUEUED is re-enterable
        (preempt + restore-requeue)."""
        from radixmesh_tpu.engine.request import (
            RequestState,
            VALID_TRANSITIONS,
        )

        members = set(RequestState)
        assert {s for s, _ in VALID_TRANSITIONS} == members - {
            RequestState.FINISHED
        }
        assert {d for _, d in VALID_TRANSITIONS} == members
        assert (RequestState.RUNNING, RequestState.QUEUED) in VALID_TRANSITIONS


# ---------------------------------------------------------------------------
# --changed scoping: the per-commit gate
# ---------------------------------------------------------------------------


class TestChangedScope:
    def test_scope_widens_by_reverse_imports(self, tmp_path):
        from radixmesh_tpu.analysis import changed_scope

        (tmp_path / "utils").mkdir()
        (tmp_path / "cache").mkdir()
        (tmp_path / "server").mkdir()
        (tmp_path / "utils" / "base.py").write_text("def f():\n    return 1\n")
        (tmp_path / "cache" / "mid.py").write_text(
            "from radixmesh_tpu.utils.base import f\n"
        )
        (tmp_path / "server" / "top.py").write_text(
            "from radixmesh_tpu.cache.mid import f\n"
        )
        (tmp_path / "server" / "aloof.py").write_text("x = 1\n")
        index = SourceIndex(tmp_path)
        scope = changed_scope(index, ["utils/base.py"])
        # The change widens transitively up the import chain but never
        # touches unrelated modules.
        assert scope == {"utils/base.py", "cache/mid.py", "server/top.py"}
        assert changed_scope(index, ["server/aloof.py"]) == {"server/aloof.py"}
        assert changed_scope(index, ["gone/deleted.py"]) == set()

def test_meshcheck_cli_exit_zero_on_clean_tree():
    proc = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "meshcheck.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    assert "controls tripped" in proc.stdout
    assert "thread roots" in proc.stdout


def test_meshcheck_cli_changed_mode():
    """The per-commit gate: scoped to git-changed files + reverse-import
    dependents, same exit-code contract (0 = clean; a dirty tree in CI
    is clean too, because the full tree is clean)."""
    proc = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "meshcheck.py"), "--changed"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "scope:" in proc.stdout


def test_meshcheck_cli_changed_refuses_artifact():
    proc = subprocess.run(
        [
            sys.executable, str(_REPO / "scripts" / "meshcheck.py"),
            "--changed", "--out", "unwritten.json",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "whole tree" in proc.stderr


def test_meshcheck_cli_out_writes_a_validated_report(tmp_path):
    """``--out`` writes the report where it says, and what it writes
    passes the report's own gates; without the positive controls it
    refuses, since the gates read them."""
    import json

    out = tmp_path / "analysis.json"
    proc = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "meshcheck.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    written = json.loads(out.read_text())
    assert analysis_report.validate_analysis(written) == []
    assert "schema_violation" not in written
    refused = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "meshcheck.py"),
         "--no-fixtures", "--out", str(tmp_path / "unwritten.json")],
        capture_output=True, text=True, timeout=120,
    )
    assert refused.returncode == 2
    assert not (tmp_path / "unwritten.json").exists()


def test_meshcheck_cli_exit_one_on_findings(tmp_path):
    """The third pinned exit code (the per-PR quick gate's contract:
    0 clean / 1 findings / 2 framework error): a seeded vocabulary
    violation in a --root tree must exit 1 and print the finding."""
    (tmp_path / "obs").mkdir()
    (tmp_path / "obs" / "bad.py").write_text(
        "from radixmesh_tpu.obs.metrics import get_registry\n"
        "c = get_registry().counter('unprefixed_name', 'd')\n"
        "c.inc()\n"
    )
    proc = subprocess.run(
        [
            sys.executable, str(_REPO / "scripts" / "meshcheck.py"),
            "--root", str(tmp_path), "--no-fixtures",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "metrics-prefix" in proc.stdout


def test_meshcheck_changed_gate_covers_this_pr(tmp_path):
    """Satellite (PR 13): the --changed quick gate IS the per-PR static
    pass — run it exactly as CI would and pin the full exit-code
    contract in one place: clean tree + dirty worktree exits 0, the
    artifact refusal exits 2 (exit 1 is proven by the seeded-finding
    test above)."""
    proc = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "meshcheck.py"),
         "--changed"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    refused = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "meshcheck.py"),
         "--changed", "--out", str(tmp_path / "unwritten.json")],
        capture_output=True, text=True, timeout=120,
    )
    assert refused.returncode == 2


class TestAnalysisArtifactSchema:
    """The ANALYSIS report (meshcheck, concurrency plane included) gates
    on ZERO unsuppressed findings over the tree, every default checker
    present (thread-roots/guarded-by/protocol among them, each with
    per-checker control counts), every positive-control fixture tripped,
    a justification on every suppression, and a non-empty derived thread
    map."""

    def _report(self) -> dict:
        checker = {
            "id": "lock-order",
            "description": "x",
            "raw_findings": 0,
            "kept_findings": 0,
            "suppressed": 0,
            "controls": 1,
            "controls_tripped": 1,
        }
        ids = analysis_report.ANALYSIS_CHECKER_IDS
        report = {
            "schema_version": analysis_report.ANALYSIS_SCHEMA_VERSION,
            "metric": "unsuppressed_findings",
            "value": 0,
            "package": "radixmesh_tpu",
            "files_indexed": 80,
            "checkers": [dict(checker, id=cid) for cid in ids],
            "findings": [],
            "suppressions": [
                {
                    "file": "workload.py", "line": 19, "scope": "file",
                    "invariants": ["sleep-audit"],
                    "justification": "generators pace by wall clock",
                    "used": True,
                },
            ],
            "positive_controls": [
                {
                    "fixture": "lock_cycle",
                    "invariant": "lock-order-cycle",
                    "file": "engine/engine.py", "line": 19,
                    "tripped": True,
                },
            ],
            "clean": True,
        }
        report["thread_roots"] = {
            "count": 2,
            "roots": [
                {
                    "name": "mesh-sender",
                    "target": "cache/mesh_cache.py:MeshCache._sender",
                    "file": "cache/mesh_cache.py", "line": 624,
                    "multi": False, "kind": "spawn",
                },
                {
                    "name": "wire-receive",
                    "target": "cache/mesh_cache.py:MeshCache.oplog_received",
                    "file": "cache/mesh_cache.py", "line": 905,
                    "multi": True, "kind": "declared",
                },
            ],
        }
        return report

    def test_valid_report_passes(self):
        assert analysis_report.validate_analysis(self._report()) == []

    def test_v2_requires_concurrency_checkers(self):
        report = self._report()
        report["checkers"] = [
            c for c in report["checkers"] if c["id"] != "guarded-by"
        ]
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "guarded-by" in problems and "checked less" in problems

    def test_v2_requires_thread_map(self):
        report = self._report()
        del report["thread_roots"]
        assert any(
            "thread_roots" in p for p in analysis_report.validate_analysis(report)
        )
        report = self._report()
        report["thread_roots"] = {"count": 0, "roots": []}
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "checked nothing" in problems

    def test_v2_thread_map_entries_are_schema_complete(self):
        report = self._report()
        del report["thread_roots"]["roots"][0]["multi"]
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "mesh-sender" in problems and ".multi" in problems
        report = self._report()
        report["thread_roots"]["count"] = 5
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "disagrees" in problems

    def test_v2_requires_control_counts(self):
        report = self._report()
        del report["checkers"][0]["controls_tripped"]
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "controls_tripped" in problems

    def test_missing_fields_reported(self):
        report = self._report()
        del report["positive_controls"]
        del report["files_indexed"]
        missing = analysis_report.validate_analysis(report)
        assert any("files_indexed" in p for p in missing)
        assert any("positive_controls" in p for p in missing)

    def test_non_dict_rejected(self):
        assert analysis_report.validate_analysis([1]) == ["artifact is not a JSON object"]

    def test_findings_fail_the_gate(self):
        report = self._report()
        report["findings"] = [
            {"file": "cache/mesh_cache.py", "line": 7,
             "invariant": "send-seam", "message": "raw send"},
        ]
        report["clean"] = False
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "unsuppressed finding" in problems

    def test_clean_flag_must_agree(self):
        report = self._report()
        report["clean"] = False
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "clean flag disagrees" in problems

    def test_untripped_control_fails(self):
        report = self._report()
        report["positive_controls"][0]["tripped"] = False
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "NOT tripped" in problems and "went blind" in problems

    def test_empty_controls_fail(self):
        report = self._report()
        report["positive_controls"] = []
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "proves nothing" in problems

    def test_missing_checker_fails(self):
        report = self._report()
        report["checkers"] = report["checkers"][1:]
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "lock-order" in problems and "checked less" in problems

    def test_unjustified_suppression_fails(self):
        report = self._report()
        report["suppressions"][0]["justification"] = "  "
        problems = "\n".join(analysis_report.validate_analysis(report))
        assert "silencing" in problems

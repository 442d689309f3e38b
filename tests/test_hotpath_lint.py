"""Hot-path synchronization lint, running through the meshcheck
framework: the serving loop must never regrow a blocking KV copy.

PR 4 moved every bulk KV materialization into ``cache/kv_transfer.py``
— the ONE module allowed to block on device→host data. The old version
of this file pinned that boundary with regex greps over three scopes;
the ``hot-path`` checker (``radixmesh_tpu/analysis/hot_path.py``) now
enforces the same scoped bans off the AST (invariant ``hotpath-sync``)
PLUS what a scope-grep cannot see: a blocking call N frames down the
call graph from ``Engine.step`` / ``match_prefix`` / admission / oplog
receive (invariant ``hotpath-blocking``). Test names preserved; each
asserts its slice of the checker's findings is empty."""

import ast

import pytest

from radixmesh_tpu.analysis import check_tree as _result
from radixmesh_tpu.analysis import tree_index as _index

pytestmark = pytest.mark.quick


def _sync_findings(rel: str):
    return [
        f for f in _result().findings
        if f.invariant in ("hotpath-sync", "hotpath-blocking") and f.file == rel
    ]


class TestHotPathSyncLint:
    def test_engine_step_admit_paths_have_no_blocking_kv_copies(self):
        bad = _sync_findings("engine/engine.py")
        assert not bad, "\n".join(str(f) for f in bad)

    def test_host_cache_match_path_stays_dispatch_only(self):
        """``match_and_load`` may read the arena (that is the documented
        synchronous fallback — the checker's host_cache scope bans the
        gather/sync constructs, not ``host.read``); the fused sweep
        gather lives in the flush/plane seam."""
        bad = _sync_findings("cache/host_cache.py")
        assert not bad, "\n".join(str(f) for f in bad)

    def test_disagg_admit_has_no_host_materialization(self):
        """The decode-side admit writes staged blocks; materializing a
        packet back to numpy there would undo the reader-thread
        staging. (The checker's disagg scope bans ANY np.asarray in
        ``_admit_one``.)"""
        bad = _sync_findings("engine/disagg.py")
        assert not bad, "\n".join(str(f) for f in bad)

    def test_nothing_reachable_from_serving_entry_points_blocks(self):
        """The grep-invisible half: across the WHOLE package, no
        function reachable from the serving entry points contains an
        unbounded wait/sleep/device-sync."""
        bad = [
            f for f in _result().findings
            if f.invariant == "hotpath-blocking"
        ]
        assert not bad, "\n".join(str(f) for f in bad)

    def test_entry_points_and_thread_map_share_one_call_graph(self):
        """PR 11 refactor guard: the hot-path checker, the thread map,
        and guarded-by all resolve through ONE CallGraph per index
        (analysis/callgraph.py) — a second derivation could silently
        diverge on resolution rules, and the whole point of the shared
        substrate is that a reachability fact proven for one checker
        holds for all of them."""
        from radixmesh_tpu.analysis.callgraph import get_callgraph
        from radixmesh_tpu.analysis.hot_path import DEFAULT_ENTRY_POINTS

        index = _index()
        cg = get_callgraph(index)
        assert get_callgraph(index) is cg  # memoized on the index
        # The serving entry points resolve in the same graph the thread
        # map used, and each reaches a non-trivial frame set.
        for ep in DEFAULT_ENTRY_POINTS:
            assert ep in cg.funcs, f"entry point {ep} not in the call graph"
        reachable, _chains = cg.reach(DEFAULT_ENTRY_POINTS)
        assert len(reachable) > 50, "serving call graph collapsed"

    def test_staging_module_is_the_only_sync_owner(self):
        """Positive control: the banned constructs ARE present in the
        staging module (the checker scopes ban real patterns, not
        typos) — and the staging module itself is exempt by design."""
        tree = _index().module("cache/kv_transfer.py").tree
        names = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute):
                    names.add(f.attr)
                elif isinstance(f, ast.Name):
                    names.add(f.id)
        assert "asarray" in names, "kv_transfer no longer materializes?"
        assert "read" in names, "kv_transfer no longer reads the arena?"
        assert not _sync_findings("cache/kv_transfer.py")

    def test_token_timeline_rides_the_hot_path_clean(self):
        """PR 18: the token timeline's ``note_token`` runs once per
        decoded token INSIDE the serving loop — the speedometer module
        must carry zero blocking findings, and the call graph must
        actually see it from the engine entry points (otherwise the
        reachability guarantee above is vacuous for the newest
        per-token code)."""
        assert not _sync_findings("obs/token_timeline.py")
        from radixmesh_tpu.analysis.callgraph import get_callgraph
        from radixmesh_tpu.analysis.hot_path import DEFAULT_ENTRY_POINTS

        cg = get_callgraph(_index())
        reachable, _chains = cg.reach(DEFAULT_ENTRY_POINTS)
        hits = {fn[1] for fn in reachable if "note_token" in fn[1]}
        assert "TokenTimeline.note_token" in hits
        assert "GoodputLedger.note_token" in hits

    def test_wave_scheduler_and_decode_step_ride_the_hot_path_clean(self):
        """The wave scheduler's ``plan``/``note`` run once per compute
        wave and ``_decode_once`` (with the dispatch record's
        ``batch_bucket``) once per decode step — both INSIDE the serving
        loop. Zero blocking findings in the policy module, and the call
        graph must actually see both seams from the engine entry points
        (a wave scheduler the reachability proof can't see would make the
        starvation bound unauditable)."""
        assert not _sync_findings("engine/waves.py")
        from radixmesh_tpu.analysis.callgraph import get_callgraph
        from radixmesh_tpu.analysis.hot_path import DEFAULT_ENTRY_POINTS

        cg = get_callgraph(_index())
        reachable, _chains = cg.reach(DEFAULT_ENTRY_POINTS)
        names = {fn[1] for fn in reachable}
        assert "WaveScheduler.plan" in names
        assert "WaveScheduler.note" in names
        assert "Engine._decode_once" in names and "batch_bucket" in names, (
            "the one decode step is not on the serving call graph"
        )

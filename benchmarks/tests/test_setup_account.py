"""The readers of the set-up's account (``SETUP_ACCOUNT.md``): the five
``setup_*`` metrics take a VALUE of ``/debug/state`` ``process`` from the
snapshot taken as the window opens, ``trace_lower_s_in_window*`` a delta
between the window's two snapshots; a hand-written pair, ``None`` where the
program has no such counter (no ``process``: before PR 35; no ``startup``,
no ``trace_s``: the parent of PR 51), and every manifest entry of theirs
resolving by name to a file."""
import os

import pytest

from benchmarks.harness import registry

CHAT = "qwen2-7b.chat-sessions"
# name: what the pair below reads
SETUP = {
    "setup_startup_s": 19.0,  # ready at 21.5 s, 2.5 s of it the compile path's
    "setup_trace_s": 30.0,
    "setup_lower_s": 12.0,
    "setup_compile_s": 41.0,  # 48 s of backend compilation less 7 s of cache hits
    "setup_cache_load_s": 7.0,
}
IN_WINDOW = ("trace_lower_s_in_window", "trace_lower_s_in_window.batch")


def snap(trace_s, lower_s, s, cache_load_s, ready_at_s=21.5):
    return {"state": {"process": {
        "gc": {},
        "compiles": {"n": 90, "s": s, "cache_hits": 20, "last": [],
                     "trace_s": trace_s, "lower_s": lower_s,
                     "cache_load_s": cache_load_s, "programs": 40,
                     "by_program": {}, "by_span": {}},
        "startup": {"phases": [["imports", 6.0], ["devices", 4.0],
                               ["params", 8.0], ["engine", 3.0], ["listen", 0.5]],
                    "ready_at_s": ready_at_s, "compiling_s": 2.5},
    }}}


CTX = {"before": snap(30.0, 12.0, 48.0, 7.0), "after": snap(31.5, 13.0, 48.25, 7.0)}
NO_PROCESS = {"state": {"engine": {}}}
# The parent of PR 51: a process account that counts compilations only.
PARENT = {"state": {"process": {
    "gc": {}, "compiles": {"n": 90, "s": 48.0, "cache_hits": 20, "last": []}}}}


def _read(name, ctx):
    return registry.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name", SETUP)
def test_setup_reader_takes_the_value_as_the_window_opens(name):
    assert _read(name, CTX) == SETUP[name]
    # what the window added changes nothing of it
    assert _read(name, {**CTX, "after": NO_PROCESS}) == SETUP[name]


@pytest.mark.parametrize("name", IN_WINDOW)
def test_window_reader_takes_the_delta_of_both_kinds(name):
    assert _read(name, CTX) == pytest.approx(2.5)
    assert _read(name, {**CTX, "after": CTX["before"]}) == 0.0


@pytest.mark.parametrize("old", [NO_PROCESS, PARENT], ids=["no_process", "parent"])
@pytest.mark.parametrize("name", [*SETUP, *IN_WINDOW])
def test_reader_finds_nothing_in_a_program_without_the_counter(name, old):
    assert _read(name, {"before": old, "after": old}) is None
    if name in IN_WINDOW:  # one side alone is no delta
        assert _read(name, {"before": old, "after": CTX["after"]}) is None


def test_a_start_up_without_a_phase_reads_nothing():
    ctx = {"before": snap(0.0, 0.0, 0.0, 0.0, ready_at_s=None), "after": NO_PROCESS}
    assert _read("setup_startup_s", ctx) is None


def test_manifest_entries_resolve_by_name():
    man = registry.manifest()
    cells = [w["name"] for w in man["workloads"]]
    loops = [c for c in cells if c != CHAT]
    want = {name: ("setup_s", cells) for name in SETUP} | {
        "trace_lower_s_in_window": ("itl_p95_ms", [CHAT]),
        "trace_lower_s_in_window.batch": ("tokens_per_s", loops),
    }
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name, (moves, workloads) in want.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "s", "lower", "program_counter", "model step")
        assert (m["moves"], m["workloads"]) == (moves, workloads)
        assert os.path.isfile(os.path.join(registry.ROOT, "layer_metrics", name + ".py"))
    for cell in cells:
        names = [m["name"] for m in registry.resolve(cell)["per_layer"]]
        assert sum(n in want for n in names) == 6  # five of the set-up, one of the window
    assert len(man["per_layer"]) <= 128

"""The readers of the window's own account (``WINDOW_ACCOUNT.md``): each
takes the delta of a counter of ``/debug/state`` between the window's two
snapshots; a hand-written pair per metric, ``None`` where the program has
no such counter (the parent of PR 35), and every manifest entry of theirs
resolving by name to a file."""
import os

import pytest

from benchmarks.harness import registry

CHAT = "qwen2-7b.chat-sessions"
LOOPS = ["qwen2-7b.batch-unshared", "mellum2-12b-l12.batch-longtail",
         "glm-4.7-flash-l8.docs-hotset", "glm-5.2-ep16.repo-hotset",
         "glm-4.7-flash-l8.batch-longtail"]
# name: (unit, layer, what the pair below reads)
METRICS = {
    "prefill_wall_ms_per_ktok": ("ms", "model step", 125.0),  # 1.5 s over 12 ktok
    "prefill_pad_share": ("%", "scheduler", 25.0),  # 12 000 of 16 000
    "prefill_share_of_window": ("%", "model step", 3.0),  # 1.5 s of 50
    "decode_step_wall_ms": ("ms", "scheduler", 20.0),  # (21.5 - 1.5) s over 1 000 steps
    "stalled_s_in_window": ("s", "scheduler", 2.5),
    "gc_pause_ms_in_window": ("ms", "scheduler", 60.0),  # 10 + 20 + 30 ms
    "compile_s_in_window": ("s", "model step", 0.75),
}


def snap(t, steps, step_s, prefill, slow_s, gc_s, compile_s):
    waves, rows, new, launched, wall = prefill
    return {"t": t, "state": {
        "engine": {
            "decode_steps": steps,
            "spans": {"rm.engine.step": {"n": steps, "s": step_s},
                      "outside_step": {"n": steps, "s": 0.1}},
            "prefill": {"waves": waves, "rows": rows, "tokens_new": new,
                        "tokens_launched": launched, "wall_s": wall,
                        "by_path": {"dense": waves, "chunk": 0, "sp": 0}},
            "slow_steps": {"n": int(slow_s > 0), "s": slow_s, "threshold_s": 1.0,
                           "last": []},
        },
        "process": {
            "gc": {str(g): {"n": 1, "s": s, "max_s": s} for g, s in enumerate(gc_s)},
            "compiles": {"n": 3, "s": compile_s, "cache_hits": 0, "last": []},
        },
    }}


CTX = {
    "window": (100.0, 150.0),
    "before": snap(100.0, 500, 10.0, (10, 12, 3_000, 4_000, 0.5), 0.0,
                   (0.10, 0.20, 0.30), 40.0),
    "after": snap(150.0, 1_500, 31.5, (110, 130, 15_000, 20_000, 2.0), 2.5,
                  (0.11, 0.22, 0.33), 40.75),
}
OLD = {"state": {"engine": {"decode_steps": 5}, "cache": {}}}


def _read(name, ctx):
    return registry.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("suffix", ["", ".batch"])
@pytest.mark.parametrize("name", METRICS)
def test_reader_takes_the_window_delta(name, suffix):
    assert _read(name + suffix, CTX) == pytest.approx(METRICS[name][2])


@pytest.mark.parametrize("name", METRICS)
def test_reader_finds_nothing_in_a_program_without_the_counter(name):
    ctx = {"window": (0.0, 51.0), "before": OLD, "after": OLD}
    assert _read(name, ctx) is None and _read(name + ".batch", ctx) is None


def test_readers_divide_by_nothing_that_is_zero():
    still = {**CTX, "after": CTX["before"]}  # a window in which nothing ran
    for name in ("prefill_wall_ms_per_ktok", "prefill_pad_share", "decode_step_wall_ms"):
        assert _read(name, still) is None
    assert _read("prefill_share_of_window", still) == 0.0
    assert _read("stalled_s_in_window", still) == 0.0
    assert _read("gc_pause_ms_in_window", still) == 0.0
    assert _read("compile_s_in_window", still) == 0.0


@pytest.mark.parametrize("name", METRICS)
def test_manifest_entries_resolve_by_name(name):
    man = registry.manifest()
    unit, layer, _ = METRICS[name]
    by_name = {m["name"]: m for m in man["per_layer"]}
    plain, twin = by_name[name], by_name[name + ".batch"]
    for m, moves, cells in ((plain, "itl_p95_ms", [CHAT]), (twin, "tokens_per_s", LOOPS)):
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer, "moves": moves,
                     "workloads": cells}
        path = os.path.join(registry.ROOT, "layer_metrics", m["name"] + ".py")
        assert os.path.isfile(path)
        assert callable(registry.load_module("layer_metrics", m["name"]).read)
    for cell in [CHAT, *LOOPS]:
        listed = {m["name"] for m in registry.resolve(cell)["per_layer"]}
        want = {n + ("" if cell == CHAT else ".batch") for n in METRICS}
        assert want <= listed and not ({n + (".batch" if cell == CHAT else "") for n in METRICS} & listed)

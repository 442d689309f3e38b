"""``prefill_host_ms_per_wave`` (PR 36): host milliseconds inside the two
prefill launch spans per admission wave of the window — a hand-written
before/after pair, ``None`` where the program has no span book (the parent
of PR 35) or no wave ran, and both manifest entries resolving by name."""
import os

import pytest

from benchmarks.harness import registry

NAME = "prefill_host_ms_per_wave"
CHAT = "qwen2-7b.chat-sessions"
LOOPS = ["qwen2-7b.batch-unshared", "mellum2-12b-l12.batch-longtail",
         "glm-4.7-flash-l8.docs-hotset", "glm-5.2-ep16.repo-hotset",
         "glm-4.7-flash-l8.batch-longtail"]


def snap(waves, dense_s, group_s, sync_s=0.0):
    return {"state": {"engine": {
        "spans": {"rm.prefill.dense": {"n": waves, "s": dense_s},
                  "rm.prefill.group": {"n": waves, "s": group_s},
                  "rm.first_token.sync": {"n": waves, "s": sync_s}},
        "prefill": {"waves": waves, "rows": waves, "tokens_new": 200 * waves,
                    "tokens_launched": 256 * waves, "wall_s": 0.05 * waves,
                    "by_path": {"dense": 0, "chunk": waves, "sp": 0}},
    }}}


# name: (before, after, what the reader gives)
PAIRS = {
    # 100 waves: 0.2 s under the dense span + 0.25 under the group span
    "both_spans": (snap(10, 0.5, 0.25), snap(110, 0.7, 0.5), 4.5),
    # the sync that follows a wave is not host time before the launch
    "sync_left_out": (snap(10, 0.0, 0.1, 1.0), snap(60, 0.0, 0.2, 3.0), 2.0),
    "no_wave_in_the_window": (snap(10, 0.5, 0.25), snap(10, 0.5, 0.25), None),
    "no_span_book": ({"state": {"engine": {"decode_steps": 5}}},) * 2 + (None,),
}


def read(name, ctx):
    return registry.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("suffix", ["", ".batch"])
@pytest.mark.parametrize("case", PAIRS)
def test_reader_takes_the_window_delta(case, suffix):
    before, after, want = PAIRS[case]
    got = read(NAME + suffix, {"window": (0.0, 51.0), "before": before, "after": after})
    assert got is None if want is None else got == pytest.approx(want)


def test_a_span_book_without_the_two_names_reads_nothing():
    before, after, _ = PAIRS["both_spans"]
    cut = {"state": {"engine": {**after["state"]["engine"],
                                "spans": {"rm.engine.step": {"n": 1, "s": 1.0}}}}}
    assert read(NAME, {"window": (0.0, 51.0), "before": before, "after": cut}) is None


def test_manifest_entries_resolve_by_name():
    by_name = {m["name"]: m for m in registry.manifest()["per_layer"]}
    for name, moves, cells in ((NAME, "itl_p95_ms", [CHAT]),
                               (NAME + ".batch", "tokens_per_s", LOOPS)):
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower", "source": "program_span",
            "layer": "scheduler", "moves": moves, "workloads": cells}
        assert os.path.isfile(
            os.path.join(registry.ROOT, "layer_metrics", name + ".py"))
    for cell in [CHAT, *LOOPS]:
        listed = {m["name"] for m in registry.resolve(cell)["per_layer"]}
        assert (NAME in listed) == (cell == CHAT)
        assert (NAME + ".batch" in listed) == (cell != CHAT)

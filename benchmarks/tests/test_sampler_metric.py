"""``sampler_device_ms_per_step`` on the recorded chip slice
(``fixtures/decode_slice.json``: one ``jit_decode_step`` of 16 535 927 ns and
its ``jit_sample_tokens`` of 7 880 110 ns, TPU v5 lite, PR 24 — the sorting
sampler), and on traces that hold no sampler module or no decode step."""
import json
import os

import pytest

from benchmarks.harness import registry, xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "decode_slice.json")


@pytest.mark.parametrize("name", ["sampler_device_ms_per_step",
                                  "sampler_device_ms_per_step.batch"])
def test_sampler_ms_per_decode_step(name):
    read = registry.load_module("layer_metrics", name).read
    with open(FIXTURE) as fh:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(fh).items()}
    assert read({"trace": xplane.reduce(planes)}) == pytest.approx(7.880110)
    # Two steps, two sampler calls of 30 ns and one first-token launch of 50.
    mods = [("jit_decode_step(1)", 0, 100), ("jit_sample_tokens(2)", 110, 30),
            ("jit_prefill_forward(3)", 150, 400), ("jit_sample_tokens(4)", 560, 50),
            ("jit_decode_step(1)", 620, 100), ("jit_sample_tokens(2)", 730, 30)]
    ops = [(f"%fusion.{i} = fusion()", s, d) for i, (_, s, d) in enumerate(mods)]
    red = xplane.reduce({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}})
    assert read({"trace": red}) == pytest.approx(1e3 * 110e-9 / 2)
    # A program whose sampler never ran as a module of that name reads 0;
    # a trace with no decode step (or no device plane) reads nothing.
    red = xplane.reduce({"/device:TPU:0": {"XLA Ops": ops[:1], "XLA Modules": mods[:1]}})
    assert read({"trace": red}) == 0.0
    red = xplane.reduce({"/device:TPU:0": {"XLA Ops": ops[2:3], "XLA Modules": mods[2:3]}})
    assert read({"trace": red}) is None
    assert read({"trace": {}}) is None


def test_manifest_lists_the_metric_in_the_cell_that_reports_what_it_moves():
    by_name = {m["name"]: m for m in registry.manifest()["per_layer"]}
    chat, batch = (by_name["sampler_device_ms_per_step"],
                   by_name["sampler_device_ms_per_step.batch"])
    assert (chat["moves"], chat["workloads"]) == ("itl_p95_ms", ["qwen2-7b.chat-sessions"])
    assert (batch["moves"], batch["workloads"]) == ("tokens_per_s", ["qwen2-7b.batch-unshared"])
    assert chat["layer"] == batch["layer"] == by_name["decode_step_device_ms"]["layer"]

"""The trace reduction: union of intervals, and no double count of nested
events — on hand-made events, and on a slice of a capture recorded on the
chip (``fixtures/decode_slice.json``: one decode step and its sampler call of
the batch cell, TPU v5 lite, PR 24; names cut to 72 characters)."""
import json
import os

import pytest

from benchmarks.harness import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "decode_slice.json")


def test_nested_events_are_not_counted_twice():
    # jit_decode_step ⊃ while ⊃ two fusions; then a gap; then a sort.
    ops = [
        ("%while.1 = while(...)", 0, 100),
        ("%fusion.1 = fusion(...)", 10, 30),
        ("%fusion.2 = fusion(...)", 50, 40),
        ("%sort.3 = sort(...)", 150, 50),
    ]
    mods = [("jit_decode_step(1)", 0, 100), ("jit_sample_tokens(2)", 150, 50)]
    red = xplane.reduce({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}})
    plane = red["planes"]["/device:TPU:0"]
    assert plane["busy_s"] == pytest.approx(150e-9)  # union, not 220
    assert plane["op_self_s"]["while.1"] == pytest.approx(30e-9)  # 100 - 30 - 40
    assert sum(plane["op_self_s"].values()) == pytest.approx(plane["busy_s"])
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["idle_share_worst"] == pytest.approx(0.25)
    assert plane["gaps"] == {
        "jit_decode_step -> jit_sample_tokens": {"count": 1, "seconds": pytest.approx(50e-9)}}
    assert xplane.modules_matching(red, "jit_decode") == (1, pytest.approx(100e-9))


def test_kernel_events_are_found_by_the_repo_kernel_names():
    ops = [("%radixmesh_decode_fused_mh.5 = bf16[8,28,128] custom-call(...)", 0, 7),
           ("%radixmesh_decode_fused_mh.5 = bf16[8,28,128] custom-call(...)", 10, 7)]
    red = xplane.reduce({"/device:TPU:0": {"XLA Ops": ops}})
    assert red["planes"]["/device:TPU:0"]["kernels"] == {
        "radixmesh_decode_fused_mh": {"count": 2, "seconds": pytest.approx(14e-9)}}


def test_worst_plane_sets_the_idle_share_and_busy_is_the_mean():
    a = {"XLA Ops": [("%f.1 = fusion()", 0, 80)]}
    b = {"XLA Ops": [("%f.1 = fusion()", 0, 40)]}
    red = xplane.reduce({"/device:TPU:0": a, "/device:TPU:1": b}, profiled_s=100e-9)
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["idle_share_worst"] == pytest.approx(0.6)


@pytest.mark.skipif(not os.path.isfile(FIXTURE), reason="no recorded slice")
def test_recorded_chip_slice():
    with open(FIXTURE) as fh:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(fh).items()}
    red = xplane.reduce(planes)
    plane = next(iter(red["planes"].values()))
    naive = sum(d for _, _, d in next(iter(planes.values()))[xplane.OPS_LINE]) / 1e9
    # Self times add up to the union; durations by name would overshoot it.
    assert sum(plane["op_self_s"].values()) == pytest.approx(plane["busy_s"], rel=1e-6)
    assert naive > plane["busy_s"]
    assert plane["busy_s"] <= red["window_s"]
    n, s = xplane.modules_matching(red, "jit_decode_step")
    assert n >= 1 and s > 0
    assert any(k.startswith("radixmesh_decode") for k in plane["kernels"])

"""The two XLA pieces of learned sparse attention in a capture's op line.

The index scoring and the attention over gathered rows are Pallas kernels
(``radixmesh_dsa_index_*``, ``radixmesh_mla_sparse_*``): ``xplane.reduce``
finds them by name. The selection and the gather of the selected rows are
XLA's own ops, named ``while.N`` / ``sort.N`` / ``fusion.N`` on the trace —
but an event's name is the instruction's text, result shape first, and the
shapes are this configuration's alone. The selection
(``ops/dsa.py::_pick_by_threshold``) is: a bisection loop, ``while`` over
``u32[rows, scores]`` with more scores than ``index_topk``; a sort of
128-score segments, ``sort`` of ``s32[rows, segments, 128]``; and the
compaction's gather, ``s32[n x index_topk] fusion(s32[rows, scores], ...)``
(a short row's ``lax.top_k`` is a ``sort`` of ``f32[rows, 1, scores]``:
matched too); its masks, running counts and segment lookup are unnamed
fusions OUTSIDE what is summed here. The gather of the rows yields
``bf16[n x index_topk, stored row width]``. ``classify`` sums the device
time of both inside the executions of one module; ``of_run`` finds the
run's capture (it is still on disk when the readers run) and memoises.
"""
from __future__ import annotations

import bisect
import os
import re

from benchmarks.harness import hostspans, registry, xplane
from benchmarks.harness.opsbytes_dsa import stored_row_width

_SORT = re.compile(r"^%?sort[.\w-]* = \(?f32\[(\d+),(\d+),(\d+)\]")
_SEGMENT_SORT = re.compile(r"^%?sort[.\w-]* = \(?s32\[(\d+),(\d+),128\]")
_BISECTION = re.compile(r"^%?while[.\w-]* = \(.{0,160}?u32\[(\d+),(\d+)\]")
_COMPACTION = re.compile(
    r"^%?fusion[.\w-]* = s32\[(\d+)\][^ ]* fusion\(s32\[(\d+),(\d+)\]")
_GATHER = re.compile(r"^%?[\w.-]+ = bf16\[(\d+),(\d+)\][^ ]* fusion\(")


def _is_selection(name: str, topk: int) -> bool:
    m = _SORT.match(name)
    if m:
        return int(m.group(3)) > topk
    m = _SEGMENT_SORT.match(name)
    if m:
        return int(m.group(2)) * 128 > topk
    m = _BISECTION.match(name)
    if m:
        return int(m.group(2)) > topk
    m = _COMPACTION.match(name)
    return bool(m) and int(m.group(1)) % topk == 0 and int(m.group(3)) > topk


def classify(planes: dict, config: dict, module: str = "jit_decode_step") -> dict | None:
    """``{"executions", "select_s", "gather_s", "selects", "gathers"}`` over
    the executions of ``module`` on the first device plane, or None when
    the plane has no such module."""
    if not planes:
        return None
    lines = next(iter(planes.values()))
    ops = lines.get(xplane.OPS_LINE)
    mods = [(s, s + d) for n, s, d in lines.get(xplane.MODULES_LINE, [])
            if xplane.module_name(n) == module]
    if not ops or not mods:
        return None
    topk, width = int(config["index_topk"]), stored_row_width(config)
    mods.sort()
    out = {"executions": len(mods), "select_s": 0.0, "gather_s": 0.0,
           "selects": 0, "gathers": 0}
    starts = [a for a, _ in mods]
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= mods[i][1]:
            continue
        if _is_selection(name, topk):
            out["select_s"] += dur / 1e9
            out["selects"] += 1
            continue
        m = _GATHER.match(name)
        if m and int(m.group(2)) == width and int(m.group(1)) % topk == 0:
            out["gather_s"] += dur / 1e9
            out["gathers"] += 1
    return out


_MEMO: dict = {}  # (capture path, module) -> classify's result


def of_run(ctx: dict, module: str = "jit_decode_step") -> dict | None:
    """``classify`` of the capture of the run ``ctx`` describes (parsed
    once for all readers); None for a configuration without an indexer, a
    run without a capture, or a program without the ops."""
    config = ctx["config"]
    if "index_topk" not in config or not (ctx.get("trace") or {}).get("planes"):
        return None
    profile = os.path.join(registry.ROOT, ".work", ctx["cell"]["name"], "profile")
    path = xplane.find_capture(profile)
    if path is None:
        return None
    if (path, module) not in _MEMO:
        _MEMO[path, module] = classify(hostspans.device_planes(path), config, module)
    got = _MEMO[path, module]
    return got if got and (got["selects"] or got["gathers"]) else None

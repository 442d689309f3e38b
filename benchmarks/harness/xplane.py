"""Reduction of a profiler capture (``.xplane.pb``) to device time.

Two steps, so the arithmetic can be checked on a small recorded fixture:
:func:`load` reads the capture with ``jax.profiler.ProfileData`` (nothing
but JAX, forced to the CPU — call it only after the server child has
exited) into plain ``{plane: {line: [(name, start_ns, dur_ns), ...]}}``
for the device planes; :func:`reduce` turns that into busy time, per-module
and per-op device time, and the idle gaps.

Events on one line nest (``jit_decode_step`` ⊃ ``while`` ⊃ fusions): adding
durations by name counts the same nanosecond several times. Here an op's
SELF time is its duration minus its children's, and busy time is the UNION
of the op intervals.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_capture(profile_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str) -> dict:
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events]
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(events: list[tuple[str, int, int]]) -> list[tuple[str, int, int, int]]:
    """``(name, start, dur, self_ns)`` per event of one line: duration less
    the time covered by the events nested directly inside it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e[2] for e in events]
    stack: list[int] = []
    for i in order:
        _, start, dur = events[i]
        while stack and start >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= dur
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(self_ns[i], 0))
            for i in range(len(events))]


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def module_name(name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return name.split("(", 1)[0].strip()


def reduce(planes: dict, profiled_s: float | None = None) -> dict:
    """Per device plane: busy seconds (union of op intervals), modules
    (count and device seconds by name), ops (self seconds by name), the
    idle gaps by the modules on either side. Across planes: the traced window, mean busy, worst idle
    share, and the lists the result line's ``breakdown`` carries."""
    per_plane = {}
    t_min, t_max = None, None
    for pname, lines in planes.items():
        ops = lines.get(OPS_LINE)
        if ops is None:  # an unknown runtime: take the fullest line
            ops = max(lines.values(), key=len)
        mods = lines.get(MODULES_LINE, [])
        busy_iv = _union([(s, s + d) for _, s, d in ops])
        busy_ns = sum(b - a for a, b in busy_iv)
        t_min = busy_iv[0][0] if t_min is None else min(t_min, busy_iv[0][0])
        t_max = busy_iv[-1][1] if t_max is None else max(t_max, busy_iv[-1][1])
        modules: dict = {}
        for name, _, d in mods:
            m = modules.setdefault(module_name(name), [0, 0])
            m[0] += 1
            m[1] += d
        op_self: dict = {}
        kernels: dict = {}
        mod_iv = sorted((s, s + d, module_name(n)) for n, s, d in mods)
        for name, _, dur, self_ns in self_times(ops):
            key = short_name(name)
            op_self[key] = op_self.get(key, 0) + self_ns
            k = re.match(r"%?(radixmesh_[a-z0-9_]+?)(?:\.\d+)?(?:\s|$|=)", name)
            if k:
                c = kernels.setdefault(k.group(1), [0, 0])
                c[0] += 1
                c[1] += dur
        gaps: dict = {}
        for (_, end_a), (start_b, _) in zip(busy_iv, busy_iv[1:]):
            label = f"{_owner(mod_iv, end_a - 1)} -> {_owner(mod_iv, start_b)}"
            g = gaps.setdefault(label, [0, 0])
            g[0] += 1
            g[1] += start_b - end_a
        per_plane[pname] = {
            "busy_s": busy_ns / 1e9,
            "modules": {k: {"count": c, "seconds": d / 1e9}
                        for k, (c, d) in modules.items()},
            "op_self_s": {k: v / 1e9 for k, v in op_self.items()},
            "kernels": {k: {"count": c, "seconds": d / 1e9}
                        for k, (c, d) in kernels.items()},
            "gaps": {k: {"count": c, "seconds": d / 1e9}
                     for k, (c, d) in gaps.items()},
        }
    if not per_plane:
        return {"planes": {}, "window_s": profiled_s or 0.0, "busy_s": 0.0}
    span_s = (t_max - t_min) / 1e9
    window_s = max(span_s, profiled_s or 0.0)
    busy = [p["busy_s"] for p in per_plane.values()]
    first = next(iter(per_plane.values()))
    return {
        "planes": per_plane,
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "idle_share_worst": 1.0 - min(busy) / window_s if window_s else None,
        "device_ops": [[k, v] for k, v in sorted(
            first["op_self_s"].items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v["seconds"]] for k, v in sorted(
            first["gaps"].items(), key=lambda kv: -kv[1]["seconds"])[:10]],
    }


def _owner(mod_iv: list[tuple[int, int, str]], t: int) -> str:
    """The module whose interval holds instant ``t`` (``mod_iv`` sorted)."""
    i = bisect.bisect_right(mod_iv, (t, float("inf"), "")) - 1
    if i >= 0 and mod_iv[i][0] <= t < mod_iv[i][1]:
        return mod_iv[i][2]
    return "(no module)"


def modules_matching(reduced: dict, prefix: str, plane: str | None = None) -> tuple[int, float]:
    """Count and device seconds of the modules whose name starts with
    ``prefix`` on one plane (default: the first)."""
    planes = reduced.get("planes") or {}
    if not planes:
        return 0, 0.0
    p = planes[plane] if plane else next(iter(planes.values()))
    n = sum(m["count"] for k, m in p["modules"].items() if k.startswith(prefix))
    s = sum(m["seconds"] for k, m in p["modules"].items() if k.startswith(prefix))
    return n, s


def fixture_slice(planes: dict, module: str = "jit_decode_step", steps: int = 3,
                  skip: int = 5) -> dict:
    """A few steps of a capture as plain JSON, for a checked-in test
    fixture: from the start of the ``skip``-th execution of ``module`` to
    the start of the one ``steps`` later, every event of every line of the
    first device plane that starts in between (names cut to 100
    characters, times relative)."""
    pname, lines = next(iter(planes.items()))
    starts = sorted(s for n, s, _ in lines.get(MODULES_LINE, [])
                    if module_name(n) == module)
    if len(starts) <= skip + steps:
        return {}
    a, b = starts[skip], starts[skip + steps]
    return {pname: {
        lname: [[n[:100], s - a, d] for n, s, d in evs if a <= s < b]
        for lname, evs in lines.items()
    }}

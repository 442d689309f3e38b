"""Device-idle time of a profiler capture, named by what the engine thread
was doing.

The program writes its host spans (``rm.*``, ``radixmesh_tpu/obs/tracing.py``
``annotate`` = ``jax.profiler.TraceAnnotation``) into the profiler's own
trace, plane ``/host:CPU``, one line per OS thread — so they are on the
device planes' clock and no two processes' clocks are aligned here. Lines
are told apart by the spans they hold, never by their names (every python
thread's line is called ``python``): the ENGINE line is the one holding
``rm.engine.step``; ``rm.http.emit`` lives on the handler threads' lines.

:func:`attribute` gives every idle nanosecond of the device (complement of
the busy union of the first device plane's op line, as
``xplane.reduce`` builds it) to

- the INNERMOST ``rm.*`` span of the engine line covering it, else
- ``outside_step.http_emit`` if an ``rm.http.emit`` span of any other line
  covers it, else ``outside_step.other`` (the runner's lock hand-over:
  ``time.sleep(0)``, re-taking the lock and the GIL), else
- ``unattributed`` (before the engine line's first span or after its last).

Whether the two clocks really are one is checked by causality on every
decode step of the capture (:func:`causality`): a dispatch span begins
before the device module it launched, and the sync span ends after the
sampler's device module has. A negative margin, and nothing is attributed.

:func:`of_cell` is what the per-layer readers call: it finds the run's
capture by convention (``benchmarks/.work/<cell>/profile``) and memoises
the parse per capture path. Works under ``--trace 1`` as under ``--trace 2``.
"""
from __future__ import annotations

import bisect
import functools
import os

from . import xplane
from .registry import ROOT

PREFIX = "rm."
STEP = "rm.engine.step"
DISPATCH = "rm.decode.dispatch"
SYNC = "rm.decode.sync"
EMIT = "rm.http.emit"
OUTSIDE_EMIT = "outside_step.http_emit"
OUTSIDE_OTHER = "outside_step.other"
UNATTRIBUTED = "unattributed"
DECODE_MODULE = "jit_decode_step"
SAMPLE_MODULE = "jit_sample_tokens"


def load_host(path: str) -> list[list[tuple[str, int, int]]]:
    """The ``rm.*`` events of ``/host:CPU``, one list per line that has
    any: ``[(name, start_ns, dur_ns), ...]``. JAX on the CPU, as
    ``xplane.load``: call it only after the server child has exited."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                lines.append(evs)
    return lines


def engine_line(lines: list[list[tuple]]) -> list[tuple] | None:
    """The line with the most ``rm.engine.step`` spans (None: no such span)."""
    best = max(lines, key=lambda evs: sum(e[0] == STEP for e in evs), default=None)
    return best if best and any(e[0] == STEP for e in best) else None


def flatten(events: list[tuple[str, int, int]]) -> list[tuple[int, int, str | None, bool]]:
    """One line's nested spans as consecutive segments ``(start, end,
    innermost name or None, inside rm.engine.step)`` from its first span's
    start to its last span's end. A child that outlives its parent is cut
    at the parent's end."""
    segs: list[tuple[int, int, str | None, bool]] = []
    stack: list[tuple[str, int]] = []  # (name, end)
    cursor = None

    def emit(until: int) -> None:
        nonlocal cursor
        if until > cursor:
            segs.append((cursor, until, stack[-1][0] if stack else None,
                         any(n == STEP for n, _ in stack)))
            cursor = until

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if cursor is None:
            cursor = start
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        end = start + dur
        stack.append((name, min(end, stack[-1][1]) if stack else end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return segs


def idle_intervals(ops: list[tuple[str, int, int]]) -> list[tuple[int, int]]:
    busy = xplane._union([(s, s + d) for _, s, d in ops])
    return [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:])]


def _overlap(iv: list[tuple[int, int]], a: int, b: int) -> int:
    """Nanoseconds of ``[a, b)`` covered by the sorted disjoint ``iv``."""
    i = max(bisect.bisect_right(iv, (a, float("inf"))) - 1, 0)
    total = 0
    while i < len(iv) and iv[i][0] < b:
        total += max(0, min(b, iv[i][1]) - max(a, iv[i][0]))
        i += 1
    return total


def attribute(lines: list[list[tuple]], ops: list[tuple[str, int, int]]) -> dict | None:
    """Idle seconds of the device by label (see the module text), the
    number of decode steps (``rm.decode.dispatch`` spans of the engine
    line) and the totals the per-layer metrics are made of. None when the
    capture holds no ``rm.engine.step`` span or the device ran nothing."""
    engine = engine_line(lines)
    if engine is None or not ops:
        return None
    segs = flatten(engine)
    emit = xplane._union([(s, s + d) for evs in lines if evs is not engine
                          for n, s, d in evs if n == EMIT])
    by: dict[str, int] = {}
    in_step = outside = 0

    def give(label: str, ns: int) -> None:
        if ns > 0:
            by[label] = by.get(label, 0) + ns

    lo, hi = segs[0][0], segs[-1][1]
    i = 0
    for a, b in idle_intervals(ops):
        give(UNATTRIBUTED, max(0, min(b, lo) - a) + max(0, b - max(a, hi)))
        a, b = max(a, lo), min(b, hi)
        if a >= b:
            continue
        while segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            s0, s1 = max(a, segs[j][0]), min(b, segs[j][1])
            name, inside = segs[j][2], segs[j][3]
            if name is not None:
                give(name, s1 - s0)
                if inside:
                    in_step += s1 - s0
                else:
                    outside += s1 - s0
            else:
                emitting = _overlap(emit, s0, s1)
                give(OUTSIDE_EMIT, emitting)
                give(OUTSIDE_OTHER, s1 - s0 - emitting)
                outside += s1 - s0
            j += 1
    idle = sum(by.values())
    return {
        "steps": sum(e[0] == DISPATCH for e in engine),
        "idle_s": idle / 1e9,
        "in_step_s": in_step / 1e9,
        "outside_step_s": outside / 1e9,
        "unattributed_s": by.get(UNATTRIBUTED, 0) / 1e9,
        "by_span": [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])],
    }


def causality(lines: list[list[tuple]], modules: list[tuple[str, int, int]]) -> dict | None:
    """Per decode step of the capture, two margins in microseconds that
    are positive iff host and device events are on one clock:
    ``dispatch_to_device`` (``rm.decode.dispatch`` begins → the
    ``jit_decode_step`` module it launched begins) and ``sampler_to_sync``
    (that step's ``jit_sample_tokens`` module ends → ``rm.decode.sync``
    ends). A device module belongs to the host step whose dispatch began
    last before the module's MIDPOINT (so a small negative offset still
    lands on its own step and reads negative); a step's sampler module is
    the first that starts after its decode module (device order alone).
    The device's tracer runs on after the host's last event (up to 170 ms
    on the chip): a module whose midpoint lies past the engine line's last
    span was launched by a dispatch the capture does not hold, and is no
    step of it. None when the capture has no such step (the fused
    multi-step launch, a CPU)."""
    engine = engine_line(lines)
    if engine is None:
        return None
    host_end = max(s + d for _, s, d in engine)
    dispatch = sorted(s for n, s, _ in engine if n == DISPATCH)
    sync_end = sorted(s + d for n, s, d in engine if n == SYNC)
    decode = sorted((s, d) for n, s, d in modules
                    if xplane.module_name(n).startswith(DECODE_MODULE))
    sample = sorted((s, d) for n, s, d in modules
                    if xplane.module_name(n).startswith(SAMPLE_MODULE))
    sample_start = [s for s, _ in sample]
    a_us, b_us = [], []
    for k, (s, d) in enumerate(decode):
        i = bisect.bisect_right(dispatch, s + d // 2) - 1
        if i < 0 or s + d // 2 > host_end:
            continue  # launched before the host's first event or after its last
        nxt = dispatch[i + 1] if i + 1 < len(dispatch) else None
        j = bisect.bisect_left(sync_end, dispatch[i])
        if j == len(sync_end) or (nxt is not None and sync_end[j] > nxt):
            continue  # the capture ended inside this step
        a_us.append((s - dispatch[i]) / 1e3)
        m = bisect.bisect_left(sample_start, s)
        next_decode = decode[k + 1][0] if k + 1 < len(decode) else None
        if m < len(sample) and (next_decode is None or sample[m][0] < next_decode):
            b_us.append((sync_end[j] - (sample[m][0] + sample[m][1])) / 1e3)
    if not a_us:
        return None
    out = {"steps_checked": len(a_us),
           "dispatch_to_device_us": [min(a_us), max(a_us)]}
    if b_us:
        out["sampler_to_sync_us"] = [min(b_us), max(b_us)]
    out["clocks_shared"] = min(a_us) >= 0 and (not b_us or min(b_us) >= 0)
    return out


@functools.lru_cache(maxsize=2)
def device_planes(path: str) -> dict:
    """``xplane.load(path)``, parsed once for ``run.py`` and for here."""
    return xplane.load(path)


@functools.lru_cache(maxsize=2)
def of_capture(path: str) -> dict | None:
    """Attribution + causality of one capture file (memoised: six readers
    and ``run.py`` share one parse). None: no ``rm.*`` span or no device
    plane. Clocks that are not one: the causality record alone, nothing
    attributed (``steps`` 0)."""
    lines = load_host(path)
    planes = device_planes(path)
    if not lines or not planes:
        return None
    first = next(iter(planes.values()))
    ops = first.get(xplane.OPS_LINE) or max(first.values(), key=len)
    out = attribute(lines, ops)
    if out is None:
        return None
    out["causality"] = causality(lines, first.get(xplane.MODULES_LINE, []))
    if out["causality"] is not None and not out["causality"]["clocks_shared"]:
        return {"causality": out["causality"], "steps": 0}  # nothing attributed
    return out


def of_cell(ctx: dict) -> dict | None:
    """The attribution of the run's capture, found by convention under the
    run's work directory; None when there is none or it holds nothing to
    attribute (a program without the spans: the parent commit)."""
    path = xplane.find_capture(os.path.join(ROOT, ".work", ctx["cell"]["name"], "profile"))
    got = of_capture(path) if path else None
    return got if got and got["steps"] else None


def fixture_slice(path: str, steps: int = 3, skip: int = 5) -> dict:
    """A few steps of a capture as plain JSON for a checked-in test
    fixture: the ``rm.*`` spans of every line and the first device plane's
    op and module events that start between the start of the ``skip``-th
    ``rm.engine.step`` span and that of the one ``steps`` later (times
    relative, op names cut to 60 characters)."""
    lines = load_host(path)
    engine = engine_line(lines)
    planes = device_planes(path)
    if engine is None or not planes:
        return {}
    starts = sorted(s for n, s, _ in engine if n == STEP)
    if len(starts) <= skip + steps:
        return {}
    a, b = starts[skip], starts[skip + steps]
    first = next(iter(planes.values()))
    return {
        "host": [[[n, s - a, d] for n, s, d in evs if a <= s < b] for evs in lines],
        "device": {ln: [[n[:60], s - a, d] for n, s, d in evs if a <= s < b]
                   for ln, evs in first.items()
                   if ln in (xplane.OPS_LINE, xplane.MODULES_LINE)},
    }

"""The gated short convolution in a capture's op line.

The operator is XLA's own ops (two products, the gates, the three taps):
``fusion.N`` / ``dot.N`` on the trace, no kernel of ours. The program runs
it under ``jax.named_scope("rm_shortconv")`` (``models/llama.py::
_short_conv``), and the profiler keeps every op's framework name — the
scope path — beside the event (a stat of the event or of its metadata:
``ProfileData`` yields both). ``scope_seconds`` sums the device time of the
events whose name or any string stat holds the scope, inside the
executions of one module; events that hold other matched events (a loop
around them) are not counted twice. Where a capture carries no framework
names, the operator is found as ``harness/dsa_trace.py`` finds its pieces,
by a shape that is this operator's alone: an event's name is the
instruction's text, and only the conv layers make or read a tensor ``3 x
hidden_size`` wide (the three gates side by side) — the in-projection, the
fusions that gate and convolve, and the out-projection where the gating is
fused into it; an out-projection that is a product of its own is then
missed, so that reading can only understate. A configuration without conv
layers, a run without a capture: nothing to read.
"""
from __future__ import annotations

import bisect
import os
import re

from benchmarks.harness import registry, xplane

SCOPE = "rm_shortconv"


def load_scoped(path: str, scope: str = SCOPE, gates_width: int = 0) -> dict:
    """``{"modules": [(name, start, dur)], "scoped": [(start, dur)],
    "by": "scope" | "shape"}`` of the first device plane: every module
    execution, and every op event that carries ``scope`` — or, where none
    does and ``gates_width`` is given, every op event whose instruction
    text holds a tensor that wide. JAX on the CPU, as ``xplane.load``: call
    it only after the server child has exited."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.profiler import ProfileData

    wide = re.compile(rf"[\[,]{gates_width}[,\]]") if gates_width else None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        out = {"modules": [], "scoped": [], "by": "scope"}
        shaped = []
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                out["modules"] = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                                  for ev in line.events]
            elif line.name == xplane.OPS_LINE:
                for ev in line.events:
                    at = (int(ev.start_ns), int(ev.duration_ns))
                    if scope in ev.name or any(
                            isinstance(v, str) and scope in v for _, v in ev.stats):
                        out["scoped"].append(at)
                    elif wide is not None and not ev.name.lstrip("%").startswith(
                            ("while", "conditional", "call")) and wide.search(ev.name):
                        shaped.append(at)
        if out["modules"]:
            if not out["scoped"] and shaped:
                out.update(scoped=shaped, by="shape")
            return out
    return {"modules": [], "scoped": [], "by": "scope"}


def scope_seconds(loaded: dict, module: str = "jit_decode_step") -> dict | None:
    """``{"executions", "seconds", "events"}``: device seconds of the
    scoped events inside the executions of ``module`` (the union of their
    intervals: a matched loop and the matched ops inside it count once)."""
    mods = sorted((s, s + d) for n, s, d in loaded["modules"]
                  if xplane.module_name(n) == module)
    if not mods or not loaded["scoped"]:
        return None
    starts = [a for a, _ in mods]
    inside = []
    for start, dur in loaded["scoped"]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < mods[i][1]:
            inside.append((start, start + dur))
    if not inside:
        return None
    merged = xplane._union(inside)
    return {"executions": len(mods), "events": len(inside),
            "seconds": sum(b - a for a, b in merged) / 1e9}


_MEMO: dict = {}  # capture path -> load_scoped's result


def of_run(ctx: dict, module: str = "jit_decode_step") -> dict | None:
    """``scope_seconds`` of the capture of the run ``ctx`` describes
    (parsed once for all readers); None for a configuration without conv
    layers, a run without a capture, or a program without the scope."""
    if "conv_L_cache" not in ctx["config"] or not (
            ctx.get("trace") or {}).get("planes"):
        return None
    profile = os.path.join(registry.ROOT, ".work", ctx["cell"]["name"], "profile")
    path = xplane.find_capture(profile)
    if path is None:
        return None
    if path not in _MEMO:
        _MEMO[path] = load_scoped(
            path, gates_width=3 * int(ctx["config"]["hidden_size"]))
    return scope_seconds(_MEMO[path], module)


def describe(path: str, module: str = "jit_decode_step",
             gates_width: int = 6144) -> dict:
    """What a capture offers this reader, for a person: whether the
    operator was found by its scope or by its shape, and ``scope_seconds``.
    ``python -m benchmarks.harness.lfm2_trace <capture>``."""
    loaded = load_scoped(path, gates_width=gates_width)
    return {"found_by": loaded["by"], "matched_events": len(loaded["scoped"]),
            **(scope_seconds(loaded, module) or {})}


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(describe(xplane.find_capture(sys.argv[1]) or sys.argv[1]),
                     indent=1))

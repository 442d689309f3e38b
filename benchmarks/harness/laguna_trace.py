"""The two attention kinds of a configuration with ``num_attention_heads_per_layer``
in a capture's op line.

Both kinds' decode attention is ONE kernel (``radixmesh_decode_fused_mh``).
A launch is told apart by the scope the program runs it under
(``models/llama.py``: ``rm_full_attn`` on the pool, ``rm_window_attn`` on
the window group) or — the chip's captures carry no framework names (PR
39) — by the array the custom call aliases, which is in the instruction's
text (an event's name): ``[2, full layers, kv heads, pages, page, head]``
against ``[2, sliding layers, ...]``. The two counts differ in every cut
this benchmark runs (2 and 3); were they equal, the shapes could not tell
the groups apart and nothing is read. ``harness/lfm2_trace.py`` does the
arithmetic. Another configuration, a run without a capture, a program
without the kernel: nothing to read."""
from __future__ import annotations

import os
import re

from benchmarks.harness import lfm2_trace, registry, xplane
from benchmarks.harness.opsbytes_laguna import FULL, SLIDING, _count
from benchmarks.harness.phi4flash_trace import decoded_contexts  # noqa: F401 - the
# client's ledger as contexts: nothing of it is that configuration's

KERNEL = "radixmesh_decode_fused_mh"
PARTS = {"full_attn": ("rm_full_attn", FULL), "window_attn": ("rm_window_attn", SLIDING)}
_MEMO: dict = {}  # (capture path, part) -> load_part's result


def is_cell(ctx: dict) -> bool:
    return "num_attention_heads_per_layer" in ctx["config"]


def shape_of(config: dict, part: str) -> re.Pattern | None:
    """The text that marks ``part``'s launches where no scope does."""
    if _count(config, FULL) == _count(config, SLIDING):
        return None
    return re.compile(r"\[2,%d,%d,\d+,\d+,%d\]" % (
        _count(config, PARTS[part][1]), config["num_key_value_heads"],
        config["head_dim"]))


def load_part(path: str, config: dict, part: str) -> dict:
    """``lfm2_trace.load_scoped``'s record for ``part`` of the first device
    plane: the kernel's events that carry its scope, else those its shape
    marks. JAX on the CPU: call it only after the server child has exited."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.profiler import ProfileData

    scope, shape = PARTS[part][0], shape_of(config, part)
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
                    if KERNEL not in ev.name:
                        continue
                    at = (int(ev.start_ns), int(ev.duration_ns))
                    texts = [ev.name, *(v for _, v in ev.stats if isinstance(v, str))]
                    if any(scope in t for t in texts):
                        out["scoped"].append(at)
                    elif shape is not None and any(shape.search(t) for t in texts):
                        shaped.append(at)
        if out["modules"]:
            if not out["scoped"] and shaped:
                out.update(scoped=shaped, by="shape")
            return out
    return {"modules": [], "scoped": [], "by": "scope"}


def of_run(ctx: dict, part: str, module: str = "jit_decode_step") -> dict | None:
    """``{"executions", "seconds", "events"}`` of ``part`` inside the
    executions of ``module`` in the capture of the run ``ctx`` describes."""
    if not is_cell(ctx) or not (ctx.get("trace") or {}).get("planes"):
        return None
    profile = os.path.join(registry.ROOT, ".work", ctx["cell"]["name"], "profile")
    path = xplane.find_capture(profile)
    if path is None:
        return None
    if (path, part) not in _MEMO:
        _MEMO[path, part] = load_part(path, ctx["config"], part)
    return lfm2_trace.scope_seconds(_MEMO[path, part], module)


def decode_steps(ctx: dict) -> int:
    a, b = ctx["before"]["state"]["engine"], ctx["after"]["state"]["engine"]
    return b["decode_steps"] - a["decode_steps"]


def describe(path: str, config: dict) -> dict:
    """What a capture offers these readers, for a person.
    ``python -m benchmarks.harness.laguna_trace <capture>``."""
    out = {}
    for part in PARTS:
        loaded = load_part(path, config, part)
        out[part] = {"found_by": loaded["by"], "matched_events": len(loaded["scoped"]),
                     **(lfm2_trace.scope_seconds(loaded) or {})}
    return out


if __name__ == "__main__":
    import json
    import sys

    conf = registry.resolve("laguna-xs.2-l5.agent-sessions")["config"]
    print(json.dumps(describe(xplane.find_capture(sys.argv[1]) or sys.argv[1], conf),
                     indent=1))

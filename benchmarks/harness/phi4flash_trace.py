"""A decoder-hybrid-decoder's operators in a capture's op line.

Three parts of a decode step are read, each by the scope the program runs
it under (``models/llama.py``) or — the chip's captures carry no framework
names (PR 39, call 1) — by a SHAPE in the instruction's text that is that
part's alone (an event's name is the instruction's text):

- ``ssm``: the state-space side — 9 Mamba operators (``rm_ssm``) and 7
  Gated Memory Units (``rm_gmu``). By shape: every product and fusion of
  either makes or reads a tensor ``d_inner`` (5 120), ``dt_rank`` (160) or
  ``dt_rank + 2 d_state`` (192) wide, and nothing else in the model does
  (hidden 2 560, FFN 10 240, heads x 64 = 2 560 / 1 280, vocabulary
  200 064): that is why ``in_x`` / ``in_z`` are stored apart. What this
  misses (an elementwise fusion that XLA gave another shape) can only
  understate.
- ``shared_kv_attn`` / ``window_attn``: the launches of
  ``radixmesh_decode_fused_mh`` on the full layer's rows (layer 17 and the
  7 cross-attention layers) and on the window group (8 sliding layers) —
  ONE kernel name. By shape: the custom call's text holds the pool it
  aliases, ``[2, 1, 10, pages, 16, 128]`` against ``[2, 8, 10, ...]``.

``harness/lfm2_trace.py`` does the arithmetic (device time of the matched
events inside the executions of one module, matched loops counted once).
A configuration without state-space layers, a run without a capture, a
program without the operators: nothing to read."""
from __future__ import annotations

import os
import re

from benchmarks.harness import lfm2_trace, registry, xplane
from benchmarks.harness.opsbytes_phi4flash import _count

KERNEL = "radixmesh_decode_fused_mh"
SCOPES = {"ssm": ("rm_ssm", "rm_gmu"), "shared_kv_attn": ("rm_shared_kv_attn",),
          "window_attn": ("rm_window_attn",)}
_MEMO: dict = {}  # (capture path, part) -> {"modules", "scoped", "by"}


def is_cell(ctx: dict) -> bool:
    return "mamba" in (ctx["config"].get("layer_types") or ())


def shape_of(config: dict, part: str) -> re.Pattern:
    """The text that marks ``part``'s events where no scope does."""
    if part == "ssm":
        a = config["assumed_sizes"]
        dims = {a["d_inner"], a["dt_rank"], a["dt_rank"] + 2 * a["d_state"]}
        return re.compile(r"[\[,](?:%s)[,\]]" % "|".join(map(str, sorted(dims))))
    layers = _count(config, "full_attention" if part == "shared_kv_attn"
                    else "sliding_attention")
    heads = config["num_key_value_heads"] // 2  # two heads of 64 to a row
    return re.compile(r"\[2,%d,%d,\d+,\d+,128\]" % (layers, heads))


def load_part(path: str, config: dict, part: str) -> dict:
    """``lfm2_trace.load_scoped``'s record for ``part`` of the first device
    plane: the events that carry one of its scopes, else those its shape
    marks (for the two attention parts: among the kernel's events only).
    JAX on the CPU: call it only after the server child has exited."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.profiler import ProfileData

    scopes, shape = SCOPES[part], shape_of(config, part)
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
                    texts = [ev.name, *(v for _, v in ev.stats if isinstance(v, str))]
                    if any(s in t for s in scopes for t in texts):
                        out["scoped"].append(at)
                    elif ev.name.lstrip("%").startswith(("while", "conditional", "call")):
                        continue  # a loop around every layer: not an operator
                    elif part == "ssm" or KERNEL in ev.name:
                        if any(shape.search(t) for t in texts):
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


def window_rows(ctx: dict) -> float | None:
    """Live rows a decode step of the window: delta ``generated_tokens`` /
    delta ``decode_steps``."""
    a, b = ctx["before"], ctx["after"]
    steps = b["state"]["engine"]["decode_steps"] - a["state"]["engine"]["decode_steps"]
    if steps <= 0:
        return None
    return (b["stats"]["generated_tokens"] - a["stats"]["generated_tokens"]) / steps


def decoded_contexts(ctx: dict) -> list[int]:
    """The context length at every token that arrived in the window after
    a request's first (the client's ledger): what a decode step attended."""
    t0, t1 = ctx["window"]
    return [r.n_prompt + i for r in ctx["records"]
            for i, t in enumerate(r.token_t) if i and t0 <= t < t1]


def describe(path: str, config: dict) -> dict:
    """What a capture offers these readers, for a person: how each part
    was found and its seconds inside ``jit_decode_step``.
    ``python -m benchmarks.harness.phi4flash_trace <capture>``."""
    out = {}
    for part in SCOPES:
        loaded = load_part(path, config, part)
        out[part] = {"found_by": loaded["by"], "matched_events": len(loaded["scoped"]),
                     **(lfm2_trace.scope_seconds(loaded) or {})}
    return out


if __name__ == "__main__":
    import json
    import sys

    conf = registry.resolve("phi-4-mini-flash.reasoning-sessions")["config"]
    print(json.dumps(describe(xplane.find_capture(sys.argv[1]) or sys.argv[1], conf),
                     indent=1))

"""What the readers of the configuration whose Mamba-1 layers are
interleaved with multi-query attention layers share: which cell is theirs,
and the window's increments of the counters this configuration's PR added
to ``/debug/state`` ``attn`` (``decode_context_tokens``: the live rows'
context summed over decode launches; ``table_pages_max``: the widest page
table a launch carried). The decode attention is the kernel
``radixmesh_decode_fused_mh`` on the op line (``harness/xplane.py`` collects
every ``radixmesh_*`` event) and, in this configuration, nothing else
launches it: no scope or shape has to be matched. A configuration of another
layout, a run without a capture, a program without the counters (the parent
of the PR that added them): nothing to read."""
from __future__ import annotations

KERNEL = "radixmesh_decode_fused_mh"


def is_cell(ctx: dict) -> bool:
    types = set(ctx["config"].get("layer_types") or ())
    return types == {"mamba", "full_attention"}


def decode_kernel(ctx: dict) -> dict | None:
    """``{"count", "seconds"}`` of the decode attention kernel's events in
    the capture, or None without a capture."""
    planes = (ctx.get("trace") or {}).get("planes") or {}
    if not is_cell(ctx) or not planes:
        return None
    return next(iter(planes.values()))["kernels"].get(KERNEL)


def context_tokens_per_step(ctx: dict) -> float | None:
    """The live rows' context a decode step of the window attended, summed
    over the rows: delta ``attn.decode_context_tokens`` / delta
    ``decode_steps``."""
    a, b = (ctx[k]["state"] for k in ("before", "after"))
    name = "decode_context_tokens"
    if not is_cell(ctx) or name not in a.get("attn", {}) or name not in b.get(
            "attn", {}):
        return None
    steps = b["engine"]["decode_steps"] - a["engine"]["decode_steps"]
    if steps <= 0:
        return None
    return (b["attn"][name] - a["attn"][name]) / steps

"""What the readers of the configuration with KDA linear-attention layers
share: which cell is theirs, the window's live rows a decode step, and a
kernel's events in the capture's first device plane. The three kernels
carry names of their own on the op line (``radixmesh_kda_decode``,
``radixmesh_kda_chunk``, ``radixmesh_kda_boundary``: ``harness/
xplane.py`` collects every ``radixmesh_*`` event), so no scope or shape
has to be matched. A configuration without such layers, a run without a
capture, a program without the kernels or the ``kda`` counters (the parent
of the PR that added them): nothing to read."""
from __future__ import annotations


def is_cell(ctx: dict) -> bool:
    return "kda" in (ctx["config"].get("layer_types") or ())


def kernel(ctx: dict, name: str) -> dict | None:
    """``{"count", "seconds"}`` of the kernel's events in the capture, or
    None without a capture."""
    planes = (ctx.get("trace") or {}).get("planes") or {}
    if not is_cell(ctx) or not planes:
        return None
    return next(iter(planes.values()))["kernels"].get(name) or {
        "count": 0, "seconds": 0.0}


def counters(ctx: dict) -> dict | None:
    """The window's increments of ``/debug/state`` ``kda``, with the
    engine's decode steps and prefill launches beside them."""
    a, b = (ctx[k]["state"] for k in ("before", "after"))
    if not is_cell(ctx) or "kda" not in a or "kda" not in b:
        return None
    out = {k: b["kda"][k] - a["kda"][k] for k in b["kda"]}
    ea, eb = a["engine"], b["engine"]
    out["decode_steps"] = eb["decode_steps"] - ea["decode_steps"]
    out["prefill_launches"] = eb["prefill"]["launches"] - ea["prefill"]["launches"]
    out["bytes_per_slot"] = b["state"]["bytes_per_slot"]
    return out

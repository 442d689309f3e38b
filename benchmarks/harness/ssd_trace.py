"""What the readers of the configuration with Mamba-2 blocks share: which
cell is theirs, the window's live rows a decode step, and a kernel's events
in the capture's first device plane. The kernels carry names of their own
on the op line (``radixmesh_ssd_decode``, ``radixmesh_ssd_chunk``; the
boundary copies are ``radixmesh_kda_boundary``, the kernel the other
configuration with a matrix state uses: ``harness/xplane.py`` collects
every ``radixmesh_*`` event), so no scope or shape has to be matched. A
configuration without such blocks, a run without a capture, a program
without the kernels or the ``ssd`` counters (the parent of the PR that
added them): nothing to read."""
from __future__ import annotations


def is_cell(ctx: dict) -> bool:
    return "mamba2" in (ctx["config"].get("layer_types") or ())


def kernel(ctx: dict, name: str) -> dict | None:
    """``{"count", "seconds"}`` of the kernel's events in the capture, or
    None without a capture."""
    planes = (ctx.get("trace") or {}).get("planes") or {}
    if not is_cell(ctx) or not planes:
        return None
    return next(iter(planes.values()))["kernels"].get(name) or {
        "count": 0, "seconds": 0.0}


def counters(ctx: dict) -> dict | None:
    """The window's increments of ``/debug/state`` ``ssd``, with the
    engine's decode steps and prefill launches beside them."""
    a, b = (ctx[k]["state"] for k in ("before", "after"))
    if not is_cell(ctx) or "ssd" not in a or "ssd" not in b:
        return None
    out = {k: b["ssd"][k] - a["ssd"][k] for k in b["ssd"]}
    ea, eb = a["engine"], b["engine"]
    out["decode_steps"] = eb["decode_steps"] - ea["decode_steps"]
    out["prefill_launches"] = eb["prefill"]["launches"] - ea["prefill"]["launches"]
    out["bytes_per_slot"] = b["state"]["bytes_per_slot"]
    return out


def pinned(name: str):
    """The reader ``layer_metrics/<name>.py`` held to this configuration's
    cell (the accepted lists of the namesake are pinned to their cells)."""
    from benchmarks.harness.registry import load_module

    base = load_module("layer_metrics", name).read

    def read(ctx):
        return base(ctx) if is_cell(ctx) else None

    return read

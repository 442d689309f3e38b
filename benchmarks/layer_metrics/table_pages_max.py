"""KV pool, on the cell of the configuration served at a page of its own:
the widest page table any launch carried up to the window's end, in pages of
``pool.page_size`` (``/debug/state`` ``attn.table_pages_max``: a decode
step's bucket, a prefill launch's width). 1 024 at the page of 128 is 131 072
positions a row — what the chip's scalar memory takes of a 64-row launch
(ROADMAP R11). A program without the counter: nothing to read."""
from benchmarks.harness import jamba_trace


def read(ctx):
    attn = ctx["after"]["state"].get("attn") or {}
    if not jamba_trace.is_cell(ctx) or "table_pages_max" not in attn:
        return None
    return float(attn["table_pages_max"])

"""KV pool: of the state bytes the window's launches WROTE to the state
slots, the share that boundary copies wrote, percent (counters
``/debug/state`` ``ssd``: delta ``boundary_copies`` x ``state.
bytes_per_slot`` / delta ``state_bytes_written``). A copy is made on the
step that completes a page, for the rows that completed one: one step in
16 a row at a page of 16, so ~6 of a decode-heavy window; a program that
wrote the boundary slot every step would read 50."""
from benchmarks.harness import ssd_trace


def read(ctx):
    c = ssd_trace.counters(ctx)
    if c is None or c["state_bytes_written"] <= 0:
        return None
    return (100.0 * c["boundary_copies"] * c["bytes_per_slot"]
            / c["state_bytes_written"])

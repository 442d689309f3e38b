"""Kernels: gathering and attending the selected rows, as a share of the
chip's HBM bandwidth, percent. Bound: memory (121 FLOP a byte of row, half
the ridge: ``mla_sparse_decode_mxu_share`` is the twin).

Bytes that HAD to be read per decode step: the rows the window's decode
launches selected (counter ``/debug/state``
``attn.sparse.decode.rows_selected``: min(context, index_topk) x layers,
summed over rows, delta over the window) x one row's needed bytes
(``opsbytes_dsa.selected_row_bytes``: 1 152 B; 1 280 B are stored and
gathered) / the window's decode steps. Time per step: device time of the
``radixmesh_mla_sparse_decode`` kernel events AND of the XLA gather that
feeds them (``harness/dsa_trace.py``) / the ``jit_decode_step`` executions
of the trace. A program without the counter or the ops: nothing to read."""
from benchmarks.harness import dsa_trace
from benchmarks.harness.opsbytes_dsa import selected_row_bytes
from benchmarks.harness.registry import load_module

_index = load_module("layer_metrics", "dsa_index_hbm_share")


def per_step(ctx):
    """``(rows selected per decode step, gather + attend seconds per
    step)`` or None: shared with the MXU twin."""
    rows = _index.sparse_delta(ctx, "decode", "rows_selected")
    got = _index.kernel_per_step(ctx, "radixmesh_mla_sparse_decode")
    xla = dsa_trace.of_run(ctx)
    if not rows or got is None or xla is None or not xla["gathers"]:
        return None
    steps, kernel_s = got
    return rows / steps, kernel_s + xla["gather_s"] / xla["executions"]


def read(ctx):
    got = per_step(ctx)
    if got is None or ctx["peaks"] is None:
        return None
    rows, seconds = got
    return (100.0 * rows * selected_row_bytes(ctx["config"]) / seconds
            / ctx["peaks"]["hbm_bytes_per_s"])

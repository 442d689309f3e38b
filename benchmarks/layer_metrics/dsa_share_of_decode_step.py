"""Model step: learned sparse attention's share of a decode step's device
time, percent: index scoring (``radixmesh_dsa_index_decode``) + selection
(its named pieces: ``dsa_select_share_of_decode_step``) + gather of the
selected rows + attention over them (``radixmesh_mla_sparse_decode``) /
device seconds of the ``jit_decode_step`` executions. The indexer's three
projections, the absorb and un-absorb products and the selection's masks
and running counts are unnamed XLA fusions OUTSIDE this share."""
from benchmarks.harness import dsa_trace
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    planes = (ctx["trace"] or {}).get("planes") or {}
    xla = dsa_trace.of_run(ctx)
    _, step_s = modules_matching(ctx["trace"] or {}, "jit_decode_step")
    if not planes or xla is None or not step_s:
        return None
    kernels = next(iter(planes.values()))["kernels"]
    named = sum(kernels[k]["seconds"] for k in (
        "radixmesh_dsa_index_decode", "radixmesh_mla_sparse_decode") if k in kernels)
    if not named:
        return None
    return 100.0 * (named + xla["select_s"] + xla["gather_s"]) / step_s

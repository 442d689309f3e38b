"""Model step: the prefill programs' share of the device's busy time in
the capture, percent — the reader of ``prefill_share_of_busy.docs``. Here
every prefill is a short chunk whose every query scores ~41 k index keys,
selects and gathers its own 2 048 rows in 7 layers: what the questions
cost the answers under learned sparse attention."""
from benchmarks.harness.opsbytes_dsa import expert_share
from benchmarks.harness.registry import load_module

_read = load_module("layer_metrics", "prefill_share_of_busy.docs").read


def read(ctx):
    if expert_share(ctx["config"]) <= 1:  # not this configuration's cell
        return None
    return _read(ctx)

"""Model step: the prefill programs' share of the device's busy time in
the capture, percent: device seconds of the ``jit_prefill_*`` modules /
busy seconds (the union of the op intervals), first device plane. In the
documents cell every prefill is a short tail over a long latent prefix:
this is what the questions cost the answers."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    planes = (ctx["trace"] or {}).get("planes") or {}
    if not planes:
        return None
    busy = next(iter(planes.values()))["busy_s"]
    _, prefill_s = modules_matching(ctx["trace"], "jit_prefill")
    if not busy:
        return None
    return 100.0 * prefill_s / busy

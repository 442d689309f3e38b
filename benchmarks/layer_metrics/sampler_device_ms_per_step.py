"""Model step: device time of the sampler per decode step, ms — the
``jit_sample_tokens`` modules of the trace (the decode steps' launches and
the first-token launches of the prefills among them) / the count of
``jit_decode_step`` modules, first device plane. A sampler that sorts the
vocabulary for rows that all take the argmax shows here."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    steps, _ = modules_matching(ctx["trace"], "jit_decode_step")
    _, sampler_s = modules_matching(ctx["trace"], "jit_sample_tokens")
    return 1e3 * sampler_s / steps if steps else None

"""Model step: device time of the prefill programs per 1 000 prompt tokens
they had to compute, ms. Device time: ``jit_prefill_*`` modules in the
trace. Tokens: the uncached prompt tokens admitted per decode step over
the whole window (``/stats``, ``/debug/state``) times the decode steps the
trace holds — the decode step is the clock that places the traced seconds
in the window without comparing two processes' clocks."""
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    _, prefill_s = modules_matching(ctx["trace"], "jit_prefill")
    steps_traced, _ = modules_matching(ctx["trace"], "jit_decode_step")
    a, b = ctx["before"], ctx["after"]
    steps = b["state"]["engine"]["decode_steps"] - a["state"]["engine"]["decode_steps"]
    computed = ((b["stats"]["prompt_tokens"] - a["stats"]["prompt_tokens"])
                - (b["stats"]["cached_tokens"] - a["stats"]["cached_tokens"]))
    if steps <= 0 or computed <= 0 or not steps_traced:
        return None
    return 1e3 * prefill_s / (computed / steps * steps_traced / 1e3)

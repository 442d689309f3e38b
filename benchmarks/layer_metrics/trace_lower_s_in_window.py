"""Model step: seconds of tracing and lowering that ended in the window: a
launch shape the warm-up did not reach holds the engine thread for them
even where the persistent cache then serves the program, and
``compile_s_in_window`` sees only the compiler's part. Counters
``/debug/state`` ``process.compiles.trace_s`` + ``lower_s``: delta, before
to after; ``engine.slow_steps.last[].compiles`` says which step
(``benchmarks/SETUP_ACCOUNT.md``). A program without the counters:
nothing."""


def read(ctx):
    a, b = (ctx[k]["state"].get("process") for k in ("before", "after"))
    if a is None or b is None:
        return None
    a, b = a["compiles"], b["compiles"]
    if a.get("trace_s") is None or b.get("trace_s") is None:
        return None
    return b["trace_s"] + b["lower_s"] - a["trace_s"] - a["lower_s"]

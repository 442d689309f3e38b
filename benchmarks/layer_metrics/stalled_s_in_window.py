"""Scheduler: seconds of the window lost to slow steps — steps of the
engine thread that took over 1 s more than their own prefill at the
running rate (a decode launch with its hand-over is 25-35 ms). Counter
``/debug/state`` ``engine.slow_steps``: delta ``s``; its ``last`` records
say when, under which span, and at what memory. A program without the
counter: nothing."""


def read(ctx):
    a, b = (ctx[k]["state"]["engine"].get("slow_steps") for k in ("before", "after"))
    if a is None or b is None:
        return None
    return b["s"] - a["s"]

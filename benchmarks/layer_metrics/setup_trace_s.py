"""Model step: seconds of the set-up that went to TRACING the programs —
Python running the model's code into jaxprs — as the server's own
``jax.monitoring`` listeners counted them from the process's start to the
window's opening: an outermost interval's seconds only, so a trace inside a
trace is counted once. Counter ``/debug/state`` ``process.compiles.trace_s``
in the snapshot taken as the window opens: the VALUE, not a delta.
``process.compiles.by_program`` names the programs, ``by_span`` where the
engine thread was (``benchmarks/SETUP_ACCOUNT.md``). A program without the
counter: nothing."""


def compiles_at_window_start(ctx):
    """``process.compiles`` as the window opened, or None where the program
    has no process account."""
    process = ctx["before"]["state"].get("process")
    return None if process is None else process["compiles"]


def read(ctx):
    c = compiles_at_window_start(ctx)
    return None if c is None else c.get("trace_s")

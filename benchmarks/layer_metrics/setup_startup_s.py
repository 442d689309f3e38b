"""Model step: seconds from the server process's own start
(``/proc/self/stat``) to the end of its last start-up phase, ``listen`` —
imports, the backend, the weights onto the device, the engine's pool and
tables, the HTTP front end — LESS what the start-up's own programs took of
tracing, lowering and the compiler (drawing the weights is one program: 25 s
of a cold start): those seconds are in ``setup_trace_s`` and its neighbours
with every other program's, so the five ``setup_*`` add up to no more than
``setup_s``. Counters ``/debug/state`` ``process.startup.ready_at_s`` less
``process.startup.compiling_s`` in the snapshot taken as the window opens:
the value, not a delta; ``process.startup.phases`` splits the first
(``benchmarks/SETUP_ACCOUNT.md``). A program without the counters, or a
server no phase was marked in: nothing."""


def read(ctx):
    process = ctx["before"]["state"].get("process")
    startup = None if process is None else process.get("startup")
    if startup is None or startup["ready_at_s"] is None:
        return None
    return startup["ready_at_s"] - startup["compiling_s"]

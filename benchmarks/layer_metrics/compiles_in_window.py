"""Model step (XLA): compilations that finished or were running inside the
window, a count, from the server's ``JAX_LOG_COMPILES`` lines stamped on
this process's clock. A persistent-cache hit counts too (it still stalls
the engine loop while the program loads). Should be 0."""


def read(ctx):
    return len(ctx["compiles_in_window"])

"""Scheduler: tokens generated per decode step in the window (delta
``generated_tokens`` of ``/stats`` / delta ``decode_steps`` of
``/debug/state``) — the batch occupancy the decode step really ran at."""


def read(ctx):
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if steps <= 0:
        return None
    toks = (ctx["after"]["stats"]["generated_tokens"]
            - ctx["before"]["stats"]["generated_tokens"])
    return toks / steps

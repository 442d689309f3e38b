"""KV pool: requests preempted for lack of pages in the window (delta
``preemptions`` of ``/stats``), a count."""


def read(ctx):
    return (ctx["after"]["stats"]["preemptions"]
            - ctx["before"]["stats"]["preemptions"])

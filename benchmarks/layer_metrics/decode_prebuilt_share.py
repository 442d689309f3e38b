"""Scheduler: of the window's plain decode launches, the share whose inputs
(slots, lengths, page-table bucket, their uploads) had been built behind
the launch before them, so that the device did not wait for the build,
percent (counter ``/debug/state`` ``engine.handover``: delta
``launches_prebuilt`` / (prebuilt + delta ``launches_rebuilt``)). A launch
is rebuilt in the gap when a row joined or left since the build ahead. A
program without the counter (the parent of PR 30): nothing."""


def read(ctx):
    h0, h1 = (ctx[k]["state"]["engine"].get("handover") for k in ("before", "after"))
    if h0 is None or h1 is None:
        return None
    ahead = h1["launches_prebuilt"] - h0["launches_prebuilt"]
    gap = h1["launches_rebuilt"] - h0["launches_rebuilt"]
    if ahead + gap <= 0:
        return None
    return 100.0 * ahead / (ahead + gap)

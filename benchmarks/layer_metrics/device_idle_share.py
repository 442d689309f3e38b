"""Device: share of the traced window in which no operation ran on the
device, percent (1 - union of device-op intervals / traced window, per
device plane; the worst plane)."""


def read(ctx):
    idle = ctx["trace"].get("idle_share_worst")
    return None if idle is None else 100.0 * idle

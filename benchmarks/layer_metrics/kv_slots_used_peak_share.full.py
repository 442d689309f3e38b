"""KV pool, by group: the most slots of the FULL layer's group ever taken
at once up to the window's end, percent of the group (``/debug/state``
``kv.groups.full``: ``used_peak`` / ``slots``). The tree keeps every
finished prefix until the group needs the room, so this reads ~100 once
the cache is warm: it says the group is used, not how much live rows need
(``kv_slots_used_peak_share``, from the polls, leaves evictable rows
out)."""
GROUP = "full"


def read(ctx, group=GROUP):
    g = ((ctx["after"]["state"].get("kv") or {}).get("groups") or {}).get(group)
    if not g or not g.get("slots"):
        return None
    return 100.0 * g["used_peak"] / g["slots"]

"""KV pool: the most state slots ever taken at once up to the window's
end, percent of the slots (``/debug/state`` ``state``: ``slots_used_peak``
/ ``slots``; live rows hold two each, the tree one a snapshot). At 100 the
tree evicts its least recently used snapshots (``state.evictions``): rows
stay, a later match resumes shallower."""


def read(ctx):
    st = ctx["after"]["state"].get("state")
    if st is None or not st.get("slots"):
        return None
    return 100.0 * st["slots_used_peak"] / st["slots"]

"""Radix tree: host milliseconds the engine thread spent in the tree per
finished request of the window — the walk + slot acquisition at admission
(``rm.tree.match``) and the insert at prefill, finish and preemption
(``rm.tree.publish``). (delta ``match_s`` + delta ``publish_s``) of
``/debug/state`` ``cache.tree_host`` / delta ``finished``."""


def read(ctx):
    a = ctx["before"]["state"]["cache"].get("tree_host")
    b = ctx["after"]["state"]["cache"].get("tree_host")
    finished = (ctx["after"]["state"]["engine"]["finished"]
                - ctx["before"]["state"]["engine"]["finished"])
    if a is None or b is None or finished <= 0:
        return None
    return 1e3 * ((b["match_s"] - a["match_s"])
                  + (b["publish_s"] - a["publish_s"])) / finished

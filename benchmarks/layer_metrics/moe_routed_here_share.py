"""Expert layer: share of the router's (token, expert) assignments that
went to an expert THIS chip holds, percent (counters ``/debug/state``
``moe``: delta ``assignments`` / (delta ``assignments`` + delta
``assignments_elsewhere``), decode and prefill launches together). With 16
of 256 experts held and an even router: 6.25. What went elsewhere is
computed by no one here: the other shares' part of the result is left out,
in program and reference alike."""
from benchmarks.harness.opsbytes_dsa import expert_share


def read(ctx):
    if expert_share(ctx["config"]) <= 1:  # every expert is held: nothing to say
        return None
    moe0, moe1 = (ctx[k]["state"].get("moe") for k in ("before", "after"))
    if moe0 is None or moe1 is None:
        return None
    here = away = 0
    for site in ("decode", "prefill"):
        if "assignments_elsewhere" not in moe1[site]:
            return None
        here += moe1[site]["assignments"] - moe0[site]["assignments"]
        away += (moe1[site]["assignments_elsewhere"]
                 - moe0[site]["assignments_elsewhere"])
    if here + away <= 0:
        return None
    return 100.0 * here / (here + away)

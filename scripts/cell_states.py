#!/usr/bin/env python3
"""Run one cell of the benchmark as ``benchmarks/run.py`` does, and keep
what its result line leaves out: every ``/debug/state`` snapshot the run
takes (window start, window end, end of drain, after the capture).

    python scripts/cell_states.py [--root DIR] [--out FILE] -- \\
        --workload qwen2-7b.chat-sessions --seed 7 --seconds 51 --trace 2

``--root`` is the checkout whose ``benchmarks/run.py`` runs (default: this
one; a ``git archive`` copy of the parent for the other side of a pair).
The run itself is untouched: same command line, same process, the same
numbers on stdout. One JSON line a snapshot goes to ``--out`` (default
``chiprun_out/states/<workload>.<seed>.jsonl`` under this checkout):
``engine.prefill`` (``by_path``: which launch the waves took),
``engine.spans``, ``engine.slow_steps``, ``process`` (``compiles.last``
names each compiled program, when, and the span it compiled under),
``attn`` (what decode launches attended, read and were spared), ``state``
(where requests resumed, what the state slots hold and gave up),
``cache.tree_host`` (the tree walks' host seconds, and how much of each
published key the publish walked) and ``/stats``."""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEEP = ("prefill", "spans", "slow_steps", "decode_steps", "finished", "handover")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--out", default=None)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    rest = [a for a in args.rest if a != "--"]
    root = os.path.abspath(args.root)

    def flag(name: str) -> str:
        return rest[rest.index(name) + 1] if name in rest else "none"

    out = os.path.abspath(args.out or os.path.join(  # kept across the chdir below
        REPO, "chiprun_out", "states",
        f"{flag('--workload')}.{flag('--seed')}.jsonl"))
    os.makedirs(os.path.dirname(out), exist_ok=True)
    open(out, "w").close()

    os.chdir(root)
    sys.argv = [os.path.join(root, "benchmarks", "run.py"), *rest]
    spec = importlib.util.spec_from_file_location("_cell_run", sys.argv[0])
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)  # its T_PROCESS starts here, as on its own
    snapshot = run.snapshot

    def keeping(base: str) -> dict:
        snap = snapshot(base)
        state = snap["state"]
        with open(out, "a") as fh:
            fh.write(json.dumps({
                "t": snap["t"],
                "engine": {k: state["engine"].get(k) for k in KEEP},
                "process": state.get("process"),
                "attn": state.get("attn"),
                "state": state.get("state"),
                "tree_host": (state.get("cache") or {}).get("tree_host"),
                "stats": snap["stats"],
            }) + "\n")
        return snap

    run.snapshot = keeping
    return run.main()


if __name__ == "__main__":
    sys.exit(main())

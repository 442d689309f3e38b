#!/usr/bin/env python3
"""The prefix-consistency probe alone, over several seeds: what its two
scores read by chance, so that the margins in a configuration file stand
where no seed misses them.

    python benchmarks/tools/probe_seeds.py --workload qwen2-7b.batch-unshared \
        --seeds 3,2147483659,... --n 24 --out chiprun_out/probe_seeds.json

For each seed: the configuration's server (weights from the seed), then
``run.py``'s own ``probe`` with ``--n`` prompts (the first ``probe.n`` of
them are the prompts a run of that seed sends), then the server stops.
One row per seed: the share of tokens agreeing before the first
disagreement and whether the first token agreed, prompt by prompt. Not
part of a check: a tool for the PR that sets or moves a margin.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench  # noqa: E402
from benchmarks.harness import registry  # noqa: E402
from benchmarks.harness.server import Server, child_env  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    r = registry.resolve(args.workload)
    if args.rehearse_cpu:
        r = bench.rehearsal_view(r)
    config = r["config"]
    vocab = int(config["vocab"])
    spec = {**config["probe"], "n": args.n}
    work = os.path.join(bench.HERE, ".work", "probe_seeds")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        server = Server(r["launcher"].argv(config, seed=seed, profile_dir=None),
                        env, os.path.join(work, "server.log"))
        try:
            base = server.wait_ready(
                1100.0, need_platform=None if args.rehearse_cpu else "tpu")
            row = {"seed": seed, **bench.probe(base, spec, vocab, seed)}
        finally:
            server.stop()
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as fh:
                json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

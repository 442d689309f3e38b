#!/usr/bin/env python3
"""Find the knee of an open-loop cell again: one server, one set-up, a few
fixed rates one after another.

    python benchmarks/tools/sweep.py --workload qwen2-7b.chat-sessions \
        --rates 0.64,0.8,1.0,1.25,1.56 --seconds 40 --out chiprun_out/sweep.json

For each rate (sessions/s): the cell's traffic for its warm-up time, then a
window; between rates the server drains. Prints one row per rate: requests
per second, tails, the share of requests that met both limits, and the
server's backlog (waiting + running requests) at the window's start and
end. The knee is the highest rate at which the backlog does not grow
through the window and at least 90% of requests meet both limits; the cell
file records the table and runs at 0.8 of the knee. Not part of a check:
a tool for the PR that (re)defines a cell.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench  # noqa: E402
from benchmarks.harness import ledger, loadgen, registry  # noqa: E402
from benchmarks.harness.server import Server, child_env  # noqa: E402


def backlog(base: str) -> int:
    e = loadgen.get_json(base, "/debug/state")["engine"]
    return e["waiting"] + e["batch_rows_active"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    r = registry.resolve(args.workload)
    if args.rehearse_cpu:
        r = bench.rehearsal_view(r)
    cell, config, traffic = r["cell"], r["config"], r["traffic"]
    vocab = int(config["vocab"])
    work = os.path.join(bench.HERE, ".work", "sweep")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    rows = []
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        warm_s = float(cell["warmup"]["traffic_s"])
        page = int(config["page_size"])
        rates = [float(x) for x in args.rates.split(",")]
        horizon = warm_s + args.seconds + bench.PLAN_TAIL_S  # as run.py plans

        def plan_at(rate, seed):
            return r["generator"].plan(
                traffic["params"], {**cell["loop"], "sessions_per_s": rate},
                vocab, seed, horizon_s=horizon)

        # Every rate has a plan of its own (other sizes): warm the lengths
        # of all of them, or a row is spoiled by compiles.
        plans = [plan_at(rate, args.seed) for rate in rates]
        census = [c for p in plans for c in p.census(p.pool, page)]
        print(json.dumps(bench.warm_shapes(
            base, cell["warmup"], census, vocab, args.seed, page,
            prefixes=plans[0].prefixes())), flush=True)
        for i, rate in enumerate(rates):
            loop = {**cell["loop"], "sessions_per_s": rate}
            plan = plan_at(rate, args.seed + i)  # other token ids: no hits on the last rate's sessions
            if i:
                for shared in plan.prefixes():  # this seed's system prompts
                    bench._one(base, shared, 2, vocab, "warmup")
            load = loadgen.Load(base, plan, loop, vocab)
            t0 = load.start() + warm_s
            bench.sleep_until(t0)
            b0 = backlog(base)
            n_compiles = len(server.compiles)
            bench.sleep_until(t0 + args.seconds)
            t1 = time.monotonic()
            b1 = backlog(base)
            deadline = t1 + float(cell["drain_s"])
            while load.pending_in(t0, t1) and time.monotonic() < deadline:
                time.sleep(0.1)
            records = load.stop()
            s = ledger.summarize(records, t0, t1, cell["limits"])
            half = (t0 + t1) / 2
            first = ledger.summarize(records, t0, half, cell["limits"])
            second = ledger.summarize(records, half, t1, cell["limits"])
            row = {
                "sessions_per_s": rate, "requests_per_s": s["requests_per_s"],
                "attempted": s["attempted"], "failed": s["failed"],
                "ttft_p50_ms": s["ttft_p50_ms"], "ttft_p95_ms": s["ttft_p95_ms"],
                "ttft_p95_ms_halves": [first["ttft_p95_ms"], second["ttft_p95_ms"]],
                "itl_p50_ms": s["itl_p50_ms"], "itl_p95_ms": s["itl_p95_ms"],
                "slo_share": s["slo_share"], "tokens_per_s": s["tokens_per_s"],
                "backlog_start_end": [b0, b1],
                "compiles_in_window": len(server.compiles) - n_compiles,
                "failed_by_status": s["failed_by_status"],
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
            for _ in range(600):  # let the server finish what is in flight
                if backlog(base) == 0:
                    break
                time.sleep(0.2)
    finally:
        server.stop()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

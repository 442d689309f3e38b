#!/usr/bin/env python3
"""Spread of repeated runs, by the rule the bounds are set by.

    python benchmarks/tools/spread.py chiprun_out/set1_*.out [--set-size 6]

Each file's last line is a result of ``run.py``. Per metric: the values,
the median, and the interquartile distance as a share of the median
(``statistics.quantiles(values, n=4)``) — per set of ``--set-size``
consecutive files and over all of them. A bound is about five times the
widest spread, never under 1%.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness.ledger import spread  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="+")
    ap.add_argument("--set-size", type=int, default=6)
    args = ap.parse_args()
    by_metric: dict = {}
    for f in args.files:
        with open(f) as fh:
            lines = [x for x in fh.read().strip().splitlines() if x.startswith("{")]
        if not lines or '"metrics"' not in lines[-1]:
            print(f"{f}: no result line", file=sys.stderr)
            continue
        res = json.loads(lines[-1])
        for name, m in res["metrics"].items():
            by_metric.setdefault(name, []).append(m["value"])
        by_metric.setdefault("(correct)", []).append(float(res["correct"]))
        by_metric.setdefault("(failed)", []).append(float(res["failed"]))
    for name, vals in by_metric.items():
        sets = [vals[i:i + args.set_size] for i in range(0, len(vals), args.set_size)]
        row = {"metric": name, "n": len(vals),
               "median": statistics.median(vals),
               "values": [round(v, 3) for v in vals]}
        if not name.startswith("("):
            row["spread_all"] = round(spread(vals), 4) if len(vals) >= 2 else None
            row["spread_sets"] = [round(spread(s), 4) for s in sets if len(s) >= 2]
            row["set_medians"] = [round(statistics.median(s), 3) for s in sets]
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Standalone prefill-convoy acceptance bench (the CONVOY artifact's
paired CLI emitter, like ``scripts/specbench.py`` is for SPEC).

Runs ``workload.run_convoy_workload`` — the decode-interleaved chunked
prefill A-B, all on the CPU tier — and checks the three convoy verdicts
end to end:

- **interleave** — a late-arriving short prompt's p50 TTFT beats the
  legacy alternating schedule by the pinned floor on an IDENTICAL
  virtual arrival schedule, with bit-identical outputs, decode ITL p99
  within its ceiling, and spec accepted-per-wave within its floor;
- **stalls** — the token timeline's per-request ``prefill_convoy``
  stall seconds drop by the pinned ratio, the remainder attributed to
  the new ``prefill_inline`` cause;
- **starvation** — under 20:1 prompt-length skew with boost waves
  firing, decode never goes more than ``--prefill-inline-max-defer``
  consecutive waves without a token (counted in waves, never
  wall-clock).

Prints ONE JSON line validated against the schema
``bench.validate_convoy`` pins.

Usage::

    python scripts/convoybench.py [--seed 0] [--inline-budget 32] \
        [--reps 5] [--out FILE] [--write-artifact]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import bench  # noqa: E402  (schema + report assembly live with the other validators)
from radixmesh_tpu.workload import run_convoy_workload  # noqa: E402


def convoy_round() -> int:
    """The round in progress = 1 + the highest N across every OTHER
    plane's recorded artifact (CONVOY rides whatever round they are on —
    the scripts/meshcheck.py analysis_round convention)."""
    rounds = [0]
    for name in os.listdir(_REPO_ROOT):
        m = re.fullmatch(r"[A-Z_]+_r(\d+)\.json", name)
        if m and not name.startswith("CONVOY_"):
            rounds.append(int(m.group(1)))
    return max(rounds) + 1


def run(seed: int, inline_budget: int, max_defer: int, reps: int) -> dict:
    res = run_convoy_workload(
        seed=seed,
        inline_budget=inline_budget,
        max_defer=max_defer,
        reps=reps,
    )
    report = bench.build_convoy_report(res)
    problems = bench.validate_convoy(report)
    if problems:
        report["schema_violation"] = problems
    return report


def main() -> int:
    ap = argparse.ArgumentParser(prog="convoybench")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--inline-budget", type=int, default=32, metavar="N",
        help="prefill tokens ridden per mixed wave in the treatment arm "
        "(the base arm always runs 0 = the legacy alternating schedule)",
    )
    ap.add_argument(
        "--max-defer", type=int, default=2, metavar="N",
        help="starvation bound: max consecutive prefill-only boost "
        "waves before a decode-bearing wave is forced",
    )
    ap.add_argument(
        "--reps", type=int, default=5, metavar="N",
        help="measured A-B iterations per arm (one extra warmup "
        "iteration absorbs compiles and is discarded)",
    )
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument(
        "--write-artifact", action="store_true",
        help="write the round's CONVOY_r{N}.json to the repo root",
    )
    args = ap.parse_args()
    report = run(args.seed, args.inline_budget, args.max_defer, args.reps)
    line = json.dumps(report)
    print(line)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    if args.write_artifact:
        path = os.path.join(_REPO_ROOT, f"CONVOY_r{convoy_round():02d}.json")
        with open(path, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(
            f"convoybench: wrote {os.path.basename(path)}", file=sys.stderr
        )
    return 1 if "schema_violation" in report else 0


if __name__ == "__main__":
    sys.exit(main())

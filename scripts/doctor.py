"""The mesh doctor's CLI: diagnose a LIVE cluster or a DEAD node's
black-box dump.

Live mode (default) hits a frontend's ``GET /cluster/doctor``
(``obs/doctor.py`` runs server-side — the burn-rate windows live in the
frontend's persistent doctor, so the CLI is a thin, dependency-free
reader) and renders the ranked findings with their pinned evidence.
Exit codes: 0 healthy, 1 findings, 2 unreachable/bad response.

Post-mortem mode (``--blackbox DIR``) loads a black-box dump directory
(``obs/blackbox.py`` — written by ``launch.py --blackbox-dir`` on
SIGTERM/drain/watchdog, or left as bare segments by a hard kill) and
replays the doctor's judgment over the RECORDED telemetry history
(``obs/doctor.py::postmortem_report``): hot shards, replication lag,
burn rates at their in-window peaks, and the crash itself (health
collapse windows, unclean-death truncation). Same exit codes; no
cluster required.

Usage::

    python scripts/doctor.py [--url http://HOST:PORT] [--watch SECONDS]
    python scripts/doctor.py --blackbox /var/dumps/prefill@2 [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _render(report: dict) -> None:
    findings = report.get("findings", [])
    checked = report.get("rules_checked", [])
    inputs = report.get("inputs", {})
    attached = [k for k, v in inputs.items() if v]
    if not findings:
        print(
            f"HEALTHY — {len(checked)} rule(s) ran over planes "
            f"{attached}, zero findings"
        )
        return
    print(f"{len(findings)} finding(s), ranked (planes {attached}):")
    for i, f in enumerate(findings, 1):
        print(f"  {i}. [{f['rule']}] score={f['score']:.2f}")
        print(f"     {f['summary']}")
        ev = ", ".join(f"{k}={v!r}" for k, v in f["evidence"].items())
        print(f"     evidence: {ev}")


def _live(url: str, watch: float | None) -> int:
    endpoint = url.rstrip("/") + "/cluster/doctor"
    while True:
        try:
            with urllib.request.urlopen(endpoint, timeout=10) as resp:
                report = json.load(resp)
        except Exception as e:  # noqa: BLE001 — any transport failure is the same verdict
            print(f"doctor: {endpoint} unreachable: {e}", file=sys.stderr)
            return 2
        if not isinstance(report, dict) or "findings" not in report:
            print(f"doctor: {endpoint} returned no findings field",
                  file=sys.stderr)
            return 2
        if watch is None:
            _render(report)
            return 0 if report.get("healthy") else 1
        os.write(1, f"\n=== {time.strftime('%H:%M:%S')} ===\n".encode())
        _render(report)
        time.sleep(watch)


def _postmortem(path: str, as_json: bool) -> int:
    from radixmesh_tpu.obs.blackbox import load_blackbox
    from radixmesh_tpu.obs.doctor import postmortem_report

    try:
        dump = load_blackbox(path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"doctor: cannot load black box at {path}: {e}",
              file=sys.stderr)
        return 2
    report = postmortem_report(dump)
    if as_json:
        print(json.dumps(report, indent=1))
    else:
        state = "UNCLEAN (segments only — hard kill)" if dump["unclean"] \
            else f"flushed ({', '.join(dump['causes'])})"
        print(
            f"black box: node {dump['node']!r}, {dump['segments']} "
            f"segment(s) + {dump['finals']} final(s) [{state}], "
            f"{report['samples']} samples over {report['series']} series"
        )
        _render(report)
    return 0 if report.get("healthy") else 1


def main() -> int:
    ap = argparse.ArgumentParser(prog="doctor")
    ap.add_argument(
        "--url", default="http://127.0.0.1:8000",
        help="frontend base URL (serving or router; both expose "
        "/cluster/doctor)",
    )
    ap.add_argument(
        "--watch", type=float, default=None, metavar="SECONDS",
        help="re-diagnose every SECONDS (live mode only; ctrl-c to stop)",
    )
    ap.add_argument(
        "--blackbox", default=None, metavar="DIR",
        help="post-mortem mode: replay every doctor rule over a "
        "black-box dump directory (obs/blackbox.py) instead of a live "
        "cluster — works on segment-only dumps a hard kill left behind",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="print the full post-mortem report as JSON (--blackbox mode)",
    )
    args = ap.parse_args()
    if args.blackbox:
        return _postmortem(args.blackbox, args.json)
    return _live(args.url, args.watch)


if __name__ == "__main__":
    sys.exit(main())

"""meshcheck CLI: run the AST-based static-analysis plane.

Runs every checker (``radixmesh_tpu/analysis/``) over the product
package, runs the positive-control fixtures, prints findings as
``file:line: [invariant-id] message``, and with ``--out`` writes the
schema-pinned ANALYSIS report (validated against
``radixmesh_tpu.analysis.report.validate_analysis`` before writing — a
violation is recorded in the report, not silently shipped).

Exit status (pinned — commit hooks branch on it):
  0 = tree clean AND all positive controls tripped
  1 = findings (or a blind checker) — fix or justify in-source
  2 = framework error: the run itself could not happen (missing
      fixtures, git unavailable in --changed mode, refused flags)

Usage::

    python scripts/meshcheck.py                # full tree, exit code
    python scripts/meshcheck.py --changed      # changed files + their
                                               #   reverse-import deps —
                                               #   cheap enough per commit
    python scripts/meshcheck.py --json         # full report on stdout
    python scripts/meshcheck.py --out X.json   # the full report, to a file
    python scripts/meshcheck.py --no-fixtures  # skip positive controls

``--changed`` analyzes the WHOLE tree (one parse is the cheap part;
cross-module checkers need full context) but reports only findings in
files touched by ``git diff HEAD`` / untracked files, widened to every
module that transitively imports one (``analysis.changed_scope``).
Positive controls are skipped in --changed mode unless a file under
``analysis/`` changed — a checker edit must re-prove its controls.

The quick CI gate runs the same plane in-process as ONE test:
``tests/test_analysis.py::test_tree_is_clean``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from radixmesh_tpu.analysis import (  # noqa: E402
    all_checkers,
    changed_scope,
    get_thread_map,
)
from radixmesh_tpu.analysis.controls import run_positive_controls  # noqa: E402
from radixmesh_tpu.analysis.core import (  # noqa: E402
    SourceIndex,
    package_root,
    run_checkers,
)
from radixmesh_tpu.analysis.report import (  # noqa: E402
    build_analysis_report,
    validate_analysis,
)


def git_changed_files() -> list[str] | None:
    """Package-relative paths of changed + untracked ``radixmesh_tpu``
    modules, or None when git itself fails (framework error)."""
    out: set[str] = set()
    for cmd in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            proc = subprocess.run(
                cmd, cwd=_REPO_ROOT, capture_output=True, text=True,
                timeout=30,
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        out.update(ln.strip() for ln in proc.stdout.splitlines() if ln.strip())
    rels = []
    for path in sorted(out):
        if path.startswith("radixmesh_tpu/") and path.endswith(".py"):
            rels.append(path[len("radixmesh_tpu/"):])
    return rels


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--root", default=None,
        help="package directory to analyze (default: the installed "
        "radixmesh_tpu package)",
    )
    ap.add_argument(
        "--fixtures", default=None,
        help="positive-control fixtures root (default: "
        "tests/fixtures/analysis)",
    )
    ap.add_argument(
        "--no-fixtures", action="store_true",
        help="skip the positive-control pass (a clean verdict then "
        "proves less; --out refuses this mode)",
    )
    ap.add_argument(
        "--changed", action="store_true",
        help="report only findings in git-changed files plus their "
        "reverse-import dependents (the per-commit gate)",
    )
    ap.add_argument("--json", action="store_true", help="print the full report")
    ap.add_argument(
        "--out", default=None, help="write the validated report to this path",
    )
    args = ap.parse_args()

    if args.changed and args.out:
        print(
            "meshcheck: refusing --out with --changed (the written "
            "report must cover the whole tree)",
            file=sys.stderr,
        )
        return 2

    root = args.root or package_root()
    index = SourceIndex(root)
    result = run_checkers(index, all_checkers())
    thread_map = get_thread_map(index)
    scope: set[str] | None = None

    run_fixtures = not args.no_fixtures
    if args.changed:
        changed = git_changed_files()
        if changed is None:
            print("meshcheck: git diff failed — cannot scope", file=sys.stderr)
            return 2
        scope = changed_scope(index, changed)
        # Fixture controls re-run per commit only when checker code
        # itself changed — a checker edit must re-prove it still trips.
        run_fixtures = run_fixtures and any(
            rel.startswith("analysis/") for rel in changed
        )
        # Scope the WHOLE accounting, not just the headline list — a
        # --json consumer reconciling value/findings against the
        # per-checker counts must never see a contradiction.
        result.findings = [f for f in result.findings if f.file in scope]
        result.raw_by_checker = {
            k: [f for f in v if f.file in scope]
            for k, v in result.raw_by_checker.items()
        }
        result.kept_by_checker = {
            k: [f for f in v if f.file in scope]
            for k, v in result.kept_by_checker.items()
        }
        result.suppressed = [
            (f, s) for f, s in result.suppressed if f.file in scope
        ]

    controls = []
    if run_fixtures:
        controls = run_positive_controls(args.fixtures)
        if not controls:
            print(
                "meshcheck: no positive-control fixtures found "
                "(tests/fixtures/analysis) — a clean tree proves nothing",
                file=sys.stderr,
            )
            return 2

    report = build_analysis_report(
        result, controls, len(index.modules), thread_map.roots
    )
    blind = [c for c in controls if not c.tripped]

    if args.json:
        print(json.dumps(report, indent=1))
    else:
        for f in result.findings:
            print(f)
        for c in blind:
            print(
                f"POSITIVE CONTROL MISSED: {c.fixture} {c.invariant} at "
                f"{c.file}:{c.line}"
            )
        scoped = (
            "" if scope is None
            else f" (scope: {len(scope)}/{len(index.modules)} changed+dependent files)"
        )
        print(
            f"meshcheck: {len(index.modules)} files, "
            f"{len(thread_map.roots)} thread roots, "
            f"{len(result.findings)} finding(s), "
            f"{len(result.suppressed)} suppressed by "
            f"{len(result.suppressions)} justification(s), "
            f"{sum(c.tripped for c in controls)}/{len(controls)} "
            f"controls tripped{scoped}"
        )

    if args.out:
        if args.no_fixtures:
            print(
                "meshcheck: refusing --out with --no-fixtures "
                "(the schema gates on positive controls)",
                file=sys.stderr,
            )
            return 2
        problems = validate_analysis(report)
        if problems:
            report["schema_violation"] = problems
            print(f"meshcheck: SCHEMA VIOLATION {problems}", file=sys.stderr)
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)
            fh.write("\n")
        print(f"meshcheck: wrote {args.out}")

    return 0 if (not result.findings and not blind) else 1


if __name__ == "__main__":
    sys.exit(main())

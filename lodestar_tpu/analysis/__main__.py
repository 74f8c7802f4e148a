"""CLI: python -m lodestar_tpu.analysis [--json|--sarif] [--changed]
                                        [--profile-rules] [paths]

Exit codes: 0 clean, 1 non-suppressed findings, 2 usage/internal error.

`--changed` is the pre-push mode: the full tree is parsed (cross-module
rules need it) but only findings in git-touched files (staged, unstaged,
untracked) are considered, and of those only findings NEW relative to a
baseline lint of the HEAD revision of each touched file are reported —
pre-existing debt in a file you edited does not fail your push.  Exits
nonzero on new findings only; the hidden pre-existing count goes to
stderr.  dev/lint.sh forwards to this.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from . import (
    ALL_RULES,
    Finding,
    analyze,
    findings_to_json,
    findings_to_sarif,
    render_findings,
)


def _git_toplevel() -> Optional[Path]:
    try:
        res = subprocess.run(
            ["git", "rev-parse", "--show-toplevel"],
            capture_output=True,
            text=True,
            timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if res.returncode != 0:
        return None
    return Path(res.stdout.strip())


def _git_changed_files() -> Optional[Set[str]]:
    # git prints paths relative to the repo TOPLEVEL; anchor there, not
    # at the process cwd, or a subdirectory run filters everything out
    top = _git_toplevel()
    if top is None:
        return None
    cmds = [
        # deleted files have nothing left to lint
        ["git", "diff", "--name-only", "--diff-filter=d", "HEAD", "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ]
    out: Set[str] = set()
    for cmd in cmds:
        try:
            res = subprocess.run(
                cmd, capture_output=True, text=True, timeout=30
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if res.returncode != 0:
            return None
        for line in res.stdout.splitlines():
            line = line.strip()
            if line.endswith(".py"):
                out.add(str((top / line).resolve()))
    return out


def _baseline_overrides(
    changed: Set[str],
) -> Optional[Dict[str, Optional[str]]]:
    """HEAD-revision source for every changed file (None when the file
    did not exist at HEAD — it is skipped in the baseline lint)."""
    top = _git_toplevel()
    if top is None:
        return None
    overrides: Dict[str, Optional[str]] = {}
    for p in sorted(changed):
        rel = os.path.relpath(p, top).replace(os.sep, "/")
        try:
            res = subprocess.run(
                ["git", "show", f"HEAD:{rel}"],
                capture_output=True,
                text=True,
                timeout=30,
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        overrides[p] = res.stdout if res.returncode == 0 else None
    return overrides


def _finding_key(f: Finding) -> Tuple:
    # line/col excluded on purpose: an unrelated edit above a
    # pre-existing finding must not make it look new
    return (f.rule, f.path, f.severity, f.message, f.suppressed)


def _subtract_baseline(
    findings: List[Finding], baseline: List[Finding]
) -> Tuple[List[Finding], int]:
    """Multiset difference: drop each finding matched by an identical
    baseline finding (returning the count of hidden ACTIVE ones)."""
    remaining = Counter(_finding_key(f) for f in baseline)
    out: List[Finding] = []
    hidden_active = 0
    for f in findings:
        k = _finding_key(f)
        if remaining[k] > 0:
            remaining[k] -= 1
            if not f.suppressed:
                hidden_active += 1
        else:
            out.append(f)
    return out, hidden_active


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m lodestar_tpu.analysis")
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument(
        "--sarif",
        action="store_true",
        help="emit SARIF 2.1.0 (CI/code-review annotation format)",
    )
    ap.add_argument(
        "--changed",
        action="store_true",
        help="report only NEW findings in git-changed files "
        "(baseline: the HEAD revision of each touched file)",
    )
    ap.add_argument(
        "--profile-rules",
        action="store_true",
        help="print per-rule wall-clock timings to stderr",
    )
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.as_json and args.sarif:
        print("tpulint: --json and --sarif are exclusive", file=sys.stderr)
        return 2

    if args.list_rules:
        for rule in ALL_RULES:
            print(f"{rule.name} [{rule.severity}]")
        print("bad-suppression [error]")
        return 0

    paths = args.paths or ["lodestar_tpu"]
    only: Optional[Set[str]] = None
    if args.changed:
        only = _git_changed_files()
        if only is None:
            print(
                "tpulint: --changed needs a working git; running full",
                file=sys.stderr,
            )

    timings: Optional[Dict[str, float]] = (
        {} if args.profile_rules else None
    )
    try:
        findings = analyze(paths, only_files=only, rule_timings=timings)
    except FileNotFoundError as e:
        print(f"tpulint: no such path: {e}", file=sys.stderr)
        return 2

    if args.changed and only is not None:
        overrides = _baseline_overrides(only)
        if overrides is None:
            print(
                "tpulint: --changed baseline unavailable; "
                "reporting all findings in changed files",
                file=sys.stderr,
            )
        else:
            baseline = analyze(
                paths, only_files=only, source_overrides=overrides
            )
            findings, hidden = _subtract_baseline(findings, baseline)
            if hidden:
                print(
                    f"tpulint: --changed: {hidden} pre-existing "
                    f"finding(s) hidden (baseline HEAD)",
                    file=sys.stderr,
                )

    if timings is not None:
        print("tpulint: rule timings:", file=sys.stderr)
        for name, dt in sorted(
            timings.items(), key=lambda kv: -kv[1]
        ):
            print(f"  {name:28s} {dt:7.3f}s", file=sys.stderr)

    if args.as_json:
        print(findings_to_json(findings))
    elif args.sarif:
        print(findings_to_sarif(findings))
    else:
        print(render_findings(findings))
    return 1 if any(not f.suppressed for f in findings) else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""hfellint driver: run the repo's JAX-aware static-analysis pass.

    python scripts/lint.py --check            # the tier-1 gate (default)
    python scripts/lint.py --fix-baseline     # re-record current findings
    python scripts/lint.py --check src/repro/core   # subset of targets

``--check`` lints the targets (default: src/repro, benchmarks, scripts,
examples, chip_smoke.py), diffs the findings against ``lint_baseline.json`` at the repo
root, and exits non-zero if anything NEW appears. Baselined findings must
carry an inline ``# hfellint: disable=RULE -- reason`` pragma or a baseline
entry; ``--fix-baseline`` regenerates the latter from the current state
(dropping entries for fixed violations). Stale baseline entries are reported
but never fail the gate.

Stdlib-only on purpose (no jax import): this runs unconditionally at the
top of scripts/tier1.sh.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

from repro.analysis import (diff_against_baseline, lint_paths,  # noqa: E402
                            load_baseline, save_baseline)
from repro.analysis.baseline import DEFAULT_BASELINE  # noqa: E402

DEFAULT_TARGETS = ["src/repro", "benchmarks", "scripts", "examples",
                   "chip_smoke.py"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="fail on findings not in the baseline (default)")
    mode.add_argument("--fix-baseline", action="store_true",
                      help="regenerate the baseline from current findings")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO_ROOT, DEFAULT_BASELINE),
                    help="baseline JSON path (default: repo root)")
    ap.add_argument("targets", nargs="*", default=None,
                    help=f"files/dirs to lint (default: {DEFAULT_TARGETS})")
    args = ap.parse_args(argv)

    targets = args.targets or DEFAULT_TARGETS
    findings = lint_paths(targets, root=REPO_ROOT)

    if args.fix_baseline:
        body = save_baseline(args.baseline, findings)
        print(f"lint: baseline rewritten with "
              f"{sum(e['count'] for e in body['findings'].values())} "
              f"finding(s) across {len(body['findings'])} fingerprint(s) "
              f"-> {os.path.relpath(args.baseline, REPO_ROOT)}")
        return 0

    baseline = load_baseline(args.baseline)
    new, stale = diff_against_baseline(findings, baseline)
    for entry in stale:
        print(f"lint: stale baseline entry {entry['fingerprint']} "
              f"({entry['rule']} {entry['path']}: {entry['line']!r}) — "
              "fixed? run --fix-baseline to drop it")
    baselined = len(findings) - len(new)
    if new:
        for f in new:
            print(f.render())
        print(f"lint: FAIL — {len(new)} new finding(s) "
              f"({baselined} baselined, {len(stale)} stale)")
        return 1
    print(f"lint: OK — 0 new findings "
          f"({baselined} baselined, {len(stale)} stale)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

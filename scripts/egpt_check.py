#!/usr/bin/env python
"""egpt-check runner: the unified static-analysis suite (ISSUE 8).

One report over every analyzer — the lock-discipline race detector
(``lock``), the host-sync hot-path lint (``hot-sync``), the jit-hygiene
lint (``jit-cache``), and the five telemetry rules migrated from
``lint_telemetry.py`` (``tele-*``). Non-zero exit on any unwaived
finding; the fast tier runs this via ``tests/test_egpt_check.py`` so
the shipped tree stays clean by construction.

Usage::

    python scripts/egpt_check.py [ROOT] [--json] [--rules ID[,ID...]]
                                 [--waived] [--list]

  * ``--json``   machine-readable report (stable keys + per-rule
    counts) so tooling can diff finding counts across PRs;
  * ``--rules``  run a subset (ids from ``--list``);
  * ``--waived`` also print waived findings with their justifications;
  * ``--list``   print the rule catalogue and exit.

Annotation / waiver grammar: OBSERVABILITY.md "Static analysis".
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from eventgpt_tpu.analysis import (ALL_RULES, render_json, render_text,
                                   run_checks, unwaived)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Run the egpt-check static-analysis suite")
    p.add_argument("root", nargs="?", default=_REPO)
    p.add_argument("--json", action="store_true",
                   help="machine-readable report (diff finding counts "
                        "across PRs)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule ids to run (default: all)")
    p.add_argument("--waived", action="store_true",
                   help="also print waived findings + justifications")
    p.add_argument("--list", action="store_true",
                   help="print the rule catalogue and exit")
    args = p.parse_args(argv)

    rules = list(ALL_RULES)
    if args.list:
        for r in rules:
            print(f"{r.id:12s} {r.doc}")
        return 0
    if args.rules:
        want = {x.strip() for x in args.rules.split(",") if x.strip()}
        unknown = want - {r.id for r in rules}
        if unknown:
            print(f"unknown rule id(s): {sorted(unknown)} "
                  f"(see --list)", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.id in want]

    findings = run_checks(args.root, rules)
    if args.json:
        print(render_json(findings, rules))
    else:
        print(render_text(findings, show_waived=args.waived))
    return 1 if unwaived(findings) else 0


if __name__ == "__main__":
    sys.exit(main())

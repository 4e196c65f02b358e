"""``python -m repro.analysis`` — run the invariant linter and the
jaxpr/contract sanitizers over the repo.

Exit codes: 0 = clean (modulo suppressions + baseline), 1 = findings or
contract failures, 2 = usage error.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import List

# Scanned by default: everything that is source, nothing that is corpus.
DEFAULT_ROOTS = ("src", "scripts", "examples", "tests")
BASELINE_NAME = ".analysis-baseline.json"


def repo_root() -> Path:
    """The repo root: nearest ancestor of this file holding src/repro."""
    here = Path(__file__).resolve()
    for cand in here.parents:
        if (cand / "src" / "repro").is_dir():
            return cand
    return Path.cwd()


def main(argv: List[str] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repro invariant linter + contract checker")
    parser.add_argument("paths", nargs="*",
                        help=f"files/dirs to lint (default: "
                             f"{', '.join(DEFAULT_ROOTS)} under the repo "
                             f"root)")
    parser.add_argument("--strict", action="store_true",
                        help="exit 1 on any unsuppressed, unbaselined "
                             "finding (and on contract failures)")
    parser.add_argument("--baseline", default=None,
                        help=f"baseline file (default: {BASELINE_NAME} at "
                             f"the repo root)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="accept all current findings into the baseline")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline (report everything)")
    parser.add_argument("--rules", default=None,
                        help="comma-separated rule ids to run (default all)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--contracts", nargs="?", const="all", default=None,
                        metavar="NAMES",
                        help="additionally run the jaxpr/contract checks "
                             "(all, or a comma-separated subset: "
                             "donation-guard, recompile-sentinel, dp-seams, "
                             "masked-seams, pallas-plans, "
                             "quarantine-rollback)")
    args = parser.parse_args(argv)

    root = repo_root()
    from .lint import all_rules, lint_paths
    from .findings import load_baseline, save_baseline, split_baselined

    if args.list_rules:
        for rid, cls in sorted(all_rules().items()):
            print(f"{rid:22s} {cls.contract}")
        return 0

    rule_ids = ([r.strip() for r in args.rules.split(",") if r.strip()]
                if args.rules else None)
    paths = ([Path(p) for p in args.paths] if args.paths
             else [root / r for r in DEFAULT_ROOTS if (root / r).exists()])
    try:
        findings = lint_paths(paths, root, rule_ids=rule_ids)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    baseline_path = (Path(args.baseline) if args.baseline
                     else root / BASELINE_NAME)
    if args.write_baseline:
        save_baseline(baseline_path, findings)
        print(f"wrote {len(findings)} finding(s) to {baseline_path}")
        return 0
    baseline = [] if args.no_baseline else load_baseline(baseline_path)
    new, baselined = split_baselined(findings, baseline)

    for f in new:
        print(f.format())
    if baselined:
        print(f"({len(baselined)} baselined finding(s) suppressed; "
              f"--no-baseline to show)")

    failed = bool(new)
    if args.contracts is not None:
        names = (None if args.contracts == "all"
                 else [n.strip() for n in args.contracts.split(",")
                       if n.strip()])
        from .contracts import ensure_host_devices, run_contracts
        ensure_host_devices(2)
        try:
            results = run_contracts(names)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        for name, problems in results.items():
            status = "FAIL" if problems else "ok"
            print(f"contract {name}: {status}")
            for p in problems:
                print(f"  - {p}")
            failed = failed or bool(problems)

    if not failed:
        print("analysis clean" + ("" if args.contracts is None
                                  else " (lint + contracts)"))
        return 0
    # informational mode still reports, but only --strict gates
    return 1 if args.strict else 0


if __name__ == "__main__":
    sys.exit(main())

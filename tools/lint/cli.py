"""``hivemind-lint``: run the unified static-analysis suite (ISSUE 16).

Exit status: 0 when clean; 1 on any unsuppressed finding OR any stale
allowlist entry (an allowlist row whose finding no longer fires is debt that
must be deleted, not carried). ``--json`` emits the machine-readable summary.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

from lint.engine import ALLOWLIST_DIR, LintContext, SuiteResult, run_suite
from lint.rules import ALL_RULES, get_rule


def _render_human(suite: SuiteResult) -> List[str]:
    lines: List[str] = []
    for result in suite.results:
        rule = result.rule
        status = "ok" if not (result.violations or result.stale_allowlist) else "FAIL"
        lines.append(
            f"[{status}] {rule.name}: {len(result.violations)} violation(s), "
            f"{len(result.suppressed)} suppressed, {len(result.allowlisted)} allowlisted "
            f"({result.duration_s * 1000:.0f} ms)"
        )
        for finding in result.violations:
            lines.append(f"    {finding.render()}")
        for stale in result.stale_allowlist:
            lines.append(
                f"    stale allowlist entry {stale!r} — no longer fires; delete it from "
                f"allowlists/{rule.name}.conf"
            )
        for warning in result.warnings:
            lines.append(f"    warning: {warning}")
    total_stale = sum(len(result.stale_allowlist) for result in suite.results)
    verdict = "clean" if suite.ok and not total_stale else "DIRTY"
    lines.append(
        f"hivemind-lint: {verdict} — {suite.total_violations} violation(s), "
        f"{total_stale} stale allowlist entr(y/ies) across {len(suite.results)} rule(s) "
        f"in {suite.duration_s:.2f} s"
    )
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="hivemind-lint",
        description="unified static-analysis suite for hivemind_tpu "
        "(asyncio races, task leaks, missing deadlines, wire drift, chaos coverage, "
        "plus the ported retry/blocking/hot-path/metric-docs checks)",
    )
    parser.add_argument("--json", action="store_true", help="emit a JSON summary instead of text")
    parser.add_argument(
        "--rule", action="append", metavar="NAME",
        help="run only this rule (repeatable); default: all",
    )
    parser.add_argument("--list-rules", action="store_true", help="list rules and exit")
    parser.add_argument(
        "--root", type=Path, default=None,
        help="repo root to lint (default: the repo this tool lives in)",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule_cls in ALL_RULES:
            print(f"{rule_cls.name:20s} {rule_cls.title}")
        return 0

    if args.rule:
        try:
            rules = [get_rule(name)() for name in args.rule]
        except KeyError as exc:
            print(f"hivemind-lint: {exc.args[0]}", file=sys.stderr)
            return 2
    else:
        rules = [rule_cls() for rule_cls in ALL_RULES]

    ctx = LintContext(repo_root=args.root) if args.root is not None else LintContext()
    suite = run_suite(rules=rules, ctx=ctx, allowlist_dir=ALLOWLIST_DIR)

    total_stale = sum(len(result.stale_allowlist) for result in suite.results)
    if args.json:
        payload = suite.to_json()
        payload["total_stale_allowlist"] = total_stale
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        print("\n".join(_render_human(suite)))
    return 0 if suite.ok and not total_stale else 1


if __name__ == "__main__":
    raise SystemExit(main())

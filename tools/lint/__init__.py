"""hivemind-lint: the unified static-analysis suite (ISSUE 16).

One AST-walk engine (`lint.engine`), ten rules (`lint.rules`), one console
entry point (`hivemind-lint`, `lint.cli`) and one tier-1 pytest entry
(tests/test_lint_suite.py). Rules share:

- a single parse of every package module (`LintContext`),
- in-source suppression: ``# lint: allow(<rule>[, <rule>...])`` on the flagged
  line, or on a ``def``/``class`` line to cover the whole block
  (``# lint: single-writer`` is an alias for ``allow(async-shared-state)``),
- per-rule allowlist files under ``tools/lint/allowlists/<rule>.conf`` where
  every entry must carry a one-line justification,
- ``--json`` output: the machine-readable summary.

See docs/static_analysis.md for the rule catalog and policy.
"""

from lint.engine import Finding, LintContext, RuleResult, SuiteResult, run_suite
from lint.rules import ALL_RULES, get_rule

__all__ = [
    "ALL_RULES",
    "Finding",
    "LintContext",
    "RuleResult",
    "SuiteResult",
    "get_rule",
    "run_suite",
]

#!/usr/bin/env python
"""Repo self-lint: the framework's own source held to the standards
it enforces on user code.

Scans ``learningorchestra_tpu/``, ``scripts/`` and
``__graft_entry__.py`` with a small AST pass, then runs the
concurrency analyzer (``analysis/concurrency.py``) over the package.

AST rules (each an error unless waived):

``exec-outside-sandbox``
    bare ``exec(`` / ``eval(`` anywhere except
    ``services/sandbox.py`` (the one module allowed to execute user
    code — everything else must route through it).
``debug-scaffolding``
    ``jax.debug.*`` calls and ``breakpoint()`` leftovers —
    ``jax.debug.print`` / ``jax.debug.breakpoint`` silently
    serialize TPU programs.
``monotonic-duration``
    ``time.time()`` used in a subtraction or comparison — a duration
    or deadline computed from the wall clock, which NTP slew makes
    non-monotonic (PR 2 fixed client polls doing exactly this); use
    ``time.monotonic()``.

Concurrency rules (``undeclared-lock``, ``lock-order``,
``blocking-under-lock``, ``callback-under-lock``, ...) are documented
in docs/ANALYSIS.md §Concurrency passes.

A finding is waived — downgraded to a warning — by a trailing or
preceding-line comment ``# lo-lint: waive(<rule-id>) — reason``
(concurrency rules use the ``# lo-conc:`` marker).

``--json`` prints the combined findings as a machine-readable
document on stdout::

    {"findings": [{"severity", "rule", "location", "message"}, ...],
     "counts": {"error": N, "warning": M}}

Exit 0 when no error-severity findings, 1 otherwise. Run by
``deploy/ci.sh`` before the tier-1 suite.
"""

from __future__ import annotations

import argparse
import ast
import json
import pathlib
import re
import sys
from typing import List

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from learningorchestra_tpu.analysis import concurrency  # noqa: E402
from learningorchestra_tpu.analysis.findings import (  # noqa: E402
    Finding, SEVERITY_ERROR, SEVERITY_WARNING)

PACKAGE = REPO / "learningorchestra_tpu"
EXTRA_ROOTS = (REPO / "scripts",)
EXTRA_FILES = (REPO / "__graft_entry__.py",)

# the one module that legitimately exec()s (user code, in the jail)
EXEC_ALLOWED = {PACKAGE / "services" / "sandbox.py"}

_EXEC_FAMILY = {"exec", "eval"}
_WAIVE = re.compile(r"#\s*lo-lint:\s*waive\(([a-z-]+)\)(.*)")


def _is_time_time(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


def _waiver(lines: List[str], lineno: int, rule: str) -> str | None:
    """Return the waiver reason if ``lineno`` (1-based) or the line
    above carries ``# lo-lint: waive(<rule>)``."""
    for idx in (lineno - 1, lineno - 2):
        if 0 <= idx < len(lines):
            m = _WAIVE.search(lines[idx])
            if m and m.group(1) == rule:
                reason = m.group(2).strip().lstrip("—- ").strip()
                return reason or "no reason given"
    return None


def _findings_for(path: pathlib.Path) -> List[Finding]:
    rel = path.relative_to(REPO)
    text = path.read_text()
    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as e:
        return [Finding(SEVERITY_ERROR, "syntax-error",
                        f"{rel}:{e.lineno or 0}",
                        f"does not parse: {e.msg}")]
    lines = text.splitlines()
    out: List[Finding] = []
    exec_ok = path in EXEC_ALLOWED

    def emit(rule: str, lineno: int, message: str) -> None:
        reason = _waiver(lines, lineno, rule)
        if reason is not None:
            out.append(Finding(SEVERITY_WARNING, rule,
                               f"{rel}:{lineno}",
                               f"waived ({reason}): {message}"))
        else:
            out.append(Finding(SEVERITY_ERROR, rule,
                               f"{rel}:{lineno}", message))

    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            if _is_time_time(node.left) or _is_time_time(node.right):
                emit("monotonic-duration", node.lineno,
                     "time.time() difference used as a duration — "
                     "wall clock is not monotonic (NTP slew); use "
                     "time.monotonic()")
            continue
        if isinstance(node, ast.Compare):
            operands = [node.left] + list(node.comparators)
            if any(_is_time_time(op) for op in operands):
                emit("monotonic-duration", node.lineno,
                     "time.time() compared against a deadline — "
                     "wall clock is not monotonic (NTP slew); use "
                     "time.monotonic()")
            continue
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Name):
            if func.id in _EXEC_FAMILY and not exec_ok:
                emit("exec-outside-sandbox", node.lineno,
                     f"bare {func.id}() outside services/sandbox.py "
                     f"— route through the sandbox")
            elif func.id == "breakpoint":
                emit("debug-scaffolding", node.lineno,
                     "breakpoint() left in library code")
        elif isinstance(func, ast.Attribute):
            # jax.debug.print / jax.debug.breakpoint / jax.debug.callback
            value = func.value
            if isinstance(value, ast.Attribute) and \
                    value.attr == "debug" and \
                    isinstance(value.value, ast.Name) and \
                    value.value.id == "jax":
                emit("debug-scaffolding", node.lineno,
                     f"jax.debug.{func.attr}() left in library code")
    return out


def _scan_paths() -> List[pathlib.Path]:
    paths: List[pathlib.Path] = []
    for root in (PACKAGE,) + EXTRA_ROOTS:
        paths.extend(sorted(root.rglob("*.py")))
    for path in EXTRA_FILES:
        if path.exists():
            paths.append(path)
    return paths


def main(argv: List[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", action="store_true",
                        help="machine-readable findings on stdout")
    args = parser.parse_args(argv)

    findings: List[Finding] = []
    for path in _scan_paths():
        findings.extend(_findings_for(path))
    findings.extend(concurrency.analyze_package())

    errors = [f for f in findings if f.severity == SEVERITY_ERROR]
    warnings = [f for f in findings if f.severity == SEVERITY_WARNING]

    if args.json:
        print(json.dumps({
            "findings": [f.to_dict() for f in findings],
            "counts": {"error": len(errors), "warning": len(warnings)},
        }, indent=2))
    else:
        for f in findings:
            print(f"{f.location}: [{f.severity}] {f.rule}: {f.message}",
                  file=sys.stderr)
        if errors:
            print(f"selflint: {len(errors)} error(s), "
                  f"{len(warnings)} warning(s)", file=sys.stderr)
        else:
            print(f"selflint: clean ({len(warnings)} waived warning(s))")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())

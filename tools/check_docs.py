#!/usr/bin/env python3
"""Docs-consistency gate: the user-facing docs must keep up with the
config surface.

Dependency-free on purpose (stdlib `ast` only, no repo imports) so it
runs in any environment — including a CI step before the test deps are
even installed. Checks:

  1. every `ServeConfig` dataclass field (parsed from
     src/repro/serving/scheduler.py) is mentioned in README.md or
     docs/ARCHITECTURE.md;
  2. every admission policy name (class-level `name = "..."` in
     scheduler.py) and every routing policy name (same, in
     src/repro/serving/router.py) is mentioned;
  3. every repro-lint rule id (class-level `rule_id = "..."` in
     tools/analyze/rules.py) is documented;
  4. every trace event type and TTFT-attribution cause (the
     `EVENT_TYPES` / `ATTRIBUTION_CAUSES` tuple literals in
     src/repro/obs/trace.py) and every profiler span name (`SPAN_NAMES`
     in src/repro/obs/spans.py) is documented — a tracer that emits
     vocabulary the docs don't explain is unreadable;
  5. every relative markdown link in the checked docs points at a file
     that exists (no rotting links).

Exit code 0 = consistent; nonzero prints what is missing.

    python tools/check_docs.py
"""
from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
DOCS = [ROOT / "README.md", ROOT / "docs" / "ARCHITECTURE.md"]
SCHEDULER = ROOT / "src" / "repro" / "serving" / "scheduler.py"
ROUTER = ROOT / "src" / "repro" / "serving" / "router.py"
LINT_RULES = ROOT / "tools" / "analyze" / "rules.py"
TRACE = ROOT / "src" / "repro" / "obs" / "trace.py"
SPANS = ROOT / "src" / "repro" / "obs" / "spans.py"


def serveconfig_fields(path: Path) -> list:
    """Names of the ServeConfig dataclass fields, in source order."""
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "ServeConfig":
            return [st.target.id for st in node.body
                    if isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)]
    raise SystemExit(f"ServeConfig dataclass not found in {path}")


def policy_names(path: Path) -> list:
    """Class-level `name = "..."` literals — the registry keys of
    AdmissionPolicy / RoutingPolicy subclasses (the '?' base-class
    placeholder is skipped)."""
    tree = ast.parse(path.read_text())
    names = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for st in node.body:
            if (isinstance(st, ast.Assign)
                    and len(st.targets) == 1
                    and isinstance(st.targets[0], ast.Name)
                    and st.targets[0].id == "name"
                    and isinstance(st.value, ast.Constant)
                    and isinstance(st.value.value, str)
                    and st.value.value != "?"):
                names.append(st.value.value)
    return names


def lint_rule_ids(path: Path) -> list:
    """Class-level `rule_id = "..."` literals of registered repro-lint
    rules (the Rule base's placeholder is skipped)."""
    tree = ast.parse(path.read_text())
    ids = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for st in node.body:
            if (isinstance(st, ast.Assign)
                    and len(st.targets) == 1
                    and isinstance(st.targets[0], ast.Name)
                    and st.targets[0].id == "rule_id"
                    and isinstance(st.value, ast.Constant)
                    and isinstance(st.value.value, str)
                    and not st.value.value.startswith("RULE")):
                ids.append(st.value.value)
    return ids


def tuple_literal(path: Path, name: str) -> list:
    """String members of a module-level `NAME = ("...", ...)` tuple."""
    tree = ast.parse(path.read_text())
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name
                and isinstance(node.value, ast.Tuple)):
            return [el.value for el in node.value.elts
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, str)]
    raise SystemExit(f"tuple literal {name} not found in {path}")


# matches [text](target) but not images/anchors/URLs
_LINK = re.compile(r"(?<!!)\[[^\]]+\]\(([^)#][^)]*)\)")


def broken_links(doc: Path) -> list:
    rel = doc.relative_to(ROOT) if doc.is_relative_to(ROOT) else doc.name
    out = []
    for target in _LINK.findall(doc.read_text()):
        if "://" in target:
            continue
        path = target.split("#", 1)[0]
        if path and not (doc.parent / path).exists():
            out.append(f"{rel}: broken link -> {target}")
    return out


def main() -> int:
    missing_docs = [d for d in DOCS if not d.exists()]
    if missing_docs:
        for d in missing_docs:
            print(f"MISSING DOC: {d.relative_to(ROOT)}")
        return 1

    corpus = "\n".join(d.read_text() for d in DOCS)
    required = {
        "ServeConfig field": serveconfig_fields(SCHEDULER),
        "admission policy": policy_names(SCHEDULER),
        "routing policy": policy_names(ROUTER),
        "repro-lint rule": lint_rule_ids(LINT_RULES),
        "trace event type": tuple_literal(TRACE, "EVENT_TYPES"),
        "TTFT attribution cause": tuple_literal(TRACE,
                                                "ATTRIBUTION_CAUSES"),
        "profiler span": tuple_literal(SPANS, "SPAN_NAMES"),
    }
    errors = []
    for kind, names in required.items():
        if not names:
            errors.append(f"parser found no {kind} entries — check the "
                          f"source layout assumptions in tools/check_docs.py")
        for n in names:
            # a mention must be the exact token in backticks or a table
            # cell, not a substring of another word or dotted name
            if not re.search(rf"(?<![A-Za-z0-9_.]){re.escape(n)}"
                             rf"(?![A-Za-z0-9_]|\.[A-Za-z])", corpus):
                errors.append(f"undocumented {kind}: {n!r} "
                              f"(add it to README.md or docs/ARCHITECTURE.md)")
    for d in DOCS:
        errors.extend(broken_links(d))

    if errors:
        print(f"docs check FAILED ({len(errors)} problem(s)):")
        for e in errors:
            print(f"  - {e}")
        return 1
    n_fields = len(required["ServeConfig field"])
    print(f"docs check OK: {n_fields} ServeConfig fields, "
          f"{len(required['admission policy'])} admission + "
          f"{len(required['routing policy'])} routing policies, "
          f"{len(required['repro-lint rule'])} lint rules, "
          f"{len(required['trace event type'])} trace event types + "
          f"{len(required['TTFT attribution cause'])} causes, "
          f"{len(required['profiler span'])} profiler spans documented, "
          f"links resolve.")
    return 0


if __name__ == "__main__":
    sys.exit(main())

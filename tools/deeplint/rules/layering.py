"""Rule ``layering``: package import isolation inside ``repro``.

The dependency discipline (see DESIGN.md §Invariants) is expressed as an
allow-list of importable package prefixes per ``repro`` subpackage.  Only
module-scope imports are checked: a function-local import is the
sanctioned way to break an intentional late-binding cycle, and is skipped.

Key edges enforced:

* ``repro.obs`` imports nothing from ``repro`` outside itself (it must be
  importable from any layer without cycles).
* ``repro.kernels`` never imports ``repro.api``/``serve``/``cluster``/
  ``baselines`` — kernels sit below the query layer.
* ``repro.core`` may import only the protocol surface of ``repro.api``
  (``plan``/``protocol``/``cache``), never the executor/query/serving side.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from tools.deeplint.engine import Finding, Project, SourceModule

RULE_ID = "layering"
SUMMARY = "module-scope import crosses a forbidden package boundary"

# Subpackage -> allowed repro import prefixes (itself always allowed).
# Subpackages not listed are unchecked.  Prefixes may be modules
# ("repro.api.plan") to allow a narrow slice of a wider package.
ALLOWED: Dict[str, Tuple[str, ...]] = {
    "repro.obs": (),
    "repro.storage": (),
    "repro.fault": ("repro.obs",),
    "repro.api": ("repro.obs", "repro.storage", "repro.fault"),
    "repro.kernels": ("repro.core", "repro.obs", "repro.storage"),
    "repro.core": (
        "repro.api.plan",
        "repro.api.protocol",
        "repro.api.cache",
        "repro.kernels",
        "repro.data",
        "repro.obs",
        "repro.storage",
        "repro.fault",
    ),
    "repro.baselines": (
        "repro.api",
        "repro.core",
        "repro.obs",
        "repro.storage",
        "repro.fault",
    ),
    "repro.cluster": (
        "repro.api",
        "repro.core",
        "repro.kernels",
        "repro.obs",
        "repro.storage",
        "repro.fault",
    ),
    "repro.serve": (
        "repro.api",
        "repro.core",
        "repro.cluster",
        "repro.kernels",
        "repro.obs",
        "repro.storage",
        "repro.fault",
    ),
}


def _owning_package(module: str) -> str | None:
    parts = module.split(".")
    if len(parts) < 2 or parts[0] != "repro":
        return None
    return ".".join(parts[:2])


def _module_scope_imports(src: SourceModule) -> Iterable[ast.stmt]:
    """Imports at module/class scope (not inside any function)."""

    def walk(body: List[ast.stmt]) -> Iterable[ast.stmt]:
        for node in body:
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                yield node
            elif isinstance(node, (ast.If, ast.Try, ast.ClassDef, ast.With)):
                for field in ("body", "orelse", "finalbody", "handlers"):
                    sub = getattr(node, field, [])
                    for item in sub:
                        if isinstance(item, ast.ExceptHandler):
                            yield from walk(item.body)
                        elif isinstance(item, ast.stmt):
                            yield from walk([item])

    yield from walk(src.tree.body)


def _targets(node: ast.stmt, module: str) -> List[str]:
    """Dotted names an import statement could bind (repro.* only)."""
    out: List[str] = []
    if isinstance(node, ast.Import):
        out.extend(alias.name for alias in node.names)
    elif isinstance(node, ast.ImportFrom):
        base = node.module or ""
        if node.level:
            # Resolve relative imports against the importing module.
            parts = module.split(".")
            anchor = parts[: len(parts) - node.level]
            base = ".".join(anchor + ([base] if base else []))
        for alias in node.names:
            out.append(base + "." + alias.name if base else alias.name)
        if base:
            out.append(base)
    return [t for t in out if t == "repro" or t.startswith("repro.")]


def _allowed(target: str, own_pkg: str, prefixes: Tuple[str, ...]) -> bool:
    for prefix in (own_pkg,) + prefixes:
        if target == prefix or target.startswith(prefix + "."):
            return True
    # "from repro import obs" produces targets "repro.obs" and "repro";
    # the bare package root is fine when every alias target is allowed,
    # which the caller checks alias-by-alias.  "repro" alone is allowed.
    return target == "repro"


def check(project: Project) -> Iterable[Finding]:
    findings: List[Finding] = []
    for src in project.modules:
        if not src.module:
            continue
        own_pkg = _owning_package(src.module)
        if own_pkg is None or own_pkg not in ALLOWED:
            continue
        prefixes = ALLOWED[own_pkg]
        for node in _module_scope_imports(src):
            bad: Set[str] = set()
            if isinstance(node, ast.ImportFrom):
                # Allowed iff every alias resolves inside the allow-list
                # (the bare "from X" module may be wider than the slice).
                alias_targets = _targets(node, src.module)
                base = alias_targets[-1] if alias_targets else ""
                per_alias = alias_targets[:-1] or alias_targets
                for t in per_alias:
                    if not _allowed(t, own_pkg, prefixes) and not _allowed(
                        base, own_pkg, prefixes
                    ):
                        bad.add(t)
            else:
                for t in _targets(node, src.module):
                    if not _allowed(t, own_pkg, prefixes):
                        bad.add(t)
            for t in sorted(bad):
                findings.append(
                    src.finding(
                        RULE_ID,
                        node,
                        f"{own_pkg} must not import {t} at module scope "
                        f"(allowed: {', '.join((own_pkg,) + prefixes)})",
                    )
                )
    return findings

"""Registry linter + the combined static-analysis runner.

AST rules enforcing the declaration contract of
``tpudl.analysis.registry``:

- ``raw-env-read`` (P0): ``os.environ.get("TPUDL_*")`` /
  ``os.environ["TPUDL_*"]`` anywhere outside the registry module.
  Knobs are read through the typed accessors so every knob is
  declared, defaulted, documented, and visible to the generated
  README table. Keys resolved through module-level constants
  (``KNOB = "TPUDL_X"; os.environ.get(KNOB)``) are caught too.
  Writes (``os.environ[k] = v`` — how benchmarks pin block sizes for
  child dispatches) are not reads and pass.
- ``undeclared-knob`` (P0): a ``TPUDL_*`` string literal that is not
  in the declaration table — either declare it or stop implying it
  exists.
- ``undocumented-knob`` (P1): a declared knob whose name never
  appears in README.md (the generated knob table makes this
  structurally impossible unless the table is stale).
- ``bad-metric-name`` (P1): a ``registry().counter/gauge/histogram``
  name literal that fails the PR-6 Prometheus conformance regex
  (lower_snake_case, no leading digit). F-string names are checked on
  their static fragments; fully dynamic names are the call site's
  responsibility (they sanitize — e.g. the router's _metric_suffix).

``run_lint`` combines these with the concurrency pass
(tpudl.analysis.concurrency) over the threaded subsystems — the one
entry point ``scripts/lint_tpudl.py`` and tier-1's
``tests/test_analysis.py`` share.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence

from tpudl.analysis import concurrency
from tpudl.analysis.findings import Finding
from tpudl.analysis.registry import (
    KNOBS,
    METRIC_FRAGMENT_RE,
    METRIC_NAME_RE,
)

_KNOB_RE = re.compile(r"^TPUDL_[A-Z0-9_]+$")
_METRIC_FACTORIES = ("counter", "gauge", "histogram")

#: The one module allowed to touch os.environ for TPUDL_* keys.
REGISTRY_MODULE = "tpudl/analysis/registry.py"

#: Threaded subsystems the concurrency pass covers (ISSUE 12 scope).
CONCURRENCY_TARGETS = (
    "tpudl/serve",
    "tpudl/obs",
    "tpudl/ft",
    "tpudl/data",
    "tpudl/train",
)

#: Trees the registry/metric rules scan.
REGISTRY_TARGETS = ("tpudl", "scripts")


def _iter_py_files(root: str, targets: Sequence[str]) -> List[str]:
    files: List[str] = []
    for target in targets:
        path = os.path.join(root, target)
        if os.path.isfile(path):
            files.append(path)
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    files.append(os.path.join(dirpath, fn))
    return files


class _RegistryRuleVisitor(ast.NodeVisitor):
    def __init__(self, path: str, constants: Dict[str, str]):
        self.path = path
        self.constants = constants
        self.findings: List[Finding] = []
        self._scope: List[str] = []

    # -- symbol tracking ------------------------------------------------

    def _symbol(self) -> str:
        return ".".join(self._scope) if self._scope else "<module>"

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    def visit_FunctionDef(self, node) -> None:
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- helpers --------------------------------------------------------

    def _knob_key(self, node: ast.AST) -> Optional[str]:
        """The TPUDL_* key an expression statically resolves to."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value if _KNOB_RE.match(node.value) else None
        if isinstance(node, ast.Name):
            value = self.constants.get(node.id)
            if value is not None and _KNOB_RE.match(value):
                return value
        return None

    @staticmethod
    def _is_os_environ(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Attribute)
            and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os"
        )

    def _flag_env_read(self, key: str, line: int) -> None:
        self.findings.append(
            Finding(
                rule="raw-env-read",
                path=self.path,
                line=line,
                symbol=self._symbol(),
                message=(
                    f"raw os.environ read of {key} — go through "
                    f"tpudl.analysis.registry (env_str/env_int/"
                    f"env_float/env_flag), which declares, types, and "
                    f"documents every knob"
                ),
                severity="P0",
            )
        )

    # -- rules ----------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        # os.environ.get(KEY) / os.environ.setdefault(KEY, ...)
        if (
            isinstance(func, ast.Attribute)
            and func.attr in ("get", "setdefault", "pop")
            and self._is_os_environ(func.value)
            and node.args
        ):
            key = self._knob_key(node.args[0])
            if key is not None:
                self._flag_env_read(key, node.lineno)
        # registry().counter("name") conformance
        if (
            isinstance(func, ast.Attribute)
            and func.attr in _METRIC_FACTORIES
            and node.args
        ):
            self._check_metric_name(node.args[0], node.lineno)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if self._is_os_environ(node.value) and isinstance(
            node.ctx, ast.Load
        ):
            key = self._knob_key(node.slice)
            if key is not None:
                self._flag_env_read(key, node.lineno)
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        if (
            isinstance(node.value, str)
            and _KNOB_RE.match(node.value)
            and node.value not in KNOBS
        ):
            self.findings.append(
                Finding(
                    rule="undeclared-knob",
                    path=self.path,
                    line=node.lineno,
                    symbol=self._symbol(),
                    message=(
                        f"{node.value} is not declared in "
                        f"tpudl.analysis.registry.KNOBS"
                    ),
                    severity="P0",
                )
            )

    def _check_metric_name(self, arg: ast.AST, line: int) -> None:
        bad: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            if not METRIC_NAME_RE.match(arg.value):
                bad = repr(arg.value)
        elif isinstance(arg, ast.JoinedStr):
            fragments = [
                v.value for v in arg.values
                if isinstance(v, ast.Constant)
                and isinstance(v.value, str)
            ]
            if any(
                not METRIC_FRAGMENT_RE.match(f) for f in fragments
            ):
                bad = "".join(fragments) and repr("".join(fragments))
        if bad:
            self.findings.append(
                Finding(
                    rule="bad-metric-name",
                    path=self.path,
                    line=line,
                    symbol=self._symbol(),
                    message=(
                        f"metric name {bad} fails the Prometheus "
                        f"conformance regex "
                        f"{METRIC_NAME_RE.pattern!r} — the /metrics "
                        f"exposition would need sanitizing"
                    ),
                    severity="P1",
                )
            )


def _module_constants(tree: ast.Module) -> Dict[str, str]:
    constants: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Constant
        ):
            if isinstance(node.value.value, str):
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        constants[target.id] = node.value.value
    return constants


def lint_source(
    source: str, path: str, skip_env_rule: bool = False
) -> List[Finding]:
    """Registry-family rules over one file's source text."""
    tree = ast.parse(source, filename=path)
    visitor = _RegistryRuleVisitor(path, _module_constants(tree))
    visitor.visit(tree)
    findings = visitor.findings
    if skip_env_rule:
        findings = [f for f in findings if f.rule != "raw-env-read"]
    return findings


def lint_registry(repo_root: str) -> List[Finding]:
    findings: List[Finding] = []
    for file in _iter_py_files(repo_root, REGISTRY_TARGETS):
        rel = os.path.relpath(file, repo_root).replace(os.sep, "/")
        with open(file) as f:
            source = f.read()
        findings.extend(
            lint_source(
                source, rel, skip_env_rule=(rel == REGISTRY_MODULE)
            )
        )
    findings.extend(_readme_findings(repo_root))
    return findings


def _readme_findings(repo_root: str) -> List[Finding]:
    readme = os.path.join(repo_root, "README.md")
    if not os.path.exists(readme):
        return []
    with open(readme) as f:
        text = f.read()
    findings: List[Finding] = []
    for name in sorted(KNOBS):
        if name not in text:
            findings.append(
                Finding(
                    rule="undocumented-knob",
                    path="README.md",
                    line=1,
                    symbol=name,
                    message=(
                        f"declared knob {name} does not appear in "
                        f"README.md — regenerate the knob table "
                        f"(scripts/lint_tpudl.py --knob-table)"
                    ),
                    severity="P1",
                )
            )
    return findings


def run_lint(repo_root: str) -> List[Finding]:
    """The full static tier: concurrency over the threaded subsystems
    + registry/metric/knob rules over the runtime tree."""
    findings = concurrency.analyze_paths(
        [
            os.path.join(repo_root, t)
            for t in CONCURRENCY_TARGETS
            if os.path.exists(os.path.join(repo_root, t))
        ],
        repo_root=repo_root,
    )
    findings.extend(lint_registry(repo_root))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings

"""metrics-registry: every metric name that reaches the shared ``Metrics``
surface must be a constant from the canonical registry module
``opencv_facerecognizer_tpu/utils/metric_names.py``.

The chaos soaks and the admission ledger compare counters *by string name*
across 11+ files — one typo silently breaks an accounting invariant with no
error anywhere.  This rule kills the drift: write sites (``incr`` /
``observe`` / ``set_gauge``, and each ``(name, value)`` pair handed to
``incr_many``) and read sites (``counter`` / ``percentile`` /
``counters_with_prefix``) are both checked.  Accepted argument shapes:

- a string literal whose value is registered,
- ``mn.SOME_CONSTANT`` / an imported constant that exists in the registry,
- ``f"prefix_{x}"`` or ``PREFIX + x`` where the literal prefix is a
  registered ``*_PREFIX`` constant,
- a conditional expression whose branches each satisfy the above.

Anything else (a bare variable, a computed name) is flagged — thread the
name through a registry constant instead."""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from tools.ocvf_lint.core import Checker, FileContext, Finding, register

REGISTRY_SUFFIX = "utils/metric_names.py"

#: Metrics methods whose first positional argument is a metric name.
#: The distinctive ones are checked on any receiver; ``counter`` and
#: ``percentile`` collide with common APIs (``np.percentile``) and are only
#: checked when the receiver looks like a Metrics surface.
NAME_METHODS = frozenset({"incr", "observe", "set_gauge", "counter",
                          "percentile", "counters_with_prefix",
                          # the connectors' and tracker's None-guarded shims
                          "_count", "_incr"})
GENERIC_METHODS = frozenset({"counter", "percentile"})
#: ``Metrics.incr_many((name, value), ...)``: every positional argument is
#: a literal pair whose first element is a metric name.
PAIRS_METHODS = frozenset({"incr_many"})


def _metrics_ish_receiver(func: ast.Attribute) -> bool:
    base = func.value
    name = base.attr if isinstance(base, ast.Attribute) else \
        base.id if isinstance(base, ast.Name) else ""
    return "metric" in name.lower()


def _registry_from_tree(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(full names, prefix values) from module-level ``NAME = "literal"``."""
    names: Set[str] = set()
    prefixes: Set[str] = set()
    for stmt in tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        if not (len(stmt.targets) == 1 and isinstance(stmt.targets[0], ast.Name)):
            continue
        if not (isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)):
            continue
        target = stmt.targets[0].id
        if target.startswith("_"):
            continue
        if target.endswith("_PREFIX"):
            prefixes.add(stmt.value.value)
        else:
            names.add(stmt.value.value)
    return names, prefixes


def _registry_constants(tree: ast.Module) -> Set[str]:
    return {stmt.targets[0].id for stmt in tree.body
            if isinstance(stmt, ast.Assign)
            and len(stmt.targets) == 1
            and isinstance(stmt.targets[0], ast.Name)
            and not stmt.targets[0].id.startswith("_")}


class _FileImports:
    """Which local names in a file refer to the metric_names module or to
    constants imported from it."""

    def __init__(self, tree: ast.Module):
        self.module_aliases: Set[str] = set()
        self.constant_aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                if node.module.endswith("metric_names"):
                    for alias in node.names:
                        self.constant_aliases[alias.asname or alias.name] = alias.name
                elif node.module.endswith("utils"):
                    for alias in node.names:
                        if alias.name == "metric_names":
                            self.module_aliases.add(alias.asname or alias.name)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.endswith("metric_names"):
                        self.module_aliases.add(alias.asname or alias.name.split(".")[0])


@register
class MetricsRegistryChecker(Checker):
    rule = "metrics-registry"
    description = ("metric names passed to Metrics.incr/observe/set_gauge "
                   "(and read sites) must come from "
                   "utils/metric_names.py")
    scope = "project"  # validity depends on the registry file's content

    def __init__(self) -> None:
        self._registry_tree: Optional[ast.Module] = None
        #: (file, its imports, the call, the method, the name expression)
        self._pending: List[Tuple[FileContext, _FileImports, ast.Call, str,
                                  ast.expr]] = []

    def check_file(self, ctx: FileContext) -> List[Finding]:
        norm = ctx.path.replace("\\", "/")
        if norm.endswith(REGISTRY_SUFFIX):
            self._registry_tree = ctx.tree
            return []
        imports = _FileImports(ctx.tree)
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.args):
                continue
            method = node.func.attr
            if method in PAIRS_METHODS:
                for pair in node.args:
                    if isinstance(pair, ast.Tuple) and len(pair.elts) == 2:
                        self._pending.append(
                            (ctx, imports, node, "incr", pair.elts[0]))
                    else:
                        findings.append(ctx.finding(
                            self.rule, node,
                            f"{method} takes literal (name, value) pairs — "
                            "a computed argument hides its metric names "
                            "from the registry check"))
            elif method in NAME_METHODS:
                if (method in GENERIC_METHODS
                        and not _metrics_ish_receiver(node.func)):
                    continue
                self._pending.append(
                    (ctx, imports, node, method, node.args[0]))
        return findings

    @staticmethod
    def _fallback_registry_path() -> str:
        here = os.path.dirname(os.path.abspath(__file__))
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
        return os.path.join(repo_root, "opencv_facerecognizer_tpu",
                            "utils", "metric_names.py")

    def extra_cache_fingerprint(self, files) -> str:
        """When the registry is NOT among the linted files, the verdict
        depends on the fallback registry read from disk — fold its content
        into the run-cache key so editing utils/metric_names.py can never
        replay a stale cached verdict for a subset lint."""
        if any(f.replace("\\", "/").endswith(REGISTRY_SUFFIX) for f in files):
            return ""  # in-tree: its content hash is already in the key
        candidate = self._fallback_registry_path()
        try:
            with open(candidate, "rb") as fh:
                import hashlib

                return "metrics-registry:" + hashlib.sha256(fh.read()).hexdigest()
        except OSError:
            return "metrics-registry:absent"

    def _load_fallback_registry(self) -> None:
        if self._registry_tree is not None:
            return
        candidate = self._fallback_registry_path()
        if os.path.exists(candidate):
            with open(candidate, "r", encoding="utf-8") as fh:
                self._registry_tree = ast.parse(fh.read())

    def finalize(self) -> List[Finding]:
        if not self._pending:
            return []
        self._load_fallback_registry()
        if self._registry_tree is None:
            ctx = self._pending[0][0]
            return [Finding(self.rule, ctx.path, 1, 0,
                            "no utils/metric_names.py registry found in the "
                            "scanned tree or the repository — metric names "
                            "cannot be validated")]
        values, prefixes = _registry_from_tree(self._registry_tree)
        constants = _registry_constants(self._registry_tree)
        findings: List[Finding] = []
        for ctx, imports, call, method, name in self._pending:
            problem = self._check_name_expr(name, method, values,
                                            prefixes, constants, imports)
            if problem is not None:
                findings.append(ctx.finding(self.rule, call, problem))
        return findings

    def _check_name_expr(self, arg, method, values, prefixes, constants,
                         imports) -> Optional[str]:
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            # counters_with_prefix takes a *_PREFIX value; everything else a
            # full name — the pools are deliberately disjoint checks, so a
            # bare prefix passed as a counter name (or vice versa) is drift.
            pool = prefixes if method == "counters_with_prefix" else values
            if arg.value in pool:
                return None
            kind = "prefix" if method == "counters_with_prefix" else "name"
            return (f"metric {kind} {arg.value!r} is not a registered "
                    f"{'*_PREFIX value' if kind == 'prefix' else 'full name'} "
                    f"in utils/metric_names.py — add it to the registry (typo?)")
        if isinstance(arg, ast.JoinedStr):
            head = arg.values[0] if arg.values else None
            if (isinstance(head, ast.Constant) and isinstance(head.value, str)
                    and head.value in prefixes):
                return None
            return ("f-string metric name must start with a registered "
                    "*_PREFIX constant's value from utils/metric_names.py")
        if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
            # PREFIX + suffix: the LEFT operand must be a registered prefix
            # — either its literal value, or a *_PREFIX registry constant.
            # (A full-name constant on the left would mint an unregistered
            # dynamic family, exactly the drift this rule exists to catch.)
            left = arg.left
            if (isinstance(left, ast.Constant) and isinstance(left.value, str)
                    and left.value in prefixes):
                return None
            if (isinstance(left, ast.Attribute)
                    and isinstance(left.value, ast.Name)
                    and left.value.id in imports.module_aliases
                    and left.attr in constants and left.attr.endswith("_PREFIX")):
                return None
            if (isinstance(left, ast.Name)
                    and left.id in imports.constant_aliases
                    and imports.constant_aliases[left.id] in constants
                    and imports.constant_aliases[left.id].endswith("_PREFIX")):
                return None
            return ("concatenated metric name must start with a registered "
                    "*_PREFIX constant (or its literal value) from "
                    "utils/metric_names.py")
        if isinstance(arg, ast.IfExp):
            return (self._check_name_expr(arg.body, method, values, prefixes,
                                          constants, imports)
                    or self._check_name_expr(arg.orelse, method, values,
                                             prefixes, constants, imports))
        if isinstance(arg, ast.Attribute) and isinstance(arg.value, ast.Name):
            if arg.value.id in imports.module_aliases:
                if arg.attr in constants:
                    return None
                return (f"metric_names.{arg.attr} does not exist in the "
                        f"registry module")
        if isinstance(arg, ast.Name):
            if arg.id in imports.constant_aliases:
                original = imports.constant_aliases[arg.id]
                if original in constants:
                    return None
                return f"metric_names.{original} does not exist in the registry"
            return (f"metric name is the bare variable {arg.id!r} — thread a "
                    f"registry constant (or a registered *_PREFIX + suffix) "
                    f"through instead")
        return ("metric name is not statically resolvable to a "
                "utils/metric_names.py constant")

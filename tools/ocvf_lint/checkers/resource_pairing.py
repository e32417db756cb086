"""resource-pairing: generic acquire/release protocol engine, instantiated
from the declarative table in ``tools.ocvf_lint.wiring.RESOURCE_PAIRINGS``.

Three pairing disciplines ship today (adding a resource is a wiring edit,
not a checker edit — see README "declaring a new paired resource"):

- ``acquire-release`` (custody replay): a call like ``StagingRing.acquire``
  yields a buffer that must be discharged on every exit path — released
  through one of the declared release methods (``recycle``/``forfeit``/
  ``release``), handed off to another owner (passed into any call, stored
  into a container/attribute, or returned), or overwritten by a non-custody
  value.  Custody is tracked as a set of local alias names and replayed
  over every exit path the engine enumerates — INCLUDING raising paths,
  because leaking the staging buffer in a crash handler is exactly the bug
  this rule exists for (the ring leaks one slot per crash until admission
  wedges).
- ``seq-burn``: a WAL sequence number burned with the increment idiom
  (``self._wal_seq = self._wal_seq + 1``) must be released on every path
  by an ``append_*`` on the WAL (the record that justifies the burn, or an
  ``append_abort`` on failure).  A burned-but-unreleased sequence leaves a
  hole in the WAL that recovery must special-case forever.  Watermark
  seeding (``self._wal_seq = max(...)``) is not a burn and is ignored.
- ``context``: ``Tracer.lifecycle`` and ``Tracer.span`` are contextmanagers; calling one
  anywhere but a ``with`` item produces a span that never closes.  This is
  a plain AST check, no path enumeration needed.

Functions whose path enumeration overflows the engine budget are skipped.
Designed exceptions (e.g. a fault-injection re-raise that intentionally
leaks a burned seq to exercise recovery) carry
``# ocvf-lint: boundary=resource-pairing -- why`` on the exiting statement."""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.ocvf_lint import wiring
from tools.ocvf_lint.astutil import terminal_attr
from tools.ocvf_lint.core import Checker, FileContext, Finding, register
from tools.ocvf_lint.exitpaths import LOOP, enumerate_exit_paths, walk_events


def _names_in(expr: ast.expr) -> Set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def _call_arg_names(call: ast.Call) -> Set[str]:
    """Every Name appearing anywhere in a call's arguments (handoff is
    permissive: ``self._inflight.append((packed, frames, ...))`` discharges
    ``frames`` even though it is wrapped in a tuple)."""
    names: Set[str] = set()
    for arg in call.args:
        names |= _names_in(arg)
    for kw in call.keywords:
        names |= _names_in(kw.value)
    return names


@register
class ResourcePairingChecker(Checker):
    rule = "resource-pairing"
    description = ("acquired resources (staging buffers, burned WAL "
                   "sequence numbers, lifecycle spans) must be released, "
                   "handed off, or aborted on every exit path")
    boundary_capable = True

    # ---- pairing-table accessors ----

    def _pairings_for(self, path: str) -> List[dict]:
        out = []
        for pairing in wiring.RESOURCE_PAIRINGS:
            suffixes = pairing.get("module_suffixes", ())
            if suffixes and not wiring.path_matches(path, suffixes):
                continue
            out.append(pairing)
        return out

    @staticmethod
    def _matches_method(call: ast.Call,
                        methods: Tuple[Tuple[str, str], ...]) -> bool:
        if not isinstance(call.func, ast.Attribute):
            return False
        receiver = terminal_attr(call.func.value)
        hinted = wiring.ATTR_HINTS.get(receiver or "")
        return any(hinted == cls and call.func.attr == method
                   for cls, method in methods)

    def _is_acquire(self, call: ast.Call, pairing: dict) -> bool:
        return self._matches_method(call, pairing["acquire_methods"])

    # ---- entry point ----

    def check_file(self, ctx: FileContext) -> List[Finding]:
        pairings = self._pairings_for(ctx.path)
        if not pairings:
            return []
        findings: List[Finding] = []
        contexts = [p for p in pairings if p["kind"] == "context"]
        flows = [p for p in pairings if p["kind"] in
                 ("acquire-release", "seq-burn")]
        if contexts:
            findings.extend(self._check_contexts(ctx, contexts))
        if flows:
            for node in ast.walk(ctx.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    findings.extend(self._check_function(ctx, node, flows))
        return findings

    # ---- context pairings (plain AST) ----

    def _check_contexts(self, ctx: FileContext,
                        pairings: Sequence[dict]) -> List[Finding]:
        with_items: Set[int] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    expr = item.context_expr
                    with_items.add(id(expr))
                    if isinstance(expr, ast.IfExp):
                        # ``with (tracer.span(...) if tid else NULL_SPAN):``
                        # enters whichever branch it picks.
                        with_items.update((id(expr.body), id(expr.orelse)))
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or id(node) in with_items:
                continue
            for pairing in pairings:
                if self._matches_method(node, pairing["context_methods"]):
                    cls, method = pairing["context_methods"][0][0], \
                        node.func.attr
                    findings.append(ctx.finding(
                        self.rule, node,
                        f"{cls}.{method} is a contextmanager — call it as "
                        f"`with ....{method}(...):` or the "
                        f"{pairing['what']} opened here never closes"))
        return findings

    # ---- custody replay over exit paths ----

    def _check_function(self, ctx: FileContext, fn: ast.AST,
                        pairings: Sequence[dict]) -> List[Finding]:
        findings: List[Finding] = []
        relevant = [p for p in pairings
                    if self._has_events(fn, p, ctx, findings)]
        if not relevant:
            return findings
        memo: Dict[int, List[Tuple]] = {}

        def extract(node: ast.AST) -> List[Tuple]:
            key = id(node)
            if key not in memo:
                memo[key] = self._events_for(node, relevant)
            return memo[key]

        paths, truncated = enumerate_exit_paths(
            fn.body, extract, optional_attrs=wiring.OPTIONAL_SURFACE_ATTRS)
        if truncated:
            return findings
        reported: Set[Tuple] = set()
        for path in paths:
            if path.terminal == LOOP:
                continue  # body never exits; nothing escapes custody
            self._replay(ctx, fn, path, relevant, reported, findings)
        return findings

    def _has_events(self, fn: ast.AST, pairing: dict, ctx: FileContext,
                    findings: List[Finding]) -> bool:
        """Cheap pre-scan: does this function acquire/burn at all?  Also
        flags result-discarding acquires (custody dropped on the floor)."""
        found = False
        for stmt in ast.walk(fn):
            if pairing["kind"] == "acquire-release":
                if isinstance(stmt, ast.Call) \
                        and self._is_acquire(stmt, pairing):
                    found = True
                if isinstance(stmt, ast.Expr) \
                        and isinstance(stmt.value, ast.Call) \
                        and self._is_acquire(stmt.value, pairing):
                    cls, method = pairing["acquire_methods"][0]
                    findings.append(ctx.finding(
                        self.rule, stmt.value,
                        f"result of {cls}.{method} is discarded — the "
                        f"{pairing['what']} is acquired here but nothing "
                        f"holds it, so it can never be released"))
            elif pairing["kind"] == "seq-burn":
                if self._burn_node(stmt, pairing) is not None:
                    found = True
        return found

    @staticmethod
    def _burn_node(stmt: ast.AST, pairing: dict) -> Optional[ast.AST]:
        """A burn is the increment idiom only: an Assign whose value is a
        BinOp and whose targets include ``<obj>.<burn_attr>``.  Plain or
        ``max(...)`` assignments (watermark seeding during recovery) do not
        burn a sequence."""
        if not (isinstance(stmt, ast.Assign)
                and isinstance(stmt.value, ast.BinOp)):
            return None
        for target in stmt.targets:
            if isinstance(target, ast.Attribute) \
                    and target.attr == pairing["burn_attr"]:
                return stmt
        return None

    def _events_for(self, node: ast.AST,
                    pairings: Sequence[dict]) -> List[Tuple]:
        evs: List[Tuple] = []
        stmt = node
        # Acquire assignments: custody goes to the Name targets; an
        # Attribute/Subscript target is an immediate handoff into a
        # structure another owner manages.
        if isinstance(stmt, ast.Assign):
            acquire_of = None
            for pairing in pairings:
                if pairing["kind"] != "acquire-release":
                    continue
                for sub in walk_events(stmt.value):
                    if isinstance(sub, ast.Call) \
                            and self._is_acquire(sub, pairing):
                        acquire_of = (pairing, sub)
                        break
            if acquire_of is not None:
                pairing, call = acquire_of
                names = tuple(t.id for t in stmt.targets
                              if isinstance(t, ast.Name))
                handed_off = any(not isinstance(t, ast.Name)
                                 for t in stmt.targets)
                if names or not handed_off:
                    evs.append(("acq", pairing["name"], names, call))
                return evs
            for pairing in pairings:
                burn = self._burn_node(stmt, pairing) \
                    if pairing["kind"] == "seq-burn" else None
                if burn is not None:
                    evs.append(("burn", pairing["name"], burn))
                    return evs
            targets = tuple(t.id for t in stmt.targets
                            if isinstance(t, ast.Name))
            if targets:
                evs.append(("assign", targets,
                            frozenset(_names_in(stmt.value))))
            # fall through: calls inside the value are handoff candidates
        if isinstance(stmt, ast.Return) and stmt.value is not None:
            evs.append(("ret", frozenset(_names_in(stmt.value))))
            return evs
        for sub in walk_events(node):
            if not isinstance(sub, ast.Call):
                continue
            for pairing in pairings:
                if pairing["kind"] != "seq-burn":
                    continue
                if isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr.startswith(
                            pairing["release_attr_prefix"]) \
                        and terminal_attr(sub.func.value) \
                        == pairing["release_receiver"]:
                    evs.append(("burnrel", pairing["name"]))
            names = _call_arg_names(sub)
            if names:
                evs.append(("call", frozenset(names)))
        return evs

    def _replay(self, ctx: FileContext, fn: ast.AST, path,
                pairings: Sequence[dict], reported: Set[Tuple],
                findings: List[Finding]) -> None:
        custody: Dict[str, Set[str]] = {}
        acq_node: Dict[str, ast.AST] = {}
        burned: Dict[str, ast.AST] = {}
        for ev in path.events:
            kind = ev[0]
            if kind == "acq":
                _, pname, names, node = ev
                custody[pname] = set(names)
                acq_node[pname] = node
            elif kind == "burn":
                burned[ev[1]] = ev[2]
            elif kind == "burnrel":
                burned.pop(ev[1], None)
            elif kind == "assign":
                _, targets, value_names = ev
                for pname, held in custody.items():
                    if held & value_names:
                        held.update(targets)  # alias propagation
                    else:
                        held.difference_update(targets)  # overwritten away
            elif kind in ("call", "ret"):
                names = ev[1]
                for held in custody.values():
                    if held & names:
                        held.clear()  # release or handoff
        end_line = getattr(path.end, "lineno", None)
        also = ((ctx.path, end_line),) if end_line is not None else ()
        where = (f"the exit at line {end_line}" if end_line is not None
                 else "function exit")
        by_name = {p["name"]: p for p in pairings}
        for pname, held in custody.items():
            if not held:
                continue
            node = acq_node[pname]
            key = ("leak", pname, id(node), end_line)
            if key in reported:
                continue
            reported.add(key)
            pairing = by_name[pname]
            findings.append(ctx.finding(
                self.rule, node,
                f"{fn.name}: {pairing['what']} acquired here "
                f"({'/'.join(sorted(held))}) is still held at {where} — "
                f"release it ({'/'.join(sorted(pairing['release_attrs']))}) "
                f"or hand it off on every path, including crash paths",
                also=also))
        for pname, node in burned.items():
            key = ("burn", pname, id(node), end_line)
            if key in reported:
                continue
            reported.add(key)
            pairing = by_name[pname]
            findings.append(ctx.finding(
                self.rule, node,
                f"{fn.name}: {pairing['what']} burned here reaches "
                f"{where} without a WAL "
                f"{pairing['release_attr_prefix']}* record — recovery sees "
                f"a hole in the sequence (append the record, or "
                f"append_abort on the failure path)", also=also))

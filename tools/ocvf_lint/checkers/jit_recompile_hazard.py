"""jit-recompile-hazard: Python-value-dependent control flow or host
materialization inside jax-traced code, and unsanctioned jit construction
in the serving hot path.

The PR-2 bucket ladder exists so serving NEVER compiles mid-stream: every
dispatch shape is prewarmed, every jit executable is cache-keyed by
(batch, frame, capacity, matcher).  Two bug classes silently break that
contract:

1. **Inside a traced body** — branching on a traced value (``if x.sum() >
   0:``), or materializing one (``float(x)``, ``np.asarray(x)``,
   ``.item()``), concretizes at trace time: a TracerBoolConversionError at
   best, a silently-baked constant (stale after the next enrollment) at
   worst.  Found interprocedurally: the walk follows project-local calls
   (``decode_detections(outputs, ...)``) with the taint of their actual
   arguments, so a hazard three calls deep inside ``models/`` is reported
   where it lives.

2. **jit construction in the hot path** — a stray ``jax.jit(...)`` in
   recognizer/batcher/pipeline is a latent mid-serving compile (seconds
   of stall for that batch).  The sanctioned builder sites — the
   bucket-ladder step factory, the packed-step cache fill, prewarm, the
   enrolment chunk built at construction — carry
   ``# ocvf-lint: boundary=jit-recompile-hazard`` annotations; anything
   else is a finding."""

from __future__ import annotations

import ast
from typing import List, Set, Tuple

from tools.ocvf_lint import wiring
from tools.ocvf_lint.core import Checker, Finding, register


@register
class JitRecompileHazardChecker(Checker):
    rule = "jit-recompile-hazard"
    description = ("traced-value branching / host materialization inside "
                   "jax.jit-reachable code, and jit construction in the "
                   "serving hot path outside sanctioned builder sites")
    scope = "project"
    boundary_capable = True
    needs_dataflow = True

    _KIND_MESSAGES = {
        "branch": ("{detail} inside the jax-traced function {fn!r} — the "
                   "branch concretizes at trace time (TracerBool error, or "
                   "a different executable per Python value: a recompile "
                   "the prewarmed bucket ladder can never absorb); use "
                   "jnp.where/lax.cond, or hoist the decision to the "
                   "cache-keyed builder"),
        "materialize": ("{detail} inside the jax-traced function {fn!r} — "
                        "host materialization during tracing either raises "
                        "or silently bakes the traced value in as a "
                        "compile-time constant (stale after the next "
                        "gallery mutation)"),
    }

    def finalize(self) -> List[Finding]:
        findings: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()
        if self.project is None:
            return findings
        from tools.ocvf_lint import dataflow

        checker = dataflow.JitTraceChecker(self.project).run()
        for fn, node, kind, detail in checker.findings:
            message = self._KIND_MESSAGES[kind].format(detail=detail,
                                                       fn=fn.qual)
            key = (fn.path, getattr(node, "lineno", 1), message)
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(self.rule, fn.path,
                                    getattr(node, "lineno", 1),
                                    getattr(node, "col_offset", 0), message))

        # hot-path jit construction outside annotated builder sites
        for mi in self.project.modules.values():
            if not wiring.path_matches(mi.ctx.path, wiring.HOT_PATH_SUFFIXES):
                continue
            # decorator Call nodes (@functools.partial(jax.jit, ...)) are
            # reported once by the decorator loop below, never twice
            decorator_ids = {id(dec) for fi in mi.all_funcs
                             for dec in getattr(fi.node, "decorator_list", [])}
            for node in ast.walk(mi.ctx.tree):
                if isinstance(node, ast.Call) \
                        and id(node) not in decorator_ids \
                        and self.project._jit_call_info(mi, node) is not None:
                    findings.append(Finding(
                        self.rule, mi.ctx.path, node.lineno, node.col_offset,
                        "jit construction in the serving hot path — a cold "
                        "call here is a mid-serving XLA compile; route it "
                        "through a prewarmed, cache-keyed builder and mark "
                        "that site with "
                        "'# ocvf-lint: boundary=jit-recompile-hazard -- "
                        "<why every serving call finds a warm cache>'"))
            for fi in mi.all_funcs:
                for dec in getattr(fi.node, "decorator_list", []):
                    if self.project._jit_callee_kind(mi, dec) or (
                            isinstance(dec, ast.Call)
                            and self.project._jit_call_info(mi, dec)
                            is not None):
                        findings.append(Finding(
                            self.rule, mi.ctx.path, fi.node.lineno,
                            fi.node.col_offset,
                            f"@jit-decorated {fi.name!r} in the serving hot "
                            f"path compiles per call shape — prewarm it or "
                            f"annotate the sanctioned builder site"))
        return findings

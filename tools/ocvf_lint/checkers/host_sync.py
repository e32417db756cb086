"""host-sync: a device synchronization reachable from the serving loop,
outside an annotated readback boundary.

The overlapped pipeline (PR 2) makes the serving loop's device interaction
fully asynchronous: dispatch enqueues, the readback worker waits, and
exactly ONE ``np.asarray`` per batch materializes the packed result (a
blocking sync parks the loop for a whole device round trip while the chip
sits idle).  One stray ``.block_until_ready()``/``.item()``/
``np.asarray(device_value)`` anywhere in the hot path silently serializes
the whole overlap away again.

Device values are tracked by the shared dataflow layer: taint seeds at
dispatch sites (``recognize_batch_packed``, anything assigned from
``jax.jit(...)``, ``jnp.*``), flows through locals, tuple unpacking,
attribute stores (the in-flight deque) and resolved calls; ``np.*``/
``float()`` on a tainted value IS the readback (and stops the taint —
downstream host math is fine).  ``.block_until_ready()``, ``device_get``
and ``.item()`` are flagged wherever they appear in hot-path modules:
their only purpose is to synchronize.

The designed sync points — the sacrificial blocker thread, warmup,
prewarm (grow-worker thread), the single per-batch materialize, the
enrolment thread's embeds — carry
``# ocvf-lint: boundary=host-sync -- <why>`` annotations; that audit
trail is the rule's product."""

from __future__ import annotations

from typing import List

from tools.ocvf_lint import wiring
from tools.ocvf_lint.core import Checker, Finding, register


@register
class HostSyncChecker(Checker):
    rule = "host-sync"
    description = ("blocking device->host synchronization "
                   "(block_until_ready/device_get/.item()/np.asarray on a "
                   "device value) in the serving hot path outside annotated "
                   "readback boundaries")
    scope = "project"
    boundary_capable = True
    needs_dataflow = True

    def finalize(self) -> List[Finding]:
        if self.project is None:
            return []
        from tools.ocvf_lint import dataflow

        hot = [name for name, mi in self.project.modules.items()
               if wiring.path_matches(mi.ctx.path, wiring.HOT_PATH_SUFFIXES)]
        if not hot:
            return []
        analysis = dataflow.HostSyncAnalysis(self.project, hot)
        findings: List[Finding] = []
        for fn, node, kind, detail in analysis.run():
            if kind == "sync":
                message = (
                    f"{detail} in {fn.qual!r} blocks the serving hot path on "
                    f"the device (a sync parks the loop for a device round "
                    f"trip and serializes the PR-2 overlap away); move it "
                    f"behind the readback worker, or annotate the designed "
                    f"boundary with '# ocvf-lint: boundary=host-sync -- "
                    f"<why this sync is the protocol>'")
            else:
                message = (
                    f"{detail} in {fn.qual!r} materializes a device value "
                    f"on the host — this IS a blocking readback; keep the "
                    f"serving loop to its one annotated per-batch "
                    f"materialize, or annotate this site as a host-sync "
                    f"boundary with justification")
            findings.append(Finding(self.rule, fn.path,
                                    getattr(node, "lineno", 1),
                                    getattr(node, "col_offset", 0), message))
        return findings

"""Perf-regression gate: diff two ``BENCH_SERVING_smoke.json`` artifacts
with per-metric thresholds — nonzero rc on regression.

``bench_serving.py --smoke`` writes a deterministic serving-loop perf
artifact on every run, but until now nothing ever COMPARED two of them:
the BENCH_*.json history records absolute numbers, not trajectories, so a
slow regression (e2e p50 creeping 5% per PR, tracing overhead ratio
drifting toward its gate) is invisible until a hard gate blows. This
script is the start of an actual bench trajectory: run the smoke on a
baseline commit and on a candidate, then::

    python scripts/bench_compare.py BASELINE.json CANDIDATE.json

exits **0** when every tracked metric is within its threshold, **1** with
one line per regression when not, **2** on unusable input. Self-compare
is exact-zero-regression by construction (every ratio is 1.0), which the
tests pin.

Tracked metrics (the smoke artifact's load-bearing numbers) and their
default thresholds:

=============================== =========== ==============================
metric                          direction   default threshold
=============================== =========== ==============================
overlapped e2e p50              lower       <= 1.10x baseline + 0.5 ms
overlapped ready_wait p50       lower       <= 1.25x baseline + 0.5 ms
overlapped dropped frames       lower       <= baseline (absolute)
overload 4x interactive p99     lower       <= 1.25x baseline + 5 ms
overload 4x completion ratio    higher      >= 0.98x baseline
tracing overhead p50 ratio      lower       <= baseline + 0.02 (absolute)
=============================== =========== ==============================

Latency thresholds are ratio + absolute-slack (tiny baselines must not
turn scheduler noise into a failed gate — the same reasoning as the
tracing-overhead gate's 0.5 ms slack). Override any threshold with
``--threshold NAME=VALUE`` (the ratio/absolute part only; slacks are
fixed). Missing metrics are asymmetric: absent from BOTH files or from
the BASELINE only (an older artifact predating the metric) is skipped
with a note — there is nothing to regress from; absent from the
CANDIDATE only is a structural regression (it stopped measuring
something the baseline had) and fails unless ``--allow-missing``.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple


def _overload_row(doc: dict, multiplier: float) -> dict:
    for row in (doc.get("overload_sweep") or {}).get("rows", ()):
        if row.get("offered_multiplier") == multiplier:
            return row
    return {}


def _completion_ratio(row: dict) -> Optional[float]:
    done = row.get("interactive_completed")
    offered = row.get("interactive_offered")
    if done is None or not offered:
        return None
    return done / offered


#: metric name -> (extractor, kind, default_threshold, abs_slack).
#: kind: "ratio_max"  — candidate <= baseline * thr + slack (lower=better)
#:       "ratio_min"  — candidate >= baseline * thr         (higher=better)
#:       "abs_max"    — candidate <= baseline + thr         (lower=better)
METRICS: Dict[str, Tuple[Callable[[dict], Any], str, float, float]] = {
    "overlapped_e2e_p50_ms": (
        lambda d: (d.get("modes") or {}).get("overlapped", {})
        .get("e2e_p50_ms"),
        "ratio_max", 1.10, 0.5),
    "overlapped_ready_wait_p50_ms": (
        lambda d: (d.get("modes") or {}).get("overlapped", {})
        .get("decomposition_ms", {}).get("ready_wait_p50_ms"),
        "ratio_max", 1.25, 0.5),
    "overlapped_dropped_frames": (
        lambda d: (d.get("modes") or {}).get("overlapped", {})
        .get("dropped_frames"),
        "abs_max", 0.0, 0.0),
    "overload_4x_interactive_p99_ms": (
        lambda d: _overload_row(d, 4.0).get("interactive_e2e_p99_ms"),
        "ratio_max", 1.25, 5.0),
    # Completion RATIO, not the raw completed count: the smoke's offer
    # loop is time-based, so interactive_offered drifts run-to-run
    # (231 vs 244 on back-to-back clean runs) and an absolute-count gate
    # fails healthy runs. Rows predating interactive_offered read None
    # and ride the baseline-predates-metric skip.
    "overload_4x_interactive_completion": (
        lambda d: _completion_ratio(_overload_row(d, 4.0)),
        "ratio_min", 0.98, 0.0),
    "tracing_p50_ratio": (
        lambda d: (d.get("tracing_overhead") or {}).get("p50_ratio"),
        "abs_max", 0.02, 0.0),
    # Replica scale-out: completed-frames ratio at 2 replicas vs 1 (the
    # router/fleet win). A candidate may not quietly lose the scaling the
    # baseline demonstrated; artifacts predating the section ride the
    # baseline-predates-metric skip.
    "replica_scaleout_x2": (
        lambda d: (d.get("replica_scaleout") or {})
        .get("scaling", {}).get("x2"),
        "ratio_min", 0.90, 0.0),
    # Embedder rollout (ISSUE 11): the dual-score parity agreement on the
    # smoke's identity queries (a candidate quietly degrading old-vs-new
    # agreement is a rollout-gate regression) and the completed-frames
    # ratio through the cutover + re-anchor window (the serving-never-
    # blanks number — the router cordon + epoch-fenced swap must keep it
    # near 1.0). Artifacts predating the rollout section ride the
    # baseline-predates-metric skip.
    "rollout_parity_agreement": (
        lambda d: (d.get("rollout") or {}).get("parity_agreement"),
        "ratio_min", 0.98, 0.0),
    "rollout_cutover_completed_ratio": (
        lambda d: (d.get("rollout") or {})
        .get("cutover_window_completed_ratio"),
        "ratio_min", 0.80, 0.0),
    # Versioned model registry (ISSUE 18): the live detection-agreement
    # parity on the detector-swap smoke (a candidate quietly degrading
    # box-verdict agreement is a registry-gate regression) and the
    # completed-frames ratio through the fence + re-anchor window (the
    # serving-never-blanks number for non-embedder swaps — no re-embed,
    # params are jit arguments, so it must track the rollout ratio or
    # better). Artifacts predating the registry section ride the
    # baseline-predates-metric skip.
    "registry_parity_agreement": (
        lambda d: (d.get("registry") or {}).get("parity_agreement"),
        "ratio_min", 0.98, 0.0),
    "registry_swap_completed_ratio": (
        lambda d: (d.get("registry") or {})
        .get("swap_window_completed_ratio"),
        "ratio_min", 0.80, 0.0),
    # Ingest pipeline (ISSUE 12): the staging-ring uint8 H2D tail at the
    # b32 rung (the first uint8 path's 118 ms p99 pathology must
    # never creep back — ratio + absolute slack, same reasoning as the
    # other microsecond-scale latency gates) and the end-to-end
    # completed-frames uplift of uint8 mode over the f32 baseline against
    # the transfer-bound fake backend. Artifacts predating the ingest
    # section ride the baseline-predates-metric skip.
    "ingest_h2d_p99_ms": (
        lambda d: (d.get("ingest") or {})
        .get("h2d", {}).get("32", {}).get("uint8_ring", {}).get("p99_ms"),
        "ratio_max", 1.25, 0.5),
    "ingest_completed_uplift": (
        lambda d: (d.get("ingest") or {})
        .get("uplift", {}).get("b32", {}).get("uplift"),
        "ratio_min", 0.90, 0.0),
    # Cascade early-exit detection (ISSUE 13): completed-frames uplift at
    # 0% face density, cascade on vs off, against the per-frame dispatch
    # wall — the headline early-exit win. A candidate may not quietly
    # lose it (a gate that stops rejecting, a compaction that stops
    # shrinking buckets). Artifacts predating the cascade section ride
    # the baseline-predates-metric skip.
    "cascade_uplift_density0": (
        lambda d: (d.get("cascade") or {})
        .get("uplift", {}).get("d0", {}).get("uplift"),
        "ratio_min", 0.90, 0.0),
    # Temporal identity cache (ISSUE 17): completed-frames uplift at
    # coherence 0.9, cache on vs off, against the per-frame dispatch
    # wall — the headline track-cache win. A candidate may not quietly
    # lose it (an association that stops matching, a re-verify cadence
    # gone pathological, a gate that stops compacting). Artifacts
    # predating the video section ride the baseline-predates-metric
    # skip.
    "video_cache_uplift": (
        lambda d: (d.get("video") or {})
        .get("cells", {}).get("c90", {}).get("uplift"),
        "ratio_min", 0.90, 0.0),
    # Partition tolerance (ISSUE 16): partition onset to link-down
    # detection in the chaos scenario. A candidate may not quietly slow
    # the failover the baseline demonstrated (a longer deadline, a lazier
    # health loop) — ratio + half-second absolute slack, since at a
    # ~0.25 s detection floor a scheduler hiccup is a large ratio.
    # Artifacts predating the partition section ride the
    # baseline-predates-metric skip.
    "partition_failover_s": (
        lambda d: (d.get("partition") or {}).get("failover_s"),
        "ratio_max", 1.50, 0.5),
}


def compare(baseline: dict, candidate: dict,
            overrides: Optional[Dict[str, float]] = None,
            allow_missing: bool = False) -> dict:
    """Structured comparison report: per-metric verdicts plus the overall
    ``ok``. Pure — the CLI around it owns I/O and exit codes."""
    overrides = overrides or {}
    rows: List[dict] = []
    regressions: List[str] = []
    for name, (extract, kind, default_thr, slack) in METRICS.items():
        thr = overrides.get(name, default_thr)
        base = extract(baseline)
        cand = extract(candidate)
        row = {"metric": name, "baseline": base, "candidate": cand,
               "kind": kind, "threshold": thr}
        if base is None and cand is None:
            row["verdict"] = "skipped"
            row["note"] = "absent from both artifacts"
            rows.append(row)
            continue
        if base is None:
            # Asymmetric by design: a baseline that predates a tracked
            # metric (comparing against an older commit's artifact) has
            # nothing to regress FROM — only the candidate dropping a
            # measurement is the structural failure.
            row["verdict"] = "skipped"
            row["note"] = "baseline predates this metric"
            rows.append(row)
            continue
        if cand is None:
            row["verdict"] = "ok" if allow_missing else "regression"
            row["note"] = "candidate stopped measuring this"
            if not allow_missing:
                regressions.append(
                    f"{name}: candidate stopped measuring this "
                    f"(baseline={base!r})")
            rows.append(row)
            continue
        base_f, cand_f = float(base), float(cand)
        if kind == "ratio_max":
            limit = base_f * thr + slack
            ok = cand_f <= limit
        elif kind == "ratio_min":
            limit = base_f * thr
            ok = cand_f >= limit
        else:  # abs_max
            limit = base_f + thr
            ok = cand_f <= limit
        row["limit"] = round(limit, 4)
        row["verdict"] = "ok" if ok else "regression"
        if not ok:
            word = "below" if kind == "ratio_min" else "above"
            regressions.append(
                f"{name}: candidate {cand_f:g} is {word} the limit "
                f"{limit:g} (baseline {base_f:g}, threshold {thr:g})")
        rows.append(row)
    return {"ok": not regressions, "metrics": rows,
            "regressions": regressions}


def _load(path: str) -> dict:
    with open(path) as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: artifact root is not an object")
    return doc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="diff two BENCH_SERVING_smoke.json artifacts; "
                    "rc 1 on regression, 2 on unusable input")
    parser.add_argument("baseline", help="the reference smoke artifact")
    parser.add_argument("candidate", help="the artifact under test")
    parser.add_argument("--threshold", action="append", default=[],
                        metavar="NAME=VALUE",
                        help="override one metric's threshold (ratio or "
                             "absolute per its kind); repeatable")
    parser.add_argument("--allow-missing", action="store_true",
                        help="a metric present in only one artifact is a "
                             "note, not a regression")
    parser.add_argument("--json", action="store_true",
                        help="print the full report as JSON instead of "
                             "the human summary")
    args = parser.parse_args(argv)

    overrides: Dict[str, float] = {}
    for item in args.threshold:
        key, sep, value = item.partition("=")
        if not sep or key not in METRICS:
            print(f"bench_compare: unknown threshold {item!r} "
                  f"(metrics: {', '.join(METRICS)})", file=sys.stderr)
            return 2
        try:
            overrides[key] = float(value)
        except ValueError:
            print(f"bench_compare: threshold {item!r} is not a number",
                  file=sys.stderr)
            return 2
    try:
        baseline = _load(args.baseline)
        candidate = _load(args.candidate)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"bench_compare: {exc}", file=sys.stderr)
        return 2
    report = compare(baseline, candidate, overrides=overrides,
                     allow_missing=args.allow_missing)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        for row in report["metrics"]:
            mark = {"ok": "ok  ", "skipped": "skip",
                    "regression": "FAIL"}[row["verdict"]]
            print(f"[{mark}] {row['metric']}: baseline={row['baseline']} "
                  f"candidate={row['candidate']}"
                  + (f" limit={row['limit']}" if "limit" in row else "")
                  + (f" ({row['note']})" if "note" in row else ""))
        for line in report["regressions"]:
            print(f"REGRESSION: {line}", file=sys.stderr)
        print("bench_compare: "
              + ("no regressions" if report["ok"]
                 else f"{len(report['regressions'])} regression(s)"))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

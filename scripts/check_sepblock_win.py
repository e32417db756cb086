"""Gate for a conditional fused-schedule re-run: exit 0 iff
BENCH_DETAIL.json's sepblock_fused A/B (scripts/bench_sepblock.py)
recorded a >= 5% speedup at any measured batch. Kept as a script so the
decision logic is unit-testable — tests/test_queue_gate.py."""

from __future__ import annotations

import json
import os
import sys

WIN_THRESHOLD = 1.05


def sepblock_won(detail_path: str) -> bool:
    try:
        doc = json.load(open(detail_path))
    except (OSError, json.JSONDecodeError):
        return False
    batches = doc.get("sepblock_fused", {}).get("batches", {})
    speedups = [row.get("speedup") or 0 for row in batches.values()]
    return bool(speedups) and max(speedups) >= WIN_THRESHOLD


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_DETAIL.json")
    return 0 if sepblock_won(path) else 1


if __name__ == "__main__":
    sys.exit(main())

"""Structured per-batch metrics (SURVEY.md §5.5): counters + latency
percentiles + a JSONL sink. The north-star metric (faces/sec/chip) falls out
of the per-batch records.

Latency windows are **rolling log-bucket histograms**
(``utils.histogram.RollingHistogram``) as of the signals layer: an
``observe`` is one O(1) bucket increment, a percentile read is a
~100-bucket walk (exact to one bucket width — see the histogram module's
contract), the horizon is true wall-clock time (``window_s`` seconds,
sliced), and memory per window is flat forever — the old sample deques
were bounded only between ``reset_window()`` calls and reported "the last
N samples" over whatever time span that happened to be. The observe /
``percentile`` / ``summary`` surface is unchanged, including the explicit
``None`` percentiles for known-but-empty windows; ``summary`` additionally
reports ``_p99_ms`` now that p99 is cheap (the SLO layer's headline
quantile). The SLO monitor reads the same windows through
``fraction_above``/``window_count``, and ``/prom`` renders them through
``export_state``."""

from __future__ import annotations

import json
import threading
import time
from collections import defaultdict
from typing import Any, Dict, IO, Optional, Tuple

from opencv_facerecognizer_tpu.utils.histogram import RollingHistogram


class Metrics:
    """Thread-safe counters + gauges + rolling-histogram latency windows +
    optional JSONL sink.

    ``window_s``/``window_slices`` size every latency window's rolling
    ring: the default 600 s over 20 slices covers the SLO layer's stock
    long window at 30 s horizon granularity (a requested horizon is
    rounded UP to whole slices — see ``RollingHistogram.merged``). Tests
    and soaks that need fast expiry pass finer slicing."""

    def __init__(self, sink: Optional[IO[str]] = None,
                 window_s: float = 600.0, window_slices: int = 20):
        self._lock = threading.Lock()
        # The sink gets its OWN lock: a slow JSONL sink (disk stall, full
        # pipe) must serialize log lines against each other, but it must
        # never stall every counter incr on the serving hot path behind a
        # write(2) — found by ocvf-lint blocking-under-lock.
        self._sink_lock = threading.Lock()
        self._counters: Dict[str, float] = defaultdict(float)
        self._gauges: Dict[str, float] = {}
        self._window_s = float(window_s)
        self._window_slices = int(window_slices)
        self._latencies: Dict[str, RollingHistogram] = defaultdict(
            lambda: RollingHistogram(self._window_s, self._window_slices))
        self._sink = sink

    @property
    def window_s(self) -> float:
        """Rolling-horizon of every latency window (seconds). Reads over a
        longer horizon silently see at most this much data — consumers
        with configurable horizons (the SLO monitor) validate against it
        at construction."""
        return self._window_s

    @property
    def window_slice_s(self) -> float:
        """Ring resolution (seconds per slice): a horizon below this reads
        a full slice's worth of data anyway. The SLO monitor refuses
        sub-slice windows against it at construction."""
        return self._window_s / self._window_slices

    def incr(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += value

    def incr_many(self, *pairs) -> None:
        """``incr`` of several counters, each a ``(name, value)`` pair,
        under ONE acquisition of the lock: for a hot path that counts a
        section by two clocks at once (``intake_s`` and ``intake_cpu_s``)
        and must not pay the lock once per clock."""
        with self._lock:
            counters = self._counters
            for name, value in pairs:
                counters[name] += value

    def observe(self, name: str, seconds: float) -> None:
        with self._lock:
            self._latencies[name].observe(seconds)  # ocvf-lint: disable=metrics-registry -- RollingHistogram.observe takes the sample VALUE; the metric name was validated at this method's own call site

    def set_gauge(self, name: str, value: float) -> None:
        """Last-write-wins instantaneous value (e.g. the batcher's current
        adaptive flush deadline) — reported as-is in ``summary``."""
        with self._lock:
            self._gauges[name] = float(value)

    def gauge(self, name: str, default: float = float("nan")) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def counters(self) -> Dict[str, float]:
        """One atomic snapshot of every counter — the chaos tests compare
        this against a FaultInjector's injected-fault ledger, so the read
        must not interleave with concurrent incrs."""
        with self._lock:
            return dict(self._counters)

    def counters_with_prefix(self, prefix: str) -> Dict[str, float]:
        """Atomic snapshot of the counters under one namespace — e.g.
        ``frames_rejected_``, the admission layer's per-reason rejects,
        which the overload soak/bench report grouped this way."""
        with self._lock:
            return {k: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def sum_counters(self, positive, negative=()) -> float:
        """Atomic ``sum(positive) - sum(negative)`` over counter names —
        one lock acquisition, no dict copy. The admission bound reads its
        in-system count through this on every offered frame, so it must
        stay allocation-free under flood load."""
        with self._lock:
            c = self._counters
            return (sum(c.get(n, 0.0) for n in positive)
                    - sum(c.get(n, 0.0) for n in negative))

    def percentile(self, name: str, q: float,
                   horizon_s: Optional[float] = None) -> float:
        """The window's ``q``-percentile in seconds over the trailing
        ``horizon_s`` (default: the full rolling window); NaN when the
        window is unknown or empty. Exact to one histogram bucket."""
        with self._lock:
            window = self._latencies.get(name)
            if window is None:
                return float("nan")
            return window.quantile(q, horizon_s=horizon_s)

    def fraction_above(self, name: str, threshold_s: float,
                       horizon_s: Optional[float] = None) -> float:
        """Fraction of the window's observations above ``threshold_s``
        over the trailing horizon — the SLO burn-rate monitor's error-rate
        read for latency objectives. 0.0 for unknown/empty windows (no
        data never reads as a breach; ``window_count`` tells them apart)."""
        with self._lock:
            window = self._latencies.get(name)
            if window is None:
                return 0.0
            return window.fraction_above(threshold_s, horizon_s=horizon_s)

    def window_count(self, name: str,
                     horizon_s: Optional[float] = None) -> int:
        """Observations currently inside the trailing horizon."""
        with self._lock:
            window = self._latencies.get(name)
            return 0 if window is None else window.count(horizon_s=horizon_s)

    def reset_window(self, name: Optional[str] = None) -> None:
        """Clear one latency window (or all of them) without touching
        counters/gauges — bench reuse between a warm phase and a measured
        phase. A cleared window reports explicit ``None`` percentiles in
        ``summary`` until it sees new observations (never stale or zero
        values masquerading as measurements)."""
        with self._lock:
            if name is not None:
                window = self._latencies.get(name)
                if window is not None:
                    window.clear()
            else:
                for window in self._latencies.values():
                    window.clear()

    def log(self, event: str, **fields) -> None:
        if self._sink is None:
            return
        record = {"ts": time.time(), "event": event, **fields}
        line = json.dumps(record)
        # I/O deliberately held under the sink lock: serializing writers is
        # this lock's entire purpose and nothing on the counter path ever
        # takes it.
        with self._sink_lock:  # ocvf-lint: boundary-block=blocking-under-lock -- sink lock exists solely to serialize sink writes; counter paths never take it
            self._sink.write(line + "\n")
            self._sink.flush()

    def summary(self) -> Dict[str, Optional[float]]:
        """Counters + gauges + per-window percentiles (p50/p95/p99, ms,
        bucket precision). A window that is known but currently EMPTY
        (after ``reset_window`` or full rolling expiry) reports explicit
        ``None`` values — never a misleading zero, never a raise — so a
        consumer can tell "no data yet" from "measured 0 ms"."""
        with self._lock:
            out: Dict[str, Optional[float]] = dict(self._counters)
            out.update(self._gauges)
            for name, window in self._latencies.items():
                merged = window.merged()
                if merged.count:
                    out[f"{name}_p50_ms"] = merged.quantile(50) * 1e3
                    out[f"{name}_p95_ms"] = merged.quantile(95) * 1e3
                    out[f"{name}_p99_ms"] = merged.quantile(99) * 1e3
                else:
                    out[f"{name}_p50_ms"] = None
                    out[f"{name}_p95_ms"] = None
                    out[f"{name}_p99_ms"] = None
        return out

    def export_state(self) -> Tuple[Dict[str, float], Dict[str, float],
                                    Dict[str, Dict[str, Any]]]:
        """One atomic ``(counters, gauges, histograms)`` snapshot for the
        Prometheus exposition (``runtime.promtext``): histograms are the
        full-window merge in ``LogBucketHistogram.snapshot`` shape
        (bounds / per-bucket counts / count / sum). Empty-but-known
        windows export with ``count == 0`` — a scraper sees the family
        exists even before traffic."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {name: window.merged().snapshot()
                     for name, window in self._latencies.items()}
        return counters, gauges, hists

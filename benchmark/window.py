"""One measured window over a built stack, and the judgment after it.

``run_window`` starts the sender, lets the same traffic run for the mix's
warm period, then reads the counters, waits ``seconds`` in one sleep and
reads them again: the clock stops without draining, and a frame in flight
at either edge counts where it settles. Only then is the sender stopped
and the service drained, so that every frame's fate is known.

``judge`` frees the program's state and runs the plain reference over a
sample of what the window finished.
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import check, trace_reduce, traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))

COMPLETED = ("frames_completed", "frames_completed_empty",
             "frames_completed_cached")
KEEP_ONE_IN = 16  # results kept for the sample; the cache's replies all are


def keep(seq: int, salt: int) -> bool:
    """One frame number in ``KEEP_ONE_IN``, by a hash whose low bits do not
    follow the number's own: streams are interleaved round-robin, so a rule
    on ``seq`` modulo a power of two would keep one stream's frames only."""
    h = (seq * 2654435761) & 0xFFFFFFFF
    h ^= h >> 15
    h = (h * 2246822519) & 0xFFFFFFFF
    h ^= h >> 13
    return h % KEEP_ONE_IN == salt


class Collector:
    """Subscriber of the result topic: one flag per frame, one in
    ``KEEP_ONE_IN`` results kept whole, with the time each arrived, and
    for every named stream the identities its full results carried."""

    def __init__(self, seed: int, capacity: int = 1 << 22):
        self.flags = bytearray(capacity)
        self.kept: Dict[int, Any] = {}
        self.kept_at: Dict[int, float] = {}
        self.first_full: Dict[Any, Dict[int, int]] = {}  # stream -> label -> seq
        self.settled = 0
        self._salt = int(seed) % KEEP_ONE_IN

    def on_result(self, message: Dict[str, Any]) -> None:
        seq = message["meta"]["seq"]
        code = check.exit_code(message)
        self.flags[seq] = code
        self.settled += 1
        stream = message["meta"].get("stream")
        if code == check.EXIT_FULL and stream is not None and message["faces"]:
            had = self.first_full.setdefault(stream, {})
            for face in message["faces"]:
                label = int(face["label"])
                if seq < had.get(label, seq + 1):
                    had[label] = seq
        if code == check.EXIT_CACHED or keep(seq, self._salt):
            self.kept[seq] = message
            self.kept_at[seq] = time.perf_counter()


class Sampler(threading.Thread):
    """Looks every 100 ms at the count of settled frames and at how late
    its own sleep ended: the timeline of the run and the stalls of the
    whole process. Reads two numbers, nothing else."""

    def __init__(self, collector: Collector):
        super().__init__(name="bench-sampler", daemon=True)
        self.collector = collector
        self.points: List[tuple] = []  # (perf_counter, settled)
        self.stalls: List[Dict[str, float]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        last, last_cpu = time.perf_counter(), time.process_time()
        while not self._halt.wait(0.1):
            now, cpu = time.perf_counter(), time.process_time()
            if now - last > 0.15:
                self.stalls.append({"at": now, "late_ms": (now - last - 0.1) * 1e3,
                                    "process_cpu_ms": (cpu - last_cpu) * 1e3})
            self.points.append((now, self.collector.settled))
            last, last_cpu = now, cpu

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5.0)


class CompileCounter:
    """Counts the compilations JAX reports while ``armed``."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if self.armed and "backend_compile" in event:
            self.count += 1


def _delta(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
    return {k: after.get(k, 0.0) - before.get(k, 0.0)
            for k in set(after) | set(before)
            if after.get(k, 0.0) != before.get(k, 0.0)}


def run_window(stack, traffic: traffic_gen.Traffic, seconds: float, seed: int,
               say: Callable[[str], None], compiles: CompileCounter,
               trace_dir: Optional[str] = None, start_index: int = 0,
               process_start: Optional[float] = None) -> Dict[str, Any]:
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    collector = Collector(seed)
    stack.on_result = collector.on_result
    target = stack.queue_limit() - traffic.params["queue_margin"]
    sender = traffic_gen.BacklogSender(traffic, stack.inject, stack.queue_depth,
                                       target, start_index=start_index)
    stack.on_pop = sender.note_pop
    sampler = Sampler(collector)
    sender.start()
    sampler.start()
    out: Dict[str, Any] = {"queue_target": target}
    try:
        time.sleep(traffic.params["warm_seconds"])
        if collector.settled == 0:
            raise RuntimeError("nothing settled in the warm period")
        sync_mono_ns = None
        if trace_dir is not None:
            import jax

            t_prof = time.perf_counter()
            # Device operations and the host's own annotations, not every
            # Python call: the Python tracer would slow the host it measures.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
                sync_mono_ns = time.monotonic_ns()
                time.sleep(0.002)
            out["profiler_start_s"] = time.perf_counter() - t_prof
        compiles.count, compiles.armed = 0, True
        c0 = stack.counters()
        first_index = sender.next_index
        t0, t0_mono = time.perf_counter(), time.monotonic()
        if process_start is not None:
            out["setup_s"] = t0 - process_start
        time.sleep(seconds)
        t1, t1_mono = time.perf_counter(), time.monotonic()
        c1 = stack.counters()
        compiles.armed = False
        if trace_dir is not None:
            import jax

            t_prof = time.perf_counter()
            jax.profiler.stop_trace()
            out["profiler_stop_s"] = time.perf_counter() - t_prof
    finally:
        compiles.armed = False
        sender.stop()
        sampler.stop()
        stack.on_pop = None
    drained = stack.service.drain(timeout=120.0)
    ledger = stack.ledger()
    stack.on_result = None

    counters = _delta(c1, c0)
    counters["bench_backend_compiles"] = float(compiles.count)
    completed = sum(counters.get(k, 0.0) for k in COMPLETED)
    drops = {k: counters[k] for k in mn.LEDGER_DROP_COUNTERS if counters.get(k)}
    unsettled = 0.0 if drained else float(ledger["in_system"])
    window_s = t1 - t0
    by_5s, edge, base = [], t0, None
    for at, settled in sampler.points:
        if at < t0 or at > t1:
            continue
        if base is None:
            base = (at, settled)
        if at - edge >= 5.0:
            by_5s.append((settled - base[1]) / (at - base[0]))
            edge, base = at, (at, settled)
    out.update({
        "t0": t0, "t1": t1, "t0_mono": t0_mono, "t1_mono": t1_mono,
        "window_s": window_s, "sync_mono_ns": sync_mono_ns,
        "counters": counters, "completed": completed, "drops": drops,
        "served_fps": served_rate(sum(c0.get(k, 0.0) for k in COMPLETED),
                                  sum(c1.get(k, 0.0) for k in COMPLETED),
                                  window_s),
        "attempted": int(completed + sum(drops.values()) + unsettled),
        "failed": int(sum(drops.values()) + unsettled),
        "unsettled": unsettled, "drained": drained, "ledger": ledger,
        "first_index": first_index, "next_index": sender.next_index,
        "collector": collector,
        "served_fps_by_5s": by_5s,
        "stalls": [{**s, "at": s["at"] - t0} for s in sampler.stalls
                   if t0 <= s["at"] <= t1],
        "late_refills": [{"at": at - t0_mono, "late_ms": late * 1e3}
                         for at, late in sender.late_refills
                         if t0_mono <= at <= t1_mono],
        "queue_max_seen": sender.max_depth_seen,
        "batches": {k: counters.get(k, 0.0) for k in (
            mn.BATCHER_BATCHES_SIZE, mn.BATCHER_BATCHES_DEADLINE,
            mn.BATCHER_FRAMES_BATCHED, mn.BATCHER_DROPPED_OVERFLOW)},
    })
    say(f"window: {window_s:.3f} s, {completed:.0f} frames settled "
        f"({out['served_fps']:.1f} frames/s), drops {drops}, batches "
        f"{out['batches']}, drained {drained}")
    return out


def load_reference(config: Dict[str, Any]):
    path = os.path.join(HERE, "configs", config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(config["reference"], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_limits(config: Dict[str, Any]) -> Dict[str, Any]:
    """The limit of each number compared, and under ``"far"`` the reading
    of one face from which it counts into a ``_far`` share."""
    with open(os.path.join(HERE, "configs", config["name"] + ".limits.json")) as fh:
        return json.load(fh)


def numbers_compared(config: Dict[str, Any], traffic: traffic_gen.Traffic,
                     seed: int, win: Dict[str, Any], nets_dir: str,
                     gallery_rows, enrol_images, enrol_labels,
                     label_offset: int, control: Optional[str] = None,
                     plant: Optional[Callable[..., Dict[int, Any]]] = None
                     ) -> Dict[str, Any]:
    """Draws the sample of what the window finished and holds it against
    the reference. With ``control`` (a ``lower`` of the reference) it is
    not the program's results that are held against it but what the
    reference itself gives one precision step lower, on the same frames;
    with ``plant`` the program's results with a fault planted in them
    (``benchmark/tests/plants.py``): the readings that have to fail."""
    t_ref = time.perf_counter()
    collector: Collector = win["collector"]
    results = {s: m for s, m in collector.kept.items()
               if win["t0"] <= collector.kept_at[s] <= win["t1"]}
    sizes = config["check"]
    wanted = {check.EXIT_FULL: int(sizes["sample_full"]),
              check.EXIT_EMPTY: int(sizes["sample_gate"]),
              check.EXIT_CACHED: int(sizes["sample_cached"])}
    sample = check.draw_sample(seed, results, wanted)
    frames = {s: traffic.frame_of(s) for group in sample.values() for s in group}
    module = load_reference(config)
    face_size = tuple(config["face_size"])
    block_rows = int(config["gallery"].get("reference_block_rows",
                                           config["gallery"]["block_rows"]))
    reference = module.Reference(nets_dir, face_size)
    limits = load_limits(config)
    if plant is not None:
        results = plant(results, sample, seed, first_full=collector.first_full,
                        label_offset=label_offset, rows=int(gallery_rows.shape[0]),
                        enrolled=len(enrol_labels),
                        frame_size=tuple(config["frame_size"]))
    if control:
        lower = module.Reference(nets_dir, face_size, lower=control)
        results = check.publish_like(
            lower, gallery_rows, block_rows, enrol_images, enrol_labels,
            label_offset, float(config.get("similarity_threshold", 0.3)), frames)
        sample = check.draw_sample(seed, results, wanted)
    numbers, seen = check.compare(
        reference, gallery_rows, block_rows, enrol_images, enrol_labels,
        label_offset, float(reference.nets["detector_cfg"]["score_threshold"]),
        frames, results, sample, limits["far"], collector.first_full)
    out = {"sampled": {str(k): len(v) for k, v in sample.items()}, **seen}
    if not control:
        longest = check.longest_cached_run(
            np.frombuffer(collector.flags, np.uint8), traffic.params["streams"],
            win["first_index"], win["next_index"])
        numbers["unsettled"] = float(win["failed"])
        out["cached_run_longest"] = longest
    out["numbers"] = numbers
    out["reference_s"] = time.perf_counter() - t_ref
    return out


def served_rate(completed_before: float, completed_after: float,
                window_s: float) -> float:
    """Frames settled inside the window over the window's seconds: all
    the work over all the time."""
    return (completed_after - completed_before) / window_s

"""Traffic for the benchmark: one generator, driven by a parameter file.

A traffic mix is ``benchmark/traffic/<name>.json``; nothing here knows a
mix by name. The file fixes the *amount of work* (streams, how many frames
carry faces and how many faces each, face size, pool sizes, queue target);
``--seed`` changes only content: which stream gets which phase, which scene
gets which face count, identities, positions, pixels.

Three parts:

- ``benchmark/render.py`` renders the scenes and faces.
- ``FrameSchedule``: frame index -> (stream, scene). Streams are
  interleaved round-robin; each stream alternates a dwell of
  ``dwell_frames`` frames on one face scene with empty frames, period
  ``period_frames``, phases staggered evenly over the streams. So every
  run of ``streams * k`` consecutive frames holds the same number of face
  frames (to within one scene edge), whatever the seed.
- ``BacklogSender``: keeps the service's batcher queue topped up to
  ``max_pending - queue_margin`` and never over ``max_pending``; it sleeps
  on an event that a popped batch sets, and does nothing but inject.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.render import encode_frame, render_scene

FAMILY_LOOPS = ("backlog",)


def load_traffic(params: Dict[str, Any]) -> Dict[str, Any]:
    """Validate a traffic file's parameters; returns them with defaults."""
    p = dict(params)
    if p.get("loop") not in FAMILY_LOOPS:
        raise ValueError(f"traffic loop {p.get('loop')!r}: this generator "
                         f"drives {FAMILY_LOOPS}")
    for key in ("streams", "period_frames", "dwell_frames", "scene_pool",
                "empty_pool", "identities", "enrolled", "queue_margin"):
        p[key] = int(p[key])
    if not 1 <= p["dwell_frames"] <= p["period_frames"]:
        raise ValueError("dwell_frames must lie in 1..period_frames")
    counts = {int(k): int(v) for k, v in p["faces_per_frame"].items()}
    if p["scene_pool"] % sum(counts.values()):
        raise ValueError("scene_pool must be a multiple of the summed "
                         "faces_per_frame weights, so the multiset is exact")
    p["faces_per_frame"] = counts
    p["face_px"] = (int(p["face_px"][0]), int(p["face_px"][1]))
    p["warm_seconds"] = float(p.get("warm_seconds", 3.0))
    p["stream_meta"] = bool(p.get("stream_meta", True))
    # streams whose dwell shows one scene throughout; the others show a new
    # scene in every frame of the dwell (same number of faces either way)
    p["coherent_streams"] = int(p.get("coherent_streams", p["streams"]))
    return p


# ---- schedule ----


class FrameSchedule:
    """Frame index -> (stream, scene key). Pure arithmetic, no state."""

    def __init__(self, params: Dict[str, Any], seed: int):
        self.p = params
        rng = np.random.default_rng([int(seed), 11])
        s = params["streams"]
        # The phases are always the same evenly staggered set; the seed
        # only says which stream has which.
        self.phase = (rng.permutation(s) * params["period_frames"]) // s
        self.rank = rng.permutation(s)  # which streams are the coherent ones

    def lookup(self, index: int) -> Tuple[int, Tuple[str, int]]:
        p = self.p
        stream = index % p["streams"]
        pos = index // p["streams"] + int(self.phase[stream])
        cycle, within = divmod(pos, p["period_frames"])
        if within < p["dwell_frames"]:
            if self.rank[stream] < p["coherent_streams"]:
                # one scene for the whole dwell, the next scene next time
                scene = cycle + 37 * stream
            else:
                scene = (cycle * p["streams"] + stream) * p["dwell_frames"] + within
            return stream, ("face", scene % p["scene_pool"])
        return stream, ("empty", (pos + 7 * stream) % p["empty_pool"])


class Traffic:
    """The rendered, encoded frames of one run and the schedule over them."""

    def __init__(self, params: Dict[str, Any], seed: int,
                 frame_size: Tuple[int, int]):
        self.params = p = load_traffic(params)
        self.seed = int(seed)
        self.schedule = FrameSchedule(p, seed)
        rng = np.random.default_rng([int(seed), 12])
        # Every block of sum(weights) consecutive scenes holds the mix's
        # multiset of face counts exactly; the seed orders each block.
        block = [n for n, weight in sorted(p["faces_per_frame"].items())
                 for _ in range(weight)]
        counts = [block[i] for _ in range(p["scene_pool"] // len(block))
                  for i in rng.permutation(len(block))]
        self.identity_ids = [int(v) for v in
                             rng.permutation(1 << 20)[:p["identities"]]]
        self.frames: Dict[Tuple[str, int], np.ndarray] = {}
        self.boxes: Dict[Tuple[str, int], np.ndarray] = {}
        self.scene_identities: Dict[Tuple[str, int], List[int]] = {}
        for k, n in enumerate(counts):
            who = [self.identity_ids[int(j)] for j in
                   rng.choice(len(self.identity_ids), size=n,
                              replace=n > len(self.identity_ids))]
            key = ("face", k)
            self.frames[key], self.boxes[key] = render_scene(
                frame_size, who, p["face_px"], rng)
            self.scene_identities[key] = who
        for k in range(p["empty_pool"]):
            key = ("empty", k)
            self.frames[key], self.boxes[key] = render_scene(
                frame_size, [], p["face_px"], rng)
            self.scene_identities[key] = []
        self.encoded = {key: encode_frame(f) for key, f in self.frames.items()}
        self.face_counts = counts

    def enrolled_identities(self) -> List[int]:
        return self.identity_ids[:self.params["enrolled"]]

    def message(self, index: int) -> Dict[str, Any]:
        stream, key = self.schedule.lookup(index)
        meta = {"seq": index}
        if self.params["stream_meta"]:
            # names the camera: what the service's track cache keys on
            meta["stream"] = f"cam{stream:02d}"
        return {**self.encoded[key], "meta": meta}

    def frame_of(self, index: int) -> np.ndarray:
        return self.frames[self.schedule.lookup(index)[1]]

    def census(self, start: int, stop: int) -> Dict[str, Any]:
        """What the frames ``start..stop`` hold: the amount of work."""
        with_faces = 0
        per_count: Dict[int, int] = {}
        for i in range(start, stop):
            _s, key = self.schedule.lookup(i)
            n = len(self.scene_identities[key])
            if n:
                with_faces += 1
                per_count[n] = per_count.get(n, 0) + 1
        return {"frames": stop - start, "with_faces": with_faces,
                "faces_per_frame": dict(sorted(per_count.items()))}


# ---- the sender ----


class BacklogSender(threading.Thread):
    """Keeps ``depth()`` at ``target`` by injecting the next frames.

    ``depth`` reads the queue length, ``inject`` sends one message, and
    ``note_pop`` is called by whoever takes frames out of the queue. Only this
    thread adds frames, so between its look at the depth and its last
    inject the queue can only have shrunk: it never goes over ``target``.
    """

    def __init__(self, traffic: Traffic, inject, depth, target: int,
                 start_index: int = 0):
        super().__init__(name="bench-backlog-sender", daemon=True)
        self.traffic = traffic
        self._inject = inject
        self._depth = depth
        self._wake = threading.Event()
        self._pop_t = None
        self.target = int(target)
        self.next_index = int(start_index)
        self._stop_flag = False
        self.max_depth_seen = 0
        #: (monotonic time, seconds) of every refill that began more than
        #: 20 ms after the pop that made room for it.
        self.late_refills: List[Tuple[float, float]] = []

    def note_pop(self) -> None:
        """Called by whoever took frames out of the queue."""
        self._pop_t = time.monotonic()
        self._wake.set()

    def run(self) -> None:
        while not self._stop_flag:
            self._wake.clear()
            room = self.target - self._depth()
            if room <= 0:
                self._wake.wait(timeout=0.5)
                continue
            pop_t, self._pop_t = self._pop_t, None
            now = time.monotonic()
            if pop_t is not None and now - pop_t > 0.02:
                self.late_refills.append((now, now - pop_t))
            for _ in range(room):
                self._inject(self.traffic.message(self.next_index))
                self.next_index += 1
            self.max_depth_seen = max(self.max_depth_seen, self._depth())

    def stop(self) -> None:
        self._stop_flag = True
        self._wake.set()
        self.join(timeout=30.0)
        if self.is_alive():
            raise RuntimeError("the sender did not stop")

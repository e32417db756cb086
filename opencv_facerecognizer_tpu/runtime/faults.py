"""Fault injection at the serving boundaries (chaos layer).

The serving stack has four places where the outside world can hurt it, each
with its own failure shape:

- **connector receive** — a camera/transport glitch delivers a corrupt
  payload, drops a message, delivers it twice, or **floods** (one delivery
  amplified ``flood_factor``-fold — the runaway-producer shape the
  admission-control layer exists for);
- **batcher put** — a malformed frame (wrong shape, NaN garbage) reaches the
  batch queue and must not poison the whole batch;
- **device dispatch** — the backend fast-fails (``UNAVAILABLE`` at call
  time);
- **async readback** — a dispatched batch's device->host transfer never
  completes (``stuck``: ``is_ready`` stays False forever — a hung device
  call) or completes late (``slow``: ready only after
  ``slow_readback_s`` — the congested-but-alive shape the overlapped
  readback worker must pipeline behind, not stall on).

``FaultInjector`` installs at all four. Faults are either **scripted**
(``script("dispatch", "unavailable", "unavailable")`` — consumed in order,
exactly once each: the deterministic form chaos tests assert exact counts
against) or **randomized** (``rates={"receive": {"corrupt": 0.01}}`` —
drawn from a seeded ``random.Random`` so a soak run is reproducible from
its logged seed). ``injected`` counts every fault actually fired, keyed
``"boundary:fault"``, so a test can demand metrics match injections exactly.

The injector is a pure test/chaos tool: with no scripted faults and zero
rates every hook is a cheap no-op passthrough, and production code paths
never require one to be installed.
"""

from __future__ import annotations

import errno
import random
import time
from collections import Counter, deque
from typing import Any, Dict, List, Optional

import numpy as np

#: boundary name -> fault kinds it understands.
BOUNDARIES: Dict[str, tuple] = {
    "receive": ("drop", "duplicate", "corrupt", "flood"),
    "put": ("corrupt",),
    "dispatch": ("unavailable",),
    "readback": ("stuck", "slow"),
    # Stage-1 cascade gate (runtime.recognizer._cascade_gate): a
    # pathological first stage that scores EVERY frame face-free — the
    # worst-case operating point (a corrupted gate checkpoint, a camera
    # whose exposure collapsed). The service must degrade to publishing
    # empty results with exact ``completed_empty`` ledger settlement —
    # zero matches, zero wedges, zero leaked frames.
    "cascade": ("reject_all",),
    # Compressed-frame intake (runtime.ingest.DecodeWorkerPool): "slow" =
    # a congested decoder (the worker sleeps slow_decode_s before
    # decoding — the pool must absorb it off the hot thread); "corrupt" =
    # the payload is replaced with bytes no JPEG decoder accepts, so the
    # frame must dead-letter with reason decode_error and exact ledger
    # settlement.
    "decode": ("slow", "corrupt"),
    # Durability boundaries (state lifecycle layer — runtime.state_store):
    # "torn" = the process dies mid-write leaving a partial record/file on
    # disk; "crash" = it dies before the write becomes visible (before the
    # WAL bytes land / before the checkpoint tmp renames); checkpoint
    # "late" = the checkpoint file lands but the process dies before the
    # WAL truncation that follows — the window where replay must dedup
    # against the checkpoint's recorded WAL sequence.
    "wal": ("torn", "crash"),
    "checkpoint": ("torn", "crash", "late"),
    # Embedder-rollout boundaries (runtime.rollout): "stage" faults hit
    # the background re-embed's progress append ("torn" = a partial chunk
    # line lands then the process dies — resume must re-stage that chunk;
    # "crash" = death before any byte); "cutover" faults hit the atomic
    # swap ("crash_before_record" = the stage delta is durable but the
    # fence record never landed — recovery stays on the old version;
    # "crash_after_record" = the fence is durable but the in-memory swap
    # and its checkpoint never ran — recovery must COMPLETE the cutover
    # from the staged shard set).
    "stage": ("torn", "crash"),
    "cutover": ("crash_before_record", "crash_after_record"),
    # Storage-fault boundary (ISSUE 15) — the disk STAYS broken, unlike
    # the wal/checkpoint kill-point faults above which simulate process
    # death. One boundary covers every durable path (WAL append/fsync,
    # checkpoint tmp+rename+directory fsync, dead-letter/span journals,
    # rollout stage appends, replica tailer reads, flight dumps):
    # "enospc" = the write raises OSError(ENOSPC) — a full disk;
    # "eio" = the write raises OSError(EIO) — dying media;
    # "slow_fsync" = the operation completes but only after
    # ``slow_fsync_s`` (a congested/remounting device — callers must
    # bound what serves behind it, not wedge);
    # "read_error" = a READ crossing raises OSError(EIO) (tailer polls,
    # checkpoint loads). Write crossings draw only the three write
    # kinds and read crossings only "read_error", so one scripted queue
    # can interleave both without a read consuming a write fault.
    "storage": ("enospc", "eio", "slow_fsync", "read_error"),
    # Transport boundary (ISSUE 16) — the network the PR 10/11 fleet lives
    # on.  Two families share the boundary:
    # STATEFUL link conditions, toggled per-(peer, direction) via
    # ``set_partition`` / ``set_half_open`` / ``set_slow_link`` and
    # cleared by the ``heal_*`` siblings — they apply to EVERY crossing
    # of that link while set:
    #   "partition" = the link is cut; messages vanish (the caller sees
    #     the same nothing a real partition delivers);
    #   "half_open" = the peer's TCP stack still ACKs but the application
    #     never sees the bytes — indistinguishable from partition at the
    #     message level, detectable only by heartbeat deadline;
    #   "slow"      = every crossing sleeps latency + uniform jitter (a
    #     congested or long-haul link — blocking senders feel it).
    # PER-CROSSING faults, scripted/rate-drawn like every other boundary:
    #   "drop" = this one message vanishes; "duplicate" = delivered
    #   twice; "reorder" = held back and delivered AFTER the next
    #   message that crosses the same link (out-of-order delivery the
    #   idempotent-routing layer must absorb).
    "transport": ("partition", "half_open", "slow",
                  "drop", "duplicate", "reorder"),
}

#: storage-boundary fault kinds applicable per crossing direction (the
#: filtered draw ``on_storage``/``on_storage_read`` use).
STORAGE_WRITE_KINDS = ("enospc", "eio", "slow_fsync")
STORAGE_READ_KINDS = ("read_error",)

#: transport-boundary kinds eligible for the per-crossing scripted/rate
#: draw (the stateful link conditions are toggled, never drawn — a
#: scripted "partition" would be a one-message blackhole masquerading as
#: a link cut, so ``script`` refuses the stateful kinds for transport).
TRANSPORT_DRAW_KINDS = ("drop", "duplicate", "reorder")

#: valid directions of a transport crossing, from the injecting side's
#: point of view: "send" = toward the peer, "recv" = from the peer.
TRANSPORT_DIRECTIONS = ("send", "recv")


class InjectedCrashError(RuntimeError):
    """Simulated process death at a durability boundary (``wal`` /
    ``checkpoint`` faults). The recovery chaos scenario raises this where
    a real kill -9 would land, then "restarts" by rebuilding the state
    lifecycle from disk — the caller must treat it as fatal, never catch
    and continue (a real SIGKILL offers no such choice)."""

    def __init__(self, msg: str = "injected crash at a durability boundary"):
        super().__init__(msg)


class InjectedUnavailableError(RuntimeError):
    """Simulates the backend's fast-fail outage mode. The message carries
    the literal ``UNAVAILABLE`` token so ``resilience.is_transient_error``
    classifies it exactly like the real PJRT error string."""

    def __init__(self, msg: str = "UNAVAILABLE: injected dispatch fault"):
        super().__init__(msg)


class StuckReadback:
    """Wraps a dispatched device array whose transfer "never" completes —
    the hang-mode outage at the readback boundary. ``is_ready()`` is False
    forever; materializing it raises instead of blocking, so an accounting
    bug that tries to read a stuck batch fails loudly in tests rather than
    wedging the suite."""

    def __init__(self, wrapped: Any):
        self._wrapped = wrapped

    def is_ready(self) -> bool:
        return False

    def copy_to_host_async(self) -> None:
        pass

    def block_until_ready(self):
        raise RuntimeError("blocked forever on an injected stuck readback")

    def __array__(self, dtype=None):
        raise RuntimeError("materialized an injected stuck readback — the "
                           "drain loop must dead-letter it at the deadline")


class SlowReadback:
    """Wraps a dispatched device array whose transfer completes only after
    ``delay_s`` — the degraded-but-alive readback shape (a congested
    link, not an outage). ``is_ready`` turns True at the deadline;
    ``block_until_ready`` sleeps out the remainder (so the event-driven
    readback worker waits exactly the injected delay); materializing
    blocks the same way. Lets tests pin pipelining behavior — batches
    dispatched behind a slow head must still overlap — with deterministic
    timing and no real device. (``runtime.fakes.FakePacked`` is the
    sibling shape for whole-pipeline fakes; this one wraps a REAL
    dispatched array, so the chaos layer stays free of test-fake
    imports.)"""

    def __init__(self, wrapped: Any, delay_s: float):
        self._wrapped = wrapped
        self._ready_at = time.monotonic() + float(delay_s)

    def is_ready(self) -> bool:
        return time.monotonic() >= self._ready_at

    def copy_to_host_async(self) -> None:
        pass

    def block_until_ready(self):
        delay = self._ready_at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        return self._wrapped

    def __array__(self, dtype=None):
        self.block_until_ready()
        return np.asarray(self._wrapped, dtype=dtype)


class FaultInjector:
    """Deterministic, seedable fault injection for the serving loop.

    ``script(boundary, *faults)`` queues faults consumed one per boundary
    crossing (exact-count chaos tests); ``rates`` injects probabilistically
    from the seeded RNG (soak tests). ``disarm()`` turns every hook into a
    passthrough — the soak harness uses it to prove liveness with clean
    traffic after the chaos window.
    """

    def __init__(self, seed: int = 0,
                 rates: Optional[Dict[str, Dict[str, float]]] = None,
                 slow_readback_s: float = 0.05,
                 flood_factor: int = 8,
                 slow_decode_s: float = 0.05,
                 slow_fsync_s: float = 0.05):
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        #: injected transfer latency of a ``readback: slow`` fault.
        self.slow_readback_s = float(slow_readback_s)
        #: injected decoder stall of a ``decode: slow`` fault (the worker
        #: sleeps this long before decoding the payload).
        self.slow_decode_s = float(slow_decode_s)
        #: injected stall of a ``storage: slow_fsync`` fault — the durable
        #: operation completes, but only after this long (the 2-second
        #: fsync shape: a congested or error-retrying block device).
        self.slow_fsync_s = float(slow_fsync_s)
        #: amplification of a ``receive: flood`` fault — one delivery
        #: becomes this many (a runaway producer / retry storm in
        #: miniature; the admission layer must shed the excess with
        #: explicit reasons instead of wedging).
        self.flood_factor = max(2, int(flood_factor))
        self.rates = rates or {}
        for boundary, fault_rates in self.rates.items():
            valid = (TRANSPORT_DRAW_KINDS if boundary == "transport"
                     else BOUNDARIES.get(boundary, ()))
            unknown = set(fault_rates) - set(valid)
            if boundary not in BOUNDARIES or unknown:
                raise ValueError(f"unknown fault(s) for {boundary!r}: "
                                 f"{sorted(unknown) or boundary}")
        self._scripted: Dict[str, deque] = {b: deque() for b in BOUNDARIES}
        self.injected: Counter = Counter()
        self.enabled = True
        # ---- transport link state (ISSUE 16) ----
        # Keys are (peer, direction) with direction in
        # TRANSPORT_DIRECTIONS; ``set_*(peer, direction="both")`` expands
        # to both keys.  ``_slow_links`` maps the key to
        # (latency_s, jitter_s); ``_holdback`` parks a reordered message
        # until the next crossing of the same link flushes it behind the
        # newer delivery.
        self._partitioned: set = set()
        self._half_open: set = set()
        self._slow_links: Dict[tuple, tuple] = {}
        self._holdback: Dict[tuple, list] = {}

    def script(self, boundary: str, *faults: str) -> None:
        """Queue deterministic faults at ``boundary``, consumed in order —
        one per crossing, exactly once each."""
        kinds = BOUNDARIES.get(boundary)
        if kinds is None:
            raise ValueError(f"unknown boundary {boundary!r}")
        if boundary == "transport":
            kinds = TRANSPORT_DRAW_KINDS  # stateful kinds are toggled
        for fault in faults:
            if fault not in kinds:
                raise ValueError(f"boundary {boundary!r} has no fault "
                                 f"{fault!r} (valid: {kinds})")
            self._scripted[boundary].append(fault)

    def disarm(self) -> None:
        """Every hook becomes a passthrough (scripted queues included)."""
        self.enabled = False

    def arm(self) -> None:
        self.enabled = True

    def _draw(self, boundary: str) -> Optional[str]:
        """Next fault to fire at this crossing, or None. Scripted faults
        take priority (and are consumed even when a rate is also set).
        The unfiltered form: every kind the boundary knows is eligible
        (``script`` already validated them), so this is exactly
        ``_draw_filtered`` over the boundary's full kind tuple — one
        implementation, never two to drift apart."""
        return self._draw_filtered(boundary, BOUNDARIES[boundary])

    def _draw_filtered(self, boundary: str, allowed: tuple) -> Optional[str]:
        """Like ``_draw`` but the crossing accepts only ``allowed`` kinds:
        a scripted fault at the queue head is consumed only when it
        matches (a scripted ``read_error`` waits for the next READ
        crossing instead of being burned by a write), and rate draws skip
        non-matching kinds."""
        if not self.enabled:
            return None
        queue = self._scripted[boundary]
        fault = None
        if queue and queue[0] in allowed:
            fault = queue.popleft()
        elif not queue:
            for kind, rate in self.rates.get(boundary, {}).items():
                if kind in allowed and rate > 0 and self._rng.random() < rate:
                    fault = kind
                    break
        if fault is not None:
            self.injected[f"{boundary}:{fault}"] += 1
        return fault

    # ---- boundary hooks ----

    def on_receive(self, message: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Connector-receive boundary: returns the message list to actually
        deliver — ``[]`` (dropped), ``[m, m]`` (duplicated), or a corrupted
        payload whose frame can no longer decode."""
        fault = self._draw("receive")
        if fault is None:
            return [message]
        if fault == "drop":
            return []
        if fault == "duplicate":
            return [message, message]
        if fault == "flood":
            return [message] * self.flood_factor
        # corrupt: force the decode_frame path onto a payload whose byte
        # count cannot match its declared dtype (5 bytes into float32) —
        # the service must count it malformed and keep serving.
        corrupted = dict(message)
        corrupted["__frame__"] = "corrupt!"
        corrupted.setdefault("shape", [1])
        corrupted.setdefault("dtype", "float32")
        return [corrupted]

    def on_put(self, frame: np.ndarray) -> np.ndarray:
        """Batcher-put boundary: a poisoned frame (wrong shape, NaN fill)
        that shape/dtype validation must drop before it joins a batch."""
        if self._draw("put") is None:
            return frame
        return np.full((1, 1), np.nan, np.float32)

    def on_dispatch(self) -> None:
        """Device-dispatch boundary: raises the fast-fail outage."""
        if self._draw("dispatch") is not None:
            raise InjectedUnavailableError()

    def on_readback(self, device_array: Any) -> Any:
        """Async-readback boundary: wraps the dispatched output in a
        never-ready proxy (``stuck`` — the hang-mode outage) or a
        delayed-ready one (``slow`` — ``slow_readback_s`` of injected
        transfer latency)."""
        fault = self._draw("readback")
        if fault is None:
            return device_array
        if fault == "slow":
            return SlowReadback(device_array, self.slow_readback_s)
        return StuckReadback(device_array)

    def on_cascade(self, keep: np.ndarray) -> np.ndarray:
        """Stage-1 cascade boundary: ``reject_all`` replaces the gate's
        keep mask with all-False — every frame in the batch scores
        face-free, so the whole batch must exit early as
        ``completed_empty`` with exact ledger settlement."""
        if self._draw("cascade") is None:
            return keep
        return np.zeros_like(keep, dtype=bool)

    def on_decode(self, payload: bytes) -> bytes:
        """Compressed-intake decode boundary (runs on a decode worker,
        never the hot thread): ``slow`` sleeps out the injected decoder
        stall then passes the payload through; ``corrupt`` returns a
        truncated pseudo-JPEG no decoder accepts (SOI marker then
        garbage), so the downstream decode raises exactly like real
        corrupt camera bytes."""
        fault = self._draw("decode")
        if fault is None:
            return payload
        if fault == "slow":
            time.sleep(self.slow_decode_s)
            return payload
        return b"\xff\xd8\xff" + b"\x00" * 5  # corrupt: truncated garbage

    def on_wal_append(self) -> Optional[str]:
        """Enrollment-WAL append boundary: returns the fault kind the
        writer must enact (``"torn"``: persist a partial line then die;
        ``"crash"``: die before any byte lands) or None. The WRITER
        performs the torn write and raises ``InjectedCrashError`` — the
        injector only draws, so the torn bytes are exactly the writer's
        real encoding, not a fake."""
        return self._draw("wal")

    def on_checkpoint(self) -> Optional[str]:
        """Checkpoint-save boundary: ``"torn"`` (die mid-tmp-write),
        ``"crash"`` (die after the tmp is complete but before the rename
        installs it), ``"late"`` (the checkpoint lands; die before the WAL
        truncation that follows), or None."""
        return self._draw("checkpoint")

    def on_stage(self) -> Optional[str]:
        """Rollout stage-append boundary (the background re-embed's
        progress journal): the WRITER enacts the fault — ``"torn"``
        persists a partial chunk line then raises, ``"crash"`` raises
        before any byte lands — so the torn bytes are its real encoding."""
        return self._draw("stage")

    def on_cutover(self) -> Optional[str]:
        """Atomic-cutover boundary (``StateLifecycle.perform_cutover``):
        returns which side of the fence record the simulated kill lands
        on, or None."""
        return self._draw("cutover")

    def on_storage(self, op: str = "write") -> None:
        """Durable-WRITE storage boundary (ISSUE 15): called by every
        durable writer (WAL/journal appends, checkpoint installs, rollout
        stage appends, flight dumps) immediately before the real syscall,
        INSIDE the caller's existing OSError handling — the injected
        errno therefore exercises the exact production error path.
        ``enospc``/``eio`` raise the corresponding ``OSError``;
        ``slow_fsync`` sleeps ``slow_fsync_s`` then lets the write
        proceed (the disk is slow, not broken). ``op`` only labels the
        raised error for forensics; the draw is op-agnostic."""
        fault = self._draw_filtered("storage", STORAGE_WRITE_KINDS)
        if fault is None:
            return
        if fault == "slow_fsync":
            time.sleep(self.slow_fsync_s)
            return
        code = errno.ENOSPC if fault == "enospc" else errno.EIO
        raise OSError(code, f"injected storage fault ({fault}) at {op}")

    def on_storage_read(self, op: str = "read") -> None:
        """Durable-READ storage boundary: replica tailer polls, checkpoint
        recovery reads. ``read_error`` raises ``OSError(EIO)`` — a read
        failure proves nothing about the bytes, and every consumer must
        already treat it as transient (retry/fall back), never as
        corruption."""
        if self._draw_filtered("storage", STORAGE_READ_KINDS) is not None:
            raise OSError(errno.EIO,
                          f"injected storage fault (read_error) at {op}")

    # ---- transport boundary (ISSUE 16) ----

    @staticmethod
    def _link_keys(peer: str, direction: str) -> List[tuple]:
        if direction == "both":
            return [(peer, d) for d in TRANSPORT_DIRECTIONS]
        if direction not in TRANSPORT_DIRECTIONS:
            raise ValueError(f"unknown transport direction {direction!r} "
                             f"(valid: {TRANSPORT_DIRECTIONS + ('both',)})")
        return [(peer, direction)]

    def set_partition(self, peer: str, direction: str = "both") -> None:
        """Cut the link to ``peer``: every crossing in ``direction``
        vanishes until ``heal_partition``."""
        self._partitioned.update(self._link_keys(peer, direction))

    def heal_partition(self, peer: str, direction: str = "both") -> None:
        self._partitioned.difference_update(self._link_keys(peer, direction))

    def set_half_open(self, peer: str, direction: str = "send") -> None:
        """Half-open link: crossings in ``direction`` are silently
        blackholed — no error, no EOF, exactly the shape a dead peer
        behind a still-ACKing TCP stack presents.  Only a heartbeat
        deadline can detect it."""
        self._half_open.update(self._link_keys(peer, direction))

    def heal_half_open(self, peer: str, direction: str = "both") -> None:
        self._half_open.difference_update(self._link_keys(peer, direction))

    def set_slow_link(self, peer: str, latency_s: float,
                      jitter_s: float = 0.0,
                      direction: str = "both") -> None:
        """Every crossing of the link sleeps ``latency_s`` plus a uniform
        draw from ``[0, jitter_s]`` before delivering."""
        for key in self._link_keys(peer, direction):
            self._slow_links[key] = (float(latency_s), float(jitter_s))

    def heal_slow_link(self, peer: str, direction: str = "both") -> None:
        for key in self._link_keys(peer, direction):
            self._slow_links.pop(key, None)

    def heal_all_links(self) -> None:
        """Clear every stateful link condition (scripted/rate transport
        faults are untouched — use ``disarm`` for a full passthrough).
        Held-back reordered messages stay parked until traffic flushes
        them; a drained link's remnant is dropped by ``flush_holdback``."""
        self._partitioned.clear()
        self._half_open.clear()
        self._slow_links.clear()

    def flush_holdback(self, peer: str,
                       direction: str = "both") -> List[Dict[str, Any]]:
        """Return (and forget) any reorder-held messages for the link —
        callers that tear a link down use this so an accounting test can
        settle exactly."""
        flushed: List[Dict[str, Any]] = []
        for key in self._link_keys(peer, direction):
            flushed.extend(self._holdback.pop(key, ()))
        return flushed

    def on_transport(self, peer: str, direction: str,
                     message: Dict[str, Any],
                     sink=None) -> List[Dict[str, Any]]:
        """Transport boundary: one send/recv crossing of the link to
        ``peer``.  Returns the messages to actually deliver, in order —
        ``[]`` (partitioned / half-open / dropped / held for reorder),
        ``[m, m]`` (duplicated), or the newer message followed by a
        previously held one (the reorder materializing).  ``sink``, when
        given, is called with each fault kind enacted — the caller's
        bridge to its own ``transport_fault_<kind>`` counters."""
        if not self.enabled:
            return [message]
        key = (peer, direction)

        def fire(kind: str) -> None:
            self.injected[f"transport:{kind}"] += 1
            if sink is not None:
                sink(kind)

        # Stateful link conditions first: a cut or half-open link eats
        # the message before any per-crossing draw (and leaves holdback
        # parked — nothing crosses a dead link, not even stragglers).
        if key in self._partitioned:
            fire("partition")
            return []
        if key in self._half_open:
            fire("half_open")
            return []
        slow = self._slow_links.get(key)
        if slow is not None:
            latency_s, jitter_s = slow
            delay = latency_s + (self._rng.random() * jitter_s
                                 if jitter_s > 0 else 0.0)
            if delay > 0:
                time.sleep(delay)
            fire("slow")
        fault = self._draw_filtered("transport", TRANSPORT_DRAW_KINDS)
        if fault is not None and sink is not None:
            sink(fault)  # _draw_filtered already counted into .injected
        if fault == "drop":
            return []
        if fault == "reorder":
            self._holdback.setdefault(key, []).append(message)
            return []
        held = self._holdback.pop(key, None)
        if fault == "duplicate":
            out = [message, message]
        else:
            out = [message]
        if held:
            out.extend(held)  # newer-first: the held message lands late
        return out

    def summary(self) -> Dict[str, int]:
        return dict(self.injected)

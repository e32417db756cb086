"""Middleware connectors (SURVEY.md §1 L8, §5.8): the pluggable transport
boundary the reference put behind ``mwconnector/abstractconnector.py``.

Four transports ship:
- ``FakeConnector`` — in-process pub-sub; the test/bench transport (the
  SURVEY.md §4 prescription: the serving loop must be testable without ROS).
- ``JSONLConnector`` — newline-delimited JSON over arbitrary streams
  (stdin/stdout, files): the shippable default in an environment with no
  ROS/RSB. Frames travel as base64 raw bytes + shape/dtype. Signals EOF via
  the ``eof`` event so apps can shut down when the input stream ends.
- ``SocketConnector`` — the same JSONL framing over TCP: the second real
  remote transport (fills the slot the reference's RSB connector held,
  SURVEY.md §2.1 "RSB recognizer" — rsb itself is not installable here).
  Server mode accepts many clients and broadcasts published messages to all
  of them; client mode connects out.
- ``ROSConnector`` — the reference's primary transport (rosconnector.py
  equivalent): subscribes ``sensor_msgs/Image``, publishes results as JSON
  on a ``std_msgs/String`` topic. Import-guarded: constructing it without
  rospy raises with a pointer to the alternatives; the message-handling
  bodies are real and unit-tested against a mocked rospy.

Messages are dicts; topics are strings. Handlers run on the connector's
dispatch thread — keep them cheap (the recognizer's handler just enqueues
into the FrameBatcher).
"""

from __future__ import annotations

import base64
import io
import json
import math
import os
import random
import select
import socket
import threading
import time
from typing import Any, Callable, Dict, IO, List, Optional, Tuple

import numpy as np
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils import native

Handler = Callable[[str, Dict[str, Any]], None]

#: subscribe() under this topic receives EVERY message regardless of its
#: topic (the handler's first argument carries the real one). The
#: replication topic router forwards arbitrary camera topics wholesale —
#: without a wildcard it would have to know every topic up front.
WILDCARD_TOPIC = "*"


def encode_frame(frame: np.ndarray) -> Dict[str, Any]:
    frame = np.ascontiguousarray(frame)
    return {
        "__frame__": base64.b64encode(frame.tobytes()).decode("ascii"),
        "shape": list(frame.shape),
        "dtype": str(frame.dtype),
    }


def _decode_frame_native(obj: Dict[str, Any]) -> Optional[np.ndarray]:
    """The frame of a clean message, decoded by ``utils.native`` straight
    into a new array with the interpreter's lock released; None, never an
    exception, for anything else. Clean: ASCII text whose length is a
    multiple of four and whose decoded size is what a plain numeric
    ``dtype`` and a ``shape`` of non-negative ints say (and, checked by
    the decoder as it goes, pure alphabet plus ``=`` padding). Everything
    else — line breaks, stray characters, a wrong size, a shape with -1,
    a missing key — is the standard decoder's to return or raise for."""
    text, shape = obj["__frame__"], obj.get("shape")
    if not (native.b64_available()
            and isinstance(text, str) and text.isascii() and len(text) % 4 == 0
            and isinstance(shape, (list, tuple))
            and all(type(d) is int and d >= 0 for d in shape)):
        return None
    try:
        dtype = np.dtype(obj["dtype"])
    except Exception:  # ocvf-lint: disable=swallowed-exception -- not swallowed: None sends the message to the standard decoder, which raises for the same missing or unknown dtype in its own order (after the text's own errors)
        return None
    size = len(text) // 4 * 3 - (text.endswith("=") + text.endswith("=="))
    if (dtype.kind not in "biufc"
            or size != math.prod(shape) * dtype.itemsize):
        return None
    out = np.empty(shape, dtype)
    return out if native.b64_decode_into(text.encode("ascii"), out) else None


def decode_frame_counted(obj: Dict[str, Any]) -> Tuple[np.ndarray, bool]:
    """``decode_frame`` and whether the native decoder produced the array
    (the service counts it: ``frames_decoded_native``)."""
    frame = _decode_frame_native(obj)
    if frame is not None:
        return frame, True
    raw = base64.b64decode(obj["__frame__"])
    return np.frombuffer(raw, dtype=np.dtype(obj["dtype"])).reshape(
        obj["shape"]).copy(), False


def decode_frame(obj: Dict[str, Any]) -> np.ndarray:
    """The array ``encode_frame`` was given: new, writable, C-contiguous.
    One result whichever decoder ran — what ``base64.b64decode`` and
    ``np.frombuffer`` return or raise for a message, this returns or
    raises."""
    return decode_frame_counted(obj)[0]


class MiddlewareConnector:
    """publish/subscribe over topics; start/stop lifecycle."""

    def publish(self, topic: str, message: Dict[str, Any]) -> None:
        raise NotImplementedError

    def subscribe(self, topic: str, handler: Handler) -> None:
        raise NotImplementedError

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass


class FakeConnector(MiddlewareConnector):
    """In-process pub-sub; synchronous dispatch on the publisher's thread.

    ``sent`` records every published message for assertions; ``inject`` is
    an alias of ``publish`` that reads better in tests.
    """

    def __init__(self):
        self._handlers: Dict[str, List[Handler]] = {}
        self._lock = threading.Lock()
        self.sent: List[tuple] = []

    def publish(self, topic: str, message: Dict[str, Any]) -> None:
        with self._lock:
            self.sent.append((topic, message))
            handlers = list(self._handlers.get(topic, ()))
            if topic != WILDCARD_TOPIC:
                handlers += list(self._handlers.get(WILDCARD_TOPIC, ()))
        for handler in handlers:
            handler(topic, message)

    inject = publish

    def subscribe(self, topic: str, handler: Handler) -> None:
        with self._lock:
            self._handlers.setdefault(topic, []).append(handler)

    def messages(self, topic: str) -> List[Dict[str, Any]]:
        with self._lock:
            return [m for t, m in self.sent if t == topic]


def _parse_jsonl_line(line: str):
    """One JSONL wire line -> (topic, data) or None if malformed/empty."""
    line = line.strip()
    if not line:
        return None
    try:
        obj = json.loads(line)
        return obj["topic"], obj.get("data", {})
    except (json.JSONDecodeError, KeyError, TypeError):
        return "__malformed__", None


class _TopicDispatchConnector(MiddlewareConnector):
    """Shared handler registry + JSONL-line handling for the wire
    transports (JSONL/socket/ROS all dispatch the same way; one body).

    ``metrics`` (optional, a ``utils.metrics.Metrics``) mirrors the
    transport failure counters — ``connector_malformed_lines``,
    ``connector_peer_disconnects`` — onto the same surface the serving
    metrics live on, so failure-path tests (and a stats consumer) read one
    ledger instead of poking per-transport attributes."""

    def __init__(self, metrics=None):
        self._handlers: Dict[str, List[Handler]] = {}
        self._lock = threading.Lock()
        self.malformed_lines = 0
        self.metrics = metrics

    def subscribe(self, topic: str, handler: Handler) -> None:
        with self._lock:
            self._handlers.setdefault(topic, []).append(handler)

    def _count(self, name: str) -> None:
        if self.metrics is not None:
            # ocvf-lint: disable=metrics-registry -- thin None-guard shim; _count is itself in the rule's NAME_METHODS, so every caller's argument is validated against the registry at its own call site
            self.metrics.incr(name)

    def _dispatch(self, topic: str, data: Dict[str, Any]) -> None:
        with self._lock:
            handlers = list(self._handlers.get(topic, ()))
            if topic != WILDCARD_TOPIC:
                handlers += list(self._handlers.get(WILDCARD_TOPIC, ()))
        for handler in handlers:
            handler(topic, data)

    def _handle_line(self, line: str) -> None:
        parsed = _parse_jsonl_line(line)
        if parsed is None:
            return
        topic, data = parsed
        if data is None:
            self.malformed_lines += 1
            self._count(mn.CONNECTOR_MALFORMED_LINES)
            return
        self._dispatch(topic, data)


class JSONLConnector(_TopicDispatchConnector):
    """One JSON object per line: {"topic": ..., "data": {...}}.

    A reader thread dispatches incoming lines to subscribed handlers;
    ``publish`` writes lines to the output stream. Malformed lines are
    counted and skipped, never fatal (SURVEY.md §5.3).

    Lifecycle: ``eof`` is set when the reader finishes (input stream ended
    or ``stop()`` was called) — apps wait on it to shut down instead of
    spinning forever. For real-fd streams (stdin, pipes, socket files) the
    reader multiplexes the fd against a self-pipe with ``select``, so
    ``stop()`` genuinely unblocks a reader waiting for input. (Closing the
    stream from another thread — the obvious alternative — deadlocks on the
    buffered reader's internal lock in CPython.)
    """

    def __init__(
        self,
        in_stream: Optional[IO[str]] = None,
        out_stream: Optional[IO[str]] = None,
        metrics=None,
    ):
        super().__init__(metrics=metrics)
        self._in = in_stream
        self._out = out_stream
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._wake_r: Optional[int] = None
        self._wake_w: Optional[int] = None
        self.eof = threading.Event()

    def publish(self, topic: str, message: Dict[str, Any]) -> None:
        if self._out is None:
            return
        line = json.dumps({"topic": topic, "data": message})
        with self._lock:  # ocvf-lint: boundary-block=blocking-under-lock -- this transport lock EXISTS to serialize whole lines onto the stream; no serving-path lock nests inside it
            try:
                self._out.write(line + "\n")
                self._out.flush()
            except (ValueError, OSError):
                # Stream closed during shutdown, or the consumer died
                # (BrokenPipeError) — either way publishing must never kill
                # the serving loop thread that called it.
                pass

    def start(self) -> None:
        if self._in is None or self._thread is not None:
            return
        self._running = True
        self._wake_r, self._wake_w = os.pipe()
        self._thread = threading.Thread(target=self._read_loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        if self._wake_w is not None:
            try:
                os.write(self._wake_w, b"x")  # wake a select()-blocked reader
            except OSError:
                pass
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        for fd in (self._wake_r, self._wake_w):
            if fd is not None:
                try:
                    os.close(fd)
                except OSError:
                    pass
        self._wake_r = self._wake_w = None

    def _read_loop(self) -> None:
        stream = self._in
        try:
            fd = stream.fileno()
        except (OSError, AttributeError, ValueError, io.UnsupportedOperation):
            fd = None
        try:
            if fd is None:
                # In-memory stream (StringIO etc.): iteration never blocks.
                for line in stream:
                    if not self._running:
                        break
                    self._handle_line(line)
            else:
                self._read_loop_fd(fd)
        except ValueError:
            pass  # stream closed under us
        finally:
            self.eof.set()

    def _read_loop_fd(self, fd: int) -> None:
        """select() on the stream fd + the wake pipe; split lines manually
        (the raw fd bypasses the TextIO buffer, so all reads go through
        here — do not mix with stream.readline())."""
        buf = b""
        while self._running:
            ready, _, _ = select.select([fd, self._wake_r], [], [])
            if self._wake_r in ready:
                break  # stop() requested
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                # True EOF: a final line without a trailing newline is
                # still a line (matches text-stream iteration semantics).
                if buf.strip():
                    self._handle_line(buf.decode("utf-8", errors="replace"))
                break
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if not self._running:
                    return
                self._handle_line(line.decode("utf-8", errors="replace"))


class SocketConnector(_TopicDispatchConnector):
    """JSONL framing over TCP — the second real remote transport.

    ``SocketConnector(port=N, listen=True)`` binds and accepts any number of
    clients; every ``publish`` is broadcast to all connected clients, every
    client line is dispatched to subscribed handlers. ``listen=False``
    connects out to ``(host, port)``. Either end speaks the exact
    JSONLConnector wire format, so a JSONL client can talk to a socket
    server through ``nc`` unchanged.

    Client mode survives server blips: a peer-initiated disconnect redials
    with bounded exponential backoff (``reconnect_attempts`` consecutive
    tries, ``reconnect_backoff_base_s`` doubling up to
    ``reconnect_backoff_max_s``; successes counted as
    ``connector_reconnects``). ``eof`` fires only once the budget is
    exhausted — not on the first blip, which previously killed the client
    connector permanently.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 listen: bool = False, metrics=None,
                 reconnect_attempts: int = 8,
                 reconnect_backoff_base_s: float = 0.05,
                 reconnect_backoff_max_s: float = 2.0,
                 reconnect_jitter: float = 0.5,
                 fault_injector=None, peer_name: Optional[str] = None):
        super().__init__(metrics=metrics)
        self.host = host
        self.port = port
        self.listen = listen
        # Transport fault boundary (ISSUE 16): when an injector is
        # installed, every published message crosses
        # ``on_transport(peer, "send", ...)`` before hitting the wire and
        # every received message crosses ``(peer, "recv", ...)`` before
        # dispatch — partition/half-open/slow/drop/duplicate/reorder all
        # land on the exact send/recv paths production traffic uses.
        # ``peer_name`` labels the remote end for per-peer injection;
        # defaults to "host:port".
        self._faults = fault_injector
        self._peer_name = peer_name
        # Reconnect backoff jitter: a deterministic exponential schedule
        # synchronizes a thundering herd (every peer of a restarted
        # replica redials on the same beat). Each delay is multiplied by
        # a uniform draw from [1 - jitter, 1 + jitter]; 0 restores the
        # deterministic schedule for tests that pin timing.
        self.reconnect_jitter = min(1.0, max(0.0, float(reconnect_jitter)))
        self._backoff_rng = random.Random()
        # Client-mode reconnect (bounded exponential backoff): a server
        # blip used to permanently kill the client connector — the read
        # loop ended, ``eof`` fired, and nothing ever dialed again. Now a
        # peer-initiated disconnect retries the connection up to
        # ``reconnect_attempts`` consecutive times (counted as
        # ``connector_reconnects`` on success), and ``eof`` fires only
        # once the budget is exhausted (or stop() is called). 0 disables.
        self.reconnect_attempts = max(0, int(reconnect_attempts))
        self.reconnect_backoff_base_s = float(reconnect_backoff_base_s)
        self.reconnect_backoff_max_s = float(reconnect_backoff_max_s)
        # Per-SOCKET send locks: interleaved partial writes from concurrent
        # publishes would splice two JSON lines into one corrupt frame, but
        # one stalled client (full TCP buffer) must not wedge publishes to
        # the healthy ones — so serialization is per socket, and each send
        # is deadline-bounded (see ``_send_deadline_s``); a client that
        # can't accept a payload in time is dropped like a dead one.
        self._send_locks: Dict[socket.socket, threading.Lock] = {}
        self._send_deadline_s = 2.0
        self._threads: List[threading.Thread] = []
        self._server_sock: Optional[socket.socket] = None
        self._client_socks: List[socket.socket] = []
        self._running = False
        self.eof = threading.Event()

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        if self.listen:
            self._server_sock = socket.create_server((self.host, self.port))
            self.port = self._server_sock.getsockname()[1]
            accept_thread = threading.Thread(target=self._accept_loop, daemon=True)
            accept_thread.start()
            self._threads.append(accept_thread)
        else:
            # The FIRST connect stays synchronous (and raising): a server
            # that was never there is a configuration error the caller
            # should see immediately, unlike a mid-session blip.
            sock = socket.create_connection((self.host, self.port), timeout=10.0)
            sock.settimeout(None)
            self._register(sock)
            thread = threading.Thread(target=self._client_loop, args=(sock,),
                                      daemon=True)
            thread.start()
            self._threads.append(thread)

    def _accept_loop(self) -> None:
        while self._running:
            try:
                sock, _addr = self._server_sock.accept()
            except OSError:
                break  # server socket closed by stop()
            self._attach(sock)
        self.eof.set()

    def _register(self, sock: socket.socket) -> bool:
        """Track a live socket for publish/teardown. Checked against
        ``_running`` UNDER the lock: stop() clears the registry under the
        same lock after flipping the flag, so a socket that registers here
        is guaranteed to be seen (and closed) by stop() — a reconnect
        completing concurrently with stop() must not leak a live
        connection past it. Returns False (socket closed) after stop."""
        with self._lock:
            if not self._running:
                try:
                    sock.close()
                except OSError:
                    pass
                return False
            self._client_socks.append(sock)
            self._send_locks[sock] = threading.Lock()
            return True

    def _attach(self, sock: socket.socket) -> None:
        if not self._register(sock):
            return
        thread = threading.Thread(target=self._read_loop, args=(sock,), daemon=True)
        thread.start()
        self._threads.append(thread)

    def _read_sock(self, sock: socket.socket) -> None:
        """Read one socket until it dies or stop(): dispatch lines, count
        a peer-initiated disconnect, and deregister the socket."""
        fh = sock.makefile("r", encoding="utf-8", errors="replace")
        try:
            # A peer that dies mid-message leaves a final line without a
            # newline; iteration still yields it, _handle_line counts it
            # malformed (truncated JSON never parses) — then the disconnect
            # itself is counted below. Two counters, two distinct faults.
            for line in fh:
                if not self._running:
                    break
                self._handle_line(line)
        except (OSError, ValueError):
            pass  # peer gone or socket closed during shutdown
        finally:
            if self._running:
                # Peer-initiated EOF/reset (our own stop() closes sockets
                # only after clearing _running): a flaky peer, counted.
                self._count(mn.CONNECTOR_PEER_DISCONNECTS)
            with self._lock:
                if sock in self._client_socks:
                    self._client_socks.remove(sock)
                self._send_locks.pop(sock, None)

    def _read_loop(self, sock: socket.socket) -> None:
        """Server-side per-client reader."""
        self._read_sock(sock)
        with self._lock:
            remaining = len(self._client_socks)
        if not self._running and remaining == 0:
            self.eof.set()

    def _client_loop(self, sock: socket.socket) -> None:
        """Client-side reader + reconnect supervisor: read until the
        connection dies, then redial with bounded exponential backoff.
        ``eof`` fires only when the reconnect budget is exhausted (the
        transport is genuinely gone) or stop() ends the session."""
        while True:
            self._read_sock(sock)
            if not self._running or self.reconnect_attempts <= 0:
                break
            sock = self._reconnect_with_backoff()
            if sock is None:
                break
        self.eof.set()

    def _reconnect_with_backoff(self) -> Optional[socket.socket]:
        """Up to ``reconnect_attempts`` redials, exponential backoff
        between them; sleeps in slices so stop() is honored promptly.
        Returns the registered socket, or None when the budget is spent."""
        for attempt in range(self.reconnect_attempts):
            delay = min(self.reconnect_backoff_max_s,
                        self.reconnect_backoff_base_s * 2 ** attempt)
            if self.reconnect_jitter > 0:
                delay *= self._backoff_rng.uniform(
                    1.0 - self.reconnect_jitter, 1.0 + self.reconnect_jitter)
            deadline = time.monotonic() + delay
            while self._running and time.monotonic() < deadline:
                time.sleep(min(0.05, max(0.0, deadline - time.monotonic())))
            if not self._running:
                return None
            try:
                sock = socket.create_connection((self.host, self.port),
                                                timeout=10.0)
            except OSError:
                self._count(mn.CONNECTOR_RECONNECT_FAILURES)
                continue
            try:
                if sock.getsockname() == sock.getpeername():
                    # TCP self-connect (simultaneous open): dialing a dead
                    # EPHEMERAL port on loopback can land on the socket's
                    # own source port and "succeed" — a live connection to
                    # ourselves, not to a revived server. Treat as failure.
                    sock.close()
                    self._count(mn.CONNECTOR_RECONNECT_FAILURES)
                    continue
            except OSError:
                self._count(mn.CONNECTOR_RECONNECT_FAILURES)
                continue
            sock.settimeout(None)
            if not self._register(sock):
                return None  # stop() won the race; socket already closed
            self._count(mn.CONNECTOR_RECONNECTS)
            return sock
        return None

    def _send_bounded(self, sock: socket.socket, payload: bytes) -> bool:
        """Deadline-bounded send without touching the socket's blocking
        state (the read loop shares the socket): ``MSG_DONTWAIT`` makes each
        individual send non-blocking — a blocking-mode TCP ``send`` would
        otherwise park until the ENTIRE buffer is queued, which is exactly
        the wedge this guards against — and ``select`` bounds the wait for
        buffer space. Returns False when the deadline passes."""
        deadline = time.monotonic() + self._send_deadline_s
        view = memoryview(payload)
        while view:
            try:
                view = view[sock.send(view, socket.MSG_DONTWAIT):]
                continue
            except (BlockingIOError, InterruptedError):
                pass  # buffer full: wait (bounded) for space below
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            _, writable, _ = select.select((), (sock,), (), remaining)
            if not writable:
                return False
        return True

    def _transport_peer(self) -> str:
        return self._peer_name or f"{self.host}:{self.port}"

    def _transport_sink(self, kind: str) -> None:
        self._count(mn.TRANSPORT_FAULTS_PREFIX + kind)

    def _dispatch(self, topic: str, data: Dict[str, Any]) -> None:
        # Receive side of the transport fault boundary: a parsed wire
        # message crosses the injector before any handler sees it, so an
        # injected recv-drop/duplicate/reorder is indistinguishable from
        # the network doing it.
        if self._faults is None:
            super()._dispatch(topic, data)
            return
        for msg in self._faults.on_transport(self._transport_peer(), "recv",
                                             data, sink=self._transport_sink):
            super()._dispatch(topic, msg)

    def publish(self, topic: str, message: Dict[str, Any]) -> None:
        messages = [message]
        if self._faults is not None:
            # Send side of the transport boundary: a dropped/partitioned
            # message never reaches the wire; a duplicated one is framed
            # twice in the same payload (back-to-back lines, exactly what
            # a retransmit-happy link delivers).
            messages = self._faults.on_transport(
                self._transport_peer(), "send", message,
                sink=self._transport_sink)
            if not messages:
                return
        payload = "".join(
            json.dumps({"topic": topic, "data": m}) + "\n"
            for m in messages).encode()
        with self._lock:
            socks = [(s, self._send_locks[s]) for s in self._client_socks]
        dead = []
        for sock, lock in socks:
            with lock:
                try:
                    ok = self._send_bounded(sock, payload)
                except (OSError, ValueError):
                    # ValueError: select on a socket another thread closed
                    # mid-publish (fileno() == -1) — same as a dead client.
                    ok = False
                if not ok:
                    # Close while STILL holding the send lock: a concurrent
                    # publisher that already snapshotted this sock must get
                    # an immediate OSError, not append its line after our
                    # truncated one (spliced JSON frames on the wire).
                    # shutdown() first: close() alone does not interrupt a
                    # thread parked in recv() on Linux, so the read loop
                    # would stay blocked until the peer acts.
                    try:
                        sock.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    try:
                        sock.close()
                    except OSError:
                        pass
            if not ok:
                dead.append(sock)
        if dead:
            with self._lock:
                for sock in dead:
                    if sock in self._client_socks:
                        self._client_socks.remove(sock)
                    self._send_locks.pop(sock, None)
            for _ in dead:
                self._count(mn.CONNECTOR_STALLED_CLIENTS_DROPPED)

    def stop(self) -> None:
        self._running = False
        if self._server_sock is not None:
            # shutdown() BEFORE close(): a thread blocked in accept()
            # holds a kernel reference to the listening socket, so a bare
            # close() leaves it listening — it would absorb one final
            # "ghost" connection (observed: a reconnecting client dials a
            # stopping server, connects, and parks forever on a socket
            # nobody will ever service). shutdown() wakes the accept with
            # an error and genuinely stops the listener.
            try:
                self._server_sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._server_sock.close()
            except OSError:
                pass
        with self._lock:
            socks = list(self._client_socks)
            self._client_socks.clear()
            self._send_locks.clear()
        for sock in socks:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        for thread in self._threads:
            thread.join(timeout=2.0)
        self._threads.clear()


def decode_ros_image(msg) -> np.ndarray:
    """sensor_msgs/Image -> float32 grayscale [H, W] without cv_bridge.

    Handles the encodings a camera driver actually emits: mono8/mono16
    directly, rgb8/bgr8/rgba8/bgra8 via the standard luma weights. Honors
    ``step`` (row stride) and ``is_bigendian`` for mono16.
    """
    h, w, step = int(msg.height), int(msg.width), int(msg.step)
    enc = str(msg.encoding).lower()
    raw = np.frombuffer(bytes(msg.data), dtype=np.uint8)
    channels = {"mono8": 1, "mono16": 2, "rgb8": 3, "bgr8": 3,
                "rgba8": 4, "bgra8": 4}
    if enc not in channels:
        raise ValueError(f"unsupported image encoding: {msg.encoding!r}")
    rows = raw.reshape(h, step)[:, : w * channels[enc]]
    if enc == "mono8":
        return rows.astype(np.float32)
    if enc == "mono16":
        dt = ">u2" if getattr(msg, "is_bigendian", 0) else "<u2"
        img16 = rows.reshape(h, w, 2).copy().view(dt)[..., 0]
        return (img16.astype(np.float32) / 257.0)  # 16-bit -> 0..255 scale
    c = channels[enc]
    rgb = rows.reshape(h, w, c)[..., :3].astype(np.float32)
    if enc.startswith("bgr"):
        rgb = rgb[..., ::-1]
    return rgb @ np.asarray([0.299, 0.587, 0.114], np.float32)


class ROSConnector(_TopicDispatchConnector):
    """The reference's ROS transport (SURVEY.md §2.1 "ROS recognizer node",
    BASELINE.json:5/:10 — the named target workload's transport).

    - ``sensor_msgs/Image`` on ``image_topic`` -> decoded grayscale frame
      dispatched to FRAME_TOPIC subscribers (same dict schema as the other
      connectors, so RecognizerService is transport-agnostic).
    - ``std_msgs/String`` JSON on ``control_topic`` -> control commands
      (enroll/stats — the reference's retrain/restart channel).
    - ``publish`` serializes result/status dicts as JSON into
      ``std_msgs/String`` on ``result_topic``/``status_topic`` (custom msg
      types would need a catkin build; String-JSON keeps the node drop-in).

    rospy is imported at construction and the node handles are injectable
    for tests (a mocked rospy module exercises the full body without ROS).
    """

    def __init__(
        self,
        image_topic: str = "/camera/image_raw",
        result_topic: str = "/ocvfacerec/results",
        control_topic: str = "/ocvfacerec/control",
        status_topic: str = "/ocvfacerec/status",
        node_name: str = "ocvf_recognizer",
        rospy_module=None,
    ):
        if rospy_module is None:
            try:
                import rospy as rospy_module  # type: ignore[no-redef]
            except ImportError as e:
                raise ImportError(
                    "rospy is not installed in this environment; use "
                    "JSONLConnector, SocketConnector, or FakeConnector, which "
                    "implement the same MiddlewareConnector interface"
                ) from e
        super().__init__()
        self._rospy = rospy_module
        self.image_topic = image_topic
        self.result_topic = result_topic
        self.control_topic = control_topic
        self.status_topic = status_topic
        self.node_name = node_name
        self._publishers: Dict[str, Any] = {}
        self._subscribers: List[Any] = []
        self._started = False
        self.frames_malformed = 0

    # Topic names on the app side (FRAME_TOPIC et al.) map onto the ROS
    # graph names given in the constructor.
    def _ros_topic_for(self, topic: str) -> str:
        from opencv_facerecognizer_tpu.runtime import recognizer as rec

        return {
            rec.RESULT_TOPIC: self.result_topic,
            rec.STATUS_TOPIC: self.status_topic,
        }.get(topic, topic)

    def start(self) -> None:
        if self._started:
            return
        rospy = self._rospy
        rospy.init_node(self.node_name, anonymous=True, disable_signals=True)
        self._string_cls = self._string_msg_cls()
        self._subscribers.append(
            rospy.Subscriber(self.image_topic, self._image_msg_cls(), self._on_image)
        )
        self._subscribers.append(
            rospy.Subscriber(self.control_topic, self._string_cls, self._on_control)
        )
        self._started = True

    @staticmethod
    def _string_msg_cls():
        try:
            from std_msgs.msg import String  # only exists beside rospy
        except ImportError:
            class String:  # stand-in with std_msgs/String's one field
                def __init__(self, data: str = ""):
                    self.data = data

        return String

    @staticmethod
    def _image_msg_cls():
        try:
            from sensor_msgs.msg import Image  # only exists beside rospy
        except ImportError:
            class Image:  # stand-in; only used as the Subscriber type arg
                pass

        return Image

    def _on_image(self, msg) -> None:
        from opencv_facerecognizer_tpu.runtime import recognizer as rec

        try:
            frame = decode_ros_image(msg)
        except Exception:  # noqa: BLE001 — malformed frame must not kill the node
            self.frames_malformed += 1
            # mirror onto the shared Metrics surface like the JSONL/socket
            # transports do, so one ledger covers every transport
            self._count(mn.CONNECTOR_MALFORMED_LINES)
            return
        stamp = getattr(getattr(msg, "header", None), "stamp", None)
        message = {**encode_frame(frame),
                   "meta": {"stamp": str(stamp) if stamp is not None else None}}
        self._dispatch(rec.FRAME_TOPIC, message)

    def _on_control(self, msg) -> None:
        from opencv_facerecognizer_tpu.runtime import recognizer as rec

        parsed = _parse_jsonl_line(getattr(msg, "data", ""))
        if parsed is None:
            return
        topic, data = parsed
        if data is None:
            # Accept bare command payloads too: {"cmd": "enroll", ...}
            try:
                data = json.loads(msg.data)
                topic = rec.CONTROL_TOPIC
            except (json.JSONDecodeError, TypeError):
                return
        self._dispatch(topic if topic != "__malformed__" else rec.CONTROL_TOPIC, data)

    def publish(self, topic: str, message: Dict[str, Any]) -> None:
        if not self._started:
            return
        ros_topic = self._ros_topic_for(topic)
        with self._lock:
            pub = self._publishers.get(ros_topic)
            if pub is None:
                pub = self._rospy.Publisher(ros_topic, self._string_cls, queue_size=16)
                self._publishers[ros_topic] = pub
        pub.publish(self._string_cls(data=json.dumps(message)))

    def subscribe(self, topic: str, handler: Handler) -> None:
        with self._lock:
            self._handlers.setdefault(topic, []).append(handler)

    def stop(self) -> None:
        for sub in self._subscribers:
            try:
                sub.unregister()
            except Exception:  # ocvf-lint: disable=swallowed-exception -- rospy teardown is best-effort by contract: a half-dead node handle raising here must not block shutdown, and there is nothing to recover
                pass
        self._subscribers.clear()
        self._started = False

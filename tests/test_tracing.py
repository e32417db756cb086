"""Frame-lifecycle tracing tests (observability layer): span lifecycle &
ordering under the threaded serving pipeline, ring-buffer bounds,
deterministic sampling, the flight-recorder dump on an injected wedge,
the expo endpoint's read-only contract, and the Metrics empty-window /
reset_window fixes that ride along.

All over ``runtime.fakes.InstantPipeline`` — fast, deterministic, no
hardware.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
from opencv_facerecognizer_tpu.runtime.expo import ExpoServer
from opencv_facerecognizer_tpu.runtime.fakes import InstantPipeline
from opencv_facerecognizer_tpu.runtime.faults import FaultInjector
from opencv_facerecognizer_tpu.runtime.journal import DeadLetterJournal
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    RecognizerService,
)
from opencv_facerecognizer_tpu.runtime.resilience import ResiliencePolicy
from opencv_facerecognizer_tpu.utils import tracing
from opencv_facerecognizer_tpu.utils.metrics import Metrics
from opencv_facerecognizer_tpu.utils.tracing import Tracer, account_spans

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FRAME_HW = (16, 16)


def _make_service(tracer, **kwargs):
    pipeline = InstantPipeline(FRAME_HW, compute_s=0.001)
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=4, frame_shape=FRAME_HW,
        flush_timeout=0.01, similarity_threshold=0.0, metrics=Metrics(),
        tracer=tracer, **kwargs)
    return pipeline, connector, service


def _drive(connector, n, start=0):
    frame = np.zeros(FRAME_HW, np.float32)
    for i in range(start, start + n):
        connector.inject(FRAME_TOPIC, {"frame": frame, "meta": {"seq": i}})


# ---- span lifecycle & ordering under the threaded pipeline ----


def test_span_lifecycle_and_ordering_through_pipeline():
    tracer = Tracer(ring_size=4096, sample=1.0)
    _pipe, connector, service = _make_service(tracer)
    service.start(warmup=False)
    try:
        _drive(connector, 12)
        assert service.drain(timeout=10.0)
    finally:
        service.stop()

    frame_spans = tracer.snapshot(topic=FRAME_TOPIC)
    by_trace = {}
    for span in frame_spans:
        by_trace.setdefault(span["trace"], []).append(span)
    assert len(by_trace) == 12
    batch_spans = tracer.snapshot(topic=tracing.BATCH_TOPIC)
    dispatch_by_batch = {s["trace"]: s for s in batch_spans
                        if s["stage"] == "dispatch"}
    for spans in by_trace.values():
        # Causal order: receive -> intake -> queue_wait -> settle, in
        # span-id order (ids are drawn when a span opens or is emitted).
        # Not in the ring's order, which is emission order: ``intake``
        # is emitted when its block ends, and the loop can pop the batch
        # this frame closed, and emit its ``queue_wait``, before that.
        spans.sort(key=lambda s: s["span"])
        stages = [s["stage"] for s in spans]
        assert stages == ["receive", "intake", "queue_wait", "settle"]
        assert len({s["span"] for s in spans}) == 4
        assert spans[0]["verdict"] == "admitted"
        assert spans[3]["outcome"] == tracing.OUTCOME_COMPLETED
        # intake starts where receive ended: at the admission verdict.
        assert spans[1]["t0"] >= spans[0]["t0"] + spans[0]["dur"]
        # Coalescing ancestry: the queue_wait span names the batch trace
        # that carried the frame, and that batch has a dispatch span with
        # the bucket it served at.
        batch = spans[2]["batch"]
        assert batch and batch == spans[3]["batch"]
        assert dispatch_by_batch[batch]["bucket"] >= 1
    # Batch spans: every dispatched batch has its round-trip recorded.
    stages = {s["stage"] for s in batch_spans}
    assert {"dispatch", "ready_wait", "publish"} <= stages
    # Span accounting mirrors the (settled) ledger exactly.
    acct = account_spans(frame_spans)
    ledger = service.ledger()
    assert acct["completed"] == int(ledger["completed"]) == 12
    assert acct["traced"] == int(ledger["admitted"])
    assert acct["drops"] == {}


def test_terminal_spans_cover_drops():
    """A frame that dies in the batcher still settles exactly once, with
    the ledger counter name as its outcome."""
    tracer = Tracer(sample=1.0)
    _pipe, connector, service = _make_service(tracer)
    # Malformed decode: admitted, then fails decode_frame.
    connector.inject(FRAME_TOPIC, {"__frame__": "corrupt!", "shape": [1],
                                   "dtype": "float32", "meta": {}})
    acct = account_spans(tracer.snapshot(topic=FRAME_TOPIC))
    assert acct["drops"] == {"frames_malformed": 1}
    # Wrong shape: the batcher's malformed drop settles the frame.
    connector.inject(FRAME_TOPIC, {"frame": np.zeros((3, 3), np.float32)})
    acct = account_spans(tracer.snapshot(topic=FRAME_TOPIC))
    assert acct["drops"] == {"frames_malformed": 1,
                             "batcher_dropped_malformed": 1}
    ledger = service.ledger()
    assert acct["traced"] == int(ledger["admitted"]) == 2
    assert {k: float(v) for k, v in acct["drops"].items()} \
        == ledger["drops_by_reason"]


# ---- ring-buffer bounds ----


def test_ring_buffer_bounded():
    tracer = Tracer(ring_size=16, sample=1.0)
    for i in range(100):
        tracer.emit(tracer.new_trace(), "stage", topic="t", seq=i)
    spans = tracer.snapshot(topic="t")
    assert len(spans) == 16
    # The ring keeps the NEWEST spans (flight-recorder semantics).
    assert [s["seq"] for s in spans] == list(range(84, 100))


# ---- deterministic sampling ----


def test_sampling_deterministic_under_fixed_seed():
    def sampled_set(seed, n=400, rate=0.5):
        tracer = Tracer(sample=rate, seed=seed)
        return {i for i in range(n) if tracer.start_trace("t")}

    a = sampled_set(seed=42)
    b = sampled_set(seed=42)
    assert a == b  # same seed -> exactly the same kept traces
    c = sampled_set(seed=43)
    assert a != c  # a different seed samples a different subset
    assert 0.3 < len(a) / 400 < 0.7  # and the rate is honored roughly


def test_sampling_edge_rates():
    always = Tracer(sample=1.0)
    assert all(always.start_trace("t") for _ in range(50))
    never = Tracer(sample=0.0)
    assert not any(never.start_trace("t") for _ in range(50))
    # Sampled-out frames record nothing anywhere.
    never.emit(0, "receive", topic="t")
    assert never.snapshot() == []


# ---- flight recorder ----


def test_flight_recorder_dump_on_injected_wedge(tmp_path):
    """A scripted stuck readback (runtime.faults) dead-letters its batch;
    the dead-letter must dump the rings atomically and thread the dump
    path + per-frame trace ids into the dead-letter journal record."""
    injector = FaultInjector(seed=3)
    injector.script("readback", "stuck")
    journal = DeadLetterJournal(str(tmp_path / "dead.jsonl"))
    tracer = Tracer(sample=1.0, dump_dir=str(tmp_path / "flight"),
                    min_dump_interval_s=0.0)
    _pipe, connector, service = _make_service(
        tracer, fault_injector=injector, dead_letter_journal=journal,
        resilience=ResiliencePolicy(readback_deadline_s=0.2))
    service.start(warmup=False)
    try:
        _drive(connector, 4)
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
        journal.close()
    assert service.metrics.counter("frames_dead_lettered") == 4
    dumps = sorted(os.listdir(tmp_path / "flight"))
    assert dumps, "dead-letter did not dump the flight recorder"
    record = json.loads((tmp_path / "flight" / dumps[0]).read_text())
    assert record["reason"] == "dead_letter"
    assert record["extra"]["frames"] == 4
    # Every dead frame has its terminal span in the dump.
    acct = account_spans(record["spans"][FRAME_TOPIC])
    assert acct["drops"] == {"frames_dead_lettered": 4}
    # The journal row carries the dump path + per-frame trace_id/stage.
    rows = [r for r in journal.records() if r["reason"] == "dead_letter"]
    assert rows and rows[0]["dump"] == str(tmp_path / "flight" / dumps[0])
    for frame in rows[0]["frames"]:
        assert frame["stage"] == "readback.dead_letter"
        assert frame["trace_id"]


def test_dead_letter_slices_padded_and_trimmed_provenance(tmp_path):
    """A partial batch dead-letters with count < batch_size (padded metas)
    and count < len(trace_ids) (a brownout trim already settled the
    tail): the journal must get exactly ``count`` rows and the trimmed
    frames must NOT be settled a second time."""
    tracer = Tracer(sample=1.0)
    journal = DeadLetterJournal(str(tmp_path / "dead.jsonl"))
    _pipe, _connector, service = _make_service(
        tracer, dead_letter_journal=journal)
    tids = [tracer.start_trace(FRAME_TOPIC) for _ in range(3)]
    padded_metas = [{"seq": i} for i in range(3)] + [None] * 5  # batch_size pad
    # count=2: the third frame was brownout-trimmed (settled elsewhere).
    service._dead_letter(2, padded_metas, [1.0, 2.0, 3.0], tids,
                         batch=tracer.new_trace())
    journal.close()
    rows = [r for r in journal.records() if r["reason"] == "dead_letter"]
    assert len(rows[0]["frames"]) == 2  # count, not batch_size
    assert [f["meta"] for f in rows[0]["frames"]] == [{"seq": 0}, {"seq": 1}]
    acct = account_spans(tracer.snapshot(topic=FRAME_TOPIC))
    assert acct["drops"] == {"frames_dead_lettered": 2}  # tids[2] untouched


def test_dump_rate_limit_and_retention(tmp_path):
    tracer = Tracer(sample=1.0, dump_dir=str(tmp_path), keep_dumps=3,
                    min_dump_interval_s=60.0)
    tracer.emit(tracer.new_trace(), "s", topic="t")
    assert tracer.dump("dead_letter") is not None
    assert tracer.dump("dead_letter") is None  # rate-limited
    assert tracer.dump("dead_letter", force=True) is not None
    for _ in range(5):
        assert tracer.dump("end", force=True) is not None
    names = [n for n in os.listdir(tmp_path) if n.startswith("flight-")]
    assert len(names) == 3  # retention pruned the oldest


def test_dump_without_dir_is_none():
    tracer = Tracer(sample=1.0)
    assert tracer.dump("anything", force=True) is None


# ---- lifecycle spans ----


def test_lifecycle_context_manager_records_errors():
    tracer = Tracer(sample=1.0)
    with tracer.lifecycle("checkpoint", wal_seq=7) as attrs:
        attrs["rows"] = 3
    with pytest.raises(RuntimeError):
        with tracer.lifecycle("checkpoint"):
            raise RuntimeError("boom")
    spans = tracer.snapshot(topic=tracing.LIFECYCLE_TOPIC)
    assert len(spans) == 2
    assert spans[0]["ok"] and spans[0]["rows"] == 3 and spans[0]["wal_seq"] == 7
    assert spans[1]["ok"] is False and "boom" in spans[1]["error"]


def test_brownout_transition_emits_lifecycle_span():
    from opencv_facerecognizer_tpu.runtime.resilience import BrownoutPolicy

    tracer = Tracer(sample=1.0)
    _pipe, _connector, service = _make_service(
        tracer, brownout=BrownoutPolicy(queue_wait_s=0.01, dwell_s=0.0))
    service._note_queue_wait(1.0)  # EWMA over threshold -> level 1
    spans = [s for s in tracer.snapshot(topic=tracing.LIFECYCLE_TOPIC)
             if s["stage"] == "brownout"]
    assert spans and spans[0]["level"] == 1 and spans[0]["from_level"] == 0


# ---- expo endpoint ----


def _get(url):
    with urllib.request.urlopen(url, timeout=5.0) as resp:
        return resp.status, json.loads(resp.read().decode())


def test_expo_endpoint_read_only_contract():
    tracer = Tracer(sample=1.0)
    _pipe, connector, service = _make_service(tracer)
    service.start(warmup=False)
    expo = ExpoServer(service, tracer=tracer, metrics=service.metrics,
                      port=0)
    expo.start()
    base = f"http://{expo.host}:{expo.port}"
    try:
        _drive(connector, 8)
        assert service.drain(timeout=10.0)

        status, index = _get(base + "/")
        assert status == 200 and "/metrics" in index["endpoints"]
        status, metrics = _get(base + "/metrics")
        assert status == 200
        assert metrics["frames_completed"] == 8
        status, ledger = _get(base + "/ledger")
        assert ledger["admitted"] == 8 and ledger["in_system"] == 0
        status, brownout = _get(base + "/brownout")
        assert brownout["level"] == 0
        status, spans = _get(base + f"/spans?topic={FRAME_TOPIC}&n=1000")
        assert {s["stage"] for s in spans["spans"]} \
            == {"receive", "intake", "queue_wait", "settle"}
        # Unknown path -> 404 (the stage-attribution exporter is gone:
        # nothing fed it); every mutating verb -> 405 (read-only).
        assert "/attribution" not in index["endpoints"]
        for path in ("/nope", "/attribution"):
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(base + path)
            assert err.value.code == 404, path
        for method in ("POST", "PUT", "DELETE"):
            req = urllib.request.Request(base + "/metrics", data=b"{}",
                                         method=method)
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=5.0)
            assert err.value.code == 405, method
        assert service.metrics.counter("expo_requests") > 0
    finally:
        expo.stop()
        service.stop()


# ---- leaf spans, parents, profiler annotations, busy-time counters ----

#: children of ``dispatch`` in the order the loop passes them (README
#: "Observability", the table of leaves); ``cascade`` holds the gate's
#: readback, ``stage`` is the instant ingest-provenance span.
#: ``settle_early`` is a child only of a ``dispatch`` that enqueued no
#: step; behind a step it is a root that follows ``dispatch``.
DISPATCH_CHILD_RANK = {"track_cache": 0, "gate_enqueue": 1, "cascade": 2,
                       "compact": 3, "track_miss": 4, "settle_early": 5,
                       "stage": 5, "upload": 6, "step_enqueue": 7}
VIDEO_HW = (32, 32)


class _Notes:
    """Stand-in for ``jax.profiler.TraceAnnotation``: records every
    object constructed and the thread that entered and left it."""

    def __init__(self):
        self.made = []

    def __call__(self, name):
        note = _Note(name)
        self.made.append(note)
        return note


class _Note:
    def __init__(self, name):
        self.name = name
        self.entered = self.left = None

    def __enter__(self):
        self.entered = threading.get_ident()
        return self

    def __exit__(self, *exc):
        self.left = threading.get_ident()
        return False


def _run_video(tracer, monkeypatch=None, n=96):
    """A threaded service with every leaf's machinery on (gate stub,
    track cache, ingest upload), fed two coherent camera streams with
    some face-free frames; returns what a test may look at afterwards."""
    from opencv_facerecognizer_tpu.runtime.fakes import synthetic_video_stream
    from opencv_facerecognizer_tpu.runtime.ingest import IngestConfig
    from opencv_facerecognizer_tpu.runtime.tracker import (
        IdentityTracker,
        TrackerConfig,
    )

    metrics = Metrics()
    pipeline = InstantPipeline(VIDEO_HW, cascade_stub=True, video_oracle=True,
                               compute_s=0.002)
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=4, frame_shape=VIDEO_HW,
        flush_timeout=0.01, inflight_depth=1, similarity_threshold=0.0,
        metrics=metrics, bucket_sizes=(1, 2, 4), cascade=True,
        subject_names=["id0", "id1", "id2", "id3"], tracer=tracer,
        tracker=IdentityTracker(TrackerConfig(reverify_frames=3),
                                metrics=metrics),
        ingest=IngestConfig(mode="uint8"))
    flushes = []
    flush = service._flush_loop_busy

    def recorded_flush(wall, cpu):
        flush(wall, cpu)
        # (instant, the iteration's wall and CPU seconds, the loop
        # thread's CPU clock at that instant)
        flushes.append((time.monotonic(), wall, cpu, time.thread_time()))

    service._flush_loop_busy = recorded_flush
    counters_seen = []
    t_before = time.monotonic()
    service.start(warmup=False)
    t_started = time.monotonic()
    try:
        rows = synthetic_video_stream(n, VIDEO_HW, streams=2, coherence=1.0,
                                      face_density=0.7, seed=3)
        for i, (frame, key, _k) in enumerate(rows):
            connector.inject(FRAME_TOPIC, {"frame": frame,
                                           "meta": {"seq": i, "stream": key}})
            if i % 8 == 7:
                assert service.drain(timeout=20.0)
                counters_seen.append(metrics.counters())
        assert service.drain(timeout=20.0)
    finally:
        threads = {"loop": service._thread.ident,
                   "readback": service._worker.ident,
                   "connector": threading.get_ident()}
        service.stop()
    return {"service": service, "metrics": metrics, "flushes": flushes,
            "counters_seen": counters_seen, "threads": threads,
            "t_before": t_before, "t_started": t_started}


@pytest.fixture(scope="module")
def video_run():
    notes = _Notes()
    saved = tracing._annotation_factory
    tracing._annotation_factory = notes
    try:
        tracer = Tracer(ring_size=1 << 16, sample=1.0)
        run = _run_video(tracer)
    finally:
        tracing._annotation_factory = saved
    run["tracer"], run["notes"] = tracer, notes
    by_trace = {}
    for span in tracer.snapshot(topic=tracing.BATCH_TOPIC):
        by_trace.setdefault(span["trace"], []).append(span)
    run["by_trace"] = by_trace
    return run


def _inside(child, parent, slack=1e-9):
    return (child["t0"] >= parent["t0"] - slack
            and child["t0"] + child["dur"]
            <= parent["t0"] + parent["dur"] + slack)


def _assert_disjoint_in_order(spans):
    spans = sorted(spans, key=lambda s: (s["t0"], s["span"]))
    for a, b in zip(spans, spans[1:]):
        assert a["t0"] + a["dur"] <= b["t0"] + 1e-9, (a, b)
    return spans


def test_dispatch_children_name_it_lie_inside_and_tile_in_order(video_run):
    by_trace = video_run["by_trace"]
    assert len(by_trace) >= 10
    stages_seen, exits, deferred = set(), set(), 0
    for spans in by_trace.values():
        dispatch = [s for s in spans if s["stage"] == "dispatch"]
        assert len(dispatch) == 1
        dispatch = dispatch[0]
        assert dispatch["parent"] == 0
        exits.add(dispatch["exit"])
        ids = {s["span"] for s in spans}
        # every parent resolves to a span of the same trace
        assert all(s["parent"] in ids for s in spans if s["parent"])
        children = [s for s in spans if s["parent"] == dispatch["span"]]
        assert children and all(_inside(c, dispatch) for c in children)
        assert all(c["span"] > dispatch["span"] for c in children)
        ordered = _assert_disjoint_in_order(children)
        ranks = [DISPATCH_CHILD_RANK[c["stage"]] for c in ordered]
        # table B's order; the cache's compact (rank 3) comes before the
        # gate, hence the one allowed step down
        assert ranks == sorted(ranks) or [
            r for r in ranks if r != 3] == sorted(r for r in ranks if r != 3)
        stages_seen.update(c["stage"] for c in children)
        cascade = [c for c in children if c["stage"] == "cascade"]
        for gate in cascade:
            leaves = [s for s in spans if s["parent"] == gate["span"]]
            assert [s["stage"] for s in leaves] == ["gate_wait"]
            assert all(_inside(s, gate) for s in leaves)
        # early exits are published behind the step where there is one
        # (roots after ``dispatch``), in its place where there is none
        settles = [s for s in spans if s["stage"] == "settle_early"]
        for settle in settles:
            if dispatch["exit"] == "full":
                assert settle["parent"] == 0
                assert settle["t0"] >= dispatch["t0"] + dispatch["dur"] - 1e-9
                deferred += settle["frames"]
            else:
                assert settle["parent"] == dispatch["span"]
        if dispatch["exit"] == "full":
            assert {"upload", "step_enqueue"} <= {c["stage"] for c in children}
    assert exits >= {"full"} and len(exits) >= 2
    assert stages_seen == set(DISPATCH_CHILD_RANK)
    assert deferred == video_run["metrics"].counter("early_exits_deferred") > 0


def test_pop_wait_and_inflight_wait_are_roots_around_dispatch(video_run):
    seen_inflight = 0
    for spans in video_run["by_trace"].values():
        by_stage = {}
        for s in spans:
            by_stage.setdefault(s["stage"], []).append(s)
        dispatch = by_stage["dispatch"][0]
        (pop,) = by_stage["pop_wait"]
        assert pop["parent"] == 0
        assert pop["t0"] + pop["dur"] <= dispatch["t0"] + 1e-9
        for wait in by_stage.get("inflight_wait", ()):
            seen_inflight += 1
            assert wait["parent"] == 0
            assert wait["t0"] >= dispatch["t0"] + dispatch["dur"] - 1e-9
        # real starts on one clock: ready_wait begins where dispatch ended
        for ready in by_stage.get("ready_wait", ()):
            assert ready["t0"] == pytest.approx(
                dispatch["t0"] + dispatch["dur"], abs=1e-9)
    assert seen_inflight > 0


def test_publish_children_are_track_updates_of_sampled_frames(video_run):
    frame_traces = {s["trace"] for s in
                    video_run["tracer"].snapshot(topic=FRAME_TOPIC)}
    updates = 0
    for spans in video_run["by_trace"].values():
        for publish in (s for s in spans if s["stage"] == "publish"):
            children = [s for s in spans if s["parent"] == publish["span"]]
            assert all(c["stage"] == "track_update" for c in children)
            assert all(_inside(c, publish) for c in children)
            _assert_disjoint_in_order(children)
            assert all(c["frame"] in frame_traces for c in children)
            assert len(children) == publish["frames"]  # sample=1.0, all named
            updates += len(children)
    assert updates == video_run["metrics"].counter("track_updates") > 0


def test_annotations_are_ocvf_stage_on_the_emitting_thread(video_run):
    notes, threads = video_run["notes"].made, video_run["threads"]
    assert notes and all(n.name.startswith("ocvf:") for n in notes)
    assert all(n.entered is not None and n.entered == n.left for n in notes)
    by_name = {}
    for note in notes:
        by_name.setdefault(note.name[len("ocvf:"):], set()).add(note.entered)
    on_loop = {"pop_wait", "track_cache", "cascade", "gate_enqueue",
               "gate_wait", "compact", "track_miss", "settle_early",
               "upload", "step_enqueue", "inflight_wait"}
    assert set(by_name) == on_loop | {"publish", "track_update", "intake"}
    for stage in on_loop:
        assert by_name[stage] == {threads["loop"]}, stage
    assert by_name["publish"] == by_name["track_update"] == {threads["readback"]}
    assert by_name["intake"] == {threads["connector"]}
    # one annotation per span that ``span()`` opened, none for ``emit``
    opened = sum(1 for topic in video_run["tracer"].topics()
                 for s in video_run["tracer"].snapshot(topic)
                 if s["stage"] in by_name and s["stage"] != "pop_wait")
    assert opened == sum(1 for n in notes if n.name != "ocvf:pop_wait")


def test_busy_counters_registered_monotone_and_tile_the_loops_wall_time(
        video_run):
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    assert mn.LOOP_S_PREFIX in mn.all_prefixes()
    assert {mn.LOOP_BATCHES, mn.PUBLISH_S, mn.PUBLISH_S_TRACK_UPDATE,
            mn.TRACK_UPDATES, mn.INTAKE_S} <= set(mn.all_names())
    metrics = video_run["metrics"]
    final = metrics.counters()
    loop = {k: v for k, v in final.items() if k.startswith(mn.LOOP_S_PREFIX)}
    assert set(loop) == ({mn.LOOP_S_PREFIX + leaf for leaf in mn.LOOP_LEAVES}
                         | {mn.LOOP_S_PREFIX + "unnamed"})
    busy = [k for k in final if k.startswith(mn.LOOP_S_PREFIX)] + [
        mn.LOOP_BATCHES, mn.PUBLISH_S, mn.PUBLISH_S_TRACK_UPDATE,
        mn.TRACK_UPDATES, mn.INTAKE_S]
    seen = video_run["counters_seen"] + [final]
    for before, after in zip(seen, seen[1:]):
        assert all(after.get(k, 0.0) >= before.get(k, 0.0) for k in busy)
    assert all(final[k] > 0 for k in busy if k != mn.LOOP_S_PREFIX + "unnamed")
    # leaves + unnamed == the wall time the loop handed to each flush ...
    flushes = video_run["flushes"]
    assert final[mn.LOOP_BATCHES] == len(flushes)
    walls = sum(flush[1] for flush in flushes)
    assert sum(loop.values()) == pytest.approx(walls, abs=1e-6)
    # ... and the flushes are contiguous from the loop's start to the last
    last = flushes[-1][0]
    assert (last - video_run["t_started"] - 1e-3 <= walls
            <= last - video_run["t_before"] + 1e-3)
    # the parts lie inside the wholes
    assert final[mn.PUBLISH_S_TRACK_UPDATE] <= final[mn.PUBLISH_S]
    assert final[mn.INTAKE_S] > 0 and final["frames_admitted"] == 96


def test_cpu_counters_registered_and_count_each_threads_cpu(video_run):
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    new = {mn.LOOP_CPU_S, mn.PUBLISH_CPU_S, mn.READBACK_CPU_S,
           mn.INTAKE_THREAD_CPU_S, mn.BATCHER_LOCK_WAIT_S,
           mn.BATCHER_LOCK_ACQUIRES, mn.BATCHER_POP_WAIT_S}
    assert new <= set(mn.all_names())
    # no name of the new ones falls into the family ``loop_s_*``
    assert not any(name.startswith(mn.LOOP_S_PREFIX) for name in new)
    final = video_run["metrics"].counters()
    flushes = video_run["flushes"]
    # ``loop_cpu_s`` is the iterations' CPU, flushed beside ``loop_s_*`` ...
    assert final[mn.LOOP_CPU_S] == pytest.approx(
        sum(flush[2] for flush in flushes), rel=1e-9)
    assert all(0.0 <= flush[2] <= flush[1] + 1e-3 for flush in flushes)
    # ... and the iterations are contiguous from the thread's start to the
    # last flush: within 5 % of the thread's own CPU clock read there
    assert final[mn.LOOP_CPU_S] == pytest.approx(flushes[-1][3], rel=0.05)
    # no more CPU than wall time less the waits the loop makes by design
    # (the condition's, inside ``pop_wait``; the fake device has none)
    loop_wall = sum(v for k, v in final.items()
                    if k.startswith(mn.LOOP_S_PREFIX))
    assert final[mn.LOOP_CPU_S] <= (loop_wall - final[mn.BATCHER_POP_WAIT_S]
                                    + 1e-3)
    assert 0.0 < final[mn.BATCHER_POP_WAIT_S] \
        <= final[mn.LOOP_S_PREFIX + "pop_wait"]
    seen = video_run["counters_seen"] + [final]
    for before, after in zip(seen, seen[1:]):
        assert all(after.get(k, 0.0) >= before.get(k, 0.0) for k in new)


@pytest.mark.parametrize("part, whole", [
    ("publish_cpu_s", "publish_s"),
    ("publish_cpu_s", "readback_cpu_s"),
    ("batcher_pop_wait_s", "loop_s_pop_wait"),
])
def test_cpu_sections_lie_inside_their_wall_twins_and_wholes(video_run, part,
                                                             whole):
    final = video_run["metrics"].counters()
    assert 0.0 < final[part] <= final[whole]


def test_intake_threads_cpu_is_read_once_in_a_batchs_worth_of_frames(
        monkeypatch):
    from opencv_facerecognizer_tpu.runtime import recognizer

    _pipe, connector, service = _make_service(None)
    reads = []
    real = time.thread_time

    def counted():
        reads.append(threading.get_ident())
        return real()

    service.start(warmup=False)
    try:
        me = threading.get_ident()
        monkeypatch.setattr(recognizer.time, "thread_time", counted)
        n = 2 * recognizer.INTAKE_CPU_EVERY + 5
        _drive(connector, 1)
        until = real() + 0.02
        while real() < until:  # CPU this thread runs outside the handler
            pass
        _drive(connector, n - 1, start=1)
        monkeypatch.setattr(recognizer.time, "thread_time", real)
        assert service.drain(timeout=20.0)
    finally:
        service.stop()
    # the first frame on a thread reads its clock, then every 128th does
    assert reads.count(me) == 3
    final = service.metrics.counters()
    assert final["frames_admitted"] == n
    # whole-thread CPU: the 20 ms outside the handler are in it
    assert final["intake_thread_cpu_s"] >= 0.02 and final["intake_s"] > 0.0


def test_a_held_batcher_lock_shows_in_its_counter_and_in_intakes_wall_time():
    _pipe, _connector, service = _make_service(None)  # never started
    batcher, metrics = service.batcher, service.metrics
    frame = np.zeros(FRAME_HW, np.float32)
    held = threading.Event()

    def hold():
        with batcher._lock:
            held.set()
            time.sleep(0.05)  # the put below has to see 25 ms of it

    holder = threading.Thread(target=hold)
    holder.start()
    assert held.wait(5.0)
    c0 = time.thread_time()
    service._on_frame(FRAME_TOPIC, {"frame": frame, "meta": {"seq": 0}})
    cpu = time.thread_time() - c0
    holder.join()
    # summed in the batcher's own attributes: no counter call a frame
    assert batcher._lock_wait_s >= 0.025 and batcher._lock_acquires == 1
    assert metrics.counter("batcher_lock_wait_s") == 0.0
    assert metrics.counter("batcher_lock_acquires") == 0.0
    # the wait is wall time of intake and no CPU of its thread
    assert metrics.counter("intake_s") - cpu >= 0.025
    for seq in range(1, 4):
        service._on_frame(FRAME_TOPIC, {"frame": frame, "meta": {"seq": seq}})
    # ... and handed over by the get_batch that pops a batch: its own
    # acquisition with the four puts'
    assert batcher.get_batch(block=False).count == 4
    assert metrics.counter("batcher_lock_wait_s") >= 0.025
    assert metrics.counter("batcher_lock_acquires") == 5
    assert batcher._lock_wait_s == 0.0 and batcher._lock_acquires == 0
    assert batcher.get_batch(block=False) is None  # nothing to hand over
    assert metrics.counter("batcher_lock_acquires") == 5
    assert batcher._lock_acquires == 1  # kept for the next batch


def test_get_batchs_condition_waits_are_counted_apart_from_its_assembly():
    from opencv_facerecognizer_tpu.runtime.batcher import FrameBatcher

    metrics = Metrics()
    batcher = FrameBatcher(4, FRAME_HW, flush_timeout=0.03, metrics=metrics)
    batcher.put(np.zeros(FRAME_HW, np.float32))
    t0 = time.monotonic()
    batch = batcher.get_batch(block=True)  # closes by deadline, 30 ms on
    waited = time.monotonic() - t0
    assert batch.count == 1 and waited >= 0.025
    assert 0.02 <= metrics.counter("batcher_pop_wait_s") <= waited
    # a full batch is popped without a wait
    for _ in range(4):
        batcher.put(np.zeros(FRAME_HW, np.float32))
    before = metrics.counter("batcher_pop_wait_s")
    assert batcher.get_batch(block=True).count == 4
    assert metrics.counter("batcher_pop_wait_s") == before


@pytest.mark.parametrize("work, cpu_bounds", [
    ("sleep", (0.0, 0.005)),
    ("spin", (0.015, 1.0)),
])
def test_the_loops_flush_counts_wall_and_cpu_of_an_iteration(work,
                                                             cpu_bounds):
    """What the serving loop does round an iteration, round 20 ms of
    sleep and of spin: the wall clock reads both, the CPU clock the spin
    alone."""
    _pipe, _connector, service = _make_service(None)  # never started
    t0, c0 = time.monotonic(), time.thread_time()
    with service._leaf("compact"):
        if work == "sleep":
            time.sleep(0.020)
        else:
            until = time.thread_time() + 0.020
            while time.thread_time() < until:
                pass
    c1, t1 = time.thread_time(), time.monotonic()
    service._flush_loop_busy(t1 - t0, c1 - c0)
    final = service.metrics.counters()
    assert final["loop_s_compact"] >= 0.020 and final["loop_batches"] == 1
    assert cpu_bounds[0] <= final["loop_cpu_s"] <= cpu_bounds[1]
    assert final["loop_cpu_s"] <= final["loop_s_compact"] \
        + final["loop_s_unnamed"]


def test_no_annotation_is_constructed_without_a_tracer(monkeypatch):
    notes = _Notes()
    monkeypatch.setattr(tracing, "_annotation_factory", notes)
    run = _run_video(None, n=24)
    assert notes.made == []
    # the busy-time counters run all the same
    assert run["metrics"].counter("loop_batches") == len(run["flushes"]) > 0
    assert run["metrics"].counter("intake_s") > 0


def test_span_with_trace_id_zero_emits_and_annotates_nothing(monkeypatch):
    notes = _Notes()
    monkeypatch.setattr(tracing, "_annotation_factory", notes)
    tracer = Tracer(sample=1.0)
    with tracer.span(0, "compact", parent=7, frames=3) as span:
        assert span is tracing.NULL_SPAN and span.id == 0
        span.attrs["kept"] = 1  # vanishes
    assert tracing.NULL_SPAN.attrs == {}
    assert tracer.snapshot() == [] and notes.made == []
    # sampled in: one annotation, one span, id drawn at entry
    with tracer.span(tracer.new_trace(), "compact", parent=7, frames=3) as span:
        assert span.id > 0 and notes.made[0].entered and not notes.made[0].left
        span.attrs["kept"] = 1
    (emitted,) = tracer.snapshot()
    assert [n.name for n in notes.made] == ["ocvf:compact"]
    assert (emitted["span"], emitted["parent"], emitted["stage"]) \
        == (span.id, 7, "compact")
    assert emitted["frames"] == 3 and emitted["kept"] == 1
    assert emitted["dur"] >= 0 and emitted["t0"] <= time.monotonic()


def test_emit_takes_parent_and_a_predrawn_span_id():
    tracer = Tracer(sample=1.0)
    tid = tracer.new_trace()
    parent_id = tracer.new_span_id()
    tracer.emit(tid, "compact", t0=1.0, dur=0.5, parent=parent_id)
    tracer.emit(tid, "dispatch", t0=0.5, dur=2.0, span_id=parent_id)
    child, parent = tracer.snapshot(topic=tracing.BATCH_TOPIC)
    assert parent["span"] == parent_id < child["span"]
    assert child["parent"] == parent_id and parent["parent"] == 0
    # a body that raises still closes and emits its span
    with pytest.raises(RuntimeError):
        with tracer.span(tid, "upload", parent=parent_id):
            raise RuntimeError("boom")
    assert tracer.snapshot(topic=tracing.BATCH_TOPIC)[-1]["stage"] == "upload"


def test_annotation_without_jax_opens_nothing(monkeypatch):
    monkeypatch.setattr(tracing, "_annotation_factory", False)
    assert tracing.annotation("compact") is tracing.NULL_SPAN
    tracer = Tracer(sample=1.0)
    with tracer.span(tracer.new_trace(), "compact"):
        pass
    assert [s["stage"] for s in tracer.snapshot()] == ["compact"]


def test_annotation_factory_resolves_to_jax_profiler(monkeypatch):
    import jax.profiler

    monkeypatch.setattr(tracing, "_annotation_factory", None)
    note = tracing.annotation("compact")
    assert isinstance(note, jax.profiler.TraceAnnotation)
    assert tracing._annotation_factory is jax.profiler.TraceAnnotation


# ---- Metrics empty/short-window fixes (satellite) ----


def test_metrics_summary_empty_window_reports_nulls():
    metrics = Metrics()
    metrics.observe("queue_wait", 0.005)
    assert metrics.summary()["queue_wait_p50_ms"] == pytest.approx(5.0, rel=0.1)  # histogram bucket precision
    metrics.reset_window("queue_wait")
    summary = metrics.summary()
    # Explicit nulls — never a stale value, a zero, or a KeyError.
    assert summary["queue_wait_p50_ms"] is None
    assert summary["queue_wait_p95_ms"] is None
    assert np.isnan(metrics.percentile("queue_wait", 50))
    # JSON-safe (the expo endpoint serves this dict verbatim).
    json.dumps(summary)


def test_metrics_reset_window_scopes():
    metrics = Metrics()
    metrics.observe("a", 0.001)
    metrics.observe("b", 0.002)
    metrics.incr("frames_completed", 3)
    metrics.reset_window("a")
    summary = metrics.summary()
    assert summary["a_p50_ms"] is None
    assert summary["b_p50_ms"] == pytest.approx(2.0, rel=0.1)  # histogram bucket precision
    metrics.reset_window()
    assert metrics.summary()["b_p50_ms"] is None
    # Counters are untouched by window resets.
    assert metrics.counter("frames_completed") == 3


# ---- journal CLI trace filter (satellite) ----


def test_journal_cli_prints_trace_and_stage(tmp_path, capsys):
    from opencv_facerecognizer_tpu.runtime import journal as journal_mod

    path = str(tmp_path / "dead.jsonl")
    journal = DeadLetterJournal(path)
    journal.append("stale", [journal.frame_entry(
        meta={"seq": 9}, enqueue_ts=1.0, priority=1, trace_id=77,
        stage="batcher.stale")])
    journal.append("dead_letter", [journal.frame_entry(
        meta={"seq": 10}, trace_id=78, stage="readback.dead_letter")],
        dump="/tmp/flight-x.json")
    journal.close()
    assert journal_mod.main([path, "--trace", "78"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 1
    assert lines[0]["frames"][0]["trace_id"] == 78
    assert lines[0]["frames"][0]["stage"] == "readback.dead_letter"
    assert lines[0]["dump"] == "/tmp/flight-x.json"


# ---- span JSONL export ----


def test_span_sink_streams_jsonl(tmp_path):
    from opencv_facerecognizer_tpu.utils.tracing import make_span_journal

    sink = make_span_journal(str(tmp_path / "spans.jsonl"))
    tracer = Tracer(sample=1.0, span_sink=sink)
    tid = tracer.new_trace()
    tracer.emit(tid, "receive", topic="frames", verdict="admitted")
    tracer.emit(tid, "settle", topic="frames", outcome="completed")
    sink.close()
    rows = [json.loads(line) for line in
            (tmp_path / "spans.jsonl").read_text().splitlines()]
    assert [r["stage"] for r in rows] == ["receive", "settle"]
    assert all(r["trace"] == tid and r["topic"] == "frames" for r in rows)

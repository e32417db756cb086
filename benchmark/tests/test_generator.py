"""The sender keeps the queue topped up and never over its mark; a seed
changes content and never the amount of work."""

import json
import os
import threading
import time

import pytest

from benchmark import traffic_gen

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def _params(name):
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as fh:
        return json.load(fh)


class FakeBatcher:
    """A queue with the batcher's rule: a put at the limit is an overflow;
    a consumer pops ``batch`` frames every ``every`` seconds."""

    def __init__(self, limit, batch, every):
        self.limit, self.batch, self.every = limit, batch, every
        self.queue, self.lock = [], threading.Lock()
        self.overflow = 0
        self.pops = []  # queue length found at each pop
        self.on_pop = None
        self.halt = False

    def put(self, message):
        with self.lock:
            if len(self.queue) >= self.limit:
                self.overflow += 1
                return
            self.queue.append(message["meta"]["seq"])

    def depth(self):
        with self.lock:
            return len(self.queue)

    def consume(self):
        while not self.halt:
            time.sleep(self.every)
            with self.lock:
                self.pops.append(len(self.queue))
                del self.queue[:self.batch]
            if self.on_pop:
                self.on_pop()


def test_sender_keeps_queue_between_its_marks():
    traffic = traffic_gen.Traffic(_params("replay"), 5, (256, 256))
    batcher = FakeBatcher(limit=256, batch=128, every=0.01)
    sender = traffic_gen.BacklogSender(traffic, batcher.put, batcher.depth,
                                       target=248)
    batcher.on_pop = sender.note_pop
    consumer = threading.Thread(target=batcher.consume, daemon=True)
    sender.start()
    time.sleep(0.2)  # first fill
    consumer.start()
    time.sleep(1.0)
    batcher.halt = True
    consumer.join(timeout=5)
    sender.stop()
    assert not sender.is_alive() and not consumer.is_alive()
    assert batcher.overflow == 0
    assert sender.max_depth_seen <= 248
    assert len(batcher.pops) > 20
    # every pop found a full batch waiting: batches close by size
    assert min(batcher.pops[1:]) >= 128
    sent = sender.next_index
    assert sorted(set(range(sent))) == list(range(sent))  # frames in order, none twice


@pytest.mark.parametrize("name", ["crowd", "replay"])
def test_seed_changes_content_not_work(name):
    a = traffic_gen.Traffic(_params(name), 11, (256, 256))
    b = traffic_gen.Traffic(_params(name), 2_900_000_123, (256, 256))
    assert sorted(a.face_counts) == sorted(b.face_counts)
    for start in (0, 1280, 12800):
        ca, cb = a.census(start, start + 6400), b.census(start, start + 6400)
        assert ca["with_faces"] == cb["with_faces"]  # exact under any seed
    # The multiset of faces per frame is exact per block of scenes; how a
    # window of a run's length cuts dwells into blocks moves each count by
    # about a per cent.
    ca, cb = a.census(4000, 62000), b.census(4000, 62000)
    for n in ca["faces_per_frame"]:
        assert abs(ca["faces_per_frame"][n] - cb["faces_per_frame"][n]) \
            <= 0.02 * ca["faces_per_frame"][n]
    # per batch of 128 the number of face frames differs by at most one
    per_batch = {a.census(i * 128, (i + 1) * 128)["with_faces"] for i in range(40)}
    per_batch |= {b.census(i * 128, (i + 1) * 128)["with_faces"] for i in range(40)}
    assert max(per_batch) - min(per_batch) <= 1
    # and the content does differ
    assert any((a.frames[k] != b.frames[k]).any() for k in a.frames)


def test_crowd_never_repeats_a_frame_on_a_stream():
    t = traffic_gen.Traffic(_params("crowd"), 3, (256, 256))
    streams = t.params["streams"]
    for i in range(4 * streams * 10):
        assert t.schedule.lookup(i)[1] != t.schedule.lookup(i + streams)[1]

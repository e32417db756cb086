"""The wire form of a frame (``runtime.connector.encode_frame`` /
``decode_frame``): the native base64 decoder of ``utils.native`` against
the standard library's, which stays the reference — same array for every
well-formed message, same array or same exception class for every
malformed one, with the library present and with it made unavailable."""

import base64
import json
import os
import sys
import threading

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime.connector import (
    FakeConnector,
    decode_frame,
    decode_frame_counted,
    encode_frame,
)
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    RecognizerService,
)
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils import native

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(33)


def _standard(obj):
    """The parent's ``decode_frame``, line for line: the reference."""
    raw = base64.b64decode(obj["__frame__"])
    return np.frombuffer(raw, dtype=np.dtype(obj["dtype"])).reshape(
        obj["shape"]).copy()


def _outcome(decode, obj):
    try:
        return decode(obj), None
    except Exception as e:  # noqa: BLE001 — the class IS what is compared
        return None, type(e)


@pytest.fixture(params=["native", "absent"])
def library(request, monkeypatch):
    """Both decoders behind the one ``decode_frame``: the library as built
    here, and made unavailable (no compiler on the machine)."""
    if request.param == "absent":
        monkeypatch.setattr(native, "_lib", lambda: None)
        assert not native.b64_available()
    elif not native.b64_available():
        pytest.skip("native loader unavailable (no g++?)")
    return request.param


def _random(dtype, shape):
    """Random BYTES under the dtype (every bit pattern, NaNs included:
    results are compared byte for byte)."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    return np.frombuffer(RNG.bytes(n), dtype).reshape(shape).copy()


# Byte counts that leave 0, 1 and 2 modulo 3 (no '=', '==', '=') for every
# dtype, the benchmark's frame, and arrays with nothing in them.
ROUNDTRIP_CASES = [
    ("uint8", (3, 4)), ("uint8", (2, 5)), ("uint8", (1, 11)),
    ("uint16", (3,)), ("uint16", (5, 7)), ("uint16", (2, 2)),
    ("float32", (3,)), ("float32", (1,)), ("float32", (2, 1)),
    ("uint8", (256, 256)), ("float32", (96, 96)), ("float64", (3,)),
    (">u2", (4, 3)), ("bool", (7,)),
    ("uint8", (0,)), ("float32", (0, 5)),
]


@pytest.mark.parametrize("dtype,shape", ROUNDTRIP_CASES)
def test_roundtrip_is_exact_and_owned(library, dtype, shape):
    a = _random(dtype, shape)
    message = encode_frame(a)
    assert message["__frame__"].count("=") == (-a.nbytes) % 3
    got, was_native = decode_frame_counted(message)
    assert was_native == (library == "native")
    assert got.dtype == a.dtype and got.shape == a.shape
    assert got.tobytes() == a.tobytes()
    assert got.flags.writeable and got.flags.c_contiguous
    assert got.flags.owndata and got.base is None
    # And through the JSON the wire carries (str, list, str).
    again = decode_frame(json.loads(json.dumps(message)))
    assert again.tobytes() == a.tobytes() and again.shape == a.shape


def _frame_text(a):
    return base64.b64encode(a.tobytes()).decode("ascii")


_A = _random("uint8", (5, 23))          # 115 bytes: 156 characters, one '='
_B = _random("float32", (6,))           # 24 bytes: no padding
_MESSAGE = {"shape": [5, 23], "dtype": "uint8"}

MALFORMED_CASES = {
    # the standard decoder drops what is outside the alphabet, and padding
    # it has no use for: same array
    "line_wrapped": {**_MESSAGE,
                     "__frame__": base64.encodebytes(_A.tobytes()).decode()},
    "stray_character": {**_MESSAGE, "__frame__":
                        _frame_text(_A)[:8] + "!" + _frame_text(_A)[8:]},
    "stray_quad": {**_MESSAGE, "__frame__":
                   _frame_text(_A)[:8] + "-_.\n" + _frame_text(_A)[8:]},
    "trailing_newline": {**_MESSAGE, "__frame__": _frame_text(_A) + "\n"},
    "bytes_not_str": {**_MESSAGE, "__frame__": _frame_text(_A).encode()},
    "shape_minus_one": {"__frame__": _frame_text(_A), "shape": [-1, 23],
                        "dtype": "uint8"},
    "shape_tuple": {"__frame__": _frame_text(_A), "shape": (5, 23),
                    "dtype": "uint8"},
    "shape_int": {"__frame__": _frame_text(_A), "shape": 115,
                  "dtype": "uint8"},
    "trailing_bits_set": {"__frame__": "QR==", "shape": [1],
                          "dtype": "uint8"},
    "padding_doubled": {**_MESSAGE, "__frame__": _frame_text(_A) + "="},
    "all_padding": {"__frame__": "====", "shape": [0], "dtype": "uint8"},
    # ... and raises for what it cannot make sense of: same class
    "padding_then_more": {"__frame__": "QQ==QUJD", "shape": [4],
                          "dtype": "uint8"},
    "character_replaced": {**_MESSAGE, "__frame__":
                           _frame_text(_A)[:8] + "!" + _frame_text(_A)[9:]},
    "padding_stripped": {**_MESSAGE,
                         "__frame__": _frame_text(_A).rstrip("=")},
    "padding_inside": {**_MESSAGE, "__frame__":
                       _frame_text(_A)[:6] + "==" + _frame_text(_A)[8:]},
    "payload_short": {"__frame__": _frame_text(_A[:4]), "shape": [5, 23],
                      "dtype": "uint8"},
    "payload_long": {"__frame__": _frame_text(_A), "shape": [4, 23],
                     "dtype": "uint8"},
    "itemsize_mismatch": {"__frame__": _frame_text(_A), "shape": [5, 23],
                          "dtype": "uint16"},
    "faults_corrupt": {"__frame__": "corrupt!", "shape": [1],
                       "dtype": "float32"},
    "not_ascii": {**_MESSAGE, "__frame__": _frame_text(_A)[:-4] + "éabc"},
    "text_is_none": {**_MESSAGE, "__frame__": None},
    "no_dtype": {"__frame__": _frame_text(_B), "shape": [6]},
    "no_shape": {"__frame__": _frame_text(_B), "dtype": "float32"},
    "no_frame": {"shape": [6], "dtype": "float32"},
    "dtype_unknown": {"__frame__": _frame_text(_B), "shape": [6],
                      "dtype": "float33"},
    "dtype_object": {"__frame__": _frame_text(_B), "shape": [3],
                     "dtype": "object"},
    "dtype_subarray": {"__frame__": _frame_text(_B), "shape": [3],
                       "dtype": "(2,)f4"},
    "shape_of_floats": {"__frame__": _frame_text(_B), "shape": [6.0],
                        "dtype": "float32"},
    "shape_of_bools": {"__frame__": _frame_text(_B)[:8], "shape": [True, 6],
                       "dtype": "uint8"},
    "corrupt_and_no_shape": {"__frame__": "corrupt!", "dtype": "float32"},
}


@pytest.mark.parametrize("case", sorted(MALFORMED_CASES))
def test_malformed_message_is_the_standard_decoders(library, case):
    message = MALFORMED_CASES[case]
    want, want_error = _outcome(_standard, message)
    got, got_error = _outcome(decode_frame, message)
    assert got_error is want_error
    if want_error is None:
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        assert got.flags.writeable and got.flags.owndata


def test_native_decoder_declines_what_is_not_canonical():
    """The strict half of the contract, at the binding: anything but
    alphabet plus correct padding at the declared size reads False, so the
    lenient decoder gets the message."""
    if not native.b64_available():
        pytest.skip("native loader unavailable (no g++?)")
    text = _frame_text(_A).encode()
    out = np.empty(_A.shape, np.uint8)
    assert native.b64_decode_into(text, out) and np.array_equal(out, _A)
    for bad in (text[:8] + b"\n" + text[8:], text[:8] + b"=" + text[9:],
                text[:-1], text[:-1] + b"A", text[:9] + b"\xff" + text[10:],
                text[:8] + b"-" + text[9:], text + b"AAAA", text[4:]):
        assert not native.b64_decode_into(bad, out), bad
    assert not native.b64_decode_into(text, out[:, ::2])   # not contiguous
    locked = np.empty(_A.shape, np.uint8)
    locked.flags.writeable = False
    assert not native.b64_decode_into(text, locked)
    small = np.empty(_A.size - 1, np.uint8)                # decoded > capacity
    assert not native.b64_decode_into(text, small)


def test_two_threads_decode_different_frames_at_once(library):
    """The call releases the interpreter's lock: two decodes do overlap,
    and each writes only the array it was given."""
    frames = [_random("uint8", (256, 256)), _random("uint16", (64, 37))]
    messages = [encode_frame(f) for f in frames]
    wrong, errors = [0, 0], []
    start = threading.Barrier(2)

    def worker(i):
        try:
            start.wait(timeout=10)
            for _ in range(150):
                got = decode_frame(messages[i])
                if got.tobytes() != frames[i].tobytes():
                    wrong[i] += 1
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and wrong == [0, 0]


def test_service_counts_native_decodes_beside_admissions(library):
    """Wire-form frames through ``_on_frame``: ``frames_decoded_native``
    rises to ``frames_admitted`` where the library serves (0 where it is
    absent, and the frames are served all the same); a corrupt frame is
    ``frames_malformed`` and no native decode; a raw ``frame`` has no wire
    form to decode."""
    from opencv_facerecognizer_tpu.runtime.fakes import InstantPipeline

    frame_hw = (16, 16)
    connector = FakeConnector()
    service = RecognizerService(
        InstantPipeline(frame_hw), connector, batch_size=4,
        frame_shape=frame_hw, flush_timeout=0.05, similarity_threshold=0.0)
    service.start(warmup=False)
    try:
        for i in range(6):
            connector.inject(FRAME_TOPIC, {
                **encode_frame(_random("uint8", frame_hw)),
                "meta": {"frame_id": i}})
        counters = service.metrics.counters()
        assert counters[mn.FRAMES_ADMITTED] == 6
        assert counters.get(mn.FRAMES_DECODED_NATIVE, 0) == (
            6 if library == "native" else 0)
        connector.inject(FRAME_TOPIC, {
            "__frame__": "corrupt!", "shape": [1], "dtype": "float32"})
        connector.inject(FRAME_TOPIC, {
            "frame": np.zeros(frame_hw, np.float32), "meta": {"frame_id": 6}})
        assert service.drain(timeout=20.0)
    finally:
        service.stop()
    counters = service.metrics.counters()
    assert counters[mn.FRAMES_ADMITTED] == 8
    assert counters[mn.FRAMES_MALFORMED] == 1
    assert counters.get(mn.FRAMES_DECODED_NATIVE, 0) == (
        6 if library == "native" else 0)
    ledger = service.ledger()
    assert ledger["completed"] == 7 and ledger["in_system"] == 0


def test_native_decode_share_reads_two_registered_counters():
    """``benchmark/layer_metrics/native_decode_share.backlog.json``: parses,
    names the reader ``counter_ratio``, and divides two counters the
    registry has — the one the service increments where it decodes over
    the one beside it."""
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as fh:
        declared = [m for m in json.load(fh)["per_layer"]
                    if m["name"] == "native_decode_share.backlog"]
    assert len(declared) == 1 and "workloads" not in declared[0]
    assert declared[0]["layer"] == "connector / intake"
    assert declared[0]["source"] == "program_counter"
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "native_decode_share.backlog.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "counter_ratio"
    assert os.path.exists(os.path.join(
        REPO_ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    registered = {v for k, v in vars(mn).items()
                  if k.isupper() and isinstance(v, str)}
    assert spec["numerator"] == [mn.FRAMES_DECODED_NATIVE]
    assert spec["denominator"] == [mn.FRAMES_ADMITTED]
    assert set(spec["numerator"] + spec["denominator"]) <= registered

"""A configuration, a traffic mix and a layer metric added as new files only
(no file of the benchmark edited), one cell of them run on the CPU; the same
run with the timed path broken underneath comes out not correct; the control
(the reference one precision step lower, in the program's place) comes out
not correct; and so does each fault of ``plants.py`` planted in sound
results. Rehearsal only: no device metric is read from these."""

import json
import os

import pytest

from benchmark.tests import rehearse

ARGV = ["--workload", "tiny.trickle", "--seed", "2999000001", "--seconds", "2",
        "--trace", "0"]

#: the answer altered where it is produced: every similarity the step
#: returns comes back 0.2 higher
BROKEN_STEP = '''
from opencv_facerecognizer_tpu.parallel import pipeline as _pipeline
_unpack = _pipeline.unpack_result
def _shifted(packed, top_k):
    r = _unpack(packed, top_k)
    return r._replace(similarities=r.similarities + 0.2)
_pipeline.unpack_result = _shifted
'''

#: the gate's verdict thrown away: every frame is settled as empty
BROKEN_GATE = '''
import numpy as _np
from opencv_facerecognizer_tpu.runtime import recognizer as _recognizer
_recognizer.RecognizerService._cascade_keep_mask = (
    lambda self, frames, count, batch_tid: _np.zeros((count,), bool))
'''


#: a face lost where it is produced: the first slot of every frame comes
#: back not valid
BROKEN_DETECT = '''
from opencv_facerecognizer_tpu.parallel import pipeline as _pipeline
_unpack = _pipeline.unpack_result
def _one_lost(packed, top_k):
    r = _unpack(packed, top_k)
    valid = r.valid.copy()
    valid[..., 0] = False
    return r._replace(valid=valid)
_pipeline.unpack_result = _one_lost
'''

#: the track cache answers with an identity nobody verified: every label
#: it serves is row 2000 of the rehearsal gallery (3 enrolled + 1000 + 2000)
BROKEN_CACHE = '''
from opencv_facerecognizer_tpu.runtime import tracker as _tracker
_lookup = _tracker.IdentityTracker.lookup
def _stranger(self, *a, **kw):
    hit = _lookup(self, *a, **kw)
    if hit is not None:
        hit = dict(hit, faces=[dict(f, label=3003) for f in hit["faces"]])
    return hit
_tracker.IdentityTracker.lookup = _stranger
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearse.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_new_cell_from_new_files_only_runs_and_is_correct(copy):
    rc, result, err = rehearse.run_cell(copy, ARGV)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"served_fps", "setup_s"}
    assert list(result)[-1] == "compared"
    assert all(v <= lim for v, lim in result["compared"].values())
    assert result["device"]["platform"] == "cpu"  # a rehearsal, not a measurement
    # the numbers compared are the last lines of standard error
    tail = [ln for ln in err.splitlines() if ln.strip()][-len(result["compared"]):]
    assert all(ln.startswith("compared ") for ln in tail)


def test_traced_run_reports_the_new_layer_metric(copy):
    rc, result, err = rehearse.run_cell(
        copy, ARGV[:-1] + ["1"], patch=rehearse.CPU_TRACE_PATCH)
    assert rc == 0, err[-3000:]
    assert "settled_share.rehearsal" in result["metrics"]
    assert "batch_fill_share.backlog" in result["metrics"]
    # no Pallas kernel and no TPU plane on a CPU: these readers return nothing
    assert "streaming_match_topk_roofline.backlog" not in result["metrics"]
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0


STILL = ["--workload", "tiny.still"] + ARGV[2:]


def test_cache_replies_are_compared_and_correct(copy):
    rc, result, err = rehearse.run_cell(copy, STILL)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny.still.seed2999000001.trace0.json")) as fh:
        detail = json.load(fh)
    assert detail["judged"]["sampled"]["3"] > 0  # cache replies in the sample
    assert detail["ledger_end"]["completed_cached"] > 0


@pytest.mark.parametrize("argv,patch,failing", [
    (ARGV, BROKEN_STEP, "sim_err"), (ARGV, BROKEN_GATE, "gate_gap"),
    (ARGV, BROKEN_DETECT, "det_miss"), (STILL, BROKEN_CACHE, "cached_strangers")])
def test_broken_timed_path_is_not_correct(copy, argv, patch, failing):
    rc, result, err = rehearse.run_cell(copy, argv, patch=patch)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    value, limit = result["compared"][failing]
    assert value > limit


def test_control_and_plants_are_not_correct(copy):
    """At a size a test run can hold: the reference with fp8 convolutions
    and an int8 gallery in the program's place does not pass, and neither
    does the reference's own output with a fault of ``plants.py`` in it
    (a cache reply is made of a full result, since a reference has no
    cache). The reference against itself passes."""
    code = rehearse.DRIVER.format(root=copy, repo=rehearse.REPO, argv=[], patch='''
import json, numpy as np
from benchmark import check, traffic_gen, window
from benchmark.stacks import recognize
from benchmark.tests import plants
config = json.load(open("benchmark/configs/tiny.json"))
traffic = traffic_gen.Traffic(json.load(open("benchmark/traffic/trickle.json")), 77, (64, 64))
nets = recognize.ensure_nets(config, lambda m: None)
rows = recognize.reference_rows(config, 77)
rng = np.random.default_rng(1)
from benchmark import render
images = np.concatenate([np.floor(render.render_enrolment(i, (32, 32), 2, rng))
                         for i in traffic.enrolled_identities()])
labels = np.repeat(np.arange(3), 2)
frames = {i: traffic.frame_of(i) for i in range(0, 64, 2)}
module = window.load_reference(config)
high = module.Reference(nets["dir"], (32, 32))
limits = window.load_limits(config)
wanted = {1: 99, 2: 99, 3: 99}

def judge(results, first_full):
    sample = check.draw_sample(1, results, wanted)
    numbers, _seen = check.compare(high, rows, 1024, images, labels, 1003, 0.3,
                                   frames, results, sample, limits["far"], first_full)
    return check.verdict(numbers, limits)

out = {}
for name, lower in (("sound", None), ("control", "nets+gallery")):
    ref = high if lower is None else module.Reference(nets["dir"], (32, 32), lower=lower)
    out[name] = judge(check.publish_like(ref, rows, 1024, images, labels, 1003,
                                         0.3, frames), {})
# sound results in which every fourth full result with a face is a cache
# reply of a stream whose earlier full results carried its identities
sound = check.publish_like(high, rows, 1024, images, labels, 1003, 0.3, frames)
first_full, n = {"cam": {}}, 0
for seq in sorted(sound):
    m = sound[seq]
    m["meta"]["stream"] = "cam"
    if not m["faces"]:
        continue
    known = all(f["label"] in first_full["cam"] for f in m["faces"])
    n += 1
    if known and n % 2 == 0:
        m["exit"] = "track_cache"
    else:
        for f in m["faces"]:
            first_full["cam"].setdefault(f["label"], seq)
out["sound_with_cache"] = judge(sound, first_full)
sample = check.draw_sample(1, sound, wanted)
out["cache_replies"] = len(sample[3])
for name, plant in plants.PLANTS.items():
    planted = plant(sound, sample, 5, first_full=first_full, label_offset=1003,
                    rows=4096, enrolled=len(labels), frame_size=(64, 64))
    out["plant_" + name] = judge(planted, first_full)
print(json.dumps(out))
sys.exit(0)
''')
    import subprocess
    import sys

    from benchmark.tests import plants

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(copy, ".jax_cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True          # the reference agrees with itself
    assert out["sound_with_cache"][0] is True, out["sound_with_cache"]
    assert out["cache_replies"] >= 2
    assert out["control"][0] is False       # one step lower does not pass
    assert [k for k, (v, lim) in out["control"][1].items() if v > lim], out
    failures = {}
    for name, must in plants.HAS_TO_FAIL.items():
        ok, table = out["plant_" + name]
        over = {k for k, (v, lim) in table.items() if v > lim}
        if ok or not over & set(must):
            failures[name] = table
    assert not failures, failures

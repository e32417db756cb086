"""The ``watchlist48m-tp4`` configuration's own files, rehearsed at CPU size
on four virtual devices: the sharded stack and the reference that walks the
shards run a cell that is correct; with the timed path broken the four ways
``test_rehearsal_cell.py`` breaks it, and with one shard of the watchlist
lost, it is not; a program that would serve the watchlist by another matcher
than the configuration's is refused before anything is built; and the fill
and the reference draw the same rows, shard by shard, no device ever holding
the whole. On a CPU the program selects its XLA matcher, so the rehearsal
steers the selection to the kernel (interpret mode) from the test, the way
``tests/test_parallel.py`` does through ``use_pallas``. Rehearsal only: no
device metric is read from these. Run the file by itself, without xdist beside
it: the windows are 2 s, and on a CPU that four other rehearsals load they
finish a dozen faces, too few for the sample to hold one of every shard (the
lost-shard cases then have nothing to lose and come out correct)."""

import json
import os
import shutil

import pytest

from benchmark.tests import rehearse
from benchmark.tests.test_rehearsal_cell import (
    BROKEN_CACHE, BROKEN_DETECT, BROKEN_GATE, BROKEN_STEP)

ARGV = ["--workload", "tiny-tp4.trickle", "--seed", "2999000001", "--seconds", "2",
        "--trace", "0"]
STILL = ["--workload", "tiny-tp4.still"] + ARGV[2:]

#: what a mesh of TPU chips selects at a watchlist's size, on the CPU: the
#: kernel on every shard, under shard_map, in interpret mode
KERNEL = '''
from opencv_facerecognizer_tpu.parallel.gallery import ShardedGallery as _Gallery
_Gallery._pallas_enabled = lambda self, capacity=None: True
'''

#: a shard lost: the rows of shard {lost} are installed not valid, as if
#: that chip's fill had never landed. The enrolled subjects' rows lie in
#: shard 0; what the others hold that any face needs is the planted rows
LOST_SHARD = KERNEL + '''
import jax.numpy as _jnp
_install = _Gallery.install_device_rows
def _without_a_shard(self, embeddings, labels, valid, size):
    shard = embeddings.shape[0] // self.mesh.shape["tp"]
    valid = _jnp.where(_jnp.arange(valid.shape[0]) // shard == {lost}, False, valid)
    return _install(self, embeddings, labels, valid, size)
_Gallery.install_device_rows = _without_a_shard
'''

#: the exchange between the chips left out: every chip merges its own
#: shard's candidates alone, and the result read is the first chip's
NO_EXCHANGE = KERNEL + '''
import jax as _jax
_jax.lax.all_gather = lambda x, *args, **kwargs: x
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``rehearse.make_copy`` plus this configuration's rehearsal size, as
    new files and new entries; four virtual devices for every run of it."""
    root = rehearse.make_copy(str(tmp_path_factory.mktemp("bench_tp4")))
    for name in ("tiny-tp4.json", "tiny-tp4.limits.json"):
        target = os.path.join(root, "benchmark", "configs", name)
        assert not os.path.exists(target)
        shutil.copy(os.path.join(rehearse.FIXTURES, name), target)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tiny-tp4", "source": "rehearsal",
                             "file": "benchmark/configs/tiny-tp4.json",
                             "reduced": [], "why": "rehearsal"})
    cells = ["tiny-tp4.trickle", "tiny-tp4.still"]
    for cell in cells:
        bench["workloads"].append({"name": cell, "config": "tiny-tp4",
                                   "traffic": cell.split(".")[1], "chips": 4,
                                   "why": "rehearsal"})
    for metric in bench["per_layer"]:
        if "watchlist48m-tp4.crowd" in metric["workloads"]:
            metric["workloads"] += cells
    with open(path, "w") as fh:
        json.dump(bench, fh)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    yield root
    os.environ["XLA_FLAGS"] = flags


def test_sharded_cell_runs_on_four_devices_and_is_correct(copy):
    rc, result, err = rehearse.run_cell(copy, ARGV, patch=KERNEL)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] >= 4
    assert "4 shard(s) of 1024 rows" in err
    assert "exact Pallas streaming kernel [current]" in err
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny-tp4.trickle.seed2999000001.trace0.json")) as fh:
        detail = json.load(fh)
    counters = detail["counters_window"]
    assert detail["judged"]["sampled"]["1"] > 0
    assert counters.get("recompiles_post_warmup", 0) == 0
    assert counters["bench_backend_compiles"] == 0


def test_traced_sharded_cell_reports_the_match_and_merge_scopes(copy):
    rc, result, err = rehearse.run_cell(
        copy, ARGV[:-1] + ["1"], patch=KERNEL + rehearse.CPU_TRACE_PATCH)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]
    # the CPU's trace names no scope and no kernel: the readers return
    # nothing and the line leaves the metrics out, as on a parent commit
    assert "batch_fill_share.backlog" in result["metrics"]
    assert "match_device_ms.backlog" not in result["metrics"]


@pytest.mark.parametrize("argv,patch,failing", [
    (ARGV, BROKEN_STEP, "sim_err"), (ARGV, BROKEN_GATE, "gate_gap"),
    (ARGV, BROKEN_DETECT, "det_miss"), (STILL, BROKEN_CACHE, "cached_strangers"),
    (ARGV, LOST_SHARD.format(lost=0), "match_gap_far"),
    (ARGV, LOST_SHARD.format(lost=1), "match_gap_far"),
    (ARGV, LOST_SHARD.format(lost=2), "match_gap_far"),
    (ARGV, LOST_SHARD.format(lost=3), "match_gap_far"),
    (ARGV, NO_EXCHANGE, "match_gap_far")])
def test_broken_sharded_path_is_not_correct(copy, argv, patch, failing):
    """The last five are what a sharded watchlist adds: any one shard
    lost, and the exchange over tp left out, each read by the limits the
    cell is held to (the planted rows are what makes them read)."""
    rc, result, err = rehearse.run_cell(copy, argv, patch=KERNEL + patch)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    value, limit = result["compared"][failing]
    assert value > limit
    if failing == "match_gap_far":
        value, limit = result["compared"]["match_gap"]
        assert value > limit


def test_a_program_that_selects_another_matcher_is_refused_at_once(copy):
    """What the parent commit does on four chips (and this program on a
    CPU, unsteered): it would serve the watchlist by its XLA matcher, the
    stack says so and stops before a gallery, a net or a compile."""
    rc, result, err = rehearse.run_cell(copy, ARGV)
    assert rc != 0 and result is None
    assert "serves 4096 rows by its 'xla' matcher" in err
    assert "Nothing was built" in err
    assert "nets:" not in err  # stopped before the stack


def test_fill_and_reference_draw_the_same_rows_shard_by_shard(copy):
    code = rehearse.DRIVER.format(root=copy, repo=rehearse.REPO, argv=[], patch='''
import json, numpy as np, jax, jax.numpy as jnp
from benchmark.stacks import recognize_sharded as stack
from opencv_facerecognizer_tpu.parallel import ShardedGallery
config = json.load(open("benchmark/configs/tiny-tp4.json"))
mesh = stack.shard_mesh(config)
gallery = ShardedGallery(capacity=4096, dim=64, mesh=mesh, store_dtype=jnp.bfloat16)
head = np.random.default_rng(0).normal(size=(6, 64)).astype(np.float32)
gallery.add(head, np.arange(6, dtype=np.int32))
try:
    stack.reference_rows(config, 77)
    refused = False
except RuntimeError:
    refused = True  # no stack built for the seed: no planted rows to put in
at = np.array([9, 1023, 1024, 2500, 4095])
plants = np.random.default_rng(1).normal(size=(5, 64)).astype(np.float32)
plants = np.asarray(jnp.asarray(plants / np.linalg.norm(plants, axis=1, keepdims=True)).astype(jnp.bfloat16).astype(jnp.float32))
stack._PLANTS[77] = stack._PLANTS[78] = (at, plants)
kept = stack.fill_gallery(gallery, 77, config, 1003)
again = stack.reference_rows(config, 77)
other = stack.reference_rows(config, 78)
data = gallery.data
served, drawn = np.asarray(data.embeddings), np.asarray(again)
plain = np.asarray(stack.make_sharded_rows(77, 4096, 64, 256, mesh, 128))
one = np.asarray(stack.make_sharded_rows(77, 4096, 64, 256, stack.shard_mesh({"devices": 1}), 128, (at, plants)))
rest = np.setdiff1d(np.arange(4096), at)
stack._PLANTS[79] = (np.array([3]), plants[:1])
try:
    stack.fill_gallery(gallery, 79, config, 1003)
    among_enrolled = False
except RuntimeError:
    among_enrolled = True
print(json.dumps({
    "refused": refused, "among_enrolled": among_enrolled,
    "kept": kept, "size": int(data.size), "bulk": gallery.bulk_installs,
    "uploaded": gallery.rows_uploaded,
    "equal_past_head": bool((served[6:].view(np.uint16) == drawn[6:].view(np.uint16)).all()),
    "planted_served": bool((served[at].astype(np.float32) == plants).all()),
    "only_the_places_differ": bool((drawn[rest].view(np.uint16) == plain[rest].view(np.uint16)).all()
                                   and (drawn[at] != plain[at]).any(axis=1).all()),
    "head_kept": bool(np.allclose(served[:6].astype(np.float32),
                                  head / np.linalg.norm(head, axis=1, keepdims=True), atol=1e-2)),
    "seed_matters": bool((drawn[rest] != np.asarray(other)[rest]).any()),
    "same_on_one_device": bool((one.view(np.uint16) == drawn.view(np.uint16)).all()),
    "unit": float(np.abs(np.linalg.norm(drawn.astype(np.float32), axis=1) - 1).max()),
    "labels": [int(v) for v in np.asarray(data.labels)[[0, 5, 6, 4095]]],
    "valid": bool(np.asarray(data.valid).all()),
    "shards": sorted({tuple(s.data.shape) for a in (data.embeddings, again)
                      for s in a.addressable_shards}),
    "devices": sorted({s.device.id for s in again.addressable_shards}),
    "mirror_rows": int(len(gallery._host_emb)),
}))
sys.exit(0)
''')
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["kept"] == 6 and out["size"] == 4096 and out["valid"]
    assert out["equal_past_head"] and out["head_kept"] and out["seed_matters"]
    # the planted rows lie at their places, first and last row of a shard
    # among them, in the fill and in the reference's rows alike, and
    # nothing else moved; without a stack's planted rows nothing is drawn,
    # and a place among the enrolled subjects' rows is refused
    assert out["planted_served"] and out["only_the_places_differ"]
    assert out["refused"] and out["among_enrolled"]
    # a row is a function of the seed and its block alone: one device
    # draws what four do
    assert out["same_on_one_device"]
    assert out["unit"] < 1e-2
    assert out["labels"] == [0, 5, 1003 + 6, 1003 + 4095]
    # every shard is a quarter, on a device of its own; nothing whole
    assert out["shards"] == [[1024, 64]] and len(out["devices"]) == 4
    # installed from device arrays: the 6 enrolled rows are all that ever
    # crossed the link, and the host mirrors none of the watchlist
    assert out["bulk"] == 1 and out["uploaded"] == 6 and out["mirror_rows"] == 0


def _chip_shards(copy, variants, patch=KERNEL):
    import subprocess
    import sys

    code = rehearse.DRIVER.format(root=copy, repo=rehearse.REPO, argv=[], patch=patch + f'''
run.device_gate = gate
from benchmark.tests import chip_shards
sys.exit(chip_shards.main(["shards_rehearsal", "tiny-tp4.trickle", "2", "2999000001"] + {variants!r}))
''')
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(copy, ".jax_cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=900)
    records = [json.loads(ln) for ln in proc.stdout.strip().splitlines()
               if ln.startswith("{")]
    return proc, records[-len(variants):]


def test_chip_shards_reads_a_lost_shard_as_not_correct(copy):
    """``chip_shards.py`` (the builder's chip run) at rehearsal size: the
    program as it is comes out correct, every served row the reference's
    best row; with shard 2 cleared, which holds none of the enrolled rows,
    the cell is not correct by the limits it is held to, the faces whose
    best row lies there lose it, and only those; the shard put back, it is
    correct again, with nothing compiled on the way."""
    proc, (sound, lost, again) = _chip_shards(copy, ["sound", "lost:2", "sound"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert sound["correct"] and sound["faces"] >= 8
    assert sound["agree"] == sound["faces"], sound
    assert set(sound["by_shard"]) == {"0", "1", "2", "3"}  # a best row on every shard
    # the control and the plants that alter a full result fail over the
    # same window (trickle has no cache reply for the other two to alter)
    for key in ("control", "plant_drop_face", "plant_invent_face", "plant_wrong_row_8th"):
        assert not sound["in_its_place"][key]["correct"], key
    assert not lost["correct"] and lost["window_compiles"] == 0
    for key in ("match_gap", "match_gap_far", "sim_err"):
        value, limit = lost["compared"][key]
        assert value > limit, (key, lost["compared"])
    there = lost["by_shard"]["2"]
    assert there[0] > 0 and there[1] == 0, lost
    assert lost["faces"] - lost["agree"] == there[0], lost
    assert again["correct"] and again["agree"] == again["faces"]


def test_chip_shards_reads_a_left_out_exchange_as_not_correct(copy):
    """The merge without its ``all_gather``: the first chip's answer is all
    that is read, and every face whose best row lies on another is wrong."""
    proc, (record,) = _chip_shards(copy, ["no_exchange"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert not record["correct"]
    value, limit = record["compared"]["match_gap_far"]
    assert value > limit
    first = record["by_shard"].get("0", [0, 0])
    assert first[1] == first[0]
    assert all(record["by_shard"][s][1] == 0 for s in ("1", "2", "3")), record

"""Sharded gallery + mesh tests on the 8-virtual-device CPU backend
(SURVEY.md §7.7: N-way CPU-simulated device tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS

RNG = np.random.default_rng(17)


def _unit(v):
    return v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-12)


def _brute_force_topk(queries, gallery, labels, k):
    sims = _unit(queries) @ _unit(gallery).T
    idx = np.argsort(-sims, axis=1)[:, :k]
    return labels[idx], np.take_along_axis(sims, idx, axis=1)


def test_make_mesh_factorizations():
    assert make_mesh().shape == {DP_AXIS: 1, TP_AXIS: 8}
    assert make_mesh(dp=2).shape == {DP_AXIS: 2, TP_AXIS: 4}
    assert make_mesh(tp=2).shape == {DP_AXIS: 4, TP_AXIS: 2}
    assert make_mesh(dp=8, tp=1).shape == {DP_AXIS: 8, TP_AXIS: 1}
    with pytest.raises(ValueError):
        make_mesh(dp=3)
    with pytest.raises(ValueError):
        make_mesh(dp=2, tp=2)


@pytest.mark.parametrize("dp,tp", [(1, 8), (2, 4), (8, 1)])
def test_sharded_match_equals_bruteforce(dp, tp):
    mesh = make_mesh(dp=dp, tp=tp)
    gal_emb = RNG.normal(size=(64, 16)).astype(np.float32)
    gal_labels = RNG.integers(0, 10, size=64).astype(np.int32)
    g = ShardedGallery(capacity=64, dim=16, mesh=mesh)
    g.add(gal_emb, gal_labels)
    queries = _unit(RNG.normal(size=(8, 16)).astype(np.float32))
    for k in (1, 3):
        labels, sims, idx = (np.asarray(v) for v in g.match(queries, k=k))
        want_labels, want_sims = _brute_force_topk(queries, gal_emb, gal_labels, k)
        np.testing.assert_allclose(sims, want_sims, atol=2e-2)  # bf16 matmul
        # labels can differ at near-ties under bf16; require match on clear wins
        clear = (want_sims[:, :1] - want_sims[:, -1:]) > 0.05 if k > 1 else np.ones((8, 1), bool)
        np.testing.assert_array_equal(labels[:, 0][clear[:, 0]], want_labels[:, 0][clear[:, 0]])


def test_gallery_partial_fill_and_masking():
    mesh = make_mesh(tp=8)
    g = ShardedGallery(capacity=30, dim=8, mesh=mesh)  # rounds up to 32
    assert g.capacity == 32
    emb = RNG.normal(size=(5, 8)).astype(np.float32)
    labels = np.arange(5, dtype=np.int32)
    g.add(emb, labels)
    q = _unit(emb)
    got_labels, sims, idx = (np.asarray(v) for v in g.match(q, k=1))
    np.testing.assert_array_equal(got_labels[:, 0], labels)
    assert np.all(idx < 5)  # never matches an invalid padded row


def test_gallery_overflow_auto_grows():
    # Overflow no longer raises: capacity doubles (tp-aligned) and the
    # enrolment lands (see test_connectors.py for the full growth suite).
    mesh = make_mesh(tp=8)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh)
    g.add(RNG.normal(size=(8, 4)).astype(np.float32), np.arange(8, dtype=np.int32))
    g.add(RNG.normal(size=(1, 4)).astype(np.float32), np.array([9], dtype=np.int32))
    assert g.grow_count == 1
    assert g.size == 9
    assert g.capacity == 16 and g.capacity % 8 == 0


def test_gallery_incremental_enrolment():
    mesh = make_mesh(tp=4, dp=2)
    g = ShardedGallery(capacity=16, dim=8, mesh=mesh)
    e1 = RNG.normal(size=(4, 8)).astype(np.float32)
    e2 = RNG.normal(size=(4, 8)).astype(np.float32)
    g.add(e1, np.zeros(4, dtype=np.int32))
    g.add(e2, np.ones(4, dtype=np.int32))
    assert g.size == 8
    labels, _, _ = (np.asarray(v) for v in g.match(_unit(e2)[:2], k=1))
    np.testing.assert_array_equal(labels[:, 0], [1, 1])


def test_double_buffered_swap():
    mesh = make_mesh(tp=8)
    live = ShardedGallery(capacity=8, dim=4, mesh=mesh)
    live.add(_unit(RNG.normal(size=(4, 4)).astype(np.float32)), np.zeros(4, np.int32))
    staged = ShardedGallery(capacity=8, dim=4, mesh=mesh)
    new_emb = _unit(RNG.normal(size=(6, 4)).astype(np.float32))
    staged.add(new_emb, np.full(6, 7, np.int32))
    live.swap_from(staged)
    assert live.size == 6
    labels, _, _ = (np.asarray(v) for v in live.match(new_emb[:1], k=1))
    assert labels[0, 0] == 7


def test_query_count_must_divide_dp():
    mesh = make_mesh(dp=4, tp=2)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh)
    g.add(RNG.normal(size=(4, 4)).astype(np.float32), np.arange(4, dtype=np.int32))
    with pytest.raises(ValueError, match="divisible"):
        g.match(np.zeros((3, 4), dtype=np.float32), k=1)


def test_gallery_pallas_path_matches_gspmd():
    """use_pallas=True (interpret mode off-TPU) must agree with the GSPMD
    matcher — the auto fast path may silently switch between them on
    hardware, so they have to be interchangeable."""
    rng = np.random.default_rng(17)
    emb = rng.normal(size=(96, 16)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    labels = rng.integers(0, 12, size=96)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)

    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (DP_AXIS, TP_AXIS))
    outs = {}
    for use_pallas in (False, True):
        g = ShardedGallery(capacity=128, dim=16, mesh=mesh,
                           use_pallas=use_pallas)
        g.add(emb, labels)
        lab, sims, idx = (np.asarray(v) for v in g.match(q, k=3))
        outs[use_pallas] = (lab, sims, idx)
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][2], outs[True][2])
    np.testing.assert_allclose(outs[False][1], outs[True][1], atol=1e-2)


def test_gallery_pallas_autodetect_off_on_cpu():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (DP_AXIS, TP_AXIS))
    g = ShardedGallery(capacity=1 << 17, dim=8, mesh=mesh)
    assert not g._pallas_enabled()  # CPU backend: stays on GSPMD


@pytest.mark.parametrize("dp,tp", [(4, 2), (2, 4), (1, 8)])
def test_pod_pallas_matcher_matches_gspmd(dp, tp):
    """shard_map + per-shard pallas streaming kernel + collective merge
    (the multi-chip pallas formulation) must agree with match_global."""
    from opencv_facerecognizer_tpu.parallel.gallery import (
        match_global, match_pod_pallas)

    mesh = make_mesh(dp=dp, tp=tp)
    rng = np.random.default_rng(23)
    cap = 128
    emb = rng.normal(size=(cap, 16)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    valid = np.ones(cap, bool)
    valid[100:] = False
    labels = rng.integers(0, 20, size=cap).astype(np.int32)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)

    args = (jnp.asarray(q), jnp.asarray(emb), jnp.asarray(valid),
            jnp.asarray(labels))
    with mesh:
        pod = match_pod_pallas(*args, k=3, mesh=mesh, interpret=True)
    ref = match_global(*args, k=3, mesh=mesh)
    for a, b in zip(pod, ref):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b, atol=1e-2)
        else:
            np.testing.assert_array_equal(a, b)


def test_pod_pallas_matcher_sparse_shards():
    """Startup regime: fewer valid rows than k on most shards — sentinel
    indices must stay -1 (masked), not alias a neighbor shard's rows."""
    from opencv_facerecognizer_tpu.parallel.gallery import match_pod_pallas

    mesh = make_mesh(dp=1, tp=8)
    rng = np.random.default_rng(5)
    cap = 64  # 8 rows/shard
    emb = np.zeros((cap, 8), np.float32)
    valid = np.zeros(cap, bool)
    labels = np.full(cap, -1, np.int32)
    emb[0] = rng.normal(size=8)
    emb[0] /= np.linalg.norm(emb[0])
    valid[0] = True
    labels[0] = 7
    q = np.tile(emb[0], (8, 1))
    with mesh:
        lab, sims, idx = (np.asarray(v) for v in match_pod_pallas(
            jnp.asarray(q), jnp.asarray(emb), jnp.asarray(valid),
            jnp.asarray(labels), k=3, mesh=mesh, interpret=True))
    # best hit is the one real row
    assert (idx[:, 0] == 0).all() and (lab[:, 0] == 7).all()
    # everything else is masked: sentinel index, -inf-ish score
    assert (idx[:, 1:] == -1).all(), idx
    assert (sims[:, 1:] < -1e29).all()


def test_sentinel_slots_carry_pad_label():
    """Sentinel -1 indices must surface the PAD label even when rows 0 and
    capacity-1 hold real subjects — a clamped/wrapped gather would pair a
    real subject's label with the -1e30 sentinel sim (round-2 advisor
    finding: direct gallery.match() callers got a plausible wrong label)."""
    from opencv_facerecognizer_tpu.parallel.gallery import match_pod_pallas

    rng = np.random.default_rng(5)
    cap = 64
    emb = np.zeros((cap, 8), np.float32)
    valid = np.zeros(cap, bool)
    labels = np.full(cap, -1, np.int32)
    # real subjects at the exact rows a clamp (0) or wrap (-1 -> last row)
    # would alias onto
    for row, lab in ((0, 3), (cap - 1, 9)):
        v = rng.normal(size=8).astype(np.float32)
        emb[row] = v / np.linalg.norm(v)
        valid[row] = True
        labels[row] = lab
    q = np.tile(emb[0], (8, 1))

    # pod shard_map form (interpret mode on the CPU mesh)
    mesh = make_mesh(dp=1, tp=8)
    with mesh:
        lab, sims, idx = (np.asarray(v) for v in match_pod_pallas(
            jnp.asarray(q), jnp.asarray(emb), jnp.asarray(valid),
            jnp.asarray(labels), k=4, mesh=mesh, interpret=True))
    sentinel = idx == -1
    assert sentinel.any()
    assert (lab[sentinel] == -1).all(), lab
    assert set(lab[~sentinel].ravel()) <= {3, 9}

    # single-device pallas fast path via gallery.match_fn
    from jax.sharding import Mesh

    mesh1 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                 (DP_AXIS, TP_AXIS))
    g = ShardedGallery(capacity=cap, dim=8, mesh=mesh1, use_pallas=True)
    g.add(emb[valid], labels[valid])
    lab, sims, idx = (np.asarray(v) for v in g.match(np.asarray(q), k=4))
    sentinel = idx == -1
    assert sentinel.any()
    assert (lab[sentinel] == g.labels_pad).all(), lab


def test_initialize_multihost_single_process_noop(monkeypatch):
    from opencv_facerecognizer_tpu.parallel.mesh import initialize_multihost

    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    # no coordinator configured -> graceful single-process no-op
    assert initialize_multihost() is False
    # devices still visible, meshes still build
    assert make_mesh().devices.size == len(jax.devices())


def test_initialize_multihost_env_and_args(monkeypatch):
    from opencv_facerecognizer_tpu.parallel import mesh as mesh_mod

    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
    # env-var path
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "2")
    assert mesh_mod.initialize_multihost() is True
    assert calls[-1] == {"coordinator_address": "10.0.0.1:1234",
                         "num_processes": 4, "process_id": 2}
    # explicit args trigger initialization even without env
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        monkeypatch.delenv(var)
    assert mesh_mod.initialize_multihost(num_processes=8, process_id=3) is True
    assert calls[-1] == {"coordinator_address": None,
                         "num_processes": 8, "process_id": 3}
    # already-initialized short circuit
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)
    n = len(calls)
    assert mesh_mod.initialize_multihost() is True
    assert len(calls) == n


def test_gallery_async_grow_never_blocks_and_lands_rows():
    """async_grow: an overflowing add() returns immediately (rows staged),
    the background worker compiles the next tier via prewarm_hooks BEFORE
    installing, and the rows become matchable after wait_ready()."""
    import threading

    mesh = make_mesh(tp=4)
    g = ShardedGallery(capacity=16, dim=8, mesh=mesh, async_grow=True)
    warmed = []
    hook_thread = []

    def hook(capacity):
        warmed.append(capacity)
        hook_thread.append(threading.current_thread().name)

    g.prewarm_hooks.append(hook)
    e = RNG.normal(size=(16, 8)).astype(np.float32)
    g.add(e, np.arange(16, dtype=np.int32))
    assert g.size == 16 and g.pending_rows == 0  # fits: synchronous path
    e2 = RNG.normal(size=(8, 8)).astype(np.float32)
    g.add(e2, np.arange(16, 24, dtype=np.int32))  # overflows -> staged
    assert g.wait_ready(timeout=30)
    assert g.pending_rows == 0
    assert g.size == 24
    assert g.capacity == 32
    assert g.grow_count == 1
    assert warmed == [32]
    assert hook_thread and hook_thread[0] != threading.main_thread().name
    # staged rows are matchable post-install
    q = e2 / np.linalg.norm(e2, axis=-1, keepdims=True)
    labels, _, _ = (np.asarray(v) for v in g.match(q, k=1))
    np.testing.assert_array_equal(labels[:, 0], np.arange(16, 24))


def test_gallery_async_grow_absorbs_adds_during_grow():
    """Adds arriving while a grow is in flight are staged and spliced into
    the same (or a follow-up) install — none are lost, order preserved."""
    import threading

    mesh = make_mesh(tp=2)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh, async_grow=True)
    slow = threading.Event()

    def slow_hook(capacity):
        slow.wait(5)  # hold the grow so follow-up adds land in pending

    g.prewarm_hooks.append(slow_hook)
    g.add(RNG.normal(size=(8, 4)).astype(np.float32),
          np.arange(8, dtype=np.int32))
    g.add(RNG.normal(size=(4, 4)).astype(np.float32),
          np.arange(8, 12, dtype=np.int32))  # overflow -> worker starts
    g.add(RNG.normal(size=(4, 4)).astype(np.float32),
          np.arange(12, 16, dtype=np.int32))  # lands mid-grow
    assert g.pending_rows == 8
    slow.set()
    assert g.wait_ready(timeout=30)
    assert g.size == 16
    assert np.array_equal(np.asarray(g.labels)[:16], np.arange(16))


def test_gallery_reset_cancels_inflight_grow():
    mesh = make_mesh(tp=2)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh, async_grow=True)
    import threading

    hold = threading.Event()
    g.prewarm_hooks.append(lambda cap: hold.wait(5))
    g.add(RNG.normal(size=(8, 4)).astype(np.float32),
          np.arange(8, dtype=np.int32))
    g.add(RNG.normal(size=(4, 4)).astype(np.float32),
          np.arange(8, 12, dtype=np.int32))
    g.reset()  # bump epoch: the in-flight grow must not resurrect rows
    hold.set()
    assert g.wait_ready(timeout=30)
    assert g.size == 0
    assert g.pending_rows == 0


def test_gallery_async_grow_normalizes_on_worker_and_waits_residency():
    """add() stages RAW rows — the enrolling thread pays no normalization
    (measured 16 s for 920k rows on a 1-core host); the worker normalizes
    before splicing, waits for device residency BEFORE the atomic publish
    (so the first new-tier serving call doesn't absorb the gallery H2D),
    and records the phase decomposition in last_grow_info."""
    mesh = make_mesh(tp=2)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh, async_grow=True)
    g.add(np.full((8, 4), 7.0, np.float32), np.arange(8, dtype=np.int32))
    raw = np.full((8, 4), 5.0, np.float32)  # deliberately unnormalized
    g.add(raw, np.arange(8, 16, dtype=np.int32))  # overflow -> staged raw
    raw[:] = -3.0  # caller reuses its buffer: staging must have copied
    assert g.wait_ready(timeout=30)
    assert g.size == 16 and g.pending_rows == 0
    # every landed row is unit-norm even though the add staged raw rows
    norms = np.linalg.norm(g._host_emb[:16], axis=-1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    # ...and holds the values STAGED, not the caller's later mutation
    np.testing.assert_allclose(g._host_emb[8:16], 0.5, rtol=1e-5)
    info = g.last_grow_info
    assert "normalize_s" in info and "upload_wait_s" in info
    assert "install_s" in info and not info.get("residency_timeout")
    # the published device snapshot is the residency-checked one
    np.testing.assert_allclose(np.asarray(g.data.embeddings)[:16],
                               g._host_emb[:16], rtol=1e-6)


def test_gallery_async_grow_chunked_upload_path():
    """The grow worker uploads the staged rows in paced pieces of at most
    CHUNK_UPLOAD_BYTES, spliced on the device into the next tier (which
    already holds the served rows, copied there on the device) — several
    pieces forced here via an instance-level chunk-size override — and
    the published snapshot is identical to the host mirror."""
    import jax

    mesh = make_mesh(dp=1, tp=1, devices=jax.devices()[:1])
    g = ShardedGallery(capacity=32, dim=16, mesh=mesh, async_grow=True)
    g.CHUNK_UPLOAD_BYTES = 1024  # 16 rows/chunk: several chunks at 96 rows
    g.add(RNG.normal(size=(32, 16)).astype(np.float32),
          np.arange(32, dtype=np.int32))
    g.add(RNG.normal(size=(64, 16)).astype(np.float32) * 11.0,
          np.arange(32, 96, dtype=np.int32))  # overflow -> chunked upload
    assert g.wait_ready(timeout=60)
    assert g.size == 96 and g.capacity == 128
    np.testing.assert_allclose(np.asarray(g.data.embeddings)[:96],
                               g._host_emb[:96], rtol=1e-6)
    assert np.array_equal(np.asarray(g.data.labels)[:96], np.arange(96))
    assert not g.last_grow_info.get("error")
    # all rows matchable through the sharded matcher
    q = g._host_emb[40:44]
    labels, _, _ = (np.asarray(v) for v in g.match(q, k=1))
    np.testing.assert_array_equal(labels[:, 0], np.arange(40, 44))


def test_gallery_bf16_store_matches_f32():
    """store_dtype=bfloat16 halves gallery HBM/upload bytes and must be
    numerically interchangeable on the match path: both matchers already
    compute the similarity matmul as bf16 x bf16 -> f32, so a bf16-stored
    gallery changes only WHERE the cast happens (enrolment vs per call)."""
    import jax.numpy as jnp

    mesh = make_mesh(tp=4)
    emb = RNG.normal(size=(64, 16)).astype(np.float32)
    lab = np.arange(64, dtype=np.int32)
    q = emb[10:20] / np.linalg.norm(emb[10:20], axis=-1, keepdims=True)
    results = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        g = ShardedGallery(capacity=64, dim=16, mesh=mesh, store_dtype=dtype)
        g.add(emb, lab)
        assert g.data.embeddings.dtype == dtype
        labels, sims, idx = (np.asarray(v) for v in g.match(q, k=3))
        results[str(dtype)] = (labels, sims)
    (l32, s32), (l16, s16) = results.values()
    np.testing.assert_array_equal(l32, l16)
    np.testing.assert_allclose(s32, s16, atol=2e-3)
    # grow path keeps the dtype (incl. the chunked branch on 1-device)
    import jax

    g1 = ShardedGallery(capacity=16, dim=16,
                        mesh=make_mesh(dp=1, tp=1, devices=jax.devices()[:1]),
                        store_dtype=jnp.bfloat16, async_grow=True)
    g1.CHUNK_UPLOAD_BYTES = 512
    g1.add(emb[:16], lab[:16])
    g1.add(emb[16:], lab[16:])  # overflow -> chunked bf16 upload
    assert g1.wait_ready(timeout=30)
    assert g1.size == 64 and g1.data.embeddings.dtype == jnp.bfloat16
    labels, _, _ = (np.asarray(v) for v in g1.match(q, k=1))
    np.testing.assert_array_equal(labels[:, 0], np.arange(10, 20))


def test_gallery_async_grow_failed_upload_restores_rows_and_retries():
    """If the upload dies AFTER the splice popped entries off pending, the
    worker must restore them (pending_rows stays truthful, enrolment order
    kept) and the next add() retries the grow successfully."""
    mesh = make_mesh(tp=2)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh, async_grow=True)
    g.add(RNG.normal(size=(8, 4)).astype(np.float32),
          np.arange(8, dtype=np.int32))

    real_build = g._build_snapshot
    calls = {"n": 0}

    def dying_build(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("device RPC died mid-upload")
        return real_build(*a, **k)

    g._build_snapshot = dying_build
    g.add(RNG.normal(size=(4, 4)).astype(np.float32),
          np.arange(8, 12, dtype=np.int32))  # overflow -> worker dies
    assert g.wait_ready(timeout=30)
    assert "error" in g.last_grow_info
    assert g.pending_rows == 4  # restored, not lost
    assert g.size == 8  # nothing published from the failed round
    # next add restarts the worker; BOTH batches land, in order
    g.add(RNG.normal(size=(4, 4)).astype(np.float32),
          np.arange(12, 16, dtype=np.int32))
    assert g.wait_ready(timeout=30)
    assert g.pending_rows == 0 and g.size == 16
    assert np.array_equal(np.asarray(g.labels)[:16], np.arange(16))


@pytest.mark.parametrize("store_dtype", ["float32", "bfloat16"])
def test_pipeline_prewarm_registers_and_compiles_future_tier(store_dtype):
    """RecognitionPipeline registers a prewarm hook; after an async grow
    the serving-path cache already holds the new tier's packed step (keyed
    exactly as the post-grow lookup) and serving output stays correct.
    Parametrized over the gallery store dtype: the prewarm scratch arrays
    must match it — an f32 scratch on a bf16 gallery warms an executable
    serving never hits (aval mismatch -> post-grow serving retrace)."""
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    import jax
    import jax.numpy as jnp

    mesh = make_mesh(dp=2, tp=4)
    g = ShardedGallery(capacity=32, dim=16, mesh=mesh, async_grow=True,
                       store_dtype=getattr(jnp, store_dtype))
    emb = RNG.normal(size=(32, 16)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    g.add(emb, np.arange(32, dtype=np.int32))

    det = CNNFaceDetector(features=(8, 8), head_features=8, max_faces=2,
                          score_threshold=0.0, space_to_depth=2)
    det.load_params(det.net.init(jax.random.PRNGKey(0),
                                 np.zeros((1, 64, 64)))["params"])
    net = FaceEmbedNet(embed_dim=16, stem_features=8, stage_features=(8,),
                       stage_blocks=(1,))
    emb_params = init_embedder(net, num_classes=4, input_shape=(32, 32),
                               seed=0)["net"]
    pipe = RecognitionPipeline(det, net, emb_params, g, face_size=(32, 32),
                               top_k=1)
    assert pipe.prewarm_capacity in g.prewarm_hooks
    frames = make_synthetic_scenes(4, (64, 64), max_faces=2, seed=5)[0]
    out0 = np.asarray(pipe.recognize_batch_packed(frames))

    g.add(RNG.normal(size=(40, 16)).astype(np.float32),
          np.arange(32, 72, dtype=np.int32))  # overflow -> async grow
    assert g.wait_ready(timeout=60)
    assert g.capacity == 128
    key = pipe._step_key(pipe._as_device_frames(frames), g.data)
    assert key[4] == 128  # capacity baked into the serving cache key
    assert key in pipe._packed_cache  # prewarmed BEFORE the swap published
    # BOTH executables are warm: recognize_batch (unpacked) must not pay a
    # first-call compile after the grow either (ADVICE r4).
    assert key in pipe._step_cache
    warmed = pipe._packed_cache[key]
    before = warmed._cache_size() if hasattr(warmed, "_cache_size") else None
    out1 = np.asarray(pipe.recognize_batch_packed(frames))
    assert out1.shape == out0.shape
    if before is not None:
        # The serving call must HIT the prewarmed executable, not trace a
        # second one (e.g. scratch-vs-gallery dtype aval mismatch).
        assert warmed._cache_size() == before, (
            "post-grow serving call retraced the prewarmed step")


def test_step_key_derives_from_snapshot_not_live_gallery():
    """The serving cache key must come from the SAME GalleryData snapshot
    the call feeds: a grow installing between the snapshot read and a
    separate gallery.capacity read would otherwise pair a stale key with
    new-tier arrays (ADVICE r4 pipeline._step_key)."""
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline

    import jax

    mesh = make_mesh(dp=2, tp=4)
    g = ShardedGallery(capacity=16, dim=16, mesh=mesh)
    det = CNNFaceDetector(features=(8, 8), head_features=8, max_faces=2,
                          score_threshold=0.0, space_to_depth=2)
    det.load_params(det.net.init(jax.random.PRNGKey(0),
                                 np.zeros((1, 64, 64)))["params"])
    net = FaceEmbedNet(embed_dim=16, stem_features=8, stage_features=(8,),
                       stage_blocks=(1,))
    emb_params = init_embedder(net, num_classes=4, input_shape=(32, 32),
                               seed=0)["net"]
    pipe = RecognitionPipeline(det, net, emb_params, g, face_size=(32, 32))
    old_data = g.data  # reader's snapshot, taken pre-grow
    emb = RNG.normal(size=(40, 16)).astype(np.float32)
    g.add(emb, np.arange(40, dtype=np.int32))  # sync grow: 16 -> 64
    assert g.capacity == 64
    frames = jnp.zeros((2, 64, 64), jnp.float32)
    # Key from the OLD snapshot names the OLD tier even though the live
    # gallery has moved on — snapshot and key can never mix tiers.
    assert pipe._step_key(frames, old_data)[4] == 16
    assert pipe._step_key(frames, g.data)[4] == 64


def test_grow_evicts_tiers_older_than_previous():
    """Growing A->B->C drops tier-A compiled entries from the gallery match
    cache and registered pipelines (B survives for in-flight readers):
    without eviction, crossing many tiers retains every executable forever
    (ADVICE r4 gallery._match_cache)."""
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline

    import jax

    mesh = make_mesh(dp=2, tp=4)
    g = ShardedGallery(capacity=16, dim=16, mesh=mesh)
    det = CNNFaceDetector(features=(8, 8), head_features=8, max_faces=2,
                          score_threshold=0.0, space_to_depth=2)
    det.load_params(det.net.init(jax.random.PRNGKey(0),
                                 np.zeros((1, 64, 64)))["params"])
    net = FaceEmbedNet(embed_dim=16, stem_features=8, stage_features=(8,),
                       stage_blocks=(1,))
    emb_params = init_embedder(net, num_classes=4, input_shape=(32, 32),
                               seed=0)["net"]
    pipe = RecognitionPipeline(det, net, emb_params, g, face_size=(32, 32))
    assert pipe.evict_below in g.evict_hooks

    emb = RNG.normal(size=(8, 16)).astype(np.float32)
    g.add(emb, np.arange(8, dtype=np.int32))
    frames = np.zeros((2, 64, 64), np.float32)
    pipe.recognize_batch(frames)  # compile at tier 16
    g.match(jnp.asarray(emb[:4]), k=1)  # matcher cache entry at tier 16
    assert any(k[4] == 16 for k in pipe._step_cache)
    assert any(k[1] == 16 for k in g._match_cache)

    g.add(RNG.normal(size=(16, 16)).astype(np.float32),
          np.arange(8, 24, dtype=np.int32))  # grow 16 -> 32 (B)
    # previous tier (16) must SURVIVE the first grow (in-flight readers)
    assert any(k[4] == 16 for k in pipe._step_cache)
    pipe.recognize_batch(frames)  # compile at tier 32
    g.add(RNG.normal(size=(24, 16)).astype(np.float32),
          np.arange(24, 48, dtype=np.int32))  # grow 32 -> 64 (C)
    # tier 16 evicted everywhere; tier 32 (previous) survives
    assert not any(k[4] == 16 for k in pipe._step_cache)
    assert not any(k[4] == 16 for k in pipe._packed_cache)
    assert not any(k[1] == 16 for k in g._match_cache)
    assert any(k[4] == 32 for k in pipe._step_cache)
    # serving still correct at the new tier
    out = pipe.recognize_batch(frames)
    assert np.asarray(out.labels).shape == (2, 2, 1)


def test_gallery_async_grow_copies_staged_labels():
    """The staged path must copy LABELS too, not just embeddings: asarray
    of an int32 input is a no-copy view, and the worker splices seconds
    after add() returns — a caller reusing its label buffer would enroll
    wrong identities (round-5 advisor)."""
    import threading

    mesh = make_mesh(tp=2)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh, async_grow=True)
    hold = threading.Event()
    g.prewarm_hooks.append(lambda cap: hold.wait(5))
    g.add(RNG.normal(size=(8, 4)).astype(np.float32),
          np.arange(8, dtype=np.int32))
    label_buf = np.arange(8, 12, dtype=np.int32)  # int32: asarray is a view
    g.add(RNG.normal(size=(4, 4)).astype(np.float32), label_buf)
    label_buf[:] = 99  # caller reuses its buffer while the grow is held
    hold.set()
    assert g.wait_ready(timeout=30)
    assert g.size == 12
    np.testing.assert_array_equal(np.asarray(g.labels)[8:12],
                                  np.arange(8, 12))


def test_pace_chunk_per_chunk_deadline_and_timeout_flag():
    """_pace_chunk (the chunked-upload pacer): a chunk that never lands
    gives up at ITS deadline and records info['chunk_pacing_timeout'] so
    grow artifacts surface the degraded (unpaced) window; a ready chunk
    paces clean; a backend without is_ready stops pacing silently."""
    import time as _time

    class _Never:
        def is_ready(self):
            return False

    class _Ready:
        def is_ready(self):
            return True

    info = {}
    t0 = _time.monotonic()
    assert not ShardedGallery._pace_chunk(_Never(), _time.monotonic() + 0.1,
                                          info=info)
    assert info.get("chunk_pacing_timeout") is True
    assert _time.monotonic() - t0 < 5.0  # per-chunk deadline, not residency's
    info = {}
    assert ShardedGallery._pace_chunk(_Ready(), _time.monotonic() + 0.1,
                                      info=info)
    assert "chunk_pacing_timeout" not in info
    # cancelled wait: returns immediately (doomed snapshot), no flag
    assert ShardedGallery._pace_chunk(_Never(), _time.monotonic() + 10.0,
                                      cancel=lambda: True, info=info)
    assert "chunk_pacing_timeout" not in info
    # no is_ready: pacing impossible, not degraded — no flag
    assert not ShardedGallery._pace_chunk(object(), _time.monotonic() + 10.0,
                                          info=info)
    assert "chunk_pacing_timeout" not in info


def test_gallery_swap_from_casts_store_dtype():
    """A store_dtype mismatch on swap_from is CAST at install, not
    rejected: the documented retrain -> reload_gallery handoff stages at
    the trainer's f32 default while serving defaults to bf16 (round-5
    advisor). The installed snapshot carries the SERVING gallery's dtype,
    so compiled cache keys (capacity-keyed) never alias."""
    mesh = make_mesh(tp=4)
    serving = ShardedGallery(capacity=16, dim=8, mesh=mesh,
                             store_dtype=jnp.bfloat16)
    staged = ShardedGallery(capacity=16, dim=8, mesh=mesh)  # f32 default
    emb = _unit(RNG.normal(size=(6, 8)).astype(np.float32))
    staged.add(emb, np.full(6, 3, np.int32))
    serving.swap_from(staged)
    assert serving.size == 6
    assert serving.data.embeddings.dtype == jnp.bfloat16
    labels, sims, _ = (np.asarray(v) for v in serving.match(emb[:2], k=1))
    np.testing.assert_array_equal(labels[:, 0], [3, 3])
    assert (sims[:, 0] > 0.99).all()


def test_gallery_snapshot_roundtrip_bf16_from_f32_checkpoint():
    """Satellite (state-lifecycle PR): snapshot()/load_snapshot()
    round-trip across a store_dtype boundary — an f32 trainer gallery's
    host-mirror snapshot (what a durable checkpoint persists) installs
    into a bf16 serving gallery at the SERVING width (the swap_from cast
    path, via the restore route this time), with match parity."""
    mesh = make_mesh(tp=4)
    trainer = ShardedGallery(capacity=16, dim=8, mesh=mesh)  # f32 default
    emb = _unit(RNG.normal(size=(6, 8)).astype(np.float32))
    trainer.add(emb, np.arange(6, dtype=np.int32))
    snap = trainer.snapshot()
    serving = ShardedGallery(capacity=16, dim=8, mesh=mesh,
                             store_dtype=jnp.bfloat16)
    serving.load_snapshot(*snap)
    assert serving.size == 6
    assert serving.data.embeddings.dtype == jnp.bfloat16  # serving width
    assert serving._host_emb.dtype == np.float32  # host truth stays f32
    l32, s32, i32 = (np.asarray(v) for v in trainer.match(emb, k=1))
    l16, s16, i16 = (np.asarray(v) for v in serving.match(emb, k=1))
    np.testing.assert_array_equal(l32, l16)
    np.testing.assert_array_equal(i32, i16)
    np.testing.assert_allclose(s32, s16, atol=2e-2)  # bf16 matmul on both


def test_gallery_load_snapshot_restores_last_known_good():
    """load_snapshot (the supervisor's restore path): rows added after the
    snapshot are rolled back, the host mirrors are private copies of the
    snapshot arrays, and any in-flight async grow is invalidated."""
    mesh = make_mesh(tp=8)
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh)
    emb = _unit(RNG.normal(size=(4, 4)).astype(np.float32))
    g.add(emb, np.arange(4, dtype=np.int32))
    snap = g.snapshot()
    g.add(_unit(RNG.normal(size=(3, 4)).astype(np.float32)),
          np.full(3, 9, np.int32))
    assert g.size == 7
    g.load_snapshot(*snap)
    assert g.size == 4
    labels, _, _ = (np.asarray(v) for v in g.match(emb[:2], k=1))
    np.testing.assert_array_equal(labels[:, 0], [0, 1])
    # restored mirrors are private: mutating the snapshot can't reach them
    snap[0][:] = 0.0
    assert np.linalg.norm(g._host_emb[:4]) > 0


def test_chunked_upload_stops_pacing_after_first_timeout():
    """Hang-mode bound: once one chunk's pacing deadline expires, the
    remaining chunks are NOT paced — the total stall is one chunk
    deadline, not chunks * deadline (the final residency wait still gates
    the publish)."""
    import jax

    mesh = make_mesh(dp=1, tp=1, devices=jax.devices()[:1])
    g = ShardedGallery(capacity=128, dim=16, mesh=mesh, async_grow=True)
    g.CHUNK_UPLOAD_BYTES = 1024  # several pieces at 96 rows
    calls = []

    def never_ready_pacer(buf, deadline, cancel=None, info=None):
        calls.append(deadline)
        if info is not None:
            info["chunk_pacing_timeout"] = True
        return False  # every paced chunk "times out"

    g._pace_chunk = never_ready_pacer  # instance attr shadows the static
    info = {}
    emb = RNG.normal(size=(96, 16)).astype(np.float32)  # 6 pieces of 16 rows
    data = g.data
    arrays = g._splice_rows(
        (data.embeddings, data.labels, data.valid), emb,
        np.arange(96, dtype=np.int32), np.ones(96, bool), 0, owned=False,
        paced=True, info=info)
    assert len(calls) == 1  # paced once, then gave up for the remainder
    assert info["chunk_pacing_timeout"] is True
    # unpaced, the remaining pieces still landed, and the served snapshot
    # was copied, not written
    np.testing.assert_allclose(np.asarray(arrays[0])[:96], emb, rtol=1e-6)
    assert not np.asarray(data.valid).any() and np.asarray(arrays[2])[:96].all()


# ---- a watchlist sharded over tp: the kernel on every shard (PR 38) ----
#
# On a CPU the selection is forced through ``use_pallas`` (the kernel then
# runs in interpret mode); on a mesh of TPU chips ``_pallas_enabled`` makes
# it from the platform and the rows a shard holds.


def _reference_topk(queries, rows, valid, labels, k):
    """The plain reference: a float32 ``jax.numpy`` top-k over the
    unsharded rows, ties to the lowest row (``lax.top_k``'s order)."""
    sims = jnp.dot(jnp.asarray(queries, jnp.float32),
                   jnp.asarray(rows, jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)
    sims = jnp.where(jnp.asarray(valid)[None, :], sims, -jnp.inf)
    vals, idx = jax.lax.top_k(sims, k)
    return (np.asarray(labels)[np.asarray(idx)], np.asarray(vals),
            np.asarray(idx))


def _bf16_exact(x):
    """Values bf16 holds exactly, so the kernel's bf16 operands and the
    float32 reference rank the same rows."""
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def _sharded_gallery(tp, cap, dim, rows, labels, valid=None, **kw):
    mesh = make_mesh(dp=1, tp=tp, devices=jax.devices()[:tp])
    g = ShardedGallery(capacity=cap, dim=dim, mesh=mesh, use_pallas=True, **kw)
    data = g.data
    put = lambda a, like: jax.device_put(jnp.asarray(a, like.dtype), like.sharding)  # noqa: E731
    valid = np.ones(cap, bool) if valid is None else valid
    g.install_device_rows(put(rows, data.embeddings), put(labels, data.labels),
                          put(valid, data.valid), cap)
    return g


class _FakeMesh:
    """A mesh of ``tp`` devices of a platform, for the selection alone."""

    def __init__(self, platform, dp, tp):
        import types

        dev = types.SimpleNamespace(platform=platform, device_kind="fake")
        self.devices = np.array([dev] * (dp * tp), dtype=object).reshape(dp, tp)
        self.shape = {DP_AXIS: dp, TP_AXIS: tp}
        self.size = dp * tp


@pytest.mark.parametrize("tp", [2, 4, 8])
@pytest.mark.parametrize("shard_rows,expect", [
    (ShardedGallery.PALLAS_MIN_CAPACITY, "pallas"),
    (ShardedGallery.PALLAS_MIN_CAPACITY - 1, "xla")])
def test_pallas_selection_by_rows_a_shard_on_tpu_meshes(tp, shard_rows, expect):
    """``_pallas_enabled`` / ``matcher_name`` / ``describe_matchers`` on
    meshes of 2, 4 and 8 TPU devices, either side of ``PALLAS_MIN_CAPACITY``
    rows A SHARD (selection only: no array is made for the fake mesh)."""
    g = ShardedGallery.__new__(ShardedGallery)
    g.mesh, g._use_pallas_cfg = _FakeMesh("tpu", 1, tp), None
    g.capacity = shard_rows * tp
    g.quantizer, g.match_mode, g.store_dtype = None, "exact", jnp.dtype(jnp.bfloat16)
    assert g._pallas_enabled() is (expect == "pallas")
    assert g.matcher_name() == expect
    # a future tier is selected by ITS rows a shard, as prewarm asks
    assert g.matcher_name(ShardedGallery.PALLAS_MIN_CAPACITY * tp) == "pallas"
    assert g.matcher_name(ShardedGallery.PALLAS_MIN_CAPACITY * tp - tp) == "xla"
    lines = g.describe_matchers()
    assert f"mesh dp=1 tp={tp}" in lines[0]
    label = ShardedGallery._MATCHER_LABELS[expect]
    assert f"{g.capacity} rows -> {label} [current]" in lines[1]
    assert f"{tp} shard(s) of {shard_rows} rows" in lines[2]
    assert "the IVF matcher is OFF" in lines[2]
    assert not any("are OFF" in ln or "not tpu" in ln for ln in lines)
    # the same shards on a CPU mesh stay on the GSPMD form, and say why
    g.mesh = _FakeMesh("cpu", 1, tp)
    assert g.matcher_name() == "xla"
    assert any("platform is cpu, not tpu" in ln for ln in g.describe_matchers())


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_forced_kernel_on_a_cpu_mesh_selects_the_shard_map_form(tp):
    from opencv_facerecognizer_tpu.parallel.gallery import match_pod_pallas

    mesh = make_mesh(dp=1, tp=tp, devices=jax.devices()[:tp])
    g = ShardedGallery(capacity=16 * tp, dim=8, mesh=mesh, use_pallas=True)
    assert g._pallas_enabled() and g.matcher_name() == "pallas"
    fn = g.match_fn(1)
    assert fn.func is match_pod_pallas and fn.keywords["interpret"] is True
    assert fn.keywords["mesh"] is mesh
    off = ShardedGallery(capacity=16 * tp, dim=8, mesh=mesh, use_pallas=False)
    assert off.matcher_name() == "xla"


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_sharded_kernel_match_equals_float32_reference(tp):
    """Through ``ShardedGallery.match`` (the selection, not the function
    called by hand): labels and ROW INDICES exactly, similarities to the
    bf16 tolerance, with a best row on every shard in turn."""
    rng = np.random.default_rng(100 + tp)
    cap, dim, k = 32 * tp, 16, 3
    rows = _bf16_exact(_unit(rng.normal(size=(cap, dim)).astype(np.float32)))
    labels = (1000 + np.arange(cap)).astype(np.int32)
    # query s is (nearly) a row of shard s: the best row moves shard by shard
    best_rows = np.array([s * 32 + int(rng.integers(32)) for s in range(tp)])
    q = _bf16_exact(rows[best_rows] + 0.05 * rng.normal(size=(tp, dim)))
    q = np.concatenate([q, _bf16_exact(_unit(rng.normal(size=(8, dim))))])
    g = _sharded_gallery(tp, cap, dim, rows, labels, store_dtype=jnp.bfloat16)
    lab, sims, idx = (np.asarray(v) for v in g.match(q, k=k))
    r_lab, r_sims, r_idx = _reference_topk(q, rows, np.ones(cap, bool), labels, k)
    np.testing.assert_array_equal(idx, r_idx)
    np.testing.assert_array_equal(lab, r_lab)
    np.testing.assert_allclose(sims, r_sims, atol=1e-2)
    np.testing.assert_array_equal(idx[:tp, 0], best_rows)
    assert sorted(set(idx[:tp, 0] // 32)) == list(range(tp))


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_sharded_kernel_match_sparse_shards_and_fewer_valid_than_k(tp):
    """Most shards empty, one shard with fewer valid rows than k: the
    reference's rows in the reference's order, then sentinels (index -1,
    pad label), never a neighbour shard's rows."""
    rng = np.random.default_rng(200 + tp)
    cap, dim, k = 16 * tp, 8, 4
    rows = _bf16_exact(_unit(rng.normal(size=(cap, dim)).astype(np.float32)))
    labels = (500 + np.arange(cap)).astype(np.int32)
    valid = np.zeros(cap, bool)
    last = (tp - 1) * 16
    valid[[last + 3, last + 9]] = True      # two rows, on the LAST shard
    valid[5] = True                          # one on the first
    q = _bf16_exact(_unit(rng.normal(size=(8, dim)).astype(np.float32)))
    g = _sharded_gallery(tp, cap, dim, rows, labels, valid=valid)
    lab, sims, idx = (np.asarray(v) for v in g.match(q, k=k))
    r_lab, r_sims, r_idx = _reference_topk(q, rows, valid, labels, k)
    np.testing.assert_array_equal(idx[:, :3], r_idx[:, :3])
    np.testing.assert_array_equal(lab[:, :3], r_lab[:, :3])
    np.testing.assert_allclose(sims[:, :3], r_sims[:, :3], atol=1e-2)
    assert (idx[:, 3] == -1).all() and (lab[:, 3] == g.labels_pad).all()
    assert (sims[:, 3] < -1e29).all()


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_a_lost_shard_is_seen(tp):
    """One shard's ``valid`` cleared: every query whose best row lay there
    is answered from another shard, exactly as the reference over the rows
    that are left says, and the answer differs from the whole gallery's."""
    rng = np.random.default_rng(300 + tp)
    cap, dim = 32 * tp, 16
    rows = _bf16_exact(_unit(rng.normal(size=(cap, dim)).astype(np.float32)))
    labels = np.arange(cap).astype(np.int32)
    q = _bf16_exact(rows[np.arange(tp) * 32 + 7] + 0.05 * rng.normal(size=(tp, dim)))
    whole = _sharded_gallery(tp, cap, dim, rows, labels)
    _, sims_all, idx_all = (np.asarray(v) for v in whole.match(q, k=1))
    np.testing.assert_array_equal(idx_all[:, 0], np.arange(tp) * 32 + 7)
    for lost in range(tp):
        valid = np.ones(cap, bool)
        valid[lost * 32:(lost + 1) * 32] = False
        g = _sharded_gallery(tp, cap, dim, rows, labels, valid=valid)
        lab, sims, idx = (np.asarray(v) for v in g.match(q, k=1))
        r_lab, r_sims, r_idx = _reference_topk(q, rows, valid, labels, 1)
        np.testing.assert_array_equal(idx, r_idx)
        np.testing.assert_array_equal(lab, r_lab)
        changed = idx[:, 0] != idx_all[:, 0]
        assert changed.tolist() == [s == lost for s in range(tp)]
        assert (idx[:, 0] // 32 != lost).all()
        assert sims[lost, 0] < sims_all[lost, 0] - 1e-3


def _tiny_pipeline(gallery):
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline

    det = CNNFaceDetector(features=(8, 8), head_features=8, max_faces=2,
                          score_threshold=0.0, space_to_depth=2)
    det.load_params(det.net.init(jax.random.PRNGKey(0),
                                 np.zeros((1, 64, 64)))["params"])
    net = FaceEmbedNet(embed_dim=16, stem_features=8, stage_features=(8,),
                       stage_blocks=(1,))
    emb_params = init_embedder(net, num_classes=4, input_shape=(32, 32),
                               seed=0)["net"]
    return RecognitionPipeline(det, net, emb_params, gallery,
                               face_size=(32, 32), top_k=1)


def _step_embeddings(pipe, frames):
    """The embeddings the fused step matches, made with the step's own
    stages outside it: [B * max_faces, E]."""
    from opencv_facerecognizer_tpu.models import detector as detector_mod
    from opencv_facerecognizer_tpu.models import embedder as embedder_mod
    from opencv_facerecognizer_tpu.ops import image as image_ops

    det = pipe.detector
    x = jnp.asarray(frames, jnp.float32)
    boxes, _scores, _valid = detector_mod.decode_detections(
        det.net.apply({"params": det.params}, x), det.max_faces,
        det.score_threshold, det.iou_threshold)
    crops = image_ops.batched_crop_resize(x, boxes, pipe.face_size)
    flat = embedder_mod.normalize_faces(
        crops.reshape((-1, *pipe.face_size)), pipe.face_size)
    return np.asarray(pipe.embed_net.apply({"params": pipe.embed_params}, flat))


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_packed_step_on_a_tp_mesh_equals_float32_reference(tp):
    """Through ``RecognitionPipeline.recognize_batch_packed`` on a (dp 1,
    tp) mesh with the kernel selected on every shard: nets and frames
    replicated, rows and ``valid`` sharded, one packed result read from one
    device. Labels (one a row, some past 2^24: exact through the packed
    lanes) and similarities against the float32 reference over the
    unsharded rows, each face's best row on another shard in turn; and
    with that shard lost the answer changes."""
    from opencv_facerecognizer_tpu.parallel.pipeline import unpack_result
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    rng = np.random.default_rng(400 + tp)
    cap, dim = 16 * tp, 16
    mesh = make_mesh(dp=1, tp=tp, devices=jax.devices()[:tp])
    frames = make_synthetic_scenes(4, (64, 64), max_faces=2, seed=5)[0]
    probe = _tiny_pipeline(ShardedGallery(capacity=cap, dim=dim, mesh=mesh))
    emb = _step_embeddings(probe, frames)  # [8, 16]
    rows = _unit(rng.normal(size=(cap, dim)).astype(np.float32))
    at = np.array([(i % tp) * 16 + 3 + i // tp for i in range(len(emb))])
    rows[at] = emb  # face i's own row, on shard i % tp
    rows = _bf16_exact(rows)
    labels = ((1 << 24) + 1 + 3 * np.arange(cap)).astype(np.int32)
    r_lab, r_sims, r_idx = _reference_topk(emb, rows, np.ones(cap, bool), labels, 1)
    np.testing.assert_array_equal(r_idx[:, 0], at)

    g = _sharded_gallery(tp, cap, dim, rows, labels, store_dtype=jnp.bfloat16)
    pipe = _tiny_pipeline(g)
    assert g.matcher_name() == "pallas"
    packed = pipe.recognize_batch_packed(frames)
    assert packed.sharding.is_fully_replicated and len(packed.sharding.device_set) == tp
    out = unpack_result(np.asarray(packed), 1)
    np.testing.assert_array_equal(out.labels.reshape(-1), r_lab[:, 0])
    np.testing.assert_allclose(out.similarities.reshape(-1), r_sims[:, 0], atol=2e-2)
    # the stage-1 gate's placement is the step's: nothing to compare, it
    # has to run on the same mesh
    plain = pipe.recognize_batch(frames)
    np.testing.assert_array_equal(np.asarray(plain.labels).reshape(-1), r_lab[:, 0])

    lost = 1
    valid = np.ones(cap, bool)
    valid[lost * 16:(lost + 1) * 16] = False
    g.install_device_rows(g.data.embeddings, g.data.labels,
                          jax.device_put(jnp.asarray(valid), g.data.valid.sharding), cap)
    out2 = unpack_result(np.asarray(pipe.recognize_batch_packed(frames)), 1)
    l_lab, _l_sims, _ = _reference_topk(emb, rows, valid, labels, 1)
    np.testing.assert_array_equal(out2.labels.reshape(-1), l_lab[:, 0])
    moved = out2.labels.reshape(-1) != out.labels.reshape(-1)
    assert moved.tolist() == [(i % tp) == lost for i in range(len(emb))]


# ---- install: n rows over the link, two tier-sized arrays at most ----


def _counted_gallery(cap, dim, tp, **kw):
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    metrics = Metrics()
    mesh = make_mesh(dp=1, tp=tp, devices=jax.devices()[:tp])
    gallery = ShardedGallery(capacity=cap, dim=dim, mesh=mesh, **kw)
    gallery.attach_observability(metrics)
    return gallery, metrics


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("store_dtype", ["float32", "bfloat16"])
def test_add_moves_n_rows_over_the_link_and_keeps_the_served_snapshot(tp, store_dtype):
    """Invariant (a): an ``add`` of n rows uploads n rows (counter
    ``gallery_rows_uploaded``) and splices them into a COPY of the served
    arrays: a reader's snapshot is untouched, the new one holds old and new
    rows, every array keeps the tier's shape and sharding."""
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    g, metrics = _counted_gallery(4096, 8, tp, store_dtype=getattr(jnp, store_dtype))
    assert metrics.gauge(mn.GALLERY_SHARDS) == tp
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == 0  # construction: nothing
    first = _unit(RNG.normal(size=(13, 8)).astype(np.float32))
    g.add(first, np.arange(13, dtype=np.int32))
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == 13
    held = g.data  # what a reader took
    more = _unit(RNG.normal(size=(1000, 8)).astype(np.float32))
    g.add(more, np.arange(13, 1013, dtype=np.int32))
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == 1013 == g.rows_uploaded
    assert held.size == 13 and int(np.asarray(held.valid).sum()) == 13
    assert not np.asarray(held.embeddings)[13:].any()
    new = g.data
    assert new.size == 1013 and np.asarray(new.valid)[:1013].all()
    assert not np.asarray(new.valid)[1013:].any()
    tol = 1e-6 if store_dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(new.embeddings, np.float32)[:1013],
                               np.concatenate([first, more]), atol=tol)
    np.testing.assert_array_equal(np.asarray(new.labels)[:1013], np.arange(1013))
    for old, cur in zip(held[:3], new[:3]):
        assert cur.shape == old.shape and cur.sharding == old.sharding
        assert cur.dtype == old.dtype
    assert {s.data.shape for s in new.embeddings.addressable_shards} == {(4096 // tp, 8)}


def test_no_capacity_sized_host_array_at_construction_or_add(monkeypatch):
    """Invariant (b): nothing of capacity x dim is allocated on the host
    when a gallery is made or rows are added; the mirror holds what the
    host enrolled and grows with it."""
    cap, dim = 1 << 16, 8
    big = []
    real = {name: getattr(np, name) for name in ("zeros", "empty", "full", "ones")}

    def watch(name):
        def alloc(shape, *a, **k):
            if int(np.prod(shape)) >= cap * dim:
                big.append((name, shape))
            return real[name](shape, *a, **k)
        return alloc

    for name in real:
        monkeypatch.setattr(np, name, watch(name))
    g, _ = _counted_gallery(cap, dim, 4)
    assert len(g._host_emb) == 0
    g.add(_unit(RNG.normal(size=(100, dim)).astype(np.float32)),
          np.arange(100, dtype=np.int32))
    assert 100 <= len(g._host_emb) <= 256 and len(g._host_lab) == len(g._host_emb)
    g.add(_unit(RNG.normal(size=(5000, dim)).astype(np.float32)),
          np.arange(100, 5100, dtype=np.int32))
    assert 5100 <= len(g._host_emb) <= 2 * 5100
    g.reset()
    assert len(g._host_emb) == 0 and g.size == 0
    assert not big, big
    # asked for, a snapshot IS whole-capacity: that is its contract
    emb, lab, val, size = g.snapshot()
    assert emb.shape == (cap, dim) and size == 0 and big


@pytest.mark.parametrize("tp", [1, 4])
def test_install_device_rows_adopts_appends_after_and_reads_back_on_demand(tp):
    """Invariant (c): ``install_device_rows`` adopts tp-sharded device
    arrays as the next snapshot (nothing crosses the link, epoch bumped, an
    in-flight grow dropped), a later ``add`` appends after them and keeps
    them, and ``snapshot()`` reads them back when called."""
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    cap, dim, n = 256, 8, 200
    g, metrics = _counted_gallery(cap, dim, tp, store_dtype=jnp.bfloat16)
    g.add(_unit(RNG.normal(size=(4, dim)).astype(np.float32)),
          np.arange(4, dtype=np.int32))
    epoch0, uploaded0 = g.data.epoch, metrics.counter(mn.GALLERY_ROWS_UPLOADED)
    rows = _bf16_exact(_unit(RNG.normal(size=(cap, dim)).astype(np.float32)))
    data = g.data
    emb = jax.device_put(jnp.asarray(rows, jnp.bfloat16), data.embeddings.sharding)
    lab = jax.device_put(jnp.asarray(7000 + np.arange(cap), jnp.int32), data.labels.sharding)
    val = jax.device_put(jnp.arange(cap) < n, data.valid.sharding)
    g.install_device_rows(emb, lab, val, n)
    assert g.data.embeddings is emb and g.size == n and g.data.epoch == epoch0 + 1
    assert metrics.counter(mn.GALLERY_BULK_INSTALLS) == 1 == g.bulk_installs
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == uploaded0  # none moved
    assert len(g._host_emb) == 0  # the host holds none of them
    lab1, _, idx1 = (np.asarray(v) for v in g.match(rows[[0, n - 1]], k=1))
    np.testing.assert_array_equal(idx1[:, 0], [0, n - 1])
    np.testing.assert_array_equal(lab1[:, 0], [7000, 7000 + n - 1])

    extra = _bf16_exact(_unit(RNG.normal(size=(10, dim)).astype(np.float32)))
    g.add(extra, np.arange(10, dtype=np.int32))
    assert g.size == n + 10
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == uploaded0 + 10
    assert len(g._host_emb) >= 10 and g._host_base == n
    lab2, _, idx2 = (np.asarray(v) for v in g.match(
        np.concatenate([rows[[0, n - 1]], extra[[0, 9]]]), k=1))
    np.testing.assert_array_equal(idx2[:, 0], [0, n - 1, n, n + 9])
    np.testing.assert_array_equal(lab2[:, 0], [7000, 7000 + n - 1, 0, 9])
    s_emb, s_lab, s_val, s_size = g.snapshot()
    assert s_emb.shape == (cap, dim) and s_size == n + 10
    np.testing.assert_array_equal(s_emb[:n], rows[:n])          # read back
    np.testing.assert_allclose(s_emb[n:n + 10], _unit(extra), atol=1e-6)  # the mirror
    assert s_val[:n + 10].all() and not s_val[n + 10:].any()
    np.testing.assert_array_equal(s_lab[n - 1:n + 2], [7000 + n - 1, 0, 1])
    # a snapshot of them restores into another gallery
    other, _ = _counted_gallery(cap, dim, tp)
    other.load_snapshot(s_emb, s_lab, s_val, s_size)
    assert other.size == n + 10 and other.rows_uploaded == n + 10
    np.testing.assert_array_equal(np.asarray(other.match(rows[[5]], k=1)[2])[:, 0], [5])
    # wrong shapes and dtypes are refused before anything is touched
    with pytest.raises(ValueError):
        g.install_device_rows(emb.astype(jnp.float32), lab, val, n)
    with pytest.raises(ValueError):
        g.install_device_rows(emb, lab[: cap - 1], val, n)
    assert g.size == n + 10


def test_install_device_rows_drops_an_inflight_grow_and_grows_past_capacity():
    import threading

    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    g = ShardedGallery(capacity=8, dim=4, mesh=mesh, async_grow=True)
    hold = threading.Event()
    g.prewarm_hooks.append(lambda cap: hold.wait(5))
    g.add(RNG.normal(size=(8, 4)).astype(np.float32), np.arange(8, dtype=np.int32))
    g.add(RNG.normal(size=(4, 4)).astype(np.float32), np.arange(8, 12, dtype=np.int32))
    assert g.pending_rows == 4
    data = g.data
    rows = jax.device_put(jnp.asarray(_unit(RNG.normal(size=(8, 4)).astype(np.float32))),
                          data.embeddings.sharding)
    g.install_device_rows(rows, data.labels, jax.device_put(jnp.ones(8, bool), data.valid.sharding), 8)
    hold.set()
    assert g.wait_ready(timeout=30)
    assert g.size == 8 and g.pending_rows == 0 and g.data.embeddings is rows
    # full: the next add grows ON the devices and keeps the installed rows
    g.async_grow = False
    g.add(RNG.normal(size=(3, 4)).astype(np.float32), np.array([70, 71, 72], np.int32))
    assert g.capacity == 16 and g.size == 11
    np.testing.assert_allclose(np.asarray(g.data.embeddings)[:8], np.asarray(rows))
    np.testing.assert_array_equal(np.asarray(g.data.labels)[8:11], [70, 71, 72])
    assert g.snapshot()[0].shape == (16, 4)


def test_gallery_install_span_carries_rows_and_bytes():
    from opencv_facerecognizer_tpu.utils.metrics import Metrics
    from opencv_facerecognizer_tpu.utils.tracing import LIFECYCLE_TOPIC, Tracer

    g, _ = _counted_gallery(64, 8, 2, store_dtype=jnp.bfloat16)
    g.add(_unit(RNG.normal(size=(3, 8)).astype(np.float32)), np.arange(3, dtype=np.int32))
    metrics, tracer = Metrics(), Tracer(ring_size=64, sample=1.0, seed=0)
    g.attach_observability(metrics, tracer)
    g.attach_observability(metrics, tracer)  # wired twice: counted once
    from opencv_facerecognizer_tpu.utils import metric_names as mn
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == 3
    g.add(_unit(RNG.normal(size=(5, 8)).astype(np.float32)), np.arange(3, 8, dtype=np.int32))
    data = g.data
    g.install_device_rows(data.embeddings, data.labels, data.valid, 8)
    spans = [s for s in tracer.snapshot(LIFECYCLE_TOPIC) if s["stage"] == "gallery_install"]
    assert [(s["rows"], s["bytes"], s["source"]) for s in spans] == [
        (5, 5 * 8 * 2, "host"), (8, 8 * 8 * 2, "device")]
    assert metrics.counter(mn.GALLERY_ROWS_UPLOADED) == 8
    assert metrics.counter(mn.GALLERY_BULK_INSTALLS) == 1


def test_packed_labels_are_exact_past_two_to_the_24th():
    """A watchlist of 50 M rows labels past 2^24, where a float32 rounds:
    the packed result's lanes are int32 and carry a label as it is (and
    the floats as their bits)."""
    from opencv_facerecognizer_tpu.parallel.pipeline import (
        RecognitionResult, pack_result, unpack_result)

    labels = np.array([[-1, 0, 1, (1 << 24) + 1, 50331647 + 1032, 2**31 - 1]], np.int32).T
    labels = labels.reshape(1, 6, 1)
    result = RecognitionResult(
        boxes=jnp.zeros((1, 6, 4)), det_scores=jnp.ones((1, 6)),
        valid=jnp.ones((1, 6), bool), labels=jnp.asarray(labels),
        similarities=jnp.full((1, 6, 1), 0.5))
    packed = np.asarray(jax.jit(pack_result)(result))
    assert packed.dtype == np.int32
    out = unpack_result(packed, 1)
    np.testing.assert_array_equal(out.labels, labels)
    np.testing.assert_array_equal(out.similarities, np.float32(0.5))
    assert out.valid.all() and (out.det_scores == 1).all() and not out.boxes.any()

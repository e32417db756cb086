"""IVF coarse quantizer: the million-identity front end of the two-stage
match path (ROADMAP item #1; the "shortlist + exact rerank" structure of
PAPERS.md's *Fast Matching by 2 Lines of Code for Large Scale Face
Recognition Systems*, arxiv 1302.7180).

The brute-force cosine scan is linear in gallery size — BENCH_r05 measures
1.356 ms/batch at 262k rows and 3.607 ms/batch at 1M (``pallas_stream``),
so 10M identities would blow every serving deadline the runtime protects.
This module prunes the scan: a seeded k-means **coarse quantizer** carves
the gallery into ``nlist`` cells; each cell holds its member rows as an
**int8-quantized, cell-resident inverted list** (contiguous [nlist,
max_cell, D] blocks — a shortlisted cell gathers as one dense block, not
``max_cell`` scattered row reads); matching scores query-vs-centroid,
shortlists the top-``nprobe`` cells, and reranks only their rows with the
existing exact Pallas kernel (``ops.ivf_match`` has the device-side
formulation).

Derived-state contract (the part that must ride the PR-4 lifecycle
untouched — the quantizer is a pure function of the gallery, never a
second source of truth):

- **rebuild** on ``load_snapshot``/startup recovery: ``ShardedGallery``
  invalidates the quantizer on any wholesale state install; recovery
  either restores it from a versioned **sidecar** keyed by the
  checkpoint's ``wal_seq`` (``encode_sidecar``/``decode_sidecar`` —
  written next to the checkpoint, never trusted across a seq mismatch) or
  retrains from the recovered rows. Rebuilds are deterministic: same
  rows + same seed -> bit-identical centroids and assignments on a given
  backend.
- **incremental assignment** on ``ShardedGallery.add``: new rows are
  assigned to their nearest centroid through the same fixed-chunk
  ``assign_rows`` routine the bulk build uses, inserted into their cell's
  list (or the always-scanned **spill** when the cell is full), under the
  gallery's write lock — so WAL replay, which re-drives ``add`` in the
  original order against the sidecar-restored centroids, reproduces the
  exact assignments the live process made.
- **invalidate + rebuild** across ``swap_from``/``reset``: a swapped-in
  gallery shares nothing with the trained cells; serving falls back to
  the exact matcher until a background retrain publishes (mode selection
  lives in ``ShardedGallery.match_fn``).
- **staleness** (spill filling up, or the gallery outgrowing the trained
  row set) triggers a background retrain under the same single-flight
  pattern as the PR-4 checkpointer: one worker at a time, an overlapping
  trigger is counted and dropped, a mid-retrain crash leaves the previous
  published state (or the exact path) serving — never a torn quantizer.

Concurrency: all mutation happens under the owning gallery's write lock
(``ShardedGallery`` calls in from ``add``/``reset``/``load_snapshot``/
``swap_from``, and the retrain worker publishes through
``gallery.run_locked``); readers take the single ``data`` attribute
snapshot, exactly the ``GalleryData`` pattern. The quantizer itself never
acquires the gallery lock while holding any lock of its own — it has
none — so the PR-5 lock-order graph gains only gallery -> Metrics edges.
"""

from __future__ import annotations

import binascii
import hashlib
import json
import logging
import threading
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.tracing import LIFECYCLE_TOPIC

#: sidecar file magic — identifies the framed quantizer-sidecar format
#: (distinct from the OCVFSTATE gallery checkpoints it rides next to).
SIDECAR_MAGIC = b"OCVFIVF\n"
SIDECAR_FORMAT_VERSION = 1

#: assignment chunk ceiling: rows are scored against centroids in chunks
#: padded to a power-of-two tier (8..ASSIGN_CHUNK), so the compile count
#: is bounded and — the replay contract — a record of n rows re-assigned
#: by WAL replay runs the IDENTICAL compiled shape the live enrolment
#: ran, making the recomputed assignment bit-identical on that backend.
ASSIGN_CHUNK = 8192


class SidecarError(ValueError):
    """The sidecar file is corrupt/truncated or fails its checksum —
    recovery falls back to a full retrain, never a torn quantizer."""


class IVFDeviceData(NamedTuple):
    """One immutable snapshot of the device-visible quantizer state —
    the reader side mirrors ``GalleryData``: a single ``data`` attribute
    load can never observe mixed centroids/lists. All row payloads are
    int8-quantized (per-row scale) so a 10M-row gallery's lists fit HBM
    alongside the exact bf16 rows, and a shortlisted cell streams as one
    dense [max_cell, D] block."""

    centroids: Any      # [nlist, D] f32, L2-normalized
    cell_rows: Any      # [nlist, max_cell] int32 gallery row ids, -1 pad
    cell_q8: Any        # [nlist, max_cell, D] int8 quantized rows
    cell_scale: Any     # [nlist, max_cell] f32 per-row dequant scale
    spill_rows: Any     # [spill_cap] int32 overflow row ids, -1 pad
    spill_q8: Any       # [spill_cap, D] int8
    spill_scale: Any    # [spill_cap] f32
    #: gallery ``_epoch`` at publish: ``ShardedGallery._ivf_data`` rejects
    #: a snapshot whose epoch differs from the paired ``GalleryData``'s,
    #: so two non-atomic reads can never match one row set against
    #: another's lists. (A plain int pytree leaf: jit traces it as a
    #: scalar, so epoch changes never retrace.)
    gallery_epoch: int = 0

    @property
    def nlist(self) -> int:
        return int(self.cell_rows.shape[0])

    @property
    def max_cell(self) -> int:
        return int(self.cell_rows.shape[1])

    @property
    def spill_cap(self) -> int:
        return int(self.spill_rows.shape[0])

    def shape_signature(self) -> Tuple[int, int, int]:
        """The static-shape part of a compiled-matcher cache key: two
        snapshots with equal signatures trace to the same executable."""
        return (self.nlist, self.max_cell, self.spill_cap)


def pack_inverted_lists(ids: np.ndarray, cells: np.ndarray, q8: np.ndarray,
                        scale: np.ndarray, nlist: int,
                        cell_slack: float = 2.0, spill_floor: int = 0):
    """Pure packing of assigned rows into the cell-resident structures:
    ``(cell_rows, cell_q8, cell_scale, spill_rows, spill_q8, spill_scale,
    counts, overflow)``. Rows fill their cell in ascending row-id order;
    rows past ``max_cell`` land in the spill, also ascending — exactly
    the order incremental inserts produce, so a rebuild from a sidecar's
    assignment array reproduces the live structures bit-for-bit. Shared
    by ``CoarseQuantizer`` and the bench ladder (which builds 10M-row
    lists chunk-wise without a host-mirror gallery)."""
    ids = np.asarray(ids, np.int32)
    cells = np.asarray(cells, np.int32)
    q8 = np.asarray(q8, np.int8)
    scale = np.asarray(scale, np.float32)
    n, dim = q8.shape
    mean = max(1.0, n / max(1, nlist))
    max_cell = max(8, int(np.ceil(cell_slack * mean / 8.0) * 8))
    order = np.lexsort((ids, cells))
    s_ids, s_cells = ids[order], cells[order]
    counts = np.bincount(s_cells, minlength=nlist).astype(np.int64)
    starts = np.zeros(nlist + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(n, dtype=np.int64) - starts[s_cells]
    in_cell = pos < max_cell
    overflow = int(n - in_cell.sum())
    spill_cap = int(np.ceil((max(overflow, spill_floor) + 256) / 256.0) * 256)
    cell_rows = np.full((nlist, max_cell), -1, np.int32)
    cell_q8 = np.zeros((nlist, max_cell, dim), np.int8)
    cell_scale = np.zeros((nlist, max_cell), np.float32)
    cr, cp = s_cells[in_cell], pos[in_cell]
    cell_rows[cr, cp] = s_ids[in_cell]
    cell_q8[cr, cp] = q8[order][in_cell]
    cell_scale[cr, cp] = scale[order][in_cell]
    spill_rows = np.full((spill_cap,), -1, np.int32)
    spill_q8 = np.zeros((spill_cap, dim), np.int8)
    spill_scale = np.zeros((spill_cap,), np.float32)
    if overflow:
        sp_order = np.argsort(s_ids[~in_cell])
        spill_rows[:overflow] = s_ids[~in_cell][sp_order]
        spill_q8[:overflow] = q8[order][~in_cell][sp_order]
        spill_scale[:overflow] = scale[order][~in_cell][sp_order]
    counts_clamped = np.minimum(counts, max_cell).astype(np.int32)
    return (cell_rows, cell_q8, cell_scale, spill_rows, spill_q8,
            spill_scale, counts_clamped, overflow)


def quantize_rows(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantization: ``row ~= q8 * scale``.

    For L2-normalized embeddings the max |component| is ~0.2 at D=256, so
    the per-component step (scale ~ max/127) puts the dot-product error
    well under the bf16 rounding the exact kernel already accepts — the
    recall gate in tests measures the end-to-end effect.
    """
    rows = np.asarray(rows, np.float32)
    scale = np.max(np.abs(rows), axis=-1) / 127.0
    scale = np.maximum(scale, np.float32(1e-12)).astype(np.float32)
    q8 = np.clip(np.rint(rows / scale[..., None]), -127, 127).astype(np.int8)
    return q8, scale


def _kmeans(rows: np.ndarray, nlist: int, iters: int, seed: int) -> np.ndarray:
    """Seeded spherical k-means on the device (jax): centroids stay
    L2-normalized so centroid score == expected member cosine. Empty
    cells keep their previous centroid (deterministic; they simply stop
    attracting rows). Same rows + seed -> bit-identical centroids on a
    given backend."""
    import jax
    import jax.numpy as jnp

    rows = np.asarray(rows, np.float32)
    s = rows.shape[0]
    key = jax.random.PRNGKey(int(seed))
    perm = np.asarray(jax.random.permutation(key, s))
    init = rows[perm[np.arange(nlist) % s]]

    @jax.jit
    def step(x, c):
        sims = x @ c.T  # f32: determinism beats MXU speed at train size
        assign = jnp.argmax(sims, axis=1)
        ones = jnp.ones((x.shape[0],), jnp.float32)
        counts = jax.ops.segment_sum(ones, assign, num_segments=nlist)
        sums = jax.ops.segment_sum(x, assign, num_segments=nlist)
        mean = sums / jnp.maximum(counts, 1.0)[:, None]
        norm = jnp.linalg.norm(mean, axis=1, keepdims=True)
        newc = mean / jnp.maximum(norm, 1e-12)
        return jnp.where((counts > 0)[:, None], newc, c)

    c = jnp.asarray(init)
    x = jnp.asarray(rows)
    for _ in range(max(1, int(iters))):
        c = step(x, c)
    return np.asarray(c, np.float32)


class CoarseQuantizer:
    """Seeded k-means coarse quantizer over a ``ShardedGallery``'s rows,
    with int8 cell-resident inverted lists and an always-exact spill.

    Attach with ``gallery.attach_quantizer(quantizer, mode=...)``; the
    gallery then drives every lifecycle edge (see module docstring). The
    matcher side is ``ops.ivf_match.ivf_match_topk`` over ``self.data``.
    """

    #: spill high-water fraction that marks the quantizer stale — the
    #: spill is scanned exactly on every match, so a full spill is a
    #: perf (never a recall) problem.
    SPILL_STALE_FRACTION = 0.75

    #: gallery growth past the trained row set that marks it stale:
    #: centroids trained on 1/GROWTH_STALE_FACTOR of the rows no longer
    #: describe the distribution.
    GROWTH_STALE_FACTOR = 1.5

    #: per-cell slack over the perfectly balanced size; rows past it spill.
    CELL_SLACK = 2.0

    def __init__(self, nlist: int = 1024, nprobe: int = 8, seed: int = 0,
                 kmeans_iters: int = 10, train_sample: int = 131072,
                 metrics=None, auto_nlist: bool = False):
        #: with ``auto_nlist`` the cell count re-derives from the ACTUAL
        #: row count at every rebuild (and adopts the sidecar's on
        #: recovery) — a startup guess from ``capacity`` would otherwise
        #: freeze a too-small nlist across recovery of a much larger
        #: checkpoint or 10x runtime growth, quietly bloating every
        #: rerank bucket.
        self.auto_nlist = bool(auto_nlist)
        self.nlist = int(nlist)
        self.nprobe = int(nprobe)
        self.seed = int(seed)
        self.kmeans_iters = int(kmeans_iters)
        self.train_sample = int(train_sample)
        self.metrics = metrics
        #: optional utils.tracing.Tracer — one lifecycle span per retrain
        #: attempt (outcome ok/skipped/failed), set alongside ``metrics``
        #: by the serving app. Never touched on the match hot path.
        self.tracer = None
        self._gallery = None  # set by ShardedGallery.attach_quantizer
        #: single published device snapshot (None == not ready; serving
        #: falls back to the exact matcher).
        self._data: Optional[IVFDeviceData] = None
        self.version = 0
        self.trained_size = 0
        #: host mirrors, mutated only under the gallery write lock.
        self._h_centroids: Optional[np.ndarray] = None
        self._h_assign = np.zeros((0,), np.int32)  # [capacity] cell or -1
        self._h_counts: Optional[np.ndarray] = None  # [nlist] rows per cell
        self._spill_count = 0
        self._assigned_rows = 0  # row-id high-water covered by the lists
        # Single-flight retrain guard — the PR-4 checkpoint pattern: one
        # background worker at a time; an overlapping trigger is counted
        # and dropped (staleness re-fires on the next add).
        self._train_lock = threading.Lock()
        #: set when a build was fenced out by an epoch bump (swap/load/
        #: reset landed mid-train): rebuild_now re-fires one async build
        #: after releasing the guard, because the invalidation's own poke
        #: was skipped as in-flight — without the re-fire a match-heavy,
        #: no-further-enrolment workload would stay pinned to the exact
        #: scan forever.
        self._fence_refire = False
        self._assign_jit = None
        self._scatter_jit = None
        #: device copy of ``_h_centroids``, lazily re-put after each
        #: (re)build/invalidate — assignment must not re-upload the
        #: [nlist, D] matrix on every enrolment.
        self._c_dev = None

    @staticmethod
    def default_nlist(rows: int) -> int:
        """~4*sqrt(rows) rounded to a power of two, clamped to [64,
        16384] — the classic IVF sizing: cells of ~sqrt(rows)/4 rows keep
        the stage-1 scan and the stage-2 buckets balanced as the gallery
        scales 262k -> 10M."""
        target = 4.0 * np.sqrt(max(1, int(rows)))
        nlist = 64
        while nlist < target and nlist < 16384:
            nlist *= 2
        return nlist

    # ---- read side ----

    @property
    def ready(self) -> bool:
        return self._data is not None

    @property
    def data(self) -> Optional[IVFDeviceData]:
        return self._data

    @property
    def spill_count(self) -> int:
        return self._spill_count

    def stats(self) -> Dict[str, Any]:
        data = self._data
        return {
            "ready": data is not None,
            "version": self.version,
            "nlist": self.nlist,
            "nprobe": self.nprobe,
            "trained_size": self.trained_size,
            "assigned_rows": self._assigned_rows,
            "spill_count": self._spill_count,
            "spill_cap": 0 if data is None else data.spill_cap,
            "max_cell": 0 if data is None else data.max_cell,
        }

    # ---- assignment (the ONE routine every path shares) ----

    @staticmethod
    def _pad_tier(n: int) -> int:
        """Power-of-two pad tier for a chunk of ``n`` rows: bounds the
        compile count while keeping each record's replay on the exact
        compiled shape its live enrolment used."""
        tier = 8
        while tier < n:
            tier *= 2
        return min(tier, ASSIGN_CHUNK)

    def assign_rows(self, rows: np.ndarray,
                    centroids: Optional[np.ndarray] = None) -> np.ndarray:
        """Nearest-centroid cell ids for L2-normalized rows — the ONE
        assignment routine shared by bulk build, incremental enrolment
        and WAL replay, chunked to fixed pad tiers (``_pad_tier``) so a
        replayed record recomputes bit-identical assignments on the same
        backend. Ties break to the lowest cell id (argmax-first),
        matching the stage-1 shortlist's ``top_k`` order."""
        import jax
        import jax.numpy as jnp

        if centroids is None:
            centroids = self._h_centroids
        if centroids is None:
            raise RuntimeError("quantizer has no centroids: build first")
        rows = np.asarray(rows, np.float32)
        n = rows.shape[0]
        if self._assign_jit is None:
            self._assign_jit = jax.jit(
                lambda x, c: jnp.argmax(x @ c.T, axis=1).astype(jnp.int32))
        if centroids is self._h_centroids:
            if self._c_dev is None:
                self._c_dev = jnp.asarray(centroids)
            c_dev = self._c_dev
        else:
            c_dev = jnp.asarray(centroids)
        out = np.empty((n,), np.int32)
        for off in range(0, n, ASSIGN_CHUNK):
            chunk = rows[off:off + ASSIGN_CHUNK]
            got_n = chunk.shape[0]
            pad = self._pad_tier(got_n) - got_n
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            got = np.asarray(self._assign_jit(jnp.asarray(chunk), c_dev))
            out[off:off + got_n] = got[:got_n]
        return out

    # ---- building (bulk) ----

    def _pack(self, emb: np.ndarray, val: np.ndarray, assign: np.ndarray,
              spill_floor: int = 0):
        """Quantize the valid rows and pack them through the shared
        ``pack_inverted_lists`` routine (module docstring has the
        ordering contract)."""
        ids = np.nonzero(val)[0].astype(np.int32)
        q8, scale = quantize_rows(emb[ids])
        return pack_inverted_lists(ids, assign[ids], q8, scale, self.nlist,
                                   cell_slack=self.CELL_SLACK,
                                   spill_floor=spill_floor)

    def _device_put(self, centroids, cell_rows, cell_q8, cell_scale,
                    spill_rows, spill_q8, spill_scale) -> IVFDeviceData:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from opencv_facerecognizer_tpu.parallel.mesh import TP_AXIS

        mesh = self._gallery.mesh
        rep = NamedSharding(mesh, P())
        # Cell-resident arrays shard over cells like the gallery shards
        # over rows; on the single-device meshes the ivf path is gated to,
        # this is placement only.
        by_cell = (NamedSharding(mesh, P(TP_AXIS, None))
                   if cell_rows.shape[0] % mesh.shape[TP_AXIS] == 0 else rep)
        by_cell3 = (NamedSharding(mesh, P(TP_AXIS, None, None))
                    if cell_rows.shape[0] % mesh.shape[TP_AXIS] == 0 else rep)
        return IVFDeviceData(
            centroids=jax.device_put(jnp.asarray(centroids), rep),
            cell_rows=jax.device_put(jnp.asarray(cell_rows), by_cell),
            cell_q8=jax.device_put(jnp.asarray(cell_q8), by_cell3),
            cell_scale=jax.device_put(jnp.asarray(cell_scale), by_cell),
            spill_rows=jax.device_put(jnp.asarray(spill_rows), rep),
            spill_q8=jax.device_put(jnp.asarray(spill_q8), rep),
            spill_scale=jax.device_put(jnp.asarray(spill_scale), rep),
        )

    def rebuild_now(self, wait: bool = True,
                    skip_if_ready: bool = False) -> bool:
        """One full retrain: snapshot the gallery, train seeded k-means on
        a row subsample, assign every row, pack + upload, publish under
        the gallery write lock with a catch-up pass for rows enrolled
        since the snapshot. Returns False when another retrain holds the
        single-flight guard (and ``wait`` is False) or the build failed
        (counted ``ivf_build_failures``; previous state keeps serving).
        ``skip_if_ready`` turns the call into "ensure built": with
        ``wait`` it first rides out any in-flight background build and
        returns True without retraining when that build (or an earlier
        one) already published — the startup path uses this so a
        recovery-poked background build is never duplicated."""
        if self._gallery is None:
            raise RuntimeError("quantizer not attached to a gallery")
        if not self._train_lock.acquire(blocking=wait):
            if self.metrics is not None:
                self.metrics.incr(mn.IVF_RETRAINS_SKIPPED_INFLIGHT)
            return False
        span_t0 = time.monotonic()
        outcome = "failed"
        try:
            if skip_if_ready and self._data is not None:
                outcome = "already_ready"
                return True
            ok = self._rebuild_locked()
            outcome = "ok" if ok else "fenced"
            return ok
        except Exception:  # noqa: BLE001 — a failed retrain must leave the
            # previous quantizer (or the exact path) serving, never crash
            # an enroll/serving thread that triggered it.
            logging.getLogger(__name__).exception("ivf rebuild failed")
            if self.metrics is not None:
                self.metrics.incr(mn.IVF_BUILD_FAILURES)
            return False
        finally:
            self._train_lock.release()
            if self.tracer is not None:
                # One lifecycle span per retrain attempt, emitted after
                # the single-flight guard is released.
                self.tracer.emit(
                    self.tracer.new_trace(), "ivf_retrain",
                    topic=LIFECYCLE_TOPIC, t0=span_t0,
                    dur=time.monotonic() - span_t0, outcome=outcome,
                    nlist=self.nlist, version=self.version)
            if self._fence_refire:
                # The epoch fence discarded this build (a swap/load/reset
                # landed mid-train) AND that invalidation's poke was
                # skipped as in-flight: fire one fresh attempt against
                # the new row set. Only fences re-fire — failures must
                # not storm — and maybe_rebuild_async single-flights.
                self._fence_refire = False
                g = self._gallery
                if (g is not None and self._data is None
                        and g._ivf_wanted() and g.size > 0):
                    self.maybe_rebuild_async()

    def _rebuild_locked(self) -> bool:
        t0 = time.perf_counter()
        g = self._gallery
        # Epoch fence: a reset/swap_from/load_snapshot during this build
        # invalidates it — publishing centroids trained on the PREVIOUS
        # row set over a swapped-in gallery would be silently wrong.
        epoch0 = g.run_locked(lambda: g._epoch)
        emb, _lab, val, _size = g.snapshot()
        n_valid = int(val.sum())
        if n_valid < max(2, min(self.nlist, 8)):
            return False  # nothing meaningful to train on
        if self.auto_nlist:
            self.nlist = self.default_nlist(n_valid)
        ids = np.nonzero(val)[0]
        rows = emb[ids]
        sample = rows
        if len(rows) > self.train_sample:
            rng = np.random.default_rng(self.seed)
            pick = np.sort(rng.choice(len(rows), self.train_sample,
                                      replace=False))
            sample = rows[pick]
        centroids = _kmeans(sample, self.nlist, self.kmeans_iters, self.seed)
        assign_valid = self.assign_rows(rows, centroids)
        assign = np.full((emb.shape[0],), -1, np.int32)
        assign[ids] = assign_valid
        packed = self._pack(emb, val, assign)
        (cell_rows, cell_q8, cell_scale, spill_rows, spill_q8, spill_scale,
         counts, overflow) = packed
        data = self._device_put(centroids, cell_rows, cell_q8, cell_scale,
                                spill_rows, spill_q8, spill_scale)
        published = []

        def publish():
            if g._epoch != epoch0:
                return  # superseded: the invalidation wins, like a grow
            # Under the gallery write lock: no add can interleave, so the
            # catch-up below sees a settled row set.
            self._h_centroids = centroids
            self._c_dev = None  # lazily re-put on the next assignment
            self._h_assign = assign
            self._h_counts = counts
            self._spill_count = overflow
            self._assigned_rows = int(ids[-1]) + 1 if len(ids) else 0
            self.trained_size = n_valid
            self._data = data._replace(gallery_epoch=g._epoch)
            self.version += 1
            published.append(True)
            # Catch-up: rows enrolled between the snapshot above and this
            # publish are re-assigned against the NEW centroids and
            # inserted exactly like any incremental add. Valid rows are
            # a prefix (append-only within an epoch), so the tail is one
            # contiguous range — ONE batched insert, not a per-row loop
            # of full-array device copies under the write lock.
            tail = g.host_valid()
            tail[:emb.shape[0]] &= ~val[:len(tail)][:emb.shape[0]]
            tail_ids = np.nonzero(tail)[0]
            if len(tail_ids):
                lo, hi = int(tail_ids[0]), int(tail_ids[-1]) + 1
                if hi - lo == len(tail_ids):
                    self.on_rows_added(g.host_rows(lo, hi), lo)
                else:  # non-contiguous (defensive): per-row fallback
                    for rid in tail_ids:
                        self.on_rows_added(g.host_rows(int(rid), int(rid) + 1),
                                           int(rid))

        g.run_locked(publish)
        if not published:
            self._fence_refire = True  # retry against the new row set
            return False
        if self.metrics is not None:
            self.metrics.incr(mn.IVF_BUILDS)
            self.metrics.set_gauge(mn.IVF_SPILL_ROWS, self._spill_count)
        logging.getLogger(__name__).info(
            "ivf rebuild v%d: %d rows, nlist=%d, max_cell=%d, spill=%d "
            "(%.2fs)", self.version, n_valid, self.nlist,
            cell_rows.shape[1], overflow, time.perf_counter() - t0)
        return True

    def maybe_rebuild_async(self) -> bool:
        """Spawn a background retrain unless one is already in flight
        (single-flight, like ``StateLifecycle.maybe_checkpoint``)."""
        if self._gallery is None:
            return False
        if self._train_lock.locked():
            if self.metrics is not None:
                self.metrics.incr(mn.IVF_RETRAINS_SKIPPED_INFLIGHT)
            return False
        threading.Thread(target=self.rebuild_now, kwargs={"wait": False},
                         daemon=True, name="ivf-retrain").start()
        return True

    # ---- lifecycle edges driven by the gallery ----

    def invalidate(self) -> None:
        """Drop the published state: called (under the gallery write lock)
        on ``reset``/``load_snapshot``/``swap_from`` and on an async-grow
        splice — wholesale row-set changes the cells know nothing about.
        Serving falls back to the exact matcher until a rebuild lands."""
        self._data = None
        self._h_centroids = None
        self._c_dev = None
        self._h_assign = np.zeros((0,), np.int32)
        self._h_counts = None
        self._spill_count = 0
        self._assigned_rows = 0
        self.trained_size = 0
        if self.metrics is not None:
            self.metrics.incr(mn.IVF_INVALIDATIONS)

    def stale(self) -> bool:
        """Cheap staleness check (called outside locks after an add)."""
        data = self._data
        if data is None:
            return False
        if self._spill_count >= self.SPILL_STALE_FRACTION * data.spill_cap:
            return True
        size = self._gallery.size if self._gallery is not None else 0
        return size > self.GROWTH_STALE_FACTOR * max(1, self.trained_size)

    def on_rows_added(self, rows: np.ndarray, start: int) -> None:
        """Incrementally assign freshly enrolled rows (called by
        ``ShardedGallery.add`` under its write lock, AFTER the host
        mirrors hold the rows). ``rows`` are the L2-normalized embeddings;
        row ids are ``start..start+n``. No-op while not ready — the next
        rebuild covers everything.

        Batched: ONE assignment dispatch and one scatter per structure
        (cell side + spill side) per ``ASSIGN_CHUNK`` rows — a per-row
        loop would copy the whole [nlist, max_cell, D] arrays n times
        while holding the gallery write lock, and an unchunked scatter
        would blow the pad-tier cap on a huge WAL-replay record. A row
        that fits neither its cell nor the spill invalidates the
        quantizer (recall must never silently drop a row); the partially
        updated snapshot is never published."""
        if self._data is None:
            return
        rows = np.asarray(rows, np.float32)
        for off in range(0, rows.shape[0], ASSIGN_CHUNK):
            if not self._add_rows_chunk(rows[off:off + ASSIGN_CHUNK],
                                        start + off):
                return  # invalidated: the remaining rows are moot

    def _add_rows_chunk(self, rows: np.ndarray, start: int) -> bool:
        """One <= ASSIGN_CHUNK slice of ``on_rows_added``; False when the
        structures overflowed (or an insert failed) and the quantizer
        invalidated itself. Fail-closed: a scatter that dies mid-chunk
        (transient device error) would leave the host counts claiming
        placements the published lists never got — invalidate instead of
        crashing the enroll thread, exactly the rebuild failure
        contract."""
        try:
            return self._add_rows_chunk_inner(rows, start)
        except Exception:  # noqa: BLE001 — enroll threads must never die
            # to derived-state bookkeeping; exact serving continues.
            logging.getLogger(__name__).exception(
                "ivf incremental insert failed; invalidating")
            self.invalidate()
            return False

    def _add_rows_chunk_inner(self, rows: np.ndarray, start: int) -> bool:
        data = self._data
        if data is None:
            return False
        n = rows.shape[0]
        if not n:
            return True
        cells = self.assign_rows(rows)
        q8, scale = quantize_rows(rows)
        self._grow_assign(start + n - 1)
        c_sel, c_cell, c_pos = [], [], []
        s_sel, s_pos = [], []
        for i in range(n):
            cell = int(cells[i])
            self._h_assign[start + i] = cell
            count = int(self._h_counts[cell])
            if count < data.max_cell:
                c_sel.append(i)
                c_cell.append(cell)
                c_pos.append(count)
                self._h_counts[cell] = count + 1
            elif self._spill_count < data.spill_cap:
                s_sel.append(i)
                s_pos.append(self._spill_count)
                self._spill_count += 1
            else:
                # Cell AND spill full: the structures cannot hold the
                # row; fall back to exact serving until the retrain the
                # caller's staleness poke fires republishes.
                self.invalidate()
                return False
        rids = np.arange(start, start + n, dtype=np.int32)
        cell_sc, spill_sc = self._scatter_jits()
        if c_sel:
            tier = self._pad_tier(len(c_sel))
            c, p, r, qq, ss = self._pad_batch(
                (np.asarray(c_cell, np.int32), np.asarray(c_pos, np.int32),
                 rids[c_sel], q8[c_sel], scale[c_sel]), tier)
            cr, cq, cs = cell_sc(data.cell_rows, data.cell_q8,
                                 data.cell_scale, c, p, r, qq, ss)
            data = data._replace(cell_rows=cr, cell_q8=cq, cell_scale=cs)
        if s_sel:
            tier = self._pad_tier(len(s_sel))
            p, r, qq, ss = self._pad_batch(
                (np.asarray(s_pos, np.int32), rids[s_sel], q8[s_sel],
                 scale[s_sel]), tier)
            sr, sq, sscale = spill_sc(data.spill_rows, data.spill_q8,
                                      data.spill_scale, p, r, qq, ss)
            data = data._replace(spill_rows=sr, spill_q8=sq,
                                 spill_scale=sscale)
        self._data = data
        self._assigned_rows = max(self._assigned_rows, start + n)
        if self.metrics is not None:
            self.metrics.incr(mn.IVF_INCREMENTAL_ROWS, n)
            self.metrics.set_gauge(mn.IVF_SPILL_ROWS, self._spill_count)
        return True

    def _grow_assign(self, max_rid: int) -> None:
        if max_rid < len(self._h_assign):
            return
        grown = np.full((max(max_rid + 1, 2 * max(1, len(self._h_assign))),),
                        -1, np.int32)
        grown[:len(self._h_assign)] = self._h_assign
        self._h_assign = grown

    @staticmethod
    def _pad_batch(arrays, tier: int):
        """Pad scatter operands to the tier by repeating the LAST entry:
        duplicate scatter indices then write the same value, so the pad
        is idempotent and the compile count stays bounded."""
        out = []
        for a in arrays:
            pad = tier - len(a)
            out.append(np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
                       if pad else np.asarray(a))
        return out

    def _scatter_jits(self):
        import jax

        if self._scatter_jit is None:
            def cell_sc(cr, cq, cs, c, p, rid, q8rows, sc):
                return (cr.at[c, p].set(rid), cq.at[c, p].set(q8rows),
                        cs.at[c, p].set(sc))

            def spill_sc(sr, sq, ss, p, rid, q8rows, sc):
                return (sr.at[p].set(rid), sq.at[p].set(q8rows),
                        ss.at[p].set(sc))

            # No donation: in-flight matchers still read the old arrays;
            # the .at copy is device-bandwidth cheap and enrolment-rate.
            self._scatter_jit = (jax.jit(cell_sc), jax.jit(spill_sc))
        return self._scatter_jit

    # ---- sidecar (derived-state persistence keyed by checkpoint) ----

    def sidecar_payload_locked(self) -> Optional[Dict[str, Any]]:
        """Host-copy capture for the sidecar writer — called by
        ``ShardedGallery.snapshot_quantizer`` under the gallery write
        lock, so it pairs atomically with the gallery snapshot taken in
        the same checkpoint critical section."""
        if self._data is None or self._h_centroids is None:
            return None
        return {
            "centroids": self._h_centroids.copy(),
            "assign": self._h_assign.copy(),
            "nlist": self.nlist,
            "seed": self.seed,
            "trained_size": self.trained_size,
            "spill_count": self._spill_count,
            "version": self.version,
            # Embedder version the centroids were trained in: derived
            # state is space-bound — a sidecar surviving a rollout
            # cutover must fail closed to a retrain (state_store checks
            # this on restore; wal_seq keying covers the common case,
            # this is the defense-in-depth).
            "embedder_version": int(getattr(self._gallery,
                                            "embedder_version", 1)),
        }

    def install_from_arrays(self, centroids: np.ndarray,
                            assign: np.ndarray) -> bool:
        """Rebuild the packed structures from a sidecar's (centroids,
        assignment) against the gallery's CURRENT host mirrors — pure
        repack, no k-means — and publish. The pack routine is the same
        one live builds use, so the result is bit-identical to the state
        the sidecar captured."""
        if self._gallery is None:
            raise RuntimeError("quantizer not attached to a gallery")
        g = self._gallery
        emb, _lab, val, _size = g.snapshot()
        centroids = np.asarray(centroids, np.float32)
        if self.auto_nlist:
            # Auto-sized quantizers adopt the sidecar's cell count — the
            # startup guess from ``capacity`` may not match the recovered
            # row set's sizing (and a mismatch here is config drift only
            # when nlist was pinned explicitly).
            self.nlist = int(centroids.shape[0])
        elif int(centroids.shape[0]) != self.nlist:
            return False  # pinned nlist disagrees with the sidecar
        assign_full = np.full((emb.shape[0],), -1, np.int32)
        n = min(len(assign), emb.shape[0])
        assign_full[:n] = assign[:n]
        assign_full[~val] = -1
        if np.any(val & (assign_full < 0)):
            return False  # sidecar does not cover every live row
        packed = self._pack(emb, val, assign_full)
        (cell_rows, cell_q8, cell_scale, spill_rows, spill_q8, spill_scale,
         counts, overflow) = packed
        data = self._device_put(centroids, cell_rows, cell_q8, cell_scale,
                                spill_rows, spill_q8, spill_scale)
        ids = np.nonzero(val)[0]

        def publish():
            self._h_centroids = centroids
            self._c_dev = None
            self._h_assign = assign_full
            self._h_counts = counts
            self._spill_count = overflow
            self._assigned_rows = int(ids[-1]) + 1 if len(ids) else 0
            self.trained_size = int(val.sum())
            self._data = data._replace(gallery_epoch=g._epoch)
            self.version += 1

        g.run_locked(publish)
        return True


def encode_sidecar(payload: Dict[str, Any], wal_seq: int) -> bytes:
    """``MAGIC + u32 header_len + header_json + sha256(header) + body``
    where the body is the raw centroid f32 bytes then the assignment
    int32 bytes, each crc32'd in the header — the same framing discipline
    as the PR-4 checkpoints, because the sidecar makes the same promise:
    a torn write must fail closed (retrain), never half-load."""
    cent = np.ascontiguousarray(payload["centroids"], np.float32)
    assign = np.ascontiguousarray(payload["assign"], np.int32)
    cent_b, assign_b = cent.tobytes(), assign.tobytes()
    header = {
        "format_version": SIDECAR_FORMAT_VERSION,
        "wal_seq": int(wal_seq),
        "nlist": int(payload["nlist"]),
        "dim": int(cent.shape[1]),
        "rows": int(assign.shape[0]),
        "seed": int(payload["seed"]),
        "trained_size": int(payload["trained_size"]),
        "version": int(payload["version"]),
        "embedder_version": int(payload.get("embedder_version", 1)),
        "crc32_centroids": binascii.crc32(cent_b) & 0xFFFFFFFF,
        "crc32_assign": binascii.crc32(assign_b) & 0xFFFFFFFF,
        "created_ts": time.time(),
    }
    blob = json.dumps(header, sort_keys=True).encode("utf-8")
    return (SIDECAR_MAGIC + len(blob).to_bytes(4, "big") + blob
            + hashlib.sha256(blob).digest() + cent_b + assign_b)


def decode_sidecar(blob: bytes) -> Tuple[Dict[str, Any], np.ndarray,
                                         np.ndarray]:
    """Parse + validate sidecar bytes -> (header, centroids, assign);
    raises ``SidecarError`` on any framing/checksum miss."""
    if not blob.startswith(SIDECAR_MAGIC):
        raise SidecarError("bad sidecar magic")
    off = len(SIDECAR_MAGIC)
    if len(blob) < off + 4:
        raise SidecarError("truncated before header")
    hlen = int.from_bytes(blob[off:off + 4], "big")
    off += 4
    if hlen <= 0 or len(blob) < off + hlen + 32:
        raise SidecarError("truncated header")
    header_blob = blob[off:off + hlen]
    if hashlib.sha256(header_blob).digest() != blob[off + hlen:off + hlen + 32]:
        raise SidecarError("header sha256 mismatch")
    try:
        header = json.loads(header_blob.decode("utf-8"))
        version = int(header["format_version"])
        nlist, dim, rows = (int(header["nlist"]), int(header["dim"]),
                            int(header["rows"]))
    except (UnicodeDecodeError, json.JSONDecodeError, KeyError, TypeError,
            ValueError) as exc:
        raise SidecarError(f"header decode failed: {exc!r}") from exc
    if version > SIDECAR_FORMAT_VERSION:
        raise SidecarError(f"sidecar format v{version} newer than supported")
    body = blob[off + hlen + 32:]
    cent_bytes = nlist * dim * 4
    if len(body) != cent_bytes + rows * 4:
        raise SidecarError("payload truncated")
    cent_b, assign_b = body[:cent_bytes], body[cent_bytes:]
    if (binascii.crc32(cent_b) & 0xFFFFFFFF) != header["crc32_centroids"]:
        raise SidecarError("centroid crc32 mismatch")
    if (binascii.crc32(assign_b) & 0xFFFFFFFF) != header["crc32_assign"]:
        raise SidecarError("assignment crc32 mismatch")
    centroids = np.frombuffer(cent_b, np.float32).reshape(nlist, dim).copy()
    assign = np.frombuffer(assign_b, np.int32).copy()
    return header, centroids, assign

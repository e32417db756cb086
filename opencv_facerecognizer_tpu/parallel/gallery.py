"""Sharded enrolled gallery: the TP axis (BASELINE.json:5: "NearestNeighbor
.predict becomes a sharded cosine-similarity matmul against the enrolled
gallery held in TPU HBM").

Design:
- Fixed ``capacity`` (static shapes; XLA recompiles nothing as people
  enroll). Rows beyond ``size`` are invalid and masked to -inf similarity.
- Embeddings live sharded row-wise over the ``tp`` mesh axis; each chip
  computes a [Q, C/tp] bf16 similarity block on its MXU against its HBM
  shard, takes a local top-k, then one small ``all_gather`` of [Q, k]
  candidates per chip merges to the global top-k — the classic
  sharded-matmul + argmax-reduction pattern (SURVEY.md §2.3 TP row).
  Collective traffic is O(Q * k * tp), never O(Q * capacity).
- Labels are tiny ([capacity] int32), so they stay replicated.
- Queries are sharded over ``dp`` and replicated over ``tp``; outputs come
  back sharded over ``dp``.
- Enrolment and the double-buffered atomic swap (``runtime``'s
  model-reload-without-drop, SURVEY.md §5.3): the next snapshot is built
  beside the one being served and published with one attribute write. An
  ``add`` of n rows moves n rows over the host->device link and splices
  them into a copy of the served arrays ON the devices, so at most two
  tier-sized arrays a chip are ever live and the host holds a mirror of
  what it enrolled, never a capacity-sized array;
  ``install_device_rows`` adopts rows that are already on the chips.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS
from opencv_facerecognizer_tpu.utils import metric_names as mn

# numpy, not jnp: a module-level jnp scalar initializes the JAX backend at
# IMPORT time, which blocks every importer (even transport-only child
# processes) whenever the accelerator is unreachable. jnp ops accept the
# numpy scalar identically.
NEG_INF = np.float32(-1e30)

#: ``jax.named_scope`` of each tier's search (the kernel, or the XLA dot and
#: its top-k) and, beside it and never inside it, of what makes one answer
#: of the shards' candidates: the gather over tp, the final top-k, the
#: label gather. Siblings, because the benchmark's scope reader files an
#: operation under the FIRST ``ocvf_<stage>`` of its ``tf_op``.
MATCH_SCOPE = "ocvf_match"
MERGE_SCOPE = "ocvf_merge"

#: Jitted makers of one tier's arrays (``ShardedGallery._tier_jit``), keyed
#: (mesh, dim, store dtype, pad label) + ("empty", capacity) | ("grow", old,
#: new) | ("splice", donated?): a few small programs a kind of gallery,
#: each compiled once a process however many galleries come and go. No
#: lock: two threads that miss at once build the same program twice and
#: the later one stays.
_TIER_JITS: dict = {}


def take_labels_with_sentinel(labels, idx, labels_pad: int):
    """Gather labels for top-k indices, mapping sentinel ``idx == -1`` slots
    (a shard/gallery with fewer than k valid rows) to the pad label — a
    clamped/wrapped gather would pair a real subject's label with the
    -1e30 sentinel sim."""
    return jnp.where(
        idx < 0,
        jnp.int32(labels_pad),
        jnp.take(labels, jnp.maximum(idx, 0)),
    )


def match_global(q, g, valid, labels, *, k: int, mesh: Mesh):
    """Global-view sharded match: the GSPMD formulation.

    Written on full arrays with sharding *annotations* instead of shard_map
    (pick a mesh, annotate, let XLA insert the collectives): the similarity
    matmul is computed shard-local (g row-sharded over tp -> sims
    column-sharded), then a two-phase top-k — phase 1 per tp chunk (local,
    no comms), phase 2 over the tp*k gathered candidates — keeps collective
    traffic O(Q * k * tp) instead of all-gathering [Q, capacity].

    Chosen over shard_map because jit-with-shardings compiles to the same
    local compute with nothing to hand-write per mesh shape; whether an
    explicit shard_map formulation dispatches or runs differently on the
    locally attached chips: not measured.

    q [Q, D]; g [C, D] sharded P(tp, None); valid [C]; labels [C].
    Returns (labels [Q, k], sims [Q, k], gallery indices [Q, k]).
    """
    tp = mesh.shape[TP_AXIS]
    cap = g.shape[0]
    chunk = cap // tp
    qn = q.shape[0]
    local_k = min(k, chunk)
    with jax.named_scope(MATCH_SCOPE):
        # MXU block: bf16 operands, f32 accumulation.
        sims = jax.lax.dot_general(
            q.astype(jnp.bfloat16),
            g.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Q, C]
        sims = jnp.where(valid[None, :], sims, NEG_INF)
        if tp == 1:
            # Singleton tp: the two-phase split is identical math but the
            # reshape + sharding constraint break XLA's matmul->top_k fusion
            # (measured on v5e: 2.40 vs 1.00 ms/batch for the whole fused
            # serving step at 16k rows) — take the direct top_k.
            top_vals, top_gidx = jax.lax.top_k(sims, min(k, cap))
        else:
            # Phase 1: per-chunk top-k, chunk == tp shard (the constraint
            # pins the reshape to be shard-local).
            s3 = sims.reshape(qn, tp, chunk)
            s3 = jax.lax.with_sharding_constraint(
                s3, NamedSharding(mesh, P(DP_AXIS, TP_AXIS, None))
            )
            vals, idx = jax.lax.top_k(s3, local_k)  # [Q, tp, local_k]
            gidx = idx + (jnp.arange(tp, dtype=jnp.int32) * chunk)[None, :, None]
    with jax.named_scope(MERGE_SCOPE):
        if tp > 1:
            # Phase 2: merge the tp*local_k candidates (tiny; XLA gathers
            # these).
            vals2 = vals.reshape(qn, tp * local_k)
            gidx2 = gidx.reshape(qn, tp * local_k)
            top_vals, pos = jax.lax.top_k(vals2, min(k, tp * local_k))
            top_gidx = jnp.take_along_axis(gidx2, pos, axis=1)
        top_labels = jnp.take(labels, top_gidx)
    return top_labels, top_vals, top_gidx


def match_pod_pallas(q, g, valid, labels, *, k: int, mesh: Mesh,
                     interpret: bool = False, labels_pad: int = -1):
    """Pod-scale matcher: ``shard_map`` over tp, pallas streaming kernel
    per shard, collective merge of the tiny candidate sets.

    Each chip streams its [capacity/tp, D] gallery shard through
    ``ops.pallas_match.streaming_match_topk`` (local [Q, k] top-k, no
    [Q, capacity/tp] materialization), then one ``all_gather`` over tp of
    [Q, k] values+indices — O(Q * k * tp) ICI traffic — and a final
    ``lax.top_k`` merge on every chip. This is the multi-chip form of the
    pallas fast path: GSPMD cannot partition a custom call, so the shard
    decomposition is written explicitly here.

    The serving matcher of a mesh of several TPU chips once a shard holds
    ``PALLAS_MIN_CAPACITY`` rows (``ShardedGallery._pallas_enabled``
    selects it, ``match_fn`` returns it). It has run on four v5e chips at
    12,582,912 rows a shard; PERF.md section 6, PR 38, holds the numbers.
    Equal similarities break toward the lowest GLOBAL row: the kernel
    keeps the lowest local row, and the merge's ``top_k`` the candidate
    of the lowest shard.

    Shapes/shardings: q [Q, D] dp-sharded; g [C, D] tp row-sharded;
    valid [C] tp-sharded; labels [C] replicated. Returns the same
    (labels [Q, k], sims [Q, k], gallery indices [Q, k]) as match_global.
    """
    from opencv_facerecognizer_tpu.ops.pallas_match import streaming_match_topk

    tp = mesh.shape[TP_AXIS]
    chunk = g.shape[0] // tp

    def shard_body(q_l, g_l, valid_l, labels_l):
        with jax.named_scope(MATCH_SCOPE):
            vals, idx = streaming_match_topk(
                q_l, g_l, valid_l, k=min(k, chunk), interpret=interpret
            )
        with jax.named_scope(MERGE_SCOPE):
            offset = jax.lax.axis_index(TP_AXIS).astype(jnp.int32) * chunk
            # A shard with fewer valid rows than k emits sentinel -1
            # indices; keep them -1 instead of offsetting into a neighbor
            # shard's rows.
            idx = jnp.where(idx < 0, -1, idx + offset)
            # One tiled gather each -> [Q, tp*local_k] candidates on every
            # chip.
            cand_v = jax.lax.all_gather(vals, TP_AXIS, axis=1, tiled=True)
            cand_i = jax.lax.all_gather(idx, TP_AXIS, axis=1, tiled=True)
            out_k = min(k, cand_v.shape[1])
            top_v, pos = jax.lax.top_k(cand_v, out_k)
            top_i = jnp.take_along_axis(cand_i, pos, axis=1)
            top_l = take_labels_with_sentinel(labels_l, top_i, labels_pad)
        return top_l, top_v, top_i

    mapped = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(DP_AXIS, None), P(TP_AXIS, None), P(TP_AXIS), P()),
        out_specs=(P(DP_AXIS, None), P(DP_AXIS, None), P(DP_AXIS, None)),
        check_vma=False,
    )
    return mapped(q, g, valid, labels)


class EmbeddingDimMismatchError(ValueError):
    """A gallery swap was attempted across embedding dimensions. A new
    embedder with a different D produces vectors in a DIFFERENT space —
    installing them over rows scored in the old space would silently mix
    embedder versions in one served shard set. The only sanctioned route
    is the staged re-embed rollout (``runtime.rollout``): re-embed every
    row into the new space, fence the WAL with a cutover record, then
    install the staged set whole. Subclasses ``ValueError`` so pre-rollout
    callers that caught the old dim-mismatch error keep working."""


class GalleryData(NamedTuple):
    """One immutable snapshot of the device-visible gallery state.

    Reader side of the concurrency story: all reads go through a single
    ``self._data`` attribute load (atomic at Python level), so a reader can
    never observe a mixed snapshot (e.g. new valid mask against old
    embeddings). Writer side: ``add``/``reset``/``swap_from`` serialize on
    an internal lock, so concurrent enrolments can't both claim the same
    rows."""

    embeddings: jnp.ndarray  # [capacity, dim], P(tp, None)
    labels: jnp.ndarray  # [capacity], replicated
    valid: jnp.ndarray  # [capacity], P(tp)
    size: int
    #: gallery ``_epoch`` at snapshot build — reset/swap_from/load_snapshot
    #: bump it. Pairs this snapshot with derived state (the IVF quantizer
    #: stamps its publishes with the same counter): a reader that took the
    #: two snapshots non-atomically rejects a cross-epoch pair instead of
    #: matching one row set against another's inverted lists.
    epoch: int = 0

    @property
    def capacity(self) -> int:
        """Tier of THIS snapshot. Cache keys must derive from the snapshot
        (not ``gallery.capacity``) so a concurrent grow can never pair one
        tier's compiled step with another tier's arrays — the mixed pairing
        forces an XLA retrace on the serving thread, the exact stall
        async-grow prewarm exists to avoid."""
        return int(self.embeddings.shape[0])


class ShardedGallery:
    """Enrolled gallery of L2-normalized embeddings, row-sharded over tp."""

    #: capacity above which the pallas streaming kernel beats the XLA
    #: materialize+top_k path on real hardware (measured on v5e: 1.08x at
    #: 131k rows, 1.73x at 1M; parity/noise at 16k).
    PALLAS_MIN_CAPACITY = 65536

    #: capacity above which ``match_mode="auto"`` switches to the
    #: two-stage IVF path (when a ready quantizer is attached): the exact
    #: scan is linear in capacity (BENCH_r05: 1.356 ms/batch at 262k,
    #: 3.607 at 1M) while the shortlist+rerank cost scales with the
    #: probed cells — below this tier the exact scan is already cheap
    #: and the IVF recall trade buys nothing.
    IVF_MIN_CAPACITY = 262144

    #: start background-compiling the next tier once fill crosses this
    #: fraction (async_grow mode), so the eventual grow swaps to an
    #: already-compiled graph (SURVEY.md §5.3 elastic recovery).
    PREWARM_FILL_FRACTION = 0.75

    def __init__(
        self,
        capacity: int,
        dim: int,
        mesh: Mesh,
        labels_pad: int = -1,
        use_pallas: Optional[bool] = None,
        async_grow: bool = False,
        store_dtype: Any = jnp.float32,
        embedder_version: int = 1,
    ):
        self.mesh = mesh
        #: version of the embedder whose space EVERY row in this gallery
        #: lives in — one gallery never mixes versions (the rollout
        #: subsystem's fencing invariant, ``runtime.rollout``). Stamped
        #: into checkpoint headers and WAL rows by ``StateLifecycle``;
        #: changed only by a whole-set install (``load_snapshot`` /
        #: ``swap_from`` adopting the donor's version) — never row-wise.
        self.embedder_version = int(embedder_version)
        self._use_pallas_cfg = use_pallas
        tp = mesh.shape[TP_AXIS]
        # Round capacity up so every tp shard is equal (static shapes).
        self.capacity = int(np.ceil(capacity / tp) * tp)
        self.dim = int(dim)
        self.labels_pad = labels_pad
        #: device dtype of the gallery rows. Both matchers already compute
        #: the similarity matmul in bf16 operands / f32 accumulation
        #: (match_global:76, pallas_match kernel), so ``store_dtype=
        #: jnp.bfloat16`` is NUMERICALLY IDENTICAL on the match path while
        #: halving gallery HBM and H2D bytes (1 GB -> 0.5 GB at 1M rows).
        #: Host mirrors stay f32 (enrolment truth,
        #: snapshot/serialization unchanged); the cast happens host-side at
        #: install so the transfer itself is half-width. Default stays f32
        #: for drop-in familiarity.
        self.store_dtype = jnp.dtype(store_dtype)
        self._emb_sharding = NamedSharding(mesh, P(TP_AXIS, None))
        self._lab_sharding = NamedSharding(mesh, P())
        self._valid_sharding = NamedSharding(mesh, P(TP_AXIS))
        #: backends that alias a donated input; elsewhere (CPU) a donation
        #: is ignored with a warning per compile
        self._donate = mesh.devices.flat[0].platform in ("tpu", "gpu")
        # Host mirror: what was enrolled THROUGH THE HOST, and no more. It
        # covers rows [_host_base, _host_base + len) and grows with them
        # (``_mirror_write``); no array of capacity x dim is ever made on
        # the host at construction or on ``add``. Rows below ``_host_base``
        # were installed on the device (``install_device_rows``) and have
        # their truth there; ``snapshot`` reads them back when asked.
        self._host_base = 0
        self._host_emb = np.zeros((0, self.dim), np.float32)
        self._host_lab = np.zeros((0,), np.int32)
        self._host_val = np.zeros((0,), bool)
        self._write_lock = threading.Lock()
        #: always-on tallies (``utils.metric_names``): rows that crossed
        #: the host->device link, whole-set installs from device arrays.
        #: ``attach_observability`` carries them into a ``Metrics``.
        self.metrics = None
        self.tracer = None
        self.rows_uploaded = 0
        self.bulk_installs = 0
        self.grow_count = 0
        # ---- async (off-the-serving-path) growth state ----
        # ``async_grow=True`` turns an overflowing add() into: stage the
        # rows host-side, compile the next tier's graphs on a background
        # thread (prewarm_hooks), build + install the grown snapshot there,
        # publish atomically. Serving threads NEVER pay the XLA recompile;
        # the cost moves to enrolment-to-matchable latency (observable via
        # ``pending_rows`` / ``wait_ready``). Default stays synchronous:
        # enrolment tools that want rows matchable on return keep that
        # contract.
        self.async_grow = bool(async_grow)
        #: callables invoked with the TARGET capacity on the grow worker
        #: thread BEFORE the grown snapshot is installed — the fused
        #: pipeline registers its step-compile here (parallel.pipeline).
        self.prewarm_hooks = []
        #: callables invoked with a capacity THRESHOLD after a grow
        #: publishes: pipelines drop compiled entries for tiers strictly
        #: below it. Growing A->B->C evicts A's executables when C installs
        #: (B survives for readers that took their snapshot before C) —
        #: without this, crossing 16k->1M (7 tiers x shapes x dtypes)
        #: permanently retains every stale tier's executables.
        self.evict_hooks = []
        self._pending: list = []  # [[emb_rows, lab_rows, normalized?]] staged
        self._pending_count = 0
        self._growing = False
        self._grow_thread: Optional[threading.Thread] = None
        self._grow_done = threading.Event()
        self._grow_done.set()
        self._epoch = 0  # bumped by reset/swap_from to invalidate a grow
        self._warmed_capacities = set()
        self._warm_events = {}  # capacity -> Event, set when its warm ends
        self.last_grow_info: dict = {}
        # ---- optional IVF coarse quantizer (parallel.quantizer) ----
        # Derived state: the gallery drives every lifecycle edge —
        # incremental assignment on add, invalidation on reset/
        # load_snapshot/swap_from/async-grow splice, staleness pokes.
        # ``match_mode``: "exact" never uses it, "ivf" always (when
        # ready), "auto" switches at IVF_MIN_CAPACITY.
        self.quantizer = None
        self.match_mode = "exact"
        self._data = GalleryData(*self._empty_arrays(self.capacity), size=0)
        self._match_cache = {}

    # Single-attribute snapshot: the only device-state read path.
    @property
    def data(self) -> GalleryData:
        return self._data

    @property
    def embeddings(self) -> jnp.ndarray:
        return self._data.embeddings

    @property
    def labels(self) -> jnp.ndarray:
        return self._data.labels

    @property
    def valid(self) -> jnp.ndarray:
        return self._data.valid

    @property
    def size(self) -> int:
        return self._data.size

    # ---- enrolment (host-side; serving never blocks on these) ----

    @staticmethod
    def _normalize_rows(embeddings: np.ndarray) -> np.ndarray:
        return embeddings / np.maximum(
            np.linalg.norm(embeddings, axis=-1, keepdims=True), 1e-12
        )

    def _host_cast(self, x: np.ndarray) -> np.ndarray:
        """Cast to store_dtype on the host so the H2D wire carries the
        narrow bytes (ml_dtypes' f32->bf16 astype measures ~640M el/s —
        not a bottleneck). bf16 ships as its uint16 bits, the same bytes
        as a standard numpy dtype, which every PJRT client puts through
        its fast path; ``_splice_fn`` bitcasts them back on the device,
        inside the program that splices them (no array of its own).
        (Whether a plain ml_dtypes bf16 put is slower on the local chip:
        not measured.)"""
        if self.store_dtype == np.float32:
            return np.asarray(x, np.float32)
        cast = np.asarray(x).astype(self.store_dtype)
        return cast.view(np.uint16) if self.store_dtype == jnp.bfloat16 else cast

    # ---- one tier's device arrays: made, grown and spliced ON the devices ----

    def _tier_jit(self, key, build):
        """The jitted maker ``key`` of this gallery's kind of tier, shared
        by every gallery of the same mesh, width, dtype and pad label in
        the process: a replica's re-anchor or a rollout's staged gallery
        compiles nothing a gallery before it has compiled."""
        key = (self.mesh, self.dim, self.store_dtype, self.labels_pad, *key)
        fn = _TIER_JITS.get(key)
        if fn is None:
            fn = _TIER_JITS[key] = build()
        return fn

    def _tier_shardings(self):
        return (self._emb_sharding, self._lab_sharding, self._valid_sharding)

    def _empty_arrays(self, capacity: int):
        """(embeddings, labels, valid) of an empty tier: zero rows, pad
        labels, nothing valid, each shard made on the chip that holds it —
        no host array, and no single-device array of the whole tier."""
        dim, dtype, pad = self.dim, self.store_dtype, self.labels_pad
        make = self._tier_jit(("empty", capacity), lambda: jax.jit(
            lambda: (jnp.zeros((capacity, dim), dtype),
                     jnp.full((capacity,), pad, jnp.int32),
                     jnp.zeros((capacity,), bool)),
            out_shardings=self._tier_shardings()))
        return make()

    def _grown_arrays(self, arrays, capacity: int):
        """``arrays`` padded out to ``capacity`` rows on the devices (a
        tier's shard boundaries move with its capacity, so rows change
        chips: XLA's collectives, nothing through the host). The result
        is the caller's own; ``arrays`` stay valid for their readers."""
        old = int(arrays[0].shape[0])
        extra, pad = capacity - old, self.labels_pad
        grow = self._tier_jit(("grow", old, capacity), lambda: jax.jit(
            lambda e, l, v: (jnp.pad(e, ((0, extra), (0, 0))),
                             jnp.pad(l, (0, extra), constant_values=pad),
                             jnp.pad(v, (0, extra))),
            out_shardings=self._tier_shardings()))
        return grow(*arrays)

    def _splice_fn(self, donate: bool):
        """Jitted ``(emb, lab, val, rows, labs, vals, start, count) ->
        (emb, lab, val)``: rows [start, start + count) replaced by the
        first ``count`` of the piece handed in, everything else as it was.
        ``shard_map`` over tp, so a shard writes the rows that fall in its
        own range and drops the rest; the piece arrives replicated (its
        own rows, never a shard's worth). Without ``donate`` the result is
        a copy and the inputs stay valid for whoever reads them."""
        def build():
            store = self.store_dtype

            def body(emb_l, lab, val_l, rows, labs, vals, start, count):
                chunk, n = emb_l.shape[0], rows.shape[0]
                if rows.dtype != store:  # bf16 travels as its uint16 bits
                    rows = jax.lax.bitcast_convert_type(rows, store)
                lo = jax.lax.axis_index(TP_AXIS).astype(jnp.int32) * chunk
                at = jnp.arange(n, dtype=jnp.int32)
                pos = start - lo + at
                # the piece's padding, and rows outside this shard: an
                # index past the end, dropped
                pos = jnp.where((at < count) & (pos >= 0) & (pos < chunk),
                                pos, chunk)
                emb_l = emb_l.at[pos].set(rows, mode="drop")
                val_l = val_l.at[pos].set(vals, mode="drop")
                lab = lab.at[jnp.where(at < count, start + at,
                                       lab.shape[0])].set(labs, mode="drop")
                return emb_l, lab, val_l

            mapped = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(TP_AXIS, None), P(), P(TP_AXIS)) + (P(),) * 5,
                out_specs=(P(TP_AXIS, None), P(), P(TP_AXIS)),
                check_vma=False)
            return jax.jit(mapped, donate_argnums=(0, 1, 2) if donate else ())

        return self._tier_jit(("splice", donate), build)

    #: rows a piece of an upload holds, beside the most that
    #: ``CHUNK_UPLOAD_BYTES`` allows (65,536 at 256-d bf16): a kind of
    #: gallery compiles three splice programs, twice over, however many
    #: rows its installs bring. A piece cut to the rows it carried met a
    #: new compile at every new size: a replica's re-anchor of 181 rows
    #: took 0.4 s for five of them, and the soak tests' deadlines found it
    #: (CPU; PR 38). The price is padding on the wire, under 512 rows an
    #: install and never over eight times the rows it carries: a rest of
    #: up to 64 rows goes in pieces of 8, the program every enrolment of a
    #: few images has compiled already (a replica's re-anchor of 12 rows
    #: that met the 512-row program's first compile held its readers up
    #: long enough to fail the registry soak under a loaded CPU).
    PIECE_ROWS = (8, 512)

    def _splice_rows(self, arrays, emb: np.ndarray, lab: np.ndarray,
                     val: np.ndarray, start: int, *, owned: bool,
                     paced: bool = False, cancel=None, info=None):
        """Host rows ``emb`` [n, dim] (float32), their labels and validity
        into the device ``arrays`` at row ``start``: these n rows and the
        padding of the last piece are all that crosses the host->device
        link. With ``owned`` False the arrays belong to a published
        snapshot: the first piece copies them (readers keep theirs; two
        tier-sized arrays a chip is the most that is ever live), later
        pieces update that copy in place.

        Whole pieces of the most rows ``CHUNK_UPLOAD_BYTES`` allows, then
        whole pieces of 512, then the rest: up to 64 rows in pieces of 8,
        more in ONE piece of 512, the last piece padded out on the wire
        with rows the program drops (``PIECE_ROWS``). An enrolment of 65
        to 512 rows is one dispatch, a smaller one at most eight.
        ``paced`` (the grow worker) awaits each
        piece before the next is queued, so a serving transfer never waits
        behind more than one piece on the link; each piece gets its OWN
        deadline (``CHUNK_PACING_TIMEOUT_S``), flagged in ``info`` when it
        expires, and the FIRST pacing failure stops pacing for the rest:
        under a hang-mode backend the stall is one deadline, not pieces *
        deadline (the residency wait still gates the publish). ``cancel``
        is sampled between pieces and inside the pacing poll, so a reset
        aborts within one tick."""
        import time as _time

        t0 = _time.monotonic()
        n = int(len(emb))
        row_bytes = self.dim * self.store_dtype.itemsize
        most = max(1, self.CHUNK_UPLOAD_BYTES // row_bytes)
        sizes = [r for r in self.PIECE_ROWS if r < most] + [most]
        small = sizes[0]
        rep = self._lab_sharding  # replicated over the mesh
        lab = np.asarray(lab, np.int32)
        val = np.asarray(val, bool)
        at = 0
        while at < n:
            if cancel is not None and cancel():
                return arrays  # doomed snapshot; publish check discards it
            left = n - at
            count = next((r for r in reversed(sizes[1:] or sizes) if r <= left),
                         small if small < left <= 8 * small else left)
            pad = next(r for r in sizes if r >= count) - count
            cut = (self._host_cast(emb[at:at + count]), lab[at:at + count],
                   val[at:at + count])
            if pad:
                cut = tuple(np.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in cut)
            sent = jax.device_put(cut, rep)
            arrays = self._splice_fn(owned and self._donate)(
                *arrays, *sent, np.int32(start + at), np.int32(count))
            owned = True
            at += count
            if paced:
                paced = self._pace_chunk(
                    arrays[0], _time.monotonic() + self.CHUNK_PACING_TIMEOUT_S,
                    cancel=cancel, info=info)
        self.rows_uploaded += n
        if self.metrics is not None:
            self.metrics.incr(mn.GALLERY_ROWS_UPLOADED, n)
        self._emit_install(t0, rows=n, nbytes=n * row_bytes, source="host")
        return arrays

    def _emit_install(self, t0: float, rows: int, nbytes: int,
                      source: str) -> None:
        """Lifecycle span ``gallery_install``: the rows and bytes one
        install moved (``source`` host: over the link; device: adopted)."""
        if self.tracer is not None:
            import time as _time

            from opencv_facerecognizer_tpu.utils.tracing import LIFECYCLE_TOPIC

            self.tracer.emit(
                self.tracer.new_trace(), "gallery_install",
                topic=LIFECYCLE_TOPIC, t0=t0, dur=_time.monotonic() - t0,
                rows=rows, bytes=nbytes, source=source)

    def attach_observability(self, metrics, tracer=None) -> None:
        """Wire the serving process's ``Metrics`` and ``Tracer`` (built
        after the gallery is): the gauge ``gallery_shards``, and the two
        counters brought up to what start-up already did."""
        fresh = metrics is not None and metrics is not self.metrics
        self.metrics, self.tracer = metrics, tracer
        if fresh:
            metrics.set_gauge(mn.GALLERY_SHARDS, self.mesh.shape[TP_AXIS])
            metrics.incr(mn.GALLERY_ROWS_UPLOADED, self.rows_uploaded)
            metrics.incr(mn.GALLERY_BULK_INSTALLS, self.bulk_installs)

    def _mirror_write(self, at: int, emb: np.ndarray, lab: np.ndarray) -> None:
        """Rows [at, at + n) into the host mirror, which grows (doubling)
        to hold them; the caller holds the write lock."""
        lo = at - self._host_base
        need = lo + len(emb)
        have = len(self._host_lab)
        if need > have:
            rows = max(need, min(max(2 * have, 64),
                                 self.capacity - self._host_base))
            grown = (np.zeros((rows, self.dim), np.float32),
                     np.full((rows,), self.labels_pad, np.int32),
                     np.zeros((rows,), bool))
            for new, old in zip(grown, (self._host_emb, self._host_lab,
                                        self._host_val)):
                new[:have] = old
            self._host_emb, self._host_lab, self._host_val = grown
        self._host_emb[lo:need] = emb
        self._host_lab[lo:need] = lab
        self._host_val[lo:need] = True

    def host_valid(self) -> np.ndarray:
        """Validity of rows [0, mirrored end), the rows a quantizer's
        catch-up walks; the caller holds the write lock. Rows installed on
        the device are read back from the snapshot."""
        if not self._host_base:
            return self._host_val.copy()
        return np.concatenate([
            np.asarray(self._data.valid[:self._host_base]), self._host_val])

    def host_rows(self, lo: int, hi: int) -> np.ndarray:
        """Float32 rows [lo, hi): from the mirror, or read back from the
        device where they were installed there. Caller holds the lock."""
        base = self._host_base
        if lo >= base:
            return self._host_emb[lo - base:hi - base]
        head = np.asarray(self._data.embeddings[lo:min(hi, base)], np.float32)
        return np.concatenate([head, self._host_emb[:max(hi - base, 0)]])

    def add(self, embeddings: np.ndarray, labels: np.ndarray) -> None:
        """Append L2-normalized rows, auto-growing on overflow.

        Synchronous mode (default): growth doubles capacity (tp-aligned)
        and installs the bigger arrays before returning — rows are
        matchable on return, but the static-shape change means the matcher
        (and the fused pipeline step) recompile once on the next call,
        stalling that serving batch by seconds on real hardware.

        ``async_grow=True`` (the serving configuration): an overflowing
        add stages its rows host-side RAW and returns immediately — even
        the L2 normalization runs on the grow worker (measured 16 s for
        920k rows on a 1-core host; an enrolling connector thread must not
        pay that). The worker compiles the next tier's graphs
        (``prewarm_hooks``), normalizes + splices the staged rows, uploads
        the grown snapshot, WAITS for device residency (serving keeps
        reading the old tier — otherwise the first new-tier call absorbs
        the H2D of a large gallery; how long that is on the local chip:
        not measured), then publishes atomically. Rows become
        matchable when ``wait_ready`` unblocks (``pending_rows`` exposes
        the in-flight count). Additionally, any add that fills the gallery
        past ``PREWARM_FILL_FRACTION`` kicks the next tier's compile
        early, so the eventual grow usually only pays copy + upload.
        """
        embeddings = np.asarray(embeddings, np.float32)
        labels = np.asarray(labels, np.int32)
        n = embeddings.shape[0]
        # Optimistic branch predict, OUTSIDE the lock: the sync path needs
        # normalized rows, and normalizing a large add while holding the
        # write lock would block every other enroller behind it. A raced
        # prediction is only a cost shift: predicted-sync-but-staged wastes
        # one normalization (flagged True, worker skips), predicted-staged-
        # but-sync normalizes under the lock (rare; both windows are the
        # gap between this read and the locked re-check).
        normalized = not (self.async_grow and (self._growing or self._pending
                                               or self.size + n > self.capacity))
        if normalized:
            embeddings = self._normalize_rows(embeddings)  # dividing copy
        else:
            # Private copy before staging: asarray is a no-copy view of a
            # float32 input, and a staged-by-reference buffer the caller
            # refills after add() returns would enroll garbage (the worker
            # may not splice for seconds). ~0.3 s memcpy at 920k rows vs
            # the 16 s normalization being deferred.
            embeddings = np.array(embeddings, copy=True)
        start_worker = False
        evict_below = None
        with self._write_lock:
            size = self.size
            if self.async_grow and (self._growing or self._pending
                                    or size + n > self.capacity):
                # Stage RAW; the worker owns all host-array mutation while
                # a grow is in flight (a direct write here would race the
                # worker's copy of the old arrays) and normalizes staged
                # rows off this thread. Entries are mutable lists so the
                # worker can swap in the normalized array in place:
                # [rows, labels, normalized?]. Non-empty pending with no
                # worker means a previous grow FAILED: later adds must
                # queue behind the stranded rows (enrolment order), and
                # this add restarts the worker to retry them. Labels are
                # copied HERE, at the staging site: asarray of an int32
                # input is a no-copy view, and the worker may splice
                # seconds after add() returns — a caller reusing its label
                # buffer would otherwise enroll wrong identities (the
                # embeddings already got their private copy above, or are
                # a fresh dividing copy on the lost-race path).
                self._pending.append([embeddings, np.array(labels, copy=True),
                                      normalized])
                self._pending_count += n
                if not self._growing:
                    self._growing = True
                    self._grow_done.clear()
                    start_worker = True
            else:
                data = self._data
                arrays, owned = (data.embeddings, data.labels, data.valid), False
                if size + n > self.capacity:
                    evict_below = self.capacity  # tier being replaced
                    self.capacity = self._next_capacity(size + n)
                    self.grow_count += 1
                    arrays, owned = self._grown_arrays(arrays, self.capacity), True
                # The host mirror is the truth of what the host enrolled:
                # no device readback is needed (or wanted) under the lock.
                if not normalized:  # lost the branch-predict race
                    embeddings = self._normalize_rows(embeddings)
                self._mirror_write(size, embeddings, labels)
                if self.quantizer is not None:
                    # Incremental IVF assignment, under the same write
                    # lock as the mirror update: the rows land in their
                    # cells (or the spill) before the snapshot below
                    # publishes them as matchable, so the two-stage path
                    # never misses a row the exact path would find.
                    self.quantizer.on_rows_added(embeddings, size)
                # These n rows cross the link and are spliced into a copy
                # of the served arrays on the devices; ONE attribute write
                # publishes, so a reader never sees a partial install.
                arrays = self._splice_rows(arrays, embeddings, labels,
                                           np.ones((n,), bool), size,
                                           owned=owned)
                self._data = GalleryData(*arrays, size=size + n,
                                         epoch=self._epoch)
        if evict_below is not None:
            self._evict_stale(evict_below)
        if not self._growing:
            # Staleness poke outside the lock (a retrain mid-grow would
            # only be invalidated by the splice anyway).
            self._poke_quantizer()
        if start_worker:
            self._grow_thread = threading.Thread(
                target=self._grow_worker, daemon=True, name="gallery-grow"
            )
            self._grow_thread.start()
        elif (self.async_grow and not self._growing
              and self.size >= self.PREWARM_FILL_FRACTION * self.capacity):
            # Early warm: compile the next tier while serving continues at
            # the current one, so the eventual grow swap finds warm caches.
            self._prewarm_async(self._next_capacity(self.capacity + 1))

    @property
    def pending_rows(self) -> int:
        """Rows staged by async grow, not yet matchable."""
        return self._pending_count

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until the current async grow attempt finishes. On success
        ``pending_rows == 0`` and the staged rows are matchable; a failed
        attempt leaves ``pending_rows > 0`` with the exception recorded in
        ``last_grow_info["error"]`` (the next add() retries the grow)."""
        return self._grow_done.wait(timeout)

    def _next_capacity(self, needed: int) -> int:
        tp = self.mesh.shape[TP_AXIS]
        new_capacity = max(self.capacity, 1)
        while new_capacity < needed:
            new_capacity *= 2
        return int(np.ceil(new_capacity / tp) * tp)

    def _run_prewarm_hooks(self, capacity: int, info: dict) -> None:
        """Warm one tier exactly once across threads: the first caller
        (early-warm thread or grow worker) compiles; any concurrent caller
        for the same tier WAITS on its completion event instead of racing
        a duplicate compile (duplicate scratch arrays at a 1M-row tier
        are a device-memory spike, and the grow worker must not install
        before the compile has landed either way)."""
        import time as _time

        with self._write_lock:
            if capacity in self._warmed_capacities:
                info["prewarm_s"] = 0.0
                return
            ev = self._warm_events.get(capacity)
            if ev is None:
                ev = self._warm_events[capacity] = threading.Event()
                owner = True
            else:
                owner = False
        if not owner:
            ev.wait(timeout=600)
            info["prewarm_s"] = 0.0  # another thread paid for it
            return
        t0 = _time.perf_counter()
        try:
            for hook in list(self.prewarm_hooks):
                try:
                    hook(capacity)
                except Exception as e:  # serving must survive a failed
                    # warm: the fallback is the old behavior (compile on
                    # first call).
                    info.setdefault("prewarm_errors", []).append(repr(e))
        finally:
            with self._write_lock:
                self._warmed_capacities.add(capacity)
                self._warm_events.pop(capacity, None)
            ev.set()
        info["prewarm_s"] = round(_time.perf_counter() - t0, 3)

    def _prewarm_async(self, capacity: int) -> None:
        with self._write_lock:
            started = (capacity in self._warmed_capacities
                       or capacity in self._warm_events)
        if started or not self.prewarm_hooks:
            return
        threading.Thread(
            target=self._run_prewarm_hooks, args=(capacity, {}),
            daemon=True, name="gallery-prewarm",
        ).start()

    #: grow worker gives up waiting for device residency after this long
    #: and publishes anyway (availability over stall avoidance); generous
    #: (a 1M-row f32 gallery is ~1 GB; the local link's rate is not
    #: measured).
    RESIDENCY_TIMEOUT_S = 300.0

    @staticmethod
    def _await_residency(data: "GalleryData", timeout_s: float,
                         cancel=None, info=None) -> bool:
        """Poll ``jax.Array.is_ready`` (non-blocking, so ``cancel`` and
        the deadline are honoured between checks) until the snapshot's
        H2D transfers complete. True on
        resident, False on timeout or a backend without is_ready.
        ``cancel()`` returning True aborts the wait immediately — a
        reset/swap_from that doomed this snapshot must not keep the
        worker polling for up to the full timeout."""
        import time as _time

        arrays = (data.embeddings, data.labels, data.valid)
        deadline = _time.monotonic() + timeout_s
        while _time.monotonic() < deadline:
            if cancel is not None and cancel():
                return True  # doomed snapshot; publish check discards it
            try:
                if all(a.is_ready() for a in arrays):
                    return True
            except (AttributeError, NotImplementedError):
                return True  # no is_ready on this backend: don't block
            except Exception as e:
                # A transient backend error must not silently skip the
                # wait (publishing early recreates the 36 s first-call
                # stall this path exists to prevent) — record and keep
                # polling until resident or timeout.
                if info is not None and "residency_probe_error" not in info:
                    info["residency_probe_error"] = repr(e)
            _time.sleep(0.02)
        return False

    def _grow_worker(self) -> None:
        """Off-the-serving-path growth: compile (hooks) -> copy the served
        rows into the next tier ON the devices -> normalize staged rows ->
        upload and splice them (paced) -> await residency -> atomic
        publish. Serving threads keep reading the OLD snapshot until the
        grown arrays are device-resident — publishing earlier makes the
        first new-tier call absorb the staged rows' H2D transfer.
        ``reset``/``swap_from`` bump ``_epoch`` to invalidate an in-flight
        grow; the epoch is re-checked at splice AND at publish, so a
        reset during the residency wait wins and the stale snapshot is
        dropped."""
        import time as _time

        info = {}
        spliced = None  # popped-but-unpublished entries; see except below
        epoch = None
        try:
            while True:
                spliced = None
                # Per-round flags: a round-1 timeout must not misreport a
                # round-2 publish that DID wait successfully.
                info.pop("residency_timeout", None)
                info.pop("residency_probe_error", None)
                with self._write_lock:
                    if not self._pending:
                        self._growing = False
                        self._grow_done.set()
                        self.last_grow_info = info
                        return
                    epoch = self._epoch
                    old = self._data  # adds stage while a grow is in
                    # flight: only an epoch bump can replace it
                    size = old.size
                    pending_n = self._pending_count
                    old_cap = self.capacity
                target = self._next_capacity(size + pending_n)
                # Compile the new tier's graphs BEFORE taking rows live.
                self._run_prewarm_hooks(target, info)
                t0 = _time.perf_counter()
                grown = self._grown_arrays(
                    (old.embeddings, old.labels, old.valid), target)
                info["copy_s"] = round(_time.perf_counter() - t0, 3)
                # Normalize staged rows here, not on the enrolling thread
                # (add() stages raw). In-place entry mutation is GIL-atomic
                # and safe against a concurrent reset clearing the list —
                # a cleared entry is garbage either way. Entries staged
                # after this sweep stay unnormalized and are left for the
                # next worker round (the splice below stops at the first
                # unnormalized entry, preserving enrolment order).
                t0 = _time.perf_counter()
                with self._write_lock:
                    sweep = list(self._pending)
                for entry in sweep:
                    if not entry[2]:
                        entry[0] = self._normalize_rows(entry[0])
                        entry[2] = True
                info["normalize_s"] = round(_time.perf_counter() - t0, 3)
                with self._write_lock:
                    if self._epoch != epoch:
                        # reset/swap_from superseded this grow; drop it and
                        # re-examine what (if anything) is still pending.
                        continue
                    # Splice every normalized entry that fits (adds staged
                    # after the sweep, or overflowing the target, loop for
                    # another round). Popped entries are NOT yet published:
                    # counts and host mirrors move at publish time, and an
                    # epoch bump in between discards them exactly like a
                    # reset discards pending rows.
                    fits = []
                    n_fit = 0
                    while self._pending:
                        entry = self._pending[0]
                        if not entry[2] or size + n_fit + len(entry[0]) > target:
                            break
                        fits.append(entry)
                        n_fit += len(entry[0])
                        self._pending.pop(0)
                    spliced = fits  # restored by the except path if the
                    # upload below dies before these rows publish
                    emb = np.concatenate(
                        [e for e, _l, _ in fits]
                        or [np.zeros((0, self.dim), np.float32)])
                    lab = np.concatenate(
                        [l for _e, l, _ in fits] or [np.zeros((0,), np.int32)])
                # Upload OUTSIDE the lock and wait for residency while
                # serving threads still read the old tier. A reset/swap
                # epoch bump cancels the wait immediately.
                t0 = _time.perf_counter()
                new_data = self._build_snapshot(
                    emb, lab, np.ones((n_fit,), bool), size + n_fit,
                    chunked=True, cancel=lambda: self._epoch != epoch,
                    info=info, epoch=epoch, onto=grown, start=size)
                del grown
                if not self._await_residency(new_data, self.RESIDENCY_TIMEOUT_S,
                                             cancel=lambda: self._epoch != epoch,
                                             info=info):
                    info["residency_timeout"] = True
                info["upload_wait_s"] = round(_time.perf_counter() - t0, 3)
                t0 = _time.perf_counter()
                with self._write_lock:
                    if self._epoch != epoch:
                        continue  # a reset/swap during the wait wins; the
                        # spliced rows are discarded exactly as the reset
                        # discarded the rest of pending
                    self._mirror_write(size, emb, lab)
                    self.capacity = target
                    self.grow_count += 1
                    self._pending_count -= n_fit
                    if self.quantizer is not None:
                        # A splice lands a large staged row set at once —
                        # invalidate instead of assigning thousands of
                        # rows under the write lock; serving falls back
                        # to the exact matcher until the background
                        # retrain (poked below) republishes.
                        self.quantizer.invalidate()
                    self._data = new_data
                    spliced = None  # published: nothing to restore
                info["install_s"] = round(_time.perf_counter() - t0, 3)
                # Outside the lock: drop compiled entries for tiers below
                # the one just replaced (see evict_hooks).
                self._evict_stale(old_cap)
                self._poke_quantizer()
        except Exception as e:  # never leave waiters hanging
            info["error"] = repr(e)
            with self._write_lock:
                if spliced and self._epoch == epoch:
                    # Popped but never published (e.g. device_put died at
                    # the new tier): put the rows back at the head so
                    # ``pending_rows`` stays truthful and the next add()
                    # retries them in enrolment order. On an epoch bump
                    # they stay dropped, like the rest of pending.
                    self._pending[:0] = spliced
                self._growing = False
                self._grow_done.set()
                self.last_grow_info = info

    def _evict_stale(self, below_capacity: int) -> None:
        """Drop compiled executables for tiers strictly below
        ``below_capacity`` — called after a grow publishes, with the
        REPLACED tier as threshold, so the previous tier survives for any
        reader still holding its snapshot while everything older is freed.
        Safe without the write lock: dict mutation is atomic under the GIL
        and an in-flight call already holds its function reference."""
        for key in [k for k in list(self._match_cache) if k[1] < below_capacity]:
            self._match_cache.pop(key, None)
        # An evicted tier is no longer warm: if a swap_from shrinks the
        # gallery and enrolment re-grows THROUGH this tier, prewarm must
        # recompile it rather than skip on a stale membership.
        with self._write_lock:
            self._warmed_capacities = {
                c for c in self._warmed_capacities if c >= below_capacity
            }
        for hook in list(self.evict_hooks):
            try:
                hook(below_capacity)
            except Exception:  # ocvf-lint: disable=swallowed-exception -- eviction is best-effort cache bookkeeping; a raising hook costs warm-cache memory, never correctness, and serving must never die to cleanup
                pass

    def reset(self) -> None:
        with self._write_lock:
            self._epoch += 1  # invalidate any in-flight async grow
            self._pending.clear()
            self._pending_count = 0
            if self.quantizer is not None:
                self.quantizer.invalidate()
            self._drop_mirror()
            self._data = GalleryData(*self._empty_arrays(self.capacity),
                                     size=0, epoch=self._epoch)

    def _drop_mirror(self, base: int = 0) -> None:
        self._host_base = int(base)
        self._host_emb = np.zeros((0, self.dim), np.float32)
        self._host_lab = np.zeros((0,), np.int32)
        self._host_val = np.zeros((0,), bool)

    #: no piece of an upload is larger than this many bytes
    #: (``_splice_rows``), and the grow worker's pieces are PACED one at a
    #: time: a serving transfer queued behind an un-chunked 1 GB gallery
    #: H2D waits for all of it (queue-head blocking on the host->device
    #: link). Pacing (await each piece before queueing the next) bounds
    #: any concurrent serving transfer's wait to ~one piece. Whether the
    #: local chip's link is slow enough for this to matter: not measured.
    CHUNK_UPLOAD_BYTES = 32 * 1024 * 1024

    #: per-CHUNK pacing deadline (round-5 advisor: one shared deadline
    #: meant a mid-upload expiry silently queued every remaining chunk
    #: back-to-back — exactly the head-of-line blocking pacing exists to
    #: prevent, with nothing recorded). 60 s per 32 MB chunk is far beyond
    #: any healthy link; an expiry is real degradation and is
    #: flagged in ``info["chunk_pacing_timeout"]`` for lifecycle artifacts.
    CHUNK_PACING_TIMEOUT_S = 60.0

    @staticmethod
    def _pace_chunk(buf, deadline: float, cancel=None, info=None) -> bool:
        """Poll ``buf.is_ready()`` until resident, cancelled, or
        ``deadline``; True when the chunk landed (or the wait was
        cancelled), False when pacing gave up — deadline expiry records
        ``info["chunk_pacing_timeout"]`` so the degraded (unpaced) window
        is visible in grow artifacts; a backend without ``is_ready``
        returns False silently (pacing is impossible, not degraded — the
        final residency wait still runs). Transient is_ready errors are
        recorded and polling continues (mirrors ``_await_residency``)."""
        import time as _time

        while True:
            if cancel is not None and cancel():
                return True  # doomed snapshot; publish check discards it
            try:
                if buf.is_ready():
                    return True
            except (AttributeError, NotImplementedError):
                return False  # no is_ready on this backend: cannot pace
            except Exception as e:
                if info is not None and "residency_probe_error" not in info:
                    info["residency_probe_error"] = repr(e)
            if _time.monotonic() >= deadline:
                if info is not None:
                    info["chunk_pacing_timeout"] = True
                return False
            _time.sleep(0.02)

    def _build_snapshot(self, emb: np.ndarray, lab: np.ndarray,
                        val: np.ndarray, size: int,
                        chunked: bool = False, cancel=None,
                        info=None, epoch: Optional[int] = None,
                        onto=None, start: int = 0) -> GalleryData:
        """A device snapshot WITHOUT publishing it (the async grow worker
        waits for residency between build and publish): host rows ``emb``
        with their labels and validity land at rows [start, start + n) of
        ``onto``, device arrays the caller owns (the grow worker's next
        tier, already holding the served rows), or of a fresh empty tier
        of the current capacity (``load_snapshot``, a recast swap). Only
        the rows handed in cross the link, cast on the host so the wire
        carries store_dtype-width bytes, and no third tier-sized array is
        ever live: the old snapshot and the one being built. ``chunked``
        (grow worker only) paces the pieces so concurrent serving
        transfers are not head-blocked behind them. On a multi-chip mesh
        every chip receives the n rows and keeps those of its shard; on
        multi-host pods each host uploads over its own link."""
        if onto is None:
            onto = self._empty_arrays(self.capacity)
        arrays = self._splice_rows(onto, emb, lab, val, start, owned=True,
                                   paced=chunked, cancel=cancel, info=info)
        return GalleryData(*arrays, size=size,
                           epoch=self._epoch if epoch is None else epoch)

    def _install(self, emb: np.ndarray, lab: np.ndarray, val: np.ndarray, size: int) -> None:
        # A whole-set install from host rows (rows 0..n of a fresh tier):
        # build the full snapshot first, publish with ONE attribute write —
        # serving threads reading self._data never see a partial install.
        self._data = self._build_snapshot(emb, lab, val, size)

    def install_device_rows(self, embeddings, labels, valid, size: int) -> None:
        """Adopt device arrays as the next snapshot: the bulk install that
        never touches the host (a watchlist drawn, loaded or re-embedded
        on the chips). ``embeddings`` [C, dim] in ``store_dtype`` sharded
        over tp by rows, ``labels`` [C] int32, ``valid`` [C] bool; rows
        [0, size) are the enrolled ones. C becomes the capacity. Arrays
        that already carry the gallery's shardings are adopted as they
        are, nothing is copied and nothing crosses the link.

        A whole-set install like ``load_snapshot`` and ``swap_from``, by
        the same rules: under the write lock, the epoch bumped (an
        in-flight async grow is dropped, a quantizer invalidated and
        retrained in the background), one attribute write publishes. The
        truth of these rows is on the device: a later ``add`` appends
        after them and keeps them, and ``snapshot()`` reads them back when
        it is called, not before. The caller must not donate or mutate
        the arrays afterwards."""
        import time as _time

        t0 = _time.monotonic()
        tp = self.mesh.shape[TP_AXIS]
        rows = int(embeddings.shape[0])
        if (embeddings.ndim != 2 or embeddings.shape[1] != self.dim
                or rows % tp or labels.shape != (rows,)
                or valid.shape != (rows,)):
            raise ValueError(
                f"install_device_rows takes [C, {self.dim}] rows with C a "
                f"multiple of tp={tp}, and [C] labels and validity; got "
                f"{embeddings.shape}, {labels.shape}, {valid.shape}")
        if (embeddings.dtype != self.store_dtype or labels.dtype != jnp.int32
                or valid.dtype != jnp.bool_):
            raise ValueError(
                f"install_device_rows takes {self.store_dtype.name} rows, "
                f"int32 labels and bool validity; got {embeddings.dtype}, "
                f"{labels.dtype}, {valid.dtype}")
        if not 0 <= int(size) <= rows:
            raise ValueError(f"size {size} outside [0, {rows}]")
        arrays = tuple(
            a if getattr(a, "sharding", None) == sh else jax.device_put(a, sh)
            for a, sh in zip((embeddings, labels, valid),
                             self._tier_shardings()))
        evict_below = None
        with self._write_lock:
            self._epoch += 1  # invalidate any in-flight async grow
            self._pending.clear()
            self._pending_count = 0
            if self.quantizer is not None:
                self.quantizer.invalidate()
            if rows > self.capacity:
                evict_below = self.capacity
            self.capacity = rows
            self._drop_mirror(base=int(size))
            self._data = GalleryData(*arrays, size=int(size),
                                     epoch=self._epoch)
            self.bulk_installs += 1
        if self.metrics is not None:
            self.metrics.incr(mn.GALLERY_BULK_INSTALLS)
        self._emit_install(
            t0, rows=int(size), source="device",
            nbytes=int(size) * self.dim * self.store_dtype.itemsize)
        if evict_below is not None:
            self._evict_stale(evict_below)
        self._poke_quantizer()

    #: bounded wait for the write lock in snapshot(): long enough that a
    #: normal add/grow-splice holding it finishes, short enough that a
    #: hang-mode device transfer stuck INSIDE the locked region (observed
    #: outage shape) cannot wedge a degraded-mode caller on the serving
    #: thread — which would be the exact wedge the resilience layer exists
    #: to prevent.
    SNAPSHOT_LOCK_TIMEOUT_S = 5.0

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Whole-capacity host copies (emb f32, labels, valid, size), made
        when asked: the host mirror laid into fresh arrays, and rows that
        were installed on the device (``install_device_rows``) read back
        from it now. Rows the host enrolled need no device readback.
        Prefers the write lock (a copy racing a grow splice must not
        capture a half-written row set) but the acquire is BOUNDED: if a
        hung device_put is holding the lock past
        ``SNAPSHOT_LOCK_TIMEOUT_S``, fall back to lock-free copies —
        best-effort state now beats a guaranteed wedge."""
        acquired = self._write_lock.acquire(timeout=self.SNAPSHOT_LOCK_TIMEOUT_S)
        try:
            data, base, cap = self._data, self._host_base, self.capacity
            emb = np.zeros((cap, self.dim), np.float32)
            lab = np.full((cap,), self.labels_pad, np.int32)
            val = np.zeros((cap,), bool)
            n = min(len(self._host_lab), cap - base)
            emb[base:base + n] = self._host_emb[:n]
            lab[base:base + n] = self._host_lab[:n]
            val[base:base + n] = self._host_val[:n]
            if base:
                emb[:base] = np.asarray(data.embeddings[:base], np.float32)
                lab[:base] = np.asarray(data.labels[:base])
                val[:base] = np.asarray(data.valid[:base])
            return emb, lab, val, data.size
        finally:
            if acquired:
                self._write_lock.release()

    def load_snapshot(self, emb: np.ndarray, lab: np.ndarray,
                      val: np.ndarray, size: int,
                      embedder_version: Optional[int] = None) -> None:
        """Install host-mirror arrays from a prior ``snapshot()`` as the
        live gallery — the supervisor's last-known-good restore path
        (runtime.resilience.ServiceSupervisor): a crash mid-enrolment must
        not leave a half-written gallery serving. Adopts the snapshot's
        capacity (grows since the checkpoint are rolled back with it) and
        invalidates any in-flight async grow, exactly like ``swap_from``.
        ``embedder_version`` (when given) re-stamps the gallery's version
        along with the whole-set install — the rollout cutover and the
        replica's new-version re-anchor both change version and rows in
        this one atomic publish, so serving can never observe rows from
        one version stamped with another."""
        emb = np.asarray(emb, np.float32)
        if emb.ndim != 2 or emb.shape[1] != self.dim:
            raise ValueError(f"snapshot must be [capacity, {self.dim}], "
                             f"got {emb.shape}")
        val = np.asarray(val, bool)
        # Rows past the last one the snapshot holds are empty: neither
        # mirrored nor uploaded. The mirror is a private copy.
        held = max(int(size), int(np.flatnonzero(val)[-1]) + 1 if val.any() else 0)
        with self._write_lock:
            if embedder_version is not None:
                self.embedder_version = int(embedder_version)
            self._epoch += 1  # invalidate any in-flight async grow
            self._pending.clear()
            self._pending_count = 0
            if self.quantizer is not None:
                # Derived state: the snapshot's rows share nothing with
                # the trained cells. Recovery reinstates the quantizer
                # from its wal_seq-keyed sidecar or retrains (see
                # runtime.state_store); until then serving is exact.
                self.quantizer.invalidate()
            self.capacity = emb.shape[0]
            self._host_base = 0
            self._host_emb = np.array(emb[:held], np.float32, copy=True)
            self._host_lab = np.array(lab[:held], np.int32, copy=True)
            self._host_val = np.array(val[:held], bool, copy=True)
            self._install(self._host_emb, self._host_lab, self._host_val,
                          int(size))

    def swap_from(self, other: "ShardedGallery") -> None:
        """Atomic-at-Python-level install of another gallery's contents —
        the double-buffered reload path (SURVEY.md §5.3): build ``other``
        off to the side, then swap refs; in-flight match calls keep using
        the old arrays they captured.

        A ``store_dtype`` mismatch is CAST, not rejected: the documented
        retrain -> ``reload_gallery`` handoff builds its staged gallery at
        the trainer's default f32 while serving defaults to bf16
        (round-5 advisor) — the staged host mirrors are f32 truth either
        way, so the device snapshot is simply rebuilt at THIS gallery's
        width (one extra H2D; a reload already pays one). The installed
        snapshot therefore always carries self.store_dtype, so compiled
        cache keys (which carry capacity, not dtype) never alias.

        A ``dim`` mismatch FAILS CLOSED (``EmbeddingDimMismatchError``):
        a donor built by a different-D embedder is a different embedding
        space, and a raw swap would publish scores against rows the query
        embedder cannot compare to. Different-D embedders roll out through
        the staged re-embed path (``runtime.rollout``), never a swap. The
        donor's ``embedder_version`` is adopted atomically with its rows —
        same-version retrain reloads are unaffected (both default 1)."""
        if other.dim != self.dim:
            raise EmbeddingDimMismatchError(
                f"swap_from refused: donor gallery dim {other.dim} != "
                f"serving dim {self.dim}. A different-D embedder must roll "
                f"out via the staged re-embed path (runtime.rollout: "
                f"stage + cutover record + checkpoint), never a raw swap "
                f"— mixing embedding spaces in one served shard set would "
                f"corrupt every published score.")
        recast = other.store_dtype != self.store_dtype
        with self._write_lock:
            self.embedder_version = int(getattr(other, "embedder_version",
                                                self.embedder_version))
            self._epoch += 1  # invalidate any in-flight async grow
            self._pending.clear()
            self._pending_count = 0
            if self.quantizer is not None:
                self.quantizer.invalidate()
            if other.capacity != self.capacity:
                self.capacity = other.capacity
            self._host_base = other._host_base
            self._host_emb = other._host_emb
            self._host_lab = other._host_lab
            self._host_val = other._host_val
            if recast and not other._host_base:
                # Rebuild at our width from the (always-f32) host mirror;
                # _install publishes with the single _data write.
                n = min(len(self._host_lab), self.capacity)
                self._install(self._host_emb[:n], self._host_lab[:n],
                              self._host_val[:n], other.size)
            elif recast:
                # The donor's rows were installed on its devices and have
                # no f32 truth on the host: cast them where they are.
                donor = other._data
                self._data = donor._replace(
                    embeddings=jax.jit(
                        lambda e: e.astype(self.store_dtype),
                        out_shardings=self._emb_sharding)(donor.embeddings),
                    epoch=self._epoch)
            else:
                # Device-visible swap is the single _data assignment (last,
                # so the host mirrors are already consistent when readers
                # see it) — restamped with THIS gallery's epoch: the donor
                # snapshot carries the donor's counter, and a stale stamp
                # would make every post-swap quantizer publish (stamped
                # with the bumped epoch) fail the _ivf_data pairing check
                # forever, silently pinning serving to the exact path.
                self._data = other._data._replace(epoch=self._epoch)
        # The swapped-in rows need fresh cells: retrain in the background
        # (single-flight); exact matching serves the interim.
        self._poke_quantizer()

    # ---- IVF coarse quantizer (parallel.quantizer) ----

    def attach_quantizer(self, quantizer, mode: str = "auto") -> None:
        """Wire a ``CoarseQuantizer`` as this gallery's shortlist front
        end and select the match mode: ``"auto"`` (exact below
        ``IVF_MIN_CAPACITY``, two-stage above — the serving default),
        ``"ivf"`` (two-stage whenever the quantizer is ready), or
        ``"exact"`` (attached but never consulted). The quantizer is
        derived state: this gallery drives its whole lifecycle (add ->
        incremental assign; reset/load_snapshot/swap_from/grow-splice ->
        invalidate; staleness -> background retrain)."""
        if mode not in ("auto", "ivf", "exact"):
            raise ValueError(f"match mode must be auto|ivf|exact, got {mode!r}")
        quantizer._gallery = self
        self.quantizer = quantizer
        self.match_mode = mode

    def run_locked(self, fn):
        """Run ``fn`` under the write lock — the quantizer's publish path
        (its mutations are serialized by THIS lock, not one of its own,
        so the PR-5 lock-order graph stays a tree rooted here)."""
        with self._write_lock:
            return fn()

    def snapshot_quantizer(self):
        """Atomic (vs. enrolments and retrain publishes) host copy of the
        quantizer's sidecar payload, or None when absent/not ready — the
        checkpoint writer captures this in the same critical section as
        the gallery snapshot so the sidecar can be keyed to the
        checkpoint's ``wal_seq``."""
        if self.quantizer is None:
            return None
        with self._write_lock:
            return self.quantizer.sidecar_payload_locked()

    def _ivf_wanted(self, capacity: Optional[int] = None) -> bool:
        """Would this gallery USE a ready quantizer at ``capacity``?
        (Mode/threshold/mesh gates, ignoring readiness — the build
        trigger needs the answer before any build exists.)"""
        if self.quantizer is None or self.match_mode == "exact":
            return False
        if self.mesh.size != 1:
            return False  # two-stage path is single-device, like pallas
        if self.match_mode == "ivf":
            return True
        return ((self.capacity if capacity is None else capacity)
                >= self.IVF_MIN_CAPACITY)

    def _ivf_enabled(self, capacity: Optional[int] = None) -> bool:
        return self._ivf_wanted(capacity) and self.quantizer.ready

    def _ivf_data(self, data: GalleryData):
        """The quantizer snapshot to pair with the ALREADY-TAKEN gallery
        snapshot ``data``, or None for the exact path — ONE read of
        ``quantizer.data`` so the enabled-check and the arrays can never
        straddle an invalidation, and an epoch cross-check so the two
        non-atomic reads can never pair one row set's gallery arrays
        with another's inverted lists (a swap_from + fast retrain
        between the reads would otherwise score the OLD rows against
        the NEW lists — plausible sims, wrong identities)."""
        if not self._ivf_wanted(data.capacity):
            return None
        ivf = self.quantizer.data  # None when invalidated/not built
        if ivf is None or ivf.gallery_epoch != data.epoch:
            return None
        return ivf

    def _poke_quantizer(self) -> None:
        """Fire the background (re)build when the quantizer is missing-
        but-wanted or stale — the single-flight retrain trigger, called
        after enrolments and swaps (never on the match path)."""
        q = self.quantizer
        if q is None:
            return
        if not q.ready:
            if self._ivf_wanted() and self.size > 0:
                q.maybe_rebuild_async()
        elif q.stale():
            q.maybe_rebuild_async()

    # ---- matching (device-side) ----

    def _pallas_enabled(self, capacity: Optional[int] = None) -> bool:
        """Large-gallery fast path: the streaming pallas kernel
        (ops.pallas_match) never materializes [Q, capacity] in HBM. On a
        mesh of TPU devices it holds once the rows A SHARD holds reach
        ``PALLAS_MIN_CAPACITY``: one chip runs the kernel as it is, several
        run it on every shard under ``shard_map`` with a collective merge
        (``match_pod_pallas``; XLA cannot partition the custom call, so
        the decomposition is written out). That form has run on four v5e
        chips: PERF.md section 6, PR 38. Smaller shards and CPU meshes
        stay on the GSPMD formulation. ``capacity`` overrides the current
        one so prewarm can select for a FUTURE tier."""
        if self._use_pallas_cfg is not None:
            return bool(self._use_pallas_cfg)
        dev = self.mesh.devices.flat[0]
        capacity = self.capacity if capacity is None else capacity
        return (
            dev.platform == "tpu"
            and capacity // self.mesh.shape[TP_AXIS] >= self.PALLAS_MIN_CAPACITY
        )

    _MATCHER_LABELS = {
        "xla": "exact XLA (match_global)",
        "pallas": "exact Pallas streaming kernel",
        "ivf": "IVF shortlist + Pallas rerank",
    }

    def matcher_name(self, capacity: Optional[int] = None) -> str:
        """Which matcher ``match_fn`` selects at ``capacity`` (default: the
        current tier): ``"ivf"`` (an exact matcher serves until the
        quantizer is trained), ``"pallas"`` or ``"xla"``."""
        if self._ivf_wanted(capacity):
            return "ivf"
        return "pallas" if self._pallas_enabled(capacity) else "xla"

    def describe_matchers(self) -> list:
        """Start-up lines for the serving app's log: the mesh, the device
        kinds, the matcher each capacity tier will use, and — when a
        kernel matcher is off because of where the gallery was placed —
        why. Selection itself stays in ``match_fn``; this only reports it."""
        devices = list(self.mesh.devices.flat)
        kinds = "/".join(sorted({d.device_kind for d in devices}))
        platform = devices[0].platform
        tiers = sorted({self.capacity, self.PALLAS_MIN_CAPACITY,
                        self.IVF_MIN_CAPACITY})
        lines = [
            f"mesh dp={self.mesh.shape[DP_AXIS]} tp={self.mesh.shape[TP_AXIS]}"
            f" on {len(devices)} x {kinds} ({platform}); gallery capacity "
            f"{self.capacity} rows, {self.store_dtype.name}",
            "matcher by capacity tier: " + "; ".join(
                f"{cap} rows -> {self._MATCHER_LABELS[self.matcher_name(cap)]}"
                + (" [current]" if cap == self.capacity else "")
                for cap in tiers),
        ]
        if self.mesh.size > 1:
            tp = self.mesh.shape[TP_AXIS]
            lines.append(
                f"{tp} shard(s) of {self.capacity // tp} rows: the exact "
                f"tier runs the Pallas kernel on every shard (shard_map, "
                f"candidates gathered over tp and merged) from "
                f"{self.PALLAS_MIN_CAPACITY} rows a shard on TPU, exact XLA "
                f"by GSPMD below that and off TPU; the IVF matcher is OFF: "
                f"it is single-device and the mesh has {self.mesh.size}")
        if platform != "tpu":
            lines.append(
                f"platform is {platform}, not tpu: the Pallas exact matcher "
                f"is off unless forced, and an IVF rerank runs its kernel "
                f"in interpret mode")
        return lines

    def match_fn(self, k: int, capacity: Optional[int] = None,
                 use_ivf: Optional[bool] = None):
        """Pure match function with the mode selection applied — shared by
        ``match()`` and the fused pipeline step (``parallel.pipeline``), so
        every caller of the hot op gets the right path, not just direct
        ``gallery.match()`` users. Not jitted here: callers inline it into
        their own jitted graphs. ``capacity`` only influences the
        selection (the fn itself is shape-polymorphic) — prewarm passes
        the future tier's.

        Three tiers of selection:

        - **ivf** (``_ivf_enabled``): two-stage shortlist + exact rerank
          (``ops.ivf_match``). Signature gains a 5th argument —
          ``(q, emb, valid, labels, ivf)`` where ``ivf`` is the
          ``IVFDeviceData`` snapshot from ``_ivf_data`` — because the
          quantizer arrays must flow as jit ARGUMENTS (an incremental
          assignment publishes new arrays; a closure would freeze them).
          Callers branch on ``_ivf_enabled(capacity)`` for the arity and
          PIN their choice via ``use_ivf`` so a concurrent invalidation
          between their check and this call cannot flip the arity under
          them (``None`` re-derives the selection — the legacy shape).
        - **pallas streaming** exact: the kernel as it is on one chip,
          on every shard with a collective merge (``match_pod_pallas``)
          on several.
        - **GSPMD global view** exact, for CPU meshes and small shards.

        Each returned function names its own ``jax.named_scope`` pair,
        ``MATCH_SCOPE`` round the search and ``MERGE_SCOPE`` beside it.
        """
        if self._ivf_enabled(capacity) if use_ivf is None else use_ivf:
            from opencv_facerecognizer_tpu.ops.ivf_match import ivf_match_topk

            interpret = self.mesh.devices.flat[0].platform != "tpu"
            labels_pad = self.labels_pad
            nprobe = self.quantizer.nprobe

            def ivf_fn(q, g, valid, labels, ivf):
                # ``g`` rides along unused for signature symmetry with the
                # exact paths (XLA drops it); stage 2 reranks the int8
                # cell-resident rows, ``valid``/``labels`` stay authoritative.
                with jax.named_scope(MATCH_SCOPE):
                    vals, idx = ivf_match_topk(q, valid, ivf, k=k,
                                               nprobe=nprobe,
                                               interpret=interpret)
                with jax.named_scope(MERGE_SCOPE):
                    found = take_labels_with_sentinel(labels, idx, labels_pad)
                return found, vals, idx

            return ivf_fn
        if self._pallas_enabled(capacity):
            from opencv_facerecognizer_tpu.ops.pallas_match import (
                streaming_match_topk,
            )

            interpret = self.mesh.devices.flat[0].platform != "tpu"
            labels_pad = self.labels_pad
            if self.mesh.size > 1:
                return functools.partial(
                    match_pod_pallas, k=k, mesh=self.mesh,
                    interpret=interpret, labels_pad=labels_pad)

            def fn(q, g, valid, labels):
                with jax.named_scope(MATCH_SCOPE):
                    vals, idx = streaming_match_topk(
                        q, g, valid, k=k, interpret=interpret
                    )
                with jax.named_scope(MERGE_SCOPE):
                    found = take_labels_with_sentinel(labels, idx, labels_pad)
                return found, vals, idx

            return fn
        return functools.partial(match_global, k=k, mesh=self.mesh)

    def _matcher(self, k: int, data: GalleryData, ivf=None):
        # Keyed by (k, capacity/pallas/ivf shapes) DERIVED FROM THE
        # SNAPSHOTS being matched — a separate self.capacity read could
        # straddle a concurrent grow and pair tier B's key with tier A's
        # arrays (pipeline._step_key has the same rule). A grow changes
        # the static gallery shape, but the old tier's compiled matcher
        # stays valid for any in-flight readers and the new tier gets its
        # own entry (eviction in _evict_stale, not clear() — prewarmed
        # entries survive the swap). An IVF retrain that changes the list
        # shapes (max_cell/spill growth) lands in a fresh entry the same
        # way; same-shape republishes reuse the compiled matcher, with
        # the new arrays flowing as arguments.
        capacity = data.capacity
        ivf_sig = None if ivf is None else ivf.shape_signature()
        key = (k, capacity, self._pallas_enabled(capacity), ivf_sig)
        fn = self._match_cache.get(key)  # fetch once (evict race)
        if fn is None:
            if ivf is not None:
                # A retrain that changed the list shapes orphaned the
                # previous signature's executable at this (k, capacity):
                # purge it, or every staleness retrain leaks a compiled
                # matcher for the process lifetime (capacity-threshold
                # eviction never sees same-capacity signature churn).
                # In-flight calls already hold their function references.
                for stale in [k2 for k2 in list(self._match_cache)
                              if k2[:3] == key[:3]
                              and k2[3] not in (None, ivf_sig)]:
                    self._match_cache.pop(stale, None)
                fn = jax.jit(self.match_fn(k, capacity, use_ivf=True))
            elif self._pallas_enabled(capacity) and self.mesh.size == 1:
                fn = jax.jit(self.match_fn(k, capacity, use_ivf=False))
            else:  # either sharded form: the gallery's own shardings
                fn = jax.jit(
                    self.match_fn(k, capacity, use_ivf=False),
                    in_shardings=(
                        NamedSharding(self.mesh, P(DP_AXIS, None)),
                        self._emb_sharding,
                        self._valid_sharding,
                        self._lab_sharding,
                    ),
                )
            self._match_cache[key] = fn
        return fn

    def match(self, queries: jnp.ndarray, k: int = 1):
        """[Q, D] L2-normalized queries -> (labels [Q, k], cosine sims [Q, k],
        row indices [Q, k]); Q must divide by the dp axis size."""
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim != 2 or queries.shape[1] != self.dim:
            raise ValueError(f"queries must be [Q, {self.dim}], got {queries.shape}")
        dp = self.mesh.shape[DP_AXIS]
        if queries.shape[0] % dp:
            raise ValueError(f"query count {queries.shape[0]} not divisible by dp={dp}")
        data = self._data  # one snapshot read; never mix fields across writes
        ivf = self._ivf_data(data)  # one epoch-checked quantizer read
        if ivf is not None:
            return self._matcher(int(k), data, ivf)(
                queries, data.embeddings, data.valid, data.labels, ivf)
        return self._matcher(int(k), data)(
            queries, data.embeddings, data.valid, data.labels)

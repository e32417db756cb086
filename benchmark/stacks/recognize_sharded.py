"""The system under test for configurations whose ``"stack"`` is
``"recognize_sharded"``: the serving stack of ``stacks/recognize.py``, built
by that module's own ``build``, on a mesh of ``devices`` chips whose ``tp``
axis shards a watchlist that no single chip holds.

Three things are this module's own. The fill: every chip draws the blocks
of its own shard (a row is a function of the seed and of its block's index
alone, by the ``block`` draw of ``recognize.make_gallery_rows``), so no chip
ever holds another's rows and no array of the whole watchlist exists on one
device or on the host; the new snapshot goes in through the program's
public bulk install, ``ShardedGallery.install_device_rows``. The planted
rows (``plant``): every ``gallery.plant_every``-th face of the traffic's
scenes has a row of its own in the watchlist, its embedding by the plain
reference, and those rows are dealt round over the shards, so that in every
frame some face's best row lies on each chip and a shard that is lost, or
an exchange between the chips that is left out, costs those faces some 0.3
of similarity: the comparison that decides ``correct`` sees it. (The faces
in between find their best row among the planted ones too, another face's
at 0.5-0.85: the embedder trained here sees faces more alike than any
random row is to one.) And a look,
before anything of the watchlist's size is made, at the matcher the program
selects on this mesh at that size: a program that would serve it by any
other than the configuration's stops there, with the reason, in seconds.

``gallery.rows`` in the configuration's file is what ONE chip holds (the N
of each chip's kernel event, which the accepted roofline metric reads);
``gallery.total_rows`` is the watchlist, the gallery's capacity.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Tuple

import numpy as np

from benchmark.stacks import recognize

#: seed -> (the watchlist row of each planted row [P], the planted rows
#: [P, dim] float32 as a bf16 row stores them). ``build`` makes them from
#: the traffic's frames; the fill and ``reference_rows``, which are handed
#: the seed alone, both put these same rows over what they draw.
_PLANTS: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}


def shard_mesh(config: Dict[str, Any]):
    """The (dp 1, tp ``devices``) mesh ``recognize.build`` serves on."""
    import jax

    from opencv_facerecognizer_tpu.parallel import make_mesh

    return make_mesh(devices=jax.devices()[:int(config["devices"])])


def make_sharded_rows(seed: int, rows: int, dim: int, block_rows: int, mesh,
                      draw_rows: int = 0, plants=None):
    """[rows, dim] bf16 unit vectors, sharded by rows over the mesh's
    ``tp`` axis, every shard drawn on the chip that holds it: the key of
    block b is the b-th split of the seed's key, wherever b lies, and a
    block is drawn in runs of ``draw_rows`` (the whole block by default)
    from the splits of its own key, so that the float32 draw that is live
    beside the result is one run's, not one block's. ``plants`` (rows [P],
    values [P, dim]) replace the rows drawn at those places, each on the
    chip whose shard holds its place."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    draw_rows = int(draw_rows) or block_rows
    if rows % (block_rows * tp) or block_rows % draw_rows:
        raise ValueError(f"{rows} gallery rows are not a whole number of "
                         f"blocks of {block_rows} (drawn {draw_rows} rows at "
                         f"a time) on each of {tp} shards")

    places, values = plants if plants is not None else (
        np.zeros((0,), np.int32), np.zeros((0, dim), np.float32))

    def draw(key_data, places, values):  # [runs of this shard, words of a key]
        def run(words):
            k = jax.random.wrap_key_data(words, impl="rbg")
            x = jax.random.normal(k, (draw_rows, dim), jnp.float32)
            x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            return x.astype(jnp.bfloat16)

        drawn = jax.lax.map(run, key_data).reshape(-1, dim)
        here = places - jax.lax.axis_index("tp") * drawn.shape[0]
        # a place in another chip's shard: an index past the end, dropped
        here = jnp.where((here >= 0) & (here < drawn.shape[0]), here,
                         drawn.shape[0])
        return drawn.at[here].set(values.astype(jnp.bfloat16), mode="drop")

    key = jax.random.key(int(seed) % (1 << 31), impl="rbg")
    blocks = jax.random.split(key, rows // block_rows)
    runs = jax.vmap(lambda k: jax.random.split(k, block_rows // draw_rows))(blocks)
    key_data = jax.random.key_data(runs).reshape(rows // draw_rows, -1)
    return jax.jit(jax.shard_map(
        draw, mesh=mesh, in_specs=(P("tp", None), P(), P()),
        out_specs=P("tp", None), check_vma=False))(
            key_data, np.asarray(places, np.int32), np.asarray(values, np.float32))


def plant(config: Dict[str, Any], traffic, seed: int, nets_dir: str) -> int:
    """Gives every ``gallery.plant_every``-th face of the traffic's scenes
    (in the order of the scenes' keys and of the reference detector's
    slots) a watchlist row of its own: the plain reference's float32
    embedding of the face in its frame, as a bf16 row stores it. The n-th
    planted row goes to shard n mod ``shards``, at a place past the
    enrolled subjects' rows that the seed draws within the n-th stretch of
    the shard, so no two share a place. Returns how many were planted;
    the rows wait in ``_PLANTS`` for the fill and for ``reference_rows``."""
    from benchmark import window

    gallery = config["gallery"]
    reference = window.load_reference(config).Reference(
        nets_dir, tuple(config["face_size"]))
    keys = sorted(k for k, who in traffic.scene_identities.items() if who)
    pixels = np.stack([traffic.frames[k] for k in keys])
    boxes, _scores, valid = reference.detect(pixels)
    rows = np.asarray(reference.as_stored(
        reference.embed(pixels, boxes)[valid][::int(gallery["plant_every"])]))
    shards, shard_rows = int(gallery["shards"]), int(gallery["rows"])
    head = len(traffic.enrolled_identities()) * int(gallery["enrol_images"])
    n = np.arange(len(rows))
    stretch = (shard_rows - head) // -(-len(rows) // shards)
    rng = np.random.default_rng([int(seed), 23])
    at = ((n % shards) * shard_rows + head + (n // shards) * stretch
          + rng.integers(0, stretch, size=len(rows)))
    _PLANTS[int(seed)] = (at.astype(np.int64), rows.astype(np.float32))
    return len(rows)


def planted(seed: int) -> Tuple[np.ndarray, np.ndarray]:
    if int(seed) not in _PLANTS:
        raise RuntimeError(
            f"no stack was built for seed {seed} in this process: the planted "
            f"rows come from its traffic's frames (recognize_sharded.plant)")
    return _PLANTS[int(seed)]


def reference_rows(config: Dict[str, Any], seed: int):
    """The watchlist of ``seed`` drawn again for the reference, shard by
    shard on the chips, once the program is gone: the same call that
    filled the program's gallery, with the same planted rows, none of the
    program's memory."""
    return make_sharded_rows(seed, int(config["gallery"]["total_rows"]),
                             int(config["embed_dim"]),
                             int(config["gallery"]["block_rows"]),
                             shard_mesh(config),
                             int(config["gallery"].get("draw_rows", 0)),
                             planted(seed))


def fill_gallery(gallery, seed: int, config: Dict[str, Any],
                 label_offset: int) -> int:
    """Replaces every row past the enrolled ones with a seeded unit row,
    or the row planted at its place, and marks all rows valid; returns the
    number of enrolled rows kept. Drawn and spliced on the chips, shard by
    shard, and handed to the program through ``install_device_rows``."""
    import jax
    import jax.numpy as jnp

    data = gallery.data
    enrolled = int(data.size)
    rows = int(gallery.capacity)
    if len(planted(seed)[0]) and planted(seed)[0].min() < enrolled:
        raise RuntimeError("a planted row lies among the enrolled subjects'")
    filler = make_sharded_rows(seed, rows, int(gallery.dim),
                               int(config["gallery"]["block_rows"]),
                               gallery.mesh,
                               int(config["gallery"].get("draw_rows", 0)),
                               planted(seed))

    @functools.partial(
        jax.jit, donate_argnums=(0,),
        out_shardings=(data.embeddings.sharding, data.labels.sharding,
                       data.valid.sharding))
    def splice(filler, old_emb, old_lab):
        head = jnp.arange(rows) < enrolled
        emb = jnp.where(head[:, None], old_emb.astype(filler.dtype), filler)
        lab = jnp.where(head, old_lab,
                        label_offset + jnp.arange(rows, dtype=jnp.int32))
        return emb, lab, jnp.ones((rows,), bool)

    emb, lab, val = splice(filler, data.embeddings, data.labels)
    del filler, data
    jax.block_until_ready((emb, lab, val))
    gallery.install_device_rows(emb, lab, val, rows)
    return enrolled


def selected_matcher(config: Dict[str, Any]) -> str:
    """The matcher the program selects on this cell's mesh at the
    watchlist's size, asked of a gallery of one row a shard."""
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.parallel import ShardedGallery

    mesh = shard_mesh(config)
    probe = ShardedGallery(capacity=mesh.shape["tp"],
                           dim=int(config["embed_dim"]), mesh=mesh,
                           store_dtype=jnp.bfloat16)
    return probe.matcher_name(int(config["gallery"]["total_rows"]))


def build(config: Dict[str, Any], traffic, seed: int,
          say: Callable[[str], None], trace: bool = False) -> recognize.Stack:
    """``recognize.build`` with this module's fill in the place of its
    own, after the look at the matcher. ``recognize.build`` holds the
    gallery's capacity against ``gallery.rows``: it is handed the total
    there."""
    shards, total = int(config["gallery"]["shards"]), int(config["gallery"]["total_rows"])
    if (shards != int(config["devices"])
            or total != shards * int(config["gallery"]["rows"])
            or total != int(config["recognize_args"]["--capacity"])):
        raise SystemExit("benchmark: the configuration's gallery does not add "
                         "up: total_rows = shards x rows = --capacity, and "
                         "shards = devices")
    want, got = config["gallery"]["matcher"], selected_matcher(config)
    if got != want:
        raise SystemExit(
            f"benchmark: on a mesh of {shards} chips this program serves "
            f"{total} rows by its {got!r} matcher; the configuration states "
            f"{want!r} (the kernel on every shard). Nothing was built.")
    t0 = time.perf_counter()
    count = plant(config, traffic, seed, recognize.ensure_nets(config, say)["dir"])
    planting = time.perf_counter() - t0
    say(f"stack: {count} faces of the traffic's scenes have a row of their "
        f"own in the watchlist, dealt round over the {shards} shards")
    whole = {**config, "gallery": {**config["gallery"], "rows": total}}
    theirs, recognize.fill_gallery = recognize.fill_gallery, fill_gallery
    try:
        stack = recognize.build(whole, traffic, seed, say, trace=trace)
    finally:
        recognize.fill_gallery = theirs
    stack.split["plant"] = planting
    held = {tuple(s.data.shape) for s in stack.gallery.data.embeddings.addressable_shards}
    if held != {(total // shards, int(config["embed_dim"]))}:
        raise RuntimeError(f"the gallery's shards hold {held}")
    return stack

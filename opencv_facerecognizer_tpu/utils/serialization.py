"""Pickle-free model checkpointing (SURVEY.md §5.4).

The reference checkpointed by pickling the whole ``PredictableModel``
(``facerec/serialization.py`` save_model/load_model — SURVEY.md §2.1). That
is unsafe (arbitrary code execution on load) and version-brittle. Rebuild:

- a *spec* — a JSON-safe nested dict ``{"type": registry-name, "config":
  {...}}`` describing how to reconstruct every plugin, and
- a *state* — a nested dict of arrays (the fit results / enrolled gallery),
  serialized with flax's msgpack (no code, just tensors + structure).

``save_model`` writes one msgpack file with header/spec/state;
``load_model`` rebuilds the plugin tree from the registry and restores
arrays. Anything implementing get_config/from_config/get_state/set_state
participates — including operators, which recursively serialize children.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np
from flax import serialization as flax_serialization

FORMAT_VERSION = 1


class CheckpointCorruptError(ValueError):
    """A checkpoint file failed decode/validation — truncated, garbage, or
    missing its header. Deliberately a ``ValueError`` subclass so existing
    broad handlers keep working, but precise enough that recovery code can
    fall back to an older checkpoint instead of treating the failure as a
    code bug."""


def fsync_directory(path: str) -> None:
    """fsync a directory so a just-renamed entry survives a power cut.
    Best-effort: some filesystems refuse O_RDONLY dir fsync — a failure
    only widens the durability window back to the kernel's writeback."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def atomic_write_bytes(filename: str, blob: bytes,
                       keep_previous: int = 0) -> None:
    """Crash-safe file write: tmp in the same directory + flush + fsync +
    atomic rename + directory fsync. A crash at any point leaves either the
    old file intact or the new one complete — never a torn half-write under
    the final name (the seed's bare ``open+write`` could corrupt the ONLY
    checkpoint mid-save). With ``keep_previous > 0`` the existing file's
    content is preserved at ``filename.1..N`` — hardlinked AFTER the tmp
    is durable, so neither a write failure (ENOSPC) nor process death
    between the rotate and the install ever leaves ``filename`` absent or
    stale-only-under-``.1``."""
    filename = str(filename)
    directory = os.path.dirname(os.path.abspath(filename))
    # pid-unique tmp: two concurrent writers of the same target must not
    # share a staging file, or one's os.replace could install the other's
    # half-written bytes — the exact torn-file class this helper prevents
    tmp = f"{filename}.tmp.{os.getpid()}"
    fh = open(tmp, "wb")
    try:
        fh.write(blob)
        fh.flush()
        os.fsync(fh.fileno())
    finally:
        fh.close()
    if keep_previous > 0:
        rotate_backups(filename, keep_previous)
    os.replace(tmp, filename)
    fsync_directory(directory)


def atomic_write_text(filename: str, text: str,
                      keep_previous: int = 0) -> None:
    """``atomic_write_bytes`` for text — the required way to write reports,
    JSON artifacts and any other file whose torn half-write would be read
    later (ocvf-lint rule ``non-atomic-write`` flags bare ``open(.., 'w')``)."""
    atomic_write_bytes(filename, text.encode("utf-8"),
                       keep_previous=keep_previous)


def atomic_write_json(filename: str, obj: Any, *, indent: int = 2,
                      sort_keys: bool = False, keep_previous: int = 0) -> None:
    """Crash-safe ``json.dump`` replacement: serialize fully in memory, then
    one atomic tmp+fsync+rename install.  ``json.dump(obj, fh)`` writes
    incrementally, so a crash mid-dump leaves a truncated-but-parseable-
    prefix trap; this never does."""
    text = json.dumps(obj, indent=indent, sort_keys=sort_keys)
    atomic_write_text(filename, text + "\n", keep_previous=keep_previous)


def rotate_backups(filename: str, keep: int) -> None:
    """Preserve the current file's content at ``filename.1`` (shifting
    ``.1 -> .2 -> ... -> .keep``, dropping the oldest) so an atomic
    overwrite can retain previous versions — ``ocvf-train
    --keep-checkpoints`` uses this to keep the last N model checkpoints
    across retrains.

    ``filename`` itself is HARDLINKED to ``.1``, not renamed: the final
    name stays present throughout, so a SIGKILL/power cut anywhere in the
    rotate-then-install sequence never leaves the path empty (a rename
    here would open exactly that window). On a filesystem without
    hardlinks the rename fallback reopens that (tiny) window — renames
    only, never data loss."""
    if keep <= 0 or not os.path.exists(filename):
        return
    oldest = f"{filename}.{keep}"
    if os.path.exists(oldest):
        os.remove(oldest)
    for i in range(keep - 1, 0, -1):
        src = f"{filename}.{i}"
        if os.path.exists(src):
            os.replace(src, f"{filename}.{i + 1}")
    try:
        os.link(filename, f"{filename}.1")
    except OSError:
        os.replace(filename, f"{filename}.1")

#: registry-name -> class, populated lazily to avoid import cycles.
_REGISTRY: Dict[str, type] = {}


def _registry() -> Dict[str, type]:
    if not _REGISTRY:
        from opencv_facerecognizer_tpu.models import classifier as c
        from opencv_facerecognizer_tpu.models import feature as f
        from opencv_facerecognizer_tpu.models import model as m
        from opencv_facerecognizer_tpu.models import operators as o

        for cls in (
            f.Identity,
            f.PCA,
            f.LDA,
            f.Fisherfaces,
            f.SpatialHistogram,
            f.TanTriggsPreprocessing,
            f.HistogramEqualization,
            f.Resize,
            f.MinMaxNormalize,
            o.ChainOperator,
            o.CombineOperator,
            o.CombineOperatorND,
            c.NearestNeighbor,
            c.SVM,
            c.KernelSVM,
            m.PredictableModel,
            m.ExtendedPredictableModel,
        ):
            _REGISTRY[cls.name] = cls
        # The CNN embedders live in their own modules (heavier deps); they
        # are part of the default registry all the same — a checkpoint saved through
        # the plain save_model API must load without first touching the
        # trainer or the serving app (round-3 drive finding).
        from opencv_facerecognizer_tpu.models import embedder as e
        from opencv_facerecognizer_tpu.models import iresnet as r
        from opencv_facerecognizer_tpu.models import vit as v

        _REGISTRY[e.CNNEmbedding.name] = e.CNNEmbedding
        _REGISTRY[r.IResNetEmbedding.name] = r.IResNetEmbedding
        _REGISTRY[v.ViTEmbedding.name] = v.ViTEmbedding
    return _REGISTRY


def register(cls: type) -> type:
    """Register an external plugin class (usable as a decorator)."""
    _registry()[cls.name] = cls
    return cls


def serialize_spec(obj: Any) -> dict:
    """Object -> JSON-safe reconstruction spec {"type", "config"}."""
    return {"type": obj.name, "config": obj.get_config()}


def deserialize_spec(spec: dict) -> Any:
    reg = _registry()
    if spec["type"] not in reg:
        raise KeyError(
            f"unknown plugin type {spec['type']!r}; registered: {sorted(reg)}"
        )
    return reg[spec["type"]].from_config(spec["config"])


def _to_numpy_tree(state: Any) -> Any:
    if isinstance(state, dict):
        return {k: _to_numpy_tree(v) for k, v in state.items()}
    return np.asarray(state)


def save_model(filename: str, model: Any, keep_previous: int = 0) -> None:
    """Write {header, spec, state} as one msgpack blob. No pickle anywhere.

    The write is atomic (tmp + fsync + rename): a crash mid-save leaves the
    previous checkpoint intact, never a truncated file under ``filename``.
    ``keep_previous > 0`` additionally rotates the existing file to
    ``filename.1`` (... ``.keep_previous``) before the rename."""
    payload = {
        "header": {"format_version": FORMAT_VERSION, "spec_json": json.dumps(serialize_spec(model))},
        "state": _to_numpy_tree(model.get_state()),
    }
    blob = flax_serialization.msgpack_serialize(payload)
    atomic_write_bytes(filename, blob, keep_previous=keep_previous)


def load_model(filename: str) -> Any:
    with open(filename, "rb") as fh:
        blob = fh.read()
    try:
        payload = flax_serialization.msgpack_restore(blob)
    except Exception as exc:  # noqa: BLE001 — msgpack raises assorted types
        raise CheckpointCorruptError(
            f"checkpoint {filename!r} failed msgpack decode (truncated or "
            f"garbage): {exc!r}") from exc
    if not isinstance(payload, dict) or "header" not in payload:
        raise CheckpointCorruptError(
            f"checkpoint {filename!r} decoded but has no header — not an "
            f"ocvf model checkpoint")
    header = payload["header"]
    try:
        version = int(header["format_version"])
        spec = json.loads(header["spec_json"])
    except (KeyError, TypeError, ValueError) as exc:
        raise CheckpointCorruptError(
            f"checkpoint {filename!r} has a malformed header: {exc!r}") from exc
    if version > FORMAT_VERSION:
        raise ValueError(f"checkpoint format v{version} is newer than supported v{FORMAT_VERSION}")
    model = deserialize_spec(spec)
    model.set_state(payload.get("state", {}))
    return model

"""Faults planted in what a window published, one guarantee of the
configuration broken at a time, for the readings that have to come out not
correct where the control one precision step lower has nothing to say: the
fp8/int8 reference has no track cache and loses no face.

Each plant takes the results kept of a window and the sample drawn from
them, and returns the results with the fault in (the messages it alters are
copies). ``window.numbers_compared(..., plant=...)`` puts them through the
same ``check.compare`` and ``check.verdict`` as a run's own.

    stranger     one cache reply carries an identity that no full result of
                 its stream had
    swap         every ``every``-th cache reply carries another identity of
                 its stream: one that a full result had, at the wrong face
    drop_face    one face is missing from one full result
    invent_face  one full result holds a face where the frame has none
    wrong_row    every ``every``-th face of the full results is matched to a
                 gallery row drawn at random: one query slot gone wrong

``PLANTS`` holds the doses that have to come out not correct. Weaker doses
were read on the chip once and pass (``swap(8)``, ``wrong_row(32)``:
PERF.md says what the comparison cannot see).
"""

from __future__ import annotations

import copy
from typing import Any, Callable, Dict, List

import numpy as np

from benchmark import check


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), 23, salt])


def _altered(results: Dict[int, Any], seqs: List[int]) -> Dict[int, Any]:
    out = dict(results)
    for s in seqs:
        out[s] = copy.deepcopy(results[s])
    return out


def _random_label(rng, label_offset: int, rows: int, enrolled: int) -> int:
    return label_offset + int(rng.integers(enrolled, rows))


def stranger(results, sample, seed, *, label_offset, rows, enrolled, **_kw):
    cached = [s for s in sample.get(check.EXIT_CACHED, []) if results[s]["faces"]]
    if not cached:
        return results
    rng = _rng(seed, 1)
    seq = int(rng.choice(cached))
    out = _altered(results, [seq])
    for face in out[seq]["faces"]:
        face["label"] = _random_label(rng, label_offset, rows, enrolled)
    return out


def swap(every: int) -> Callable[..., Dict[int, Any]]:
    def plant(results, sample, seed, *, first_full, **_kw):
        cached = [s for s in sample.get(check.EXIT_CACHED, []) if results[s]["faces"]]
        rng = _rng(seed, 2)
        chosen = cached[int(rng.integers(every))::every] if cached else []
        out = _altered(results, chosen)
        for seq in chosen:
            had = first_full.get(out[seq]["meta"].get("stream"), {})
            for face in out[seq]["faces"]:
                others = sorted(label for label, first in had.items()
                                if first < seq and label >= 0
                                and label != int(face["label"]))
                if others:
                    face["label"] = int(rng.choice(others))
        return out
    return plant


def drop_face(results, sample, seed, **_kw):
    full = [s for s in sample.get(check.EXIT_FULL, []) if results[s]["faces"]]
    if not full:
        return results
    rng = _rng(seed, 3)
    seq = int(rng.choice(full))
    out = _altered(results, [seq])
    del out[seq]["faces"][int(rng.integers(len(out[seq]["faces"])))]
    return out


def invent_face(results, sample, seed, *, frame_size, **_kw):
    """A copy of one of the result's faces, put in the corner of the frame
    that is farthest from every face published there."""
    full = [s for s in sample.get(check.EXIT_FULL, []) if results[s]["faces"]]
    if not full:
        return results
    rng = _rng(seed, 4)
    seq = int(rng.choice(full))
    out = _altered(results, [seq])
    faces = out[seq]["faces"]
    ghost = copy.deepcopy(faces[int(rng.integers(len(faces)))])
    x0, y0, x1, y1 = ghost["box"]
    w, h = x1 - x0, y1 - y0
    fh, fw = (float(v) for v in frame_size)
    corners = [(0.0, 0.0), (fw - w, 0.0), (0.0, fh - h), (fw - w, fh - h)]

    def nearest(corner):
        cx, cy = corner[0] + w / 2, corner[1] + h / 2
        return min(abs((f["box"][0] + f["box"][2]) / 2 - cx)
                   + abs((f["box"][1] + f["box"][3]) / 2 - cy) for f in faces)

    cx, cy = max(corners, key=nearest)
    ghost["box"] = [cx, cy, cx + w, cy + h]
    faces.append(ghost)
    return out


def wrong_row(every: int) -> Callable[..., Dict[int, Any]]:
    def plant(results, sample, seed, *, label_offset, rows, enrolled, **_kw):
        full = sample.get(check.EXIT_FULL, [])
        rng = _rng(seed, 5)
        out = _altered(results, full)
        n = int(rng.integers(every))
        for seq in full:
            for face in out[seq]["faces"]:
                if n % every == 0:
                    face["label"] = _random_label(rng, label_offset, rows, enrolled)
                n += 1
        return out
    return plant


PLANTS: Dict[str, Callable[..., Dict[int, Any]]] = {
    "stranger": stranger, "swap_all": swap(1), "drop_face": drop_face,
    "invent_face": invent_face, "wrong_row_8th": wrong_row(8),
}

#: the numbers of which each plant has to put at least one over its limit
HAS_TO_FAIL = {
    "stranger": ("cached_strangers",),
    "swap_all": ("cached_gap",),
    "drop_face": ("det_miss",),
    "invent_face": ("det_miss",),
    "wrong_row_8th": ("match_gap", "match_gap_far"),
}

"""Rendered scenes and faces, in the style the nets are trained on.

Textured background, bright ellipse faces with dark eyes; each face carries
a smooth per-identity pattern so that the embedder has identities to tell
apart. Background and ellipse are a copy of
``utils/dataset.make_synthetic_scenes`` (see PERF.md, Open questions). The
nets' recipe hash covers this file: what it renders is what they learn.
"""

from __future__ import annotations

import base64
from typing import Any, Dict, List, Tuple

import numpy as np


def identity_pattern(identity: int, fs: int) -> np.ndarray:
    """Smooth pattern of one identity at face size ``fs``: four gaussians
    at places and strengths drawn from the identity number alone."""
    rng = np.random.default_rng(1_000_003 + int(identity))
    yy, xx = np.mgrid[0:fs, 0:fs].astype(np.float32) / float(fs)
    out = np.zeros((fs, fs), np.float32)
    for _ in range(4):
        cy, cx = rng.uniform(0.15, 0.85, size=2)
        sy, sx = rng.uniform(0.10, 0.30, size=2)
        amp = rng.uniform(-1.0, 1.0)
        out += amp * np.exp(-(((yy - cy) / sy) ** 2 + ((xx - cx) / sx) ** 2))
    return 28.0 * out / (np.abs(out).max() + 1e-6)


def render_face(identity: int, fs: int, rng: np.random.Generator
                ) -> Tuple[np.ndarray, np.ndarray]:
    """One face patch [fs, fs] and its ellipse mask."""
    yy, xx = np.mgrid[0:fs, 0:fs].astype(np.float32)
    cy, cx = fs / 2, fs / 2
    ellipse = (((yy - cy) / (fs * 0.5)) ** 2
               + ((xx - cx) / (fs * 0.42)) ** 2) <= 1.0
    face = (185.0 + 30.0 * np.cos(yy / fs * 3.1) + identity_pattern(identity, fs)
            + rng.normal(scale=6.0, size=(fs, fs)))
    for ex in (0.32, 0.68):
        eyy, exx = int(fs * 0.38), int(fs * ex)
        rr = max(1, fs // 10)
        face[eyy - rr:eyy + rr, exx - rr:exx + rr] -= 90.0
    return face.astype(np.float32), ellipse


def render_background(size: Tuple[int, int], rng: np.random.Generator
                      ) -> np.ndarray:
    h, w = size
    bg = rng.normal(size=(-(-h // 8), -(-w // 8))).astype(np.float32)
    bg = np.kron(bg, np.ones((8, 8), np.float32))[:h, :w]
    return 80.0 + 20.0 * bg + rng.normal(scale=6.0, size=(h, w)).astype(np.float32)


def render_scene(size: Tuple[int, int], identities: List[int],
                 face_px: Tuple[int, int], rng: np.random.Generator
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """A frame with exactly ``len(identities)`` faces that do not touch.
    Returns (uint8 frame, boxes [n, 4] pixel yxyx)."""
    h, w = size
    scene = render_background(size, rng)
    boxes = np.zeros((len(identities), 4), np.float32)
    for n, identity in enumerate(identities):
        for _attempt in range(10_000):
            fs = int(rng.integers(face_px[0], face_px[1] + 1))
            y0 = int(rng.integers(0, h - fs + 1))
            x0 = int(rng.integers(0, w - fs + 1))
            gap = 6  # keeps neighbouring boxes apart for NMS and the tracker
            if all(y0 + fs + gap < b[0] or b[2] + gap < y0
                   or x0 + fs + gap < b[1] or b[3] + gap < x0
                   for b in boxes[:n]):
                break
        else:
            raise RuntimeError("could not place the faces the mix asks for")
        face, ellipse = render_face(identity, fs, rng)
        scene[y0:y0 + fs, x0:x0 + fs][ellipse] = face[ellipse]
        boxes[n] = (y0, x0, y0 + fs, x0 + fs)
    return np.clip(scene, 0, 255).astype(np.uint8), boxes


def render_enrolment(identity: int, size: Tuple[int, int], count: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``count`` enrolment images of one identity at the embedder's input
    size: the face patch alone, as a tight crop would show it."""
    out = np.zeros((count, *size), np.float32)
    for i in range(count):
        face, ellipse = render_face(identity, size[0], rng)
        img = np.full(size, 80.0, np.float32) + rng.normal(
            scale=6.0, size=size).astype(np.float32)
        img[ellipse] = face[ellipse]
        out[i] = np.clip(img, 0, 255)
    return out


def encode_frame(frame: np.ndarray) -> Dict[str, Any]:
    """The wire form the connectors carry (``runtime.connector``'s)."""
    frame = np.ascontiguousarray(frame)
    return {"__frame__": base64.b64encode(frame.tobytes()).decode("ascii"),
            "shape": list(frame.shape), "dtype": str(frame.dtype)}

"""The comparison that decides ``correct``.

Once the window has closed, a sample of the frames it finished (drawn from
the seed) is run through the configuration's plain reference, and what the
service published for them is held against it: the gate's verdict, the
detector's boxes and scores, the similarity, the matched row, and what the
track cache answered without a step. A reading taken face by face is
compared twice, by its mean and by the share of faces that read far off;
the cache's replies are also held, every one of them, to the identities
that full results of their stream carried. Each number has a limit of its
own; ``PERF.md`` gives the readings each limit was set from.

The limits live in ``benchmark/configs/<config>.limits.json``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

EXIT_FULL, EXIT_EMPTY, EXIT_CACHED = 1, 2, 3


def exit_code(message: Dict[str, Any]) -> int:
    return {"cascade": EXIT_EMPTY, "track_cache": EXIT_CACHED}.get(
        message.get("exit"), EXIT_FULL)


def draw_sample(seed: int, kept: Dict[int, Dict[str, Any]], sizes: Dict[int, int]
                ) -> Dict[int, List[int]]:
    """Of the kept results, per kind of exit, ``sizes[kind]`` frame
    numbers drawn from the seed (all of them when there are fewer)."""
    rng = np.random.default_rng([int(seed), 17])
    out: Dict[int, List[int]] = {}
    for kind, want in sizes.items():
        have = sorted(s for s, m in kept.items() if exit_code(m) == kind)
        if len(have) > want:
            have = sorted(int(s) for s in rng.choice(have, size=want, replace=False))
        out[kind] = have
    return out


def _iou(a, b) -> float:
    iy = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
    ix = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
    inter = iy * ix
    union = ((a[2] - a[0]) * (a[3] - a[1]) + (b[2] - b[0]) * (b[3] - b[1]) - inter)
    return inter / union if union > 0 else 0.0


def _pair(program: List[Dict[str, Any]], ref_boxes: np.ndarray,
          ref_valid: np.ndarray) -> Tuple[List[Tuple[int, int]], List[int], List[int]]:
    """Greedy pairing by IoU >= 0.5 of the published faces with the
    reference's; returns (pairs, unpaired program, unpaired reference)."""
    boxes = [(f["box"][1], f["box"][0], f["box"][3], f["box"][2])
             for f in program]  # published x-first -> yxyx
    free = [j for j in range(len(ref_boxes)) if ref_valid[j]]
    pairs, lone = [], []
    for i, box in enumerate(boxes):
        best = max(free, key=lambda j: _iou(box, ref_boxes[j]), default=None)
        if best is not None and _iou(box, ref_boxes[best]) >= 0.5:
            pairs.append((i, best))
            free.remove(best)
        else:
            lone.append(i)
    return pairs, lone, free


def longest_cached_run(flags: np.ndarray, streams: int, first: int, stop: int
                       ) -> int:
    """Longest run of frames in a row, on one stream, that the track
    cache answered (frame i belongs to stream i % streams)."""
    longest = 0
    for s in range(streams):
        start = first + ((s - first) % streams)
        run = 0
        for flag in flags[start:stop:streams]:
            run = run + 1 if flag == EXIT_CACHED else 0
            longest = max(longest, run)
    return longest


def cached_strangers(results: Dict[int, Dict[str, Any]],
                     first_full: Dict[Any, Dict[int, int]]) -> int:
    """Faces in cache replies whose identity no full result of their
    stream carried before them. ``first_full[stream][label]`` is the
    lowest frame number of a full result of ``stream`` that carried
    ``label``; frames of one stream are served in order, so the full
    result that verified a track has a lower number than every reply the
    track gives. Every cache reply of the window is looked at, not a
    sample: the comparison is exact."""
    strangers = 0
    for seq, message in results.items():
        if exit_code(message) != EXIT_CACHED:
            continue
        had = first_full.get(message["meta"].get("stream"), {})
        for face in message["faces"]:
            if had.get(int(face["label"]), seq) >= seq:
                strangers += 1
    return strangers


def compare(reference, gallery_rows, block_rows: int, enrol_images: np.ndarray,
            enrol_labels: np.ndarray, label_offset: int, det_threshold: float,
            frames: Dict[int, np.ndarray], results: Dict[int, Dict[str, Any]],
            sample: Dict[int, List[int]], far: Dict[str, float],
            first_full: Dict[Any, Dict[int, int]]):
    """(the numbers compared, without their limits; what else was seen).
    ``frames[seq]`` are the pixels of the sampled frames, ``results[seq]``
    what was published, ``far[name]`` the reading of one face from which
    it counts into ``<name>_far``, ``first_full`` as ``cached_strangers``
    takes it."""
    out = {"gate_gap": 0.0, "det_miss": 0.0, "box_gap_px": 0.0,
           "score_err": 0.0, "sim_err": 0.0, "match_gap": 0.0,
           "cached_gap": 0.0}
    out.update({name + "_far": 0.0 for name in far})
    out["cached_strangers"] = float(cached_strangers(results, first_full))
    seen = {"cached_faces_unpaired": 0}  # observed, not compared
    # One reading per face; two numbers are compared of each (see the end)
    sim_gaps: List[float] = []
    # (``gate_gap``: one per frame, 0 where the reference is on the side
    # of the verdict that was served)
    per_face: Dict[str, List[float]] = {"gate_gap": [], "box_gap_px": [],
                                        "score_err": [], "match_gap": [],
                                        "cached_gap": []}
    thr = reference.gate_threshold

    # -- the gate's verdict, on frames it rejected and frames it passed --
    rejected, passed = sample.get(EXIT_EMPTY, []), sample.get(EXIT_FULL, [])
    seqs = rejected + passed
    if seqs:
        scores = reference.gate_scores(np.stack([frames[s] for s in seqs]))
        for n, score in enumerate(scores):
            wrong_side = (score - thr) if n < len(rejected) else (thr - score)
            per_face["gate_gap"].append(max(0.0, float(wrong_side)))

    # -- full results and cached replies: the reference's full path --
    cached = sample.get(EXIT_CACHED, [])
    seqs = passed + cached
    if not seqs:
        return _reduced(out, seen, per_face, far)
    pixels = np.stack([frames[s] for s in seqs])
    ref_boxes, ref_scores, ref_valid = reference.detect(pixels)
    ref_emb = reference.embed(pixels, ref_boxes)
    head = reference.embed_images(enrol_images)
    n_head = len(head)
    k = ref_boxes.shape[1]
    best, best_idx, sims_at = reference.match(
        ref_emb.reshape(len(seqs) * k, -1), gallery_rows, n_head, head,
        block_rows)
    best = best.reshape(len(seqs), k)
    stored = np.asarray(reference.as_stored(head))
    head_sims = (ref_emb.reshape(len(seqs) * k, -1).astype(np.float32)
                 @ stored.T).reshape(len(seqs), k, n_head)

    # The similarity is held against the reference's embedding of the box
    # that was PUBLISHED, so that a sub-pixel difference between the two
    # detectors' boxes does not pass for an error of the embedder.
    pairing = [_pair(results[s]["faces"], ref_boxes[n], ref_valid[n])
               for n, s in enumerate(seqs)]
    pub_boxes = np.zeros_like(ref_boxes)
    paired = []
    for n, s in enumerate(seqs[:len(passed)]):
        faces = results[s]["faces"]
        for i, j in pairing[n][0]:
            b = faces[i]["box"]
            pub_boxes[n, j] = (b[1], b[0], b[3], b[2])
            paired.append((n, j, float(faces[i]["similarity"])))
    if paired:
        pub_emb = reference.embed(pixels[:len(passed)], pub_boxes[:len(passed)])
        pub_best, _idx, _at = reference.match(
            pub_emb.reshape(len(passed) * k, -1), gallery_rows, n_head, head,
            block_rows)
        pub_best = pub_best.reshape(len(passed), k)
        sim_gaps = [abs(sim - float(pub_best[n, j])) for n, j, sim in paired]

    ask_rows, ask_where = [], []
    for n, s in enumerate(seqs):
        faces = results[s]["faces"]
        pairs, lone, free = pairing[n]
        is_cached = n >= len(passed)
        if is_cached:
            # The cache answers with the identities it holds; a face of the
            # frame that it holds no track for is not in its reply.
            seen["cached_faces_unpaired"] += len(lone) + len(free)
            lone, free = [], []
        for i in lone:
            out["det_miss"] = max(out["det_miss"],
                                  float(faces[i]["detection_score"]) - det_threshold)
        for j in free:
            out["det_miss"] = max(out["det_miss"],
                                  float(ref_scores[n, j]) - det_threshold)
        for i, j in pairs:
            face = faces[i]
            box = np.array([face["box"][1], face["box"][0], face["box"][3],
                            face["box"][2]], np.float32)
            if not is_cached:
                per_face["box_gap_px"].append(
                    float(np.abs(box - ref_boxes[n, j]).max()))
                per_face["score_err"].append(abs(
                    float(face["detection_score"]) - float(ref_scores[n, j])))
                seen["sim_err_own_box"] = max(seen.get("sim_err_own_box", 0.0), abs(
                    float(face["similarity"]) - float(best[n, j])))
            label = int(face["label"])
            key = "cached_gap" if is_cached else "match_gap"
            if label < 0:
                continue  # published as unknown: sim_err holds its similarity
            if label < label_offset:
                rows = np.flatnonzero(enrol_labels == label)
                at = float(head_sims[n, j, rows].max()) if len(rows) else -1.0
                gap = float(best[n, j]) - at
                if gap > out[key]:
                    seen["worst_" + key] = {
                        "seq": int(s), "label": label, "enrolled": True,
                        "published_sim": float(face["similarity"]),
                        "reference_best": float(best[n, j]),
                        "reference_best_row": int(best_idx[n * k + j]),
                        "reference_at_label": at}
                out[key] = max(out[key], gap)
                per_face[key].append(gap)
            else:
                ask_rows.append(label - label_offset)
                ask_where.append((n, j, key))
    if ask_rows:
        flat = np.array([n * k + j for n, j, _key in ask_where])
        rows = np.asarray(ask_rows, np.int32)
        # one gather for all the named rows, then the dot of each pair
        got = sims_at_pairs(sims_at, flat, rows, len(seqs) * k)
        for (n, j, key), at, row in zip(ask_where, got, ask_rows):
            gap = float(best[n, j]) - float(at)
            if gap > out[key]:
                seen["worst_" + key] = {
                    "seq": int(seqs[n]), "label_row": int(row), "enrolled": False,
                    "reference_best": float(best[n, j]),
                    "reference_best_row": int(best_idx[n * k + j]),
                    "reference_at_label": float(at)}
            out[key] = max(out[key], gap)
            per_face[key].append(gap)
    per_face["sim_err"] = sim_gaps
    seen["faces_compared"] = len(sim_gaps)
    return _reduced(out, seen, per_face, far)


def _reduced(out, seen, per_face: Dict[str, List[float]], far: Dict[str, float]):
    """Every reading taken face by face (or frame by frame) is compared
    twice: by its mean over the sample, which a fault in every face moves,
    and by the share of faces that read ``far[name]`` or more, which a fault
    in a few faces moves. The widest single reading has a tail that no limit
    holds: when the bf16 detector's heatmap peak falls in the cell next to
    the f32 one's, that face's box moves by 1-4 px and its crop, similarity
    and matched row with it; a frame whose gate score lies at the threshold
    falls on either side (PERF.md). The share allows a run a few of those;
    the widest readings stay in the run's file."""
    for name, gaps in per_face.items():
        if gaps:
            seen[name + "_widest"] = float(np.max(gaps))
            out[name] = float(np.mean(gaps))
            if name in far:
                out[name + "_far"] = float(np.mean(np.asarray(gaps) >= far[name]))
    seen["per_face"] = {name: [round(float(g), 5) for g in gaps]
                        for name, gaps in per_face.items()}
    return out, seen


def sims_at_pairs(sims_at, query_index: np.ndarray, rows: np.ndarray,
                  n_queries: int) -> np.ndarray:
    """``sims_at`` answers one row per query; ask in rounds, so that a
    query that appears twice is asked twice."""
    out = np.zeros((len(rows),), np.float32)
    todo = np.arange(len(rows))
    while len(todo):
        _uniq, first = np.unique(query_index[todo], return_index=True)
        now = todo[first]
        ask = np.zeros((n_queries,), np.int32)
        ask[query_index[now]] = rows[now]
        out[now] = sims_at(ask)[query_index[now]]
        todo = np.setdiff1d(todo, now)
    return out


def publish_like(reference, gallery_rows, block_rows: int,
                 enrol_images: np.ndarray, enrol_labels: np.ndarray,
                 label_offset: int, similarity_threshold: float,
                 frames: Dict[int, np.ndarray]) -> Dict[int, Dict[str, Any]]:
    """What ``reference`` would publish for ``frames``, in the service's
    own result format. With the reference built one precision step lower,
    this is the control put in the program's place."""
    seqs = sorted(frames)
    pixels = np.stack([frames[s] for s in seqs])
    gate = reference.gate_scores(pixels)
    boxes, scores, valid = reference.detect(pixels)
    emb = reference.embed(pixels, boxes)
    head = reference.embed_images(enrol_images)
    k = boxes.shape[1]
    best, best_idx, _sims_at = reference.match(
        emb.reshape(len(seqs) * k, -1), gallery_rows, len(head), head, block_rows)
    best, best_idx = best.reshape(len(seqs), k), best_idx.reshape(len(seqs), k)
    out = {}
    for n, s in enumerate(seqs):
        if gate[n] < reference.gate_threshold:
            out[s] = {"meta": {"seq": s}, "faces": [], "exit": "cascade"}
            continue
        faces = []
        for j in range(k):
            if not valid[n, j]:
                continue
            row = int(best_idx[n, j])
            label = (int(enrol_labels[row]) if row < len(head)
                     else label_offset + row)
            sim = float(best[n, j])
            y0, x0, y1, x1 = (float(v) for v in boxes[n, j])
            faces.append({"box": [x0, y0, x1, y1],
                          "detection_score": float(scores[n, j]),
                          "label": label if sim >= similarity_threshold else -1,
                          "similarity": sim})
        out[s] = {"meta": {"seq": s}, "faces": faces}
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, List[float]]]:
    """(correct, {name: [number, limit]}); a number with no limit, or one
    that is not finite, fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        table[name] = [float(value), None if limit is None else float(limit)]
        if limit is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, table

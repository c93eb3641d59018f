"""The comparison that decides ``correct``.

Each sampled request's answer, as the client received it, is compared with
the plain reference (:mod:`reference`) run on the same points and
parameters.  The numbers compared, each the worst over the sampled requests
of one algorithm:

``kmeans_labels_off_pct``
    percent of points whose K-Means label differs from the reference's
    (both start from the same points, so centroid j is the same cluster on
    both sides and labels compare directly);
``kmeans_inertia_off``
    relative difference of the answer's inertia (the sum of squared
    distances of the points to their centroids in the last assignment)
    from the reference's: the client receives labels and inertia, not
    centroids, and the inertia carries the distances' precision;
``dbscan_labels_off_pct``
    percent of points whose DBSCAN label differs from the reference's,
    after each reference cluster is matched to at most one cluster of the
    answer (largest overlaps first; noise is matched to noise only), so a
    renumbering costs nothing and a split or a merge costs its points;
``dbscan_expansions_off``
    largest difference between the number of frontier expansions that a
    DBSCAN answer reports and the reference's count (each cluster's
    breadth-first levels): the depth of every cluster's search, which a
    distance off at the eps edge changes before it changes a label.  The
    ``numpy-mt`` lane reports no count and is not compared on it;
``unanswered``
    requests admitted in the window that never delivered an answer, or
    failed with an error (shed requests are counted in ``failed`` and are
    not answers).

Every number has its limit in the configuration file, under ``limits``; a
number above its limit makes the run not correct.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np


def kmeans_numbers(labels, inertia: float, ref: dict) -> Dict[str, float]:
    labels = np.asarray(labels, np.int64)
    ref_labels = np.asarray(ref["labels"], np.int64)
    off = 100.0 * float(np.mean(labels != ref_labels))
    gap = abs(float(inertia) - ref["inertia"]) / max(ref["inertia"], 1e-30)
    return {"kmeans_labels_off_pct": off, "kmeans_inertia_off": gap}


def match_clusters(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """``got`` relabelled onto ``ref``'s cluster ids, one to one."""
    got = np.asarray(got, np.int64)
    ref = np.asarray(ref, np.int64)
    table = np.zeros((got.max(initial=0) + 1, ref.max(initial=0) + 1),
                     np.int64)
    np.add.at(table, (got, ref), 1)
    mapping = np.full(table.shape[0], -1, np.int64)
    mapping[0] = 0
    used = {0}
    rows, cols = np.nonzero(table[1:, 1:])
    order = np.argsort(-table[1:, 1:][rows, cols], kind="stable")
    for r, c in zip(rows[order] + 1, cols[order] + 1):
        if mapping[r] < 0 and c not in used:
            mapping[r] = c
            used.add(int(c))
    return mapping[got]


def dbscan_numbers(labels, expansions, ref: dict) -> Dict[str, float]:
    """``expansions`` is None where the answer reports no count."""
    matched = match_clusters(labels, ref["labels"])
    off = 100.0 * float(np.mean(matched != np.asarray(ref["labels"])))
    out = {"dbscan_labels_off_pct": off}
    if expansions is not None:
        out["dbscan_expansions_off"] = float(
            abs(int(expansions) - int(ref["expansions"])))
    return out


def worst(rows: Iterable[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for row in rows:
        for name, value in row.items():
            out[name] = max(out.get(name, value), value)
    return out


def judge(numbers: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """``(correct, {name: {"value": v, "limit": l}})``."""
    checks = {}
    ok = True
    for name in sorted(numbers):
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the configuration")
        value, limit = numbers[name], limits[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, checks


def check_lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {name} {c['value']!r} limit {c['limit']!r}"
            for name, c in checks.items()]

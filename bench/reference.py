"""The plain reference: exact top-k over the raw float32 corpus.

Scores are computed in float64, in blocks of base rows, so that the
reference is exact to far below the program's stored precision and never
holds more than one (queries x block) score block at a time.  Nothing here
imports the program or takes anything the program made.
"""
from __future__ import annotations

import numpy as np


def exact_topk(vectors: np.ndarray, queries: np.ndarray, k: int,
               metric: str = "l2", block: int = 8192):
    """(ids (Q, k) int64, scores (Q, k) float64), nearest first.

    ``l2`` scores are squared Euclidean distances; ``ip`` scores are negated
    inner products, so that smaller is nearer for both."""
    q = np.asarray(queries, np.float64)
    qn = (q * q).sum(1)[:, None]
    best_s = np.full((len(q), k), np.inf)
    best_i = np.zeros((len(q), k), np.int64)
    for s in range(0, len(vectors), block):
        x = np.asarray(vectors[s:s + block], np.float64)
        dot = q @ x.T
        sc = qn + (x * x).sum(1)[None, :] - 2.0 * dot if metric == "l2" \
            else -dot
        all_s = np.concatenate([best_s, sc], axis=1)
        all_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(s, s + len(x)), sc.shape)],
            axis=1)
        part = np.argpartition(all_s, k - 1, axis=1)[:, :k]
        best_s = np.take_along_axis(all_s, part, 1)
        best_i = np.take_along_axis(all_i, part, 1)
    order = np.argsort(best_s, axis=1, kind="stable")
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_s, order, 1))


def scores_of(vectors: np.ndarray, queries: np.ndarray, ids: np.ndarray,
              metric: str = "l2") -> np.ndarray:
    """Exact float64 scores of ``ids`` (Q, k) for their queries (Q, D)."""
    q = np.asarray(queries, np.float64)[:, None, :]
    x = np.asarray(vectors, np.float64)[np.clip(ids, 0, len(vectors) - 1)]
    if metric == "l2":
        return ((x - q) ** 2).sum(-1)
    return -(x * q).sum(-1)

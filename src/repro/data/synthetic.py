"""Synthetic vector databases with controlled eigen-spectra.

The container is offline, so SIFT/GIST/GloVe/Wiki/MS_MARCO/BigANN are modeled
by generators matched on the axes that matter for NasZip:

  * dimensionality and metric (Table III),
  * covariance spectrum decay (drives alpha_k / FEE effectiveness, Fig. 8 —
    SIFT-like mild decay vs GIST-like steep decay),
  * cluster structure (drives graph locality -> LNC hit rates, Fig. 21),
  * query distribution (near-DB queries, as in ANN-benchmarks).

Ground truth, graphs and PCA artifacts are cached under .cache/ keyed by the
generator settings.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np

from repro.utils import cached_npz


@dataclasses.dataclass
class VecDB:
    name: str
    vectors: np.ndarray   # (N, D) f32
    queries: np.ndarray   # (Q, D) f32
    train_queries: np.ndarray  # (Qt, D) held-out, for offline fitting
    metric: str           # "l2" | "ip"
    gt: np.ndarray        # (Q, K) exact top-K ids

    @property
    def n(self):
        return self.vectors.shape[0]

    @property
    def dim(self):
        return self.vectors.shape[1]


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    n: int
    dim: int
    metric: str
    spectrum_decay: float   # lambda_i ~ i^-decay  (higher => steeper => FEE-friendlier)
    n_clusters: int
    cluster_spread: float   # relative within-cluster scale
    n_queries: int = 256
    gt_k: int = 100


# Scaled-down stand-ins for Table III (full sizes don't fit a 1-core CPU box;
# spectra chosen so relative FEE behaviour across datasets matches Fig. 8:
# GIST (960d) steepest, SIFT moderate, GloVe/IP flat-ish).
DATASETS = {
    "sift": DatasetSpec("sift", 40_000, 128, "l2", 0.9, 64, 0.5),
    "gist": DatasetSpec("gist", 12_000, 960, "l2", 1.4, 48, 0.4),
    "bigann": DatasetSpec("bigann", 60_000, 128, "l2", 0.9, 96, 0.5),
    "glove": DatasetSpec("glove", 30_000, 100, "ip", 0.6, 64, 0.7),
    "wiki": DatasetSpec("wiki", 20_000, 768, "l2", 1.2, 24, 0.35),
    "msmarco": DatasetSpec("msmarco", 30_000, 384, "l2", 1.1, 64, 0.45),
    # tiny configs for tests
    "unit": DatasetSpec("unit", 2_000, 64, "l2", 1.0, 8, 0.5, n_queries=64, gt_k=32),
    "unit_ip": DatasetSpec("unit_ip", 2_000, 64, "ip", 0.8, 8, 0.6, n_queries=64, gt_k=32),
}


def _generate(spec: DatasetSpec, seed: int = 0) -> dict:
    # crc32, not hash(): str hashes are salted per process, and the corpus
    # must be the same on every machine and run for a given seed
    rng = np.random.default_rng(seed + zlib.crc32(spec.name.encode()) % (2**31))
    d, n = spec.dim, spec.n
    lam = np.arange(1, d + 1, dtype=np.float64) ** (-spec.spectrum_decay)
    lam /= lam.sum()
    scale = np.sqrt(lam * d).astype(np.float32)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)

    centers = rng.standard_normal((spec.n_clusters, d)).astype(np.float32) * scale
    assign = rng.integers(0, spec.n_clusters, n)
    pts = centers[assign] + spec.cluster_spread * (
        rng.standard_normal((n, d)).astype(np.float32) * scale
    )
    vectors = pts @ basis.T  # hide the principal axes (PCA must find them)

    nq_all = spec.n_queries * 3  # eval + train pools
    qi = rng.integers(0, n, nq_all)
    queries = vectors[qi] + 0.25 * spec.cluster_spread * (
        rng.standard_normal((nq_all, d)).astype(np.float32) * scale
    ) @ basis.T
    if spec.metric == "ip":
        vectors /= np.linalg.norm(vectors, axis=1, keepdims=True) + 1e-9
        queries /= np.linalg.norm(queries, axis=1, keepdims=True) + 1e-9

    gt = exact_topk(vectors, queries[: spec.n_queries], spec.gt_k, spec.metric)
    return dict(vectors=vectors, queries=queries, gt=gt.astype(np.int32))


def exact_topk(db: np.ndarray, queries: np.ndarray, k: int, metric: str,
               block: int = 8192) -> np.ndarray:
    """Blocked exact kNN (the paper's kNN/recall ground-truth oracle)."""
    q = queries.shape[0]
    n = db.shape[0]
    scores = np.empty((q, n), np.float32)
    qn = (queries**2).sum(1, keepdims=True)
    for s in range(0, n, block):
        e = min(s + block, n)
        dot = queries @ db[s:e].T
        if metric == "l2":
            scores[:, s:e] = qn + (db[s:e] ** 2).sum(1)[None, :] - 2 * dot
        else:
            scores[:, s:e] = -dot
    idx = np.argpartition(scores, k - 1, axis=1)[:, :k]
    row = np.arange(q)[:, None]
    order = np.argsort(scores[row, idx], axis=1)
    return idx[row, order]


def make_dataset(name: str, seed: int = 0) -> VecDB:
    spec = DATASETS[name]
    data = cached_npz(f"dataset/{name}/v4/{seed}/{spec}", lambda: _generate(spec, seed))
    nq = spec.n_queries
    return VecDB(
        name=name,
        vectors=data["vectors"],
        queries=data["queries"][:nq],
        train_queries=data["queries"][nq:],
        metric=spec.metric,
        gt=data["gt"],
    )


def recall_at_k(found_ids: np.ndarray, gt: np.ndarray, k: int) -> float:
    """recall@k = |found ∩ gt_k| / k, averaged over queries (§II-A4)."""
    hits = 0
    for f, g in zip(found_ids[:, :k], gt[:, :k]):
        hits += len(set(f.tolist()) & set(g.tolist()))
    return hits / (k * len(gt))

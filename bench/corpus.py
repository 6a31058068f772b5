"""Seeded synthetic corpora with a controlled eigen-spectrum.

A copy of the repo's stand-in generator (``repro.data.synthetic._generate``),
kept here so that no program change can move the data a cell runs on, and
parameterised by a configuration file instead of a table of presets:
eigenvalues ``i ** -spectrum_decay`` hidden behind a random rotation,
``n_clusters`` Gaussian clusters of relative width ``cluster_spread``, and
queries drawn near base points with relative noise ``query_noise``.

The same (configuration, seed) gives the same arrays on every machine.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


@dataclasses.dataclass
class Corpus:
    vectors: np.ndarray         # (n_base, dim) float32
    queries: np.ndarray         # (n_queries, dim) float32: the served pool
    train_queries: np.ndarray   # (n_train_queries, dim) float32: index fitting


def rng_for(name: str, seed: int, stream: str = "") -> np.random.Generator:
    """A generator keyed by a configuration or traffic name, the run's seed
    (any integer) and a stream label, so that independent uses of one seed
    draw independent numbers."""
    key = zlib.crc32(f"{name}/{stream}".encode())
    return np.random.default_rng([int(seed) % 2**64, key])


def generate(cfg: dict, seed: int) -> Corpus:
    a = cfg["assumed"]
    n, d = int(cfg["n_base"]), int(cfg["dim"])
    rng = rng_for(cfg["name"], seed, "corpus")
    lam = np.arange(1, d + 1, dtype=np.float64) ** (-float(a["spectrum_decay"]))
    lam /= lam.sum()
    scale = np.sqrt(lam * d).astype(np.float32)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    spread = float(a["cluster_spread"])
    centers = (rng.standard_normal((int(a["n_clusters"]), d))
               .astype(np.float32) * scale)
    assign = rng.integers(0, len(centers), n)
    pts = centers[assign] + spread * (
        rng.standard_normal((n, d)).astype(np.float32) * scale)
    vectors = pts @ basis.T            # hide the principal axes

    nq, nt = int(cfg["n_queries"]), int(cfg["n_train_queries"])
    qi = rng.integers(0, n, nq + nt)
    queries = vectors[qi] + float(a["query_noise"]) * spread * (
        rng.standard_normal((nq + nt, d)).astype(np.float32) * scale) @ basis.T
    if cfg["metric"] == "ip":
        vectors /= np.linalg.norm(vectors, axis=1, keepdims=True) + 1e-9
        queries /= np.linalg.norm(queries, axis=1, keepdims=True) + 1e-9
    return Corpus(vectors=np.ascontiguousarray(vectors, np.float32),
                  queries=np.ascontiguousarray(queries[:nq], np.float32),
                  train_queries=np.ascontiguousarray(queries[nq:], np.float32))

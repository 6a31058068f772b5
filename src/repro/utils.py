"""Small shared utilities: artifact caching, timing, tree sizes."""
from __future__ import annotations

import hashlib
import json
import os
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

# the checkout this package lives in (src/repro/utils.py -> two levels up)
REPO_ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = Path(os.environ.get("REPRO_CACHE", REPO_ROOT / ".cache"))


def cache_path(key: str, suffix: str = ".npz") -> Path:
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    h = hashlib.sha1(key.encode()).hexdigest()[:16]
    return CACHE_DIR / f"{h}{suffix}"


def cached_npz(key: str, builder):
    """Build-once npz artifact cache keyed by a string."""
    p = cache_path(key)
    if p.exists():
        with np.load(p, allow_pickle=False) as z:
            return {k: z[k] for k in z.files}
    out = builder()
    np.savez(p, **out)
    return out


def cached_json(key: str, builder):
    p = cache_path(key, ".json")
    if p.exists():
        return json.loads(p.read_text())
    out = builder()
    p.write_text(json.dumps(out))
    return out


@contextmanager
def timer(name: str, sink: dict | None = None):
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    if sink is not None:
        sink[name] = sink.get(name, 0.0) + dt


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_params(tree) -> int:
    import jax

    return sum(x.size for x in jax.tree.leaves(tree))

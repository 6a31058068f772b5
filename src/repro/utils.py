"""Small shared utilities: the checkout root and the artifact cache."""
from __future__ import annotations

import hashlib
import os
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

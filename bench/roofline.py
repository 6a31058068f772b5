"""Operations and bytes of one FEE kernel call, from shapes alone.

One call scores, for each of ``batch`` queries, the ``L`` neighbor lanes
that survive the hop's fresh-first compaction (``L = max(M, expand * M *
compact)`` for ``expand > 1``, with ``M`` the adjacency width).  The bytes a
call needs are the queries (float32) plus every lane's stored row (packed
Dfloat words; both tiers for tiered storage) plus the threshold, counted
whole as if no lane exited early: an upper bound on what FEE moves, so the
share of the roofline is never overstated by an exit the kernel takes.
The arithmetic is about three operations per feature (difference, square,
add): far below the chip's balance point, so HBM bandwidth bounds it.
"""
from __future__ import annotations


def lanes(adj_width: int, expand: int, compact: float) -> int:
    if expand <= 1:
        return adj_width
    return max(adj_width, int(expand * adj_width * compact))


def fee_call_bytes(cfg: dict, batch: int, shapes: dict) -> int:
    """Bytes one FEE call over ``batch`` queries needs.  ``shapes`` holds
    ``adj_width`` and ``row_words`` (uint32 words per stored row)."""
    s = cfg["search"]
    n_lanes = lanes(shapes["adj_width"], int(s["expand"]), float(s["compact"]))
    per_query = 4 * int(cfg["dim"]) + n_lanes * 4 * shapes["row_words"] + 4
    return batch * per_query


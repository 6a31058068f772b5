"""Arithmetic the metric readers share: the program's spans in the
window."""
from __future__ import annotations


def window_spans(ctx, name: str) -> list:
    """Spans called ``name`` that started inside the measured window."""
    lo, hi = int(ctx.t0 * 1e9), int(ctx.t_end * 1e9)
    return [s for s in ctx.spans if s.name == name and lo <= s.t0_ns < hi]


def per_batch(spans: list) -> list:
    """One span per batch: the batcher stamps every request of a batch with
    the same start and duration for a stage."""
    seen = {}
    for s in spans:
        seen.setdefault((s.t0_ns, s.dur_ns), s)
    return list(seen.values())

"""The comparison that decides ``correct``.

Every answer the window was due is compared with the plain reference
(``bench.reference``: exact top-k of the raw corpus), once the window has
closed.  Four numbers, each against a limit of its own:

    unanswered   requests with no answer a minute past the close      limit 0
    bad_ids      ok answers whose ids are not k distinct base rows    limit 0
    lost_share   share of ok answers that hold none of the exact
                 top-k: the search ended in the wrong region          config
    dist_gap     widest relative gap between a served distance and
                 the exact distance of the id it was served with      config

A shed or timed-out answer says so and is not wrong: it counts in
``failed``, not here.  ``lost_share`` and ``dist_gap`` are compared where
the configuration file's ``limits`` gives them a limit; ``PERF.md`` gives
the readings each was set from, and why a configuration leaves one out.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference


@dataclasses.dataclass
class Answers:
    correct: bool
    table: dict            # compared: name -> {"value", "limit"}
    values: dict           # every number, compared or not
    n_ok: int
    hits: np.ndarray       # (n_ok,) exact top-k ids found per ok answer
    k: int

    @property
    def recall(self) -> float | None:
        return float(self.hits.sum()) / (self.k * self.n_ok) \
            if self.n_ok else None


def _valid_rows(ids: np.ndarray, n: int) -> np.ndarray:
    in_range = ((ids >= 0) & (ids < n)).all(1)
    srt = np.sort(ids, axis=1)
    distinct = (srt[:, 1:] != srt[:, :-1]).all(1)
    return in_range & distinct


def compare(cfg: dict, corpus, window: list, unanswered: int) -> Answers:
    k, metric = int(cfg["k"]), cfg["metric"]
    n = len(corpus.vectors)
    ok = [r for r in window if r.ok]
    short = [r for r in ok if np.asarray(r.resp.ids).shape != (k,)]
    ok = [r for r in ok if np.asarray(r.resp.ids).shape == (k,)]
    hits = np.zeros(0, np.int64)
    lost_share = dist_gap = 0.0
    bad = len(short)
    if ok:
        pools = np.array([r.pool for r in ok])
        ids = np.stack([np.asarray(r.resp.ids, np.int64) for r in ok])
        dists = np.stack([np.asarray(r.resp.dists, np.float64) for r in ok])
        ref_ids, _ = reference.exact_topk(corpus.vectors, corpus.queries, k,
                                          metric)
        valid = _valid_rows(ids, n)
        bad += int((~valid).sum())
        hits = (ids[:, :, None] == ref_ids[pools][:, None, :]).any(-1).sum(1)
        lost_share = float((hits == 0).mean())
        # the same query is answered many times: score each distinct
        # (query, ids) pair once
        key = np.concatenate([pools[:, None], ids], axis=1)
        uniq, inv = np.unique(key, axis=0, return_inverse=True)
        exact = reference.scores_of(corpus.vectors,
                                    corpus.queries[uniq[:, 0]], uniq[:, 1:],
                                    metric)[inv.reshape(-1)]
        gap = np.abs(dists - exact) / np.maximum(np.abs(exact), 1e-12)
        gap[~valid] = 0.0            # counted in bad_ids
        dist_gap = float(gap.max())
    values = {"unanswered": int(unanswered), "bad_ids": int(bad),
              "lost_share": lost_share, "dist_gap": dist_gap}
    limits = {"unanswered": 0, "bad_ids": 0, **cfg["limits"]}
    table = {name: {"value": values[name], "limit": limits[name]}
             for name in values if name in limits}
    correct = bool(ok) and all(v["value"] <= v["limit"]
                               for v in table.values())
    return Answers(correct=correct, table=table, values=values, n_ok=len(ok),
                   hits=hits, k=k)


def describe(out: dict) -> list:
    """The stderr lines that end a run: what was compared, then each
    number beside its limit."""
    lines = [f"compared {out['attempted'] - out['failed']} ok answers of "
             f"{out['attempted']} due in the window; correct={out['correct']}"]
    for name, c in out["checks"].items():
        lines.append(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return lines

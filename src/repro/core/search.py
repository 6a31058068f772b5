"""GANNS beam search (HNSW §II-A3) as a pure-JAX program.

Two variants share one hop body:
  * ``while``  — lax.while_loop, early-terminating (fast path / deployment)
  * ``scan``   — fixed hop budget, emits a per-hop trace consumed by the
                 DIMM-NDP performance model (``repro.ndpsim``)

Semantics follow Fig. 1 with the frontier batching used by GPU graph-ANNS
engines (CAGRA) and NDP traversal accelerators (NDSEARCH): a size-``ef``
candidate priority queue (sorted beam); each hop pops the ``expand`` nearest
unexpanded entries, gathers all ``expand * M`` neighbor lists in one fused
gather, computes FEE-sPCA distances against the current threshold (= farthest
beam entry) through the ``kernels.ops.fee_distance`` dispatcher, and merges
survivors into the beam with one ``lax.top_k`` over ``ef + expand*M``
candidates.  A visited bitmap plus a sort-based first-occurrence dedup
prevents re-evaluation — including duplicates *across* the frontier batch's
neighbor lists.  Early-exited candidates are visited but not inserted — this
is exactly the recall/compute trade the paper's beta corrects.

The hop body indexes no small per-query array element by element: under
``vmap`` such an index becomes a gather or scatter of single elements,
which the TPU runs one index at a time.  Beam, frontier and compaction
picks are one-hot selects (:func:`onehot_take`), and the visited and
tombstone bitmaps are read and written as 128-word rows
(:func:`bitmap_lookup`, :func:`bitmap_mark`).

``expand=1`` reproduces the classic one-node-per-hop HNSW loop; larger values
amortize gather/sort/host cost over ~``expand``x fewer hops at equal recall.

``SearchConfig.storage`` selects the base-vector representation: ``"f32"``
scores dense float rows (the legacy path), ``"packed"`` scores the Dfloat
uint32 bitstream directly — rows are gathered packed and decoded inside the
FEE kernel (``kernels.ops.fee_distance_packed``), bit-identical to scoring
the ``emulate_db`` f32 view while moving ~3x fewer bytes per gather.

Streaming mutation support: ``tombstone`` is an optional packed uint32 bitmap
(bit set = row is dead — deleted, or an unallocated capacity-tail slot of a
``repro.streaming.MutableIndex`` snapshot).  Dead rows are folded into the
FEE exit mask (``kernels.ops`` ``lane_mask``): they are marked visited, cost
no distance work (``segs_used == 0`` — the sub-channel checks its resident
tombstone bitmap before issuing the first burst), never enter the beam, and a
final beam re-rank guarantees they never appear in results even when the
graph entry point itself has been deleted (the entry stays navigable).

Trace layout (per query): ``node`` is (H, E) — the up-to-``expand`` nodes
popped per hop (-1 pad) — and ``nbrs``/``segs``/``cand_d``/``src`` are (H, L)
with L = max(M, E*M/2): the frontier batch after the fresh-first compaction,
in pop order; ``src[j]`` is the pop slot (0..E-1) whose neighbor list slot
``j`` came from.  ``expand=1`` traces skip compaction (L = M) and are
shape-compatible with the legacy (H, M) contract along the last axis.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dfloat as dfl
from repro.core import fee as fee_mod
from repro.core.fee import FeeParams
from repro.kernels import ops as kops
from repro.obs import default_registry, tracer

BIG = jnp.float32(3.0e38)

FEE_BACKENDS = ("auto", "jnp", "pallas", "pallas_skip_dma")
STORAGES = ("f32", "packed", "tiered")


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    ef: int = 64
    k: int = 10
    metric: str = "l2"
    seg: int = 16               # FEE checkpoint granularity (features / access)
    max_hops: int = 0           # 0 -> auto (4*ef expansions / expand per hop)
    use_fee: bool = False
    expand: int = 4             # beam entries popped per hop (frontier batch)
    fee_backend: str = "auto"   # kernels.ops dispatch: auto | jnp | pallas[...]
    storage: str = "f32"        # base vectors: dense f32 | packed Dfloat words
    # fraction of the expand*M frontier batch retained by the fresh-first
    # compaction (lane budget L = max(M, expand*M*compact)).  1.0 keeps every
    # fresh lane — a pure reorder, no drops — which is what makes the
    # owner-sharded backend bit-identical to the local one; 0.5 (default)
    # halves the scoring/merge width at recall parity (tests/test_expand.py)
    compact: float = 0.5

    def __post_init__(self):
        if self.expand < 1:
            raise ValueError(f"expand must be >= 1, got {self.expand}")
        if not 0.0 < self.compact <= 1.0:
            raise ValueError(f"compact must be in (0, 1], got {self.compact}")
        if self.fee_backend not in FEE_BACKENDS:
            raise ValueError(f"fee_backend={self.fee_backend!r}; expected one "
                             f"of {FEE_BACKENDS}")
        if self.storage not in STORAGES:
            raise ValueError(f"storage={self.storage!r}; expected one of "
                             f"{STORAGES}")

    def hops(self):
        """Hop budget for the traced (fixed-length scan) path: the legacy
        4*ef expansion budget spread over ``expand``-wide hops."""
        return self.max_hops or max(-(-4 * self.ef // self.expand), 8)


# Below this frontier width the vectorized pairwise compare beats the sort:
# XLA's CPU sort + scatter are scalar loops (~12x slower than the (n, n) eq
# matrix at n<=128, measured), while the O(n^2) tril fits in cache.  The
# sort-based path takes over where the quadratic blowup would actually bite
# (wide frontiers / the all-gathered cross-shard merge at high shard counts).
_DEDUP_SORT_MIN = 256


def first_occurrence_mask(ids, valid):
    """True for the first *valid* occurrence of each id within the batch.

    Replaces the old ``_dedup_mask`` (pairwise over one neighbor list): the
    mask now spans the whole gathered frontier batch — duplicates *across*
    the ``expand`` neighbor lists of one hop are caught too — and invalid
    lanes can never shadow a real id (the old mask compared padding-clamped
    ids, so a padded 0 hid a genuine neighbor 0).  Dispatches between a
    cache-friendly pairwise compare (small n) and a sort-based
    first-occurrence pass (O(n log n), large n).
    """
    n = ids.shape[0]
    if n < _DEDUP_SORT_MIN:
        key = jnp.where(valid, ids.astype(jnp.int32), -1)
        eq = (key[:, None] == key[None, :]) & valid[None, :]
        earlier = jnp.tril(eq, k=-1).any(axis=1)
        return ~earlier & valid
    key = jnp.where(valid, ids.astype(jnp.int32), jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key)                    # stable: ties keep pop order
    sk = key[order]
    firsts = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    return jnp.zeros((n,), bool).at[order].set(firsts) & valid


def compact_width(m: int, e: int, compact: float = 0.5) -> int:
    """Lane budget after the fresh-first frontier compaction of one hop.

    ``m`` is the (per-shard) neighbor-list width, ``e`` the frontier batch
    size; ``compact`` is :attr:`SearchConfig.compact`.  ``expand == 1`` hops
    skip compaction entirely (L = M); ``compact == 1.0`` makes the compaction
    a pure stable reorder (no fresh lane is ever dropped).
    """
    return m if e <= 1 else max(m, int(e * m * compact))


def local_topk_reduce(cand_ids, cand_d, r: int):
    """Shard-local top-``r`` reduce before the cross-shard owner merge.

    Exactness: with ``r >= min(ef, lanes)`` the truncation cannot change the
    merged beam — a candidate enters the post-merge top-ef only if fewer than
    ef elements of (beam ∪ all candidates) beat it, and a lane outside its own
    shard's top-ef already has >= ef better lanes on that shard alone.  So
    ``top_ef(beam ∪ C) == top_ef(beam ∪ top_ef(C))`` shard by shard, and the
    collective ships r lanes per shard instead of the full padded batch.
    """
    neg_d, order = jax.lax.top_k(-cand_d, r)
    return cand_ids[order], -neg_d


def _one_hot(idx, n: int):
    """(len(idx), n) bool: row i is set at column ``idx[i]``."""
    return idx[:, None] == jnp.arange(n, dtype=idx.dtype)


def onehot_take(x, idx):
    """``x[idx]`` for a short 1-D int or bool ``x``, as a one-hot reduce.

    Exact (one term per row survives).  Under ``vmap`` a plain ``x[idx]``
    becomes a gather of single elements, which the TPU runs one index at a
    time; the (len(idx), len(x)) compare and reduce runs as vector work.
    """
    hit = _one_hot(idx, x.shape[0])
    if x.dtype == jnp.bool_:
        return (hit & x).any(axis=1)
    return jnp.where(hit, x, jnp.zeros((), x.dtype)).sum(axis=1,
                                                         dtype=x.dtype)


# uint32 words per bitmap row: one 128-lane vector row
BITMAP_LANES = 128


def bitmap_rows(n_bits: int) -> int:
    """Rows of the (rows, BITMAP_LANES) uint32 bitmap that holds n_bits."""
    return -(-n_bits // (32 * BITMAP_LANES))


def bitmap_from_words(words):
    """A flat (ceil(n/32),) uint32 bitmap laid out as bitmap rows."""
    r = bitmap_rows(32 * words.shape[0])
    return jnp.pad(words, (0, r * BITMAP_LANES - words.shape[0])).reshape(
        r, BITMAP_LANES)


def _bit_address(ids):
    """(row, lane one-hot (n, BITMAP_LANES), bit) of each id >= 0."""
    w = ids >> 5
    return (w // BITMAP_LANES, _one_hot(w % BITMAP_LANES, BITMAP_LANES),
            jnp.uint32(1) << (ids & 31).astype(jnp.uint32))


def bitmap_lookup(bitmap, ids):
    """True where bit ``ids`` (>= 0) of a bitmap-rows array is set.

    Gathers whole 128-word rows and picks each id's word by its lane
    one-hot, so the cost follows len(ids), not the bitmap's size."""
    row, lane, bit = _bit_address(ids)
    word = jnp.where(lane, bitmap[row], jnp.uint32(0)).sum(axis=1,
                                                           dtype=jnp.uint32)
    return (word & bit) != 0


def bitmap_mark(bitmap, ids, mask):
    """Set bit ``ids`` (>= 0) where ``mask``, by a row scatter-add.

    The marked ids must be distinct and not yet set: each then adds one
    distinct bit, which is the same as OR-ing it."""
    row, lane, bit = _bit_address(ids)
    upd = jnp.where(lane & mask[:, None], bit[:, None], jnp.uint32(0))
    return bitmap.at[row].add(upd)


def pop_frontier(beam_ids, beam_d, expanded, e: int):
    """Pop the ``e`` nearest unexpanded beam entries (the hop's frontier).

    Returns (nodes (e,), sel (e,), expanded'): ``nodes`` is -1 where fewer
    than ``e`` entries are active; inactive picks are already expanded or
    empty (d >= BIG), so blanket-setting ``expanded`` on them is a no-op.
    The picks are read and flagged through their one-hot over the beam.
    Shared by the local and sharded hop bodies.
    """
    active = (~expanded) & (beam_d < BIG)
    done = ~active.any()
    _, idxs = jax.lax.top_k(-jnp.where(active, beam_d, BIG), e)
    sel = onehot_take(active, idxs) & ~done
    nodes = jnp.where(sel, onehot_take(beam_ids, idxs), -1)
    return nodes, sel, expanded | _one_hot(idxs, expanded.shape[0]).any(0)


def merge_beam(beam_ids, beam_d, expanded, cand_ids, cand_d):
    """One top-k merge of the beam with the hop's scored candidates.

    ``lax.top_k`` on equal keys prefers lower indices, so beam entries win
    ties against candidates (matching the stable-argsort semantics of the
    classic loop).  The new beam's ids and flags are read through the
    one-hot of the picks.  Shared by the local and sharded hop bodies.
    """
    ef = beam_ids.shape[0]
    all_ids = jnp.concatenate([beam_ids, cand_ids])
    all_d = jnp.concatenate([beam_d, cand_d])
    all_exp = jnp.concatenate([expanded, jnp.zeros(cand_d.shape[0], bool)])
    neg_d, order = jax.lax.top_k(-all_d, ef)
    beam_ids, beam_d = onehot_take(all_ids, order), -neg_d
    return beam_ids, beam_d, onehot_take(all_exp, order) | (beam_d >= BIG)


def _score(q, tgt, threshold, fee: FeeParams | None, cfg: SearchConfig,
           dfl_cfg: dfl.DfloatConfig | None = None, alive=None):
    """FEE/exact distances for one gathered frontier batch, routed through the
    kernel dispatcher (Pallas with DMA skipping on TPU, jnp oracle on CPU).

    With ``cfg.storage == "packed"`` the batch ``tgt`` is (L, W) packed uint32
    rows straight from the bitstream; the fused kernel decodes them on the fly
    (bit-identical to scoring the ``emulate_db`` f32 view).  With
    ``cfg.storage == "tiered"`` it is the (coarse, residual) row pair and
    ``dfl_cfg`` the matching config pair — the coarse tier makes the exit
    decision and residual words move only for lanes that survive it.
    ``alive`` is the optional tombstone lane mask: dead lanes join the FEE
    exit mask before the first segment, so they report ``segs_used == 0``
    (no streamed bursts — and for tiered, no residual fetch either).
    """
    packed = cfg.storage == "packed"
    tiered = cfg.storage == "tiered"
    if tiered:
        n_segs = (dfl_cfg[0].dim + dfl_cfg[1].dim) // cfg.seg
    else:
        n_segs = (dfl_cfg.dim if packed else tgt.shape[1]) // cfg.seg
    if cfg.use_fee:
        if tiered:
            return kops.fee_distance_tiered(
                q, tgt[0], tgt[1], threshold, fee.alpha, fee.beta, fee.margin,
                coarse_cfg=dfl_cfg[0], resid_cfg=dfl_cfg[1], seg=cfg.seg,
                metric=cfg.metric, backend=cfg.fee_backend, lane_mask=alive)
        if packed:
            return kops.fee_distance_packed(
                q, tgt, threshold, fee.alpha, fee.beta, fee.margin,
                dfloat_cfg=dfl_cfg, seg=cfg.seg, metric=cfg.metric,
                backend=cfg.fee_backend, lane_mask=alive)
        return kops.fee_distance(q, tgt, threshold, fee.alpha, fee.beta,
                                 fee.margin, seg=cfg.seg, metric=cfg.metric,
                                 backend=cfg.fee_backend, lane_mask=alive)
    if tiered:
        tgt = kops.dfloat_unpack_tiered_rows(tgt[0], tgt[1], dfl_cfg[0],
                                             dfl_cfg[1],
                                             backend=cfg.fee_backend)
    elif packed:
        tgt = kops.dfloat_unpack_rows(tgt, dfl_cfg, backend=cfg.fee_backend)
    score = fee_mod.exact_distance(q, tgt, metric=cfg.metric)
    rejected = (jnp.zeros(tgt.shape[0], bool) if alive is None else ~alive)
    segs_used = jnp.full((tgt.shape[0],), n_segs, jnp.int32)
    if alive is not None:
        segs_used = jnp.where(alive, segs_used, 0)
    return score, rejected, segs_used


def exclude_dead(beam_ids, beam_d, tombstone):
    """Final re-rank of the beam with tombstoned entries pushed out.

    Candidate scoring already rejects dead rows, but the entry point is seeded
    into the beam unconditionally (it must stay navigable even when deleted) —
    this one cheap top_k guarantees dead ids never reach the top-k output:
    dead lanes get dist BIG *and* id -1 (the underfull-beam padding), so even
    a beam with fewer than k live entries never surfaces a tombstoned id.
    """
    dead = (bitmap_lookup(tombstone, jnp.maximum(beam_ids, 0))
            & (beam_ids >= 0))
    neg_d, order = jax.lax.top_k(-jnp.where(dead, BIG, beam_d),
                                 beam_ids.shape[0])
    return (jnp.where(onehot_take(dead, order), -1,
                      onehot_take(beam_ids, order)), -neg_d)


def _hop_body(state, vectors, adj, q, fee: FeeParams | None, cfg: SearchConfig,
              dfl_cfg: dfl.DfloatConfig | None = None, tombstone=None):
    beam_ids, beam_d, expanded, visited = state
    ef = beam_ids.shape[0]
    e, m = min(cfg.expand, ef), adj.shape[1]
    with jax.named_scope("hop.frontier"):
        nodes, sel, expanded = pop_frontier(beam_ids, beam_d, expanded, e)

        # ---- one fused gather of all E neighbor lists
        nbrs = adj[jnp.maximum(nodes, 0)].reshape(e * m)       # (E*M,)
        valid = (nbrs >= 0) & jnp.repeat(sel, m)
        safe = jnp.maximum(nbrs, 0)
        fresh = (valid & ~bitmap_lookup(visited, safe)
                 & first_occurrence_mask(safe, valid))

        # ---- fresh-first frontier compaction (expand > 1): after the
        # visited/dedup filter, typically well under half the E*M slots
        # survive, so the downstream gather, scoring, visited scatter and
        # beam merge run on an L = E*M/2 budget instead of the full batch.
        # top_k on the boolean mask is a *stable* partition (ties keep pop
        # order) and costs far less than a sort on XLA CPU.  Overflowing
        # fresh candidates are dropped *unmarked*: they stay discoverable
        # through other parents on later hops (recall parity holds; see
        # tests/test_expand.py).
        if e > 1:
            l = compact_width(m, e, cfg.compact)
            _, keep = jax.lax.top_k(fresh.astype(jnp.float32), l)
            nbrs, fresh = onehot_take(nbrs, keep), onehot_take(fresh, keep)
            safe = jnp.maximum(nbrs, 0)
            src = keep // m                                # parent pop slot
        else:
            src = jnp.arange(e * m, dtype=jnp.int32) // m
        visited = bitmap_mark(visited, safe, fresh)

        # tombstoned lanes stay in ``fresh`` (visited-marked, never
        # re-checked) but are folded into the FEE exit mask: zero segments
        # streamed, never inserted into the beam, and invisible to the trace
        # (``live``).
        alive = (None if tombstone is None
                 else ~bitmap_lookup(tombstone, safe))
        live = fresh if alive is None else fresh & alive

    threshold = beam_d[-1]
    tiered = cfg.storage == "tiered"
    with jax.named_scope("hop.gather"):
        if tiered:                # (L, Wc) coarse + (L, Wr) residual tier rows
            tgt = (vectors[0][safe], vectors[1][safe])
        else:
            tgt = vectors[safe]                  # (L, D) f32 / (L, W) packed
    with jax.named_scope("hop.score"):
        score, rejected, segs_used = _score(q, tgt, threshold, fee, cfg,
                                            dfl_cfg, alive)

    # ---- single top-k beam merge over (ef + L) candidates
    with jax.named_scope("hop.merge"):
        cand_d = jnp.where(fresh & ~rejected, score, BIG)
        beam_ids, beam_d, expanded = merge_beam(beam_ids, beam_d, expanded,
                                                safe, cand_d)

    trace = dict(
        node=nodes.astype(jnp.int32),
        nbrs=jnp.where(live, nbrs, -1).astype(jnp.int32),
        segs=jnp.where(live, segs_used, 0).astype(jnp.int32),
        cand_d=cand_d,                                   # BIG unless accepted
        src=jnp.where(live, src, -1).astype(jnp.int32),   # parent of slot j
        n_eval=live.sum().astype(jnp.int32),
        dims=(jnp.where(live, segs_used, 0).sum() * cfg.seg).astype(jnp.int32),
    )
    if tiered:
        # a lane crossed into the residual tier iff it survived every coarse
        # checkpoint — exited lanes are never charged residual bytes
        n_coarse = dfl_cfg[0].dim // cfg.seg
        trace["n_resid"] = (live & (segs_used > n_coarse)).sum() \
            .astype(jnp.int32)
    return (beam_ids, beam_d, expanded, visited), trace


def _init_state(q, entry, vectors, cfg: SearchConfig, n_rows,
                dfl_cfg: dfl.DfloatConfig | None = None):
    ef = cfg.ef
    if cfg.storage == "tiered":
        row = kops.dfloat_unpack_tiered_rows(
            vectors[0][entry][None, :], vectors[1][entry][None, :],
            dfl_cfg[0], dfl_cfg[1], backend=cfg.fee_backend)
    else:
        row = vectors[entry][None, :]
    if cfg.storage == "packed":
        row = kops.dfloat_unpack_rows(row, dfl_cfg, backend=cfg.fee_backend)
    d0 = fee_mod.exact_distance(q, row, metric=cfg.metric)[0]
    beam_ids = jnp.full((ef,), -1, jnp.int32).at[0].set(entry)
    beam_d = jnp.full((ef,), BIG, jnp.float32).at[0].set(d0)
    expanded = jnp.ones((ef,), bool).at[0].set(False)
    visited = bitmap_mark(jnp.zeros((bitmap_rows(n_rows), BITMAP_LANES),
                                    jnp.uint32), entry[None],
                          jnp.ones((1,), bool))
    return beam_ids, beam_d, expanded, visited


@partial(jax.jit, static_argnames=("cfg", "trace", "dfl_cfg"))
def _search_batch(vectors, adj, fee, tombstone, queries, entries, *,
                  cfg: SearchConfig, trace: bool,
                  dfl_cfg: dfl.DfloatConfig | None = None):
    """Top-level jitted batch search.

    ``vectors``/``adj`` are *arguments*, not closure constants, so XLA keys
    the executable on (shapes, cfg, trace): building a second same-shape
    index — or re-creating a searcher — never re-traces or re-lowers.
    ``vectors`` is the packed (N, W) uint32 bitstream when
    ``cfg.storage == "packed"`` (``dfl_cfg`` supplies the static layout).
    ``tombstone`` is the optional dead-row bitmap ((ceil(N/32),) uint32,
    laid out as bitmap rows here; or None for an immutable index — None
    flattens to nothing, so the static jit key distinguishes the two shapes
    of program).
    """
    tiered = cfg.storage == "tiered"
    n_rows = (vectors[0] if tiered else vectors).shape[0]
    if tombstone is not None:
        tombstone = bitmap_from_words(tombstone)

    # hop counters carried through the early-terminating fast path for every
    # storage (cheap: one int32 add per hop) — serving reports the live FEE
    # exit fraction and, for tiered, the survivor-fetch fraction without
    # paying for a full trace
    cnt_keys = ("n_eval", "dims", "n_resid") if tiered else ("n_eval", "dims")

    def search_one(q, entry):
        with jax.named_scope("search.init"):
            state = _init_state(q, entry, vectors, cfg, n_rows, dfl_cfg)
        counters = None
        if trace:
            def step(s, _):
                return _hop_body(s, vectors, adj, q, fee, cfg, dfl_cfg,
                                 tombstone)
            state, traces = jax.lax.scan(step, state, None, length=cfg.hops())
        else:
            # last accumulator slot counts hops (same definition as the
            # trace path: a hop where at least one node was popped)
            state = (state, jnp.zeros((len(cnt_keys) + 1,), jnp.int32))
            def cond(s):
                _, beam_d, expanded, _ = s[0]
                return ((~expanded) & (beam_d < BIG)).any()
            def body(s):
                core, cnt = s
                core, t = _hop_body(core, vectors, adj, q, fee, cfg,
                                    dfl_cfg, tombstone)
                per_hop = [t[k] for k in cnt_keys] \
                    + [(t["node"] >= 0).any().astype(jnp.int32)]
                return (core, cnt + jnp.stack(per_hop))
            state, counters = jax.lax.while_loop(cond, body, state)
            traces = None
        beam_ids, beam_d, _, _ = state
        if tombstone is not None:
            beam_ids, beam_d = exclude_dead(beam_ids, beam_d, tombstone)
        out = dict(ids=beam_ids[: cfg.k], dists=beam_d[: cfg.k])
        if trace:
            out["trace"] = traces
            out["hops"] = (traces["node"] >= 0).any(-1).sum()
            out["n_eval"] = traces["n_eval"].sum()
            out["dims"] = traces["dims"].sum()
            if tiered:
                out["n_resid"] = traces["n_resid"].sum()
        else:
            for i, k in enumerate(cnt_keys):
                out[k] = counters[i]
            out["hops"] = counters[-1]
        return out

    return jax.vmap(search_one)(queries, entries)


def make_searcher(vectors, adj, cfg: SearchConfig,
                  fee: FeeParams | dict | None = None, trace: bool = False, *,
                  dfloat_cfg: dfl.DfloatConfig | None = None, tombstone=None):
    """Returns search(queries (Q,D), entries (Q,)) -> dict of results;
    ``search.lower(queries, entries)`` lowers the program it runs.

    vectors/adj may be numpy; they are passed to one shared top-level jitted
    program (cached by shape), not closed over as constants.  With
    ``cfg.storage == "packed"``, ``vectors`` is the (N, W) uint32 Dfloat
    bitstream and ``dfloat_cfg`` (static, hashable) describes its layout.
    ``fee`` takes a typed :class:`FeeParams`; legacy alpha/beta/margin dicts
    are coerced.  ``tombstone`` ((ceil(N/32),) uint32, bit = dead row) masks
    deleted rows out of scoring and results (streaming-mutation snapshots).
    With ``cfg.storage == "tiered"``, ``vectors`` is the (coarse, residual)
    bitstream pair and ``dfloat_cfg`` the matching (coarse, residual) config
    pair from ``dfloat.split_config``.
    """
    tiered = cfg.storage == "tiered"
    if cfg.storage == "packed" and dfloat_cfg is None:
        raise ValueError('cfg.storage="packed" requires dfloat_cfg=DfloatConfig')
    if tiered and not (isinstance(dfloat_cfg, tuple) and len(dfloat_cfg) == 2):
        raise ValueError('cfg.storage="tiered" requires dfloat_cfg='
                         "(coarse_cfg, residual_cfg)")
    if tiered:
        vectors = (jnp.asarray(vectors[0]), jnp.asarray(vectors[1]))
        n_rows = vectors[0].shape[0]
    else:
        vectors = jnp.asarray(vectors)
        n_rows = vectors.shape[0]
    adj = jnp.asarray(adj, jnp.int32)
    fp = FeeParams.coerce(fee)
    if cfg.use_fee and fp is None:
        raise ValueError("cfg.use_fee=True requires fee=FeeParams(...) "
                         "(use FeeParams.identity(n_seg) for plain d_part exit)")
    dfl_cfg = dfloat_cfg if cfg.storage in ("packed", "tiered") else None
    if tombstone is not None:
        tombstone = jnp.asarray(tombstone, jnp.uint32)
        if tombstone.shape != (-(-n_rows // 32),):
            raise ValueError(f"tombstone shape {tombstone.shape} does not "
                             f"cover {n_rows} rows")

    def search(queries, entries):
        return _search_batch(vectors, adj, fp, tombstone, jnp.asarray(queries),
                             jnp.asarray(entries), cfg=cfg, trace=trace,
                             dfl_cfg=dfl_cfg)

    def lower(queries, entries):
        """The jitted program ``search`` runs, lowered for these query and
        entry shapes (arrays or ``jax.ShapeDtypeStruct``)."""
        return _search_batch.lower(vectors, adj, fp, tombstone, queries,
                                   entries, cfg=cfg, trace=trace,
                                   dfl_cfg=dfl_cfg)

    search.lower = lower
    return search


@dataclasses.dataclass(frozen=True)
class DeviceLevels:
    """The upper HNSW levels, resident on the device for the entry descent.

    ``levels`` holds one ``(rows, adj, down)`` triple per upper level, top
    level first: the (Nl, D) f32 rows the greedy descent scores against,
    the (Nl, M) int32 level-local adjacency, and the (Nl,) int32 map of each
    level-local index to the next level down (the level's global ids for
    the lowest upper level, so the last map yields base entry ids).
    ``start`` is the int32 top-level index of the graph's entry (its global
    id when there is no upper level); ``rows`` counts the rows of all
    levels."""

    levels: tuple
    start: jax.Array
    rows: int


def _local_pos(ids: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """Index of each target in the sorted level ids, 0 where it is absent."""
    pos = np.clip(np.searchsorted(ids, targets), 0, len(ids) - 1)
    return np.where(ids[pos] == targets, pos, 0)


def upload_levels(graph, fetch) -> DeviceLevels:
    """Upload the upper levels of ``graph`` for :func:`descend`.

    ``fetch(ids) -> (len(ids), D)`` supplies the f32 rows of a level's ids.
    The down maps route each level's nodes into the next by
    ``searchsorted`` on the sorted level ids, once per upload.  Adds the
    bytes shipped to the ``search.descent_h2d_bytes`` counter."""
    host = []
    for level in range(len(graph.levels) - 1, 0, -1):
        ids, adj = graph.levels[level]
        # level ids are sorted by construction (graph.build_graph)
        down = ids if level == 1 else _local_pos(graph.levels[level - 1][0],
                                                 ids)
        host.append((np.asarray(fetch(ids), np.float32),
                     np.asarray(adj, np.int32), np.asarray(down, np.int32)))
    entry = np.asarray([graph.entry])
    start = _local_pos(graph.levels[-1][0], entry)[0] if host else entry[0]
    default_registry().counter("search.descent_h2d_bytes").inc(
        float(sum(a.nbytes for lvl in host for a in lvl)))
    return DeviceLevels(
        levels=tuple(tuple(jnp.asarray(a) for a in lvl) for lvl in host),
        start=jnp.asarray(start, jnp.int32),
        rows=sum(len(lvl[0]) for lvl in host))


@partial(jax.jit, static_argnames=("metric",))
def _descend_levels(levels, start, queries, *, metric: str):
    """Greedy top-down routing through every upper level in one program:
    the rotated query batch -> (Q,) int32 base entry ids.

    A top-level jitted function (arrays are *arguments*, not closure
    constants), so XLA caches one executable per (level shapes, batch,
    metric) and repeated query batches never recompile."""

    def greedy(vecs_l, adj_l):
        def one(q, c):
            def cond(s):
                return s[2]

            def body(s):
                c, d, _ = s
                nb = adj_l[c]
                nd = fee_mod.exact_distance(q, vecs_l[nb], metric=metric)
                j = jnp.argmin(nd)
                better = nd[j] < d
                return (jnp.where(better, nb[j], c), jnp.minimum(nd[j], d),
                        better)

            d0 = fee_mod.exact_distance(q, vecs_l[c][None], metric=metric)[0]
            c, _, _ = jax.lax.while_loop(cond, body, (c, d0, jnp.bool_(True)))
            return c

        return jax.vmap(one)

    cur = jnp.broadcast_to(start, queries.shape[:1])
    for vecs_l, adj_l, down in levels:
        with jax.named_scope("descent.level"):
            cur = greedy(vecs_l, adj_l)(queries, cur)
        cur = down[cur]
    return cur


def descend(levels: DeviceLevels, queries, metric: str) -> jax.Array:
    """Enqueue the descent of a rotated query batch through resident levels;
    returns the (Q,) int32 base entry ids as a device array, without
    waiting for them.  One ``search.descent`` span (attrs ``levels``,
    ``rows``) covers the enqueue."""
    with tracer.span("search.descent", levels=len(levels.levels),
                     rows=levels.rows):
        return _descend_levels(levels.levels, levels.start,
                               jnp.asarray(queries), metric=metric)


def descend_entry(vectors, graph, queries, metric: str) -> np.ndarray:
    """Greedy top-down routing through HNSW upper layers -> base entry ids.

    ``vectors`` is either the dense (N, D) f32 array or a callable
    ``ids -> (len(ids), D) f32`` row provider — the latter lets packed-native
    indices materialize only the tiny upper-level subsets instead of a full
    f32 copy of the DB.  Uploads the levels and runs :func:`descend`; a
    searcher that descends every batch keeps its :class:`DeviceLevels`
    (``Index.device_levels``) instead.
    """
    fetch = vectors if callable(vectors) else (lambda ids: vectors[ids])
    return np.asarray(descend(upload_levels(graph, fetch), queries, metric))


def search_graph(vectors, graph, queries, cfg: SearchConfig,
                 fee: FeeParams | dict | None = None, trace: bool = False,
                 dfloat_cfg: dfl.DfloatConfig | None = None,
                 descent_vectors=None, tombstone=None) -> dict:
    """Descend to base entries, run base-layer search; numpy result dict.

    With ``cfg.storage == "packed"``, ``vectors`` is the packed bitstream and
    ``descent_vectors`` (dense array or ``ids -> rows`` callable) supplies the
    f32 rows the upper-layer greedy descent scores against.
    """
    if cfg.storage == "packed":
        if dfloat_cfg is None:
            raise ValueError('cfg.storage="packed" requires dfloat_cfg=DfloatConfig')
        if descent_vectors is None:
            descent_vectors = lambda ids: dfl.unpack_db(
                np.asarray(vectors)[ids], dfloat_cfg)
    elif cfg.storage == "tiered":
        if not (isinstance(dfloat_cfg, tuple) and len(dfloat_cfg) == 2):
            raise ValueError('cfg.storage="tiered" requires dfloat_cfg='
                             "(coarse_cfg, residual_cfg)")
        if descent_vectors is None:
            xc, xr = (np.asarray(vectors[0]), np.asarray(vectors[1]))
            descent_vectors = lambda ids: np.concatenate(
                [dfl.unpack_db(t[ids], c)
                 for t, c in ((xc, dfloat_cfg[0]), (xr, dfloat_cfg[1]))
                 if c.dim], axis=1)
    else:
        descent_vectors = vectors if descent_vectors is None else descent_vectors
    entries = descend_entry(descent_vectors, graph, queries, cfg.metric)
    searcher = make_searcher(vectors, graph.base_adjacency, cfg,
                             fee=fee, trace=trace, dfloat_cfg=dfloat_cfg,
                             tombstone=tombstone)
    out = searcher(jnp.asarray(queries), jnp.asarray(entries))
    return {k: np.asarray(v) if not isinstance(v, dict) else {kk: np.asarray(vv) for kk, vv in v.items()}
            for k, v in out.items()}

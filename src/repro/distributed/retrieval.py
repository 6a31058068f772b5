"""NasZip retrieval as a query-owner-sharded shard_map program.

This is the paper's DaM (Fig. 12) mapped onto a device mesh (DESIGN.md §4),
redesigned around *query ownership* and communication/compute overlap:

  * the vector DB is row-sharded over the ``model`` axis — one shard = one
    "sub-channel"; the adjacency is stored PRE-PARTITIONED BY OWNER: shard c
    holds, for every node v, the sub-list of v's neighbors that c owns, as
    **local slot ids** (the per-shard NLT analogue);
  * each query is *owned* by exactly one model shard: its beam, frontier and
    output state live only there.  Nothing about a query is replicated on the
    model axis except the per-hop frontier broadcast (``expand`` node ids and
    one threshold — a few dozen bytes);
  * the per-shard visited set is an **exact** bitmap over the shard's local
    slots (O(n_loc/32) words per resident query) — the old replicated hashed
    2^bits bitmap, its Bloom-style false visits, and its O(2^bits) per-shard
    state are gone;
  * per hop: the owner pops its frontier and broadcasts (all_gather of E ids
    + the beam threshold); every shard gathers + FEE-scores its local
    partitions and reduces them to a shard-local top-r (r = min(L, ef), which
    is provably lossless — see ``core.search.local_topk_reduce``); one
    ``all_to_all`` then delivers each shard's r lanes *to the owner only* —
    O(ef) lanes per query instead of the old flat C x L all-gather landing on
    every shard;
  * tombstones are per-shard words indexed by local slot, folded into the
    FEE lane mask before the first segment is streamed — the full replicated
    bitmap is gone too (streaming churn updates only the owning shard's
    words);
  * ``overlap=True`` double-buffers the pipeline: hop t's collective is in
    flight while the owner merges hop t-1's arrivals, and shards score
    against the *previous* threshold.  Stale-threshold scoring is safe — the
    FEE exit test is monotone in the threshold, so it only admits extra
    lanes, never drops one the synchronous hop keeps (re-filtered on arrival
    by the owner's top-k merge; see ``kernels.ops.fee_distance_stale``).

In sync mode (``overlap=False``, the default) the program is bit-identical
to the local backend whenever ``cfg.compact == 1.0`` (lossless frontier
compaction): same admitted candidate sets, same visited marks, same top-k
tie-breaks (beam wins).  With the default lossy compaction the two backends
drop overflowing fresh lanes on different boundaries (per-shard vs global)
and agree to recall parity instead.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dfloat as dfl
from repro.core import fee as fee_mod
from repro.core import search as search_mod
from repro.core.fee import FeeParams
from repro.core.search import SearchConfig, first_occurrence_mask
from repro.kernels import ops as kops

BIG = jnp.float32(3.0e38)


@dataclasses.dataclass(frozen=True)
class ShardedDB:
    """Abstract or concrete device-side DaM database layout.

    vectors   (C, n_loc, d)   row shards (axis 0 = model shard); for tiered
                              storage a (coarse, residual) pair of such
                              arrays — both row-sharded identically, so
                              residual words never cross shards
    local_ids (C, n_loc)      global id of each local slot (-1 pad)
    part_adj  (C, N, Mc)      per-shard neighbor partitions (local slots, -1 pad)
    tombstone (C, W_loc)      per-shard dead-slot words (uint32, bit = local
                              slot is tombstoned or padding), or None
    """
    vectors: object
    local_ids: object
    part_adj: object
    tombstone: object | None = None

    @property
    def n_total(self) -> int:
        return self.part_adj.shape[1]


def abstract_db(n: int, d: int, n_shards: int, m_part: int, dtype=jnp.float32) -> ShardedDB:
    """ShapeDtypeStruct stand-in for the multi-pod dry-run (no allocation)."""
    n_loc = -(-n // n_shards)
    return ShardedDB(
        vectors=jax.ShapeDtypeStruct((n_shards, n_loc, d), dtype),
        local_ids=jax.ShapeDtypeStruct((n_shards, n_loc), jnp.int32),
        part_adj=jax.ShapeDtypeStruct((n_shards, n, m_part), jnp.int32),
    )


def build_sharded_db(vectors: np.ndarray, dam, dtype=None,
                     tombstone: np.ndarray | None = None) -> ShardedDB:
    """Pack a core.graph.DaMPartition into the stacked device layout.

    ``vectors`` may be the dense float rows, the packed uint32 bitstream
    (row layout is identical either way), or a (coarse, residual) tier pair —
    each tier is then sharded with the same row map, keeping residual fetches
    shard-local.  By default integer inputs keep their dtype and float inputs
    are cast to f32 (the pre-packed guarantee).  The arrays stay on the host:
    ``jax.device_put`` with :func:`db_shardings` ships each shard straight to
    its own device, never staging the whole stack on the first one.

    ``tombstone`` is the *global* packed dead-row bitmap of an Index
    snapshot; it is re-folded here into per-shard words indexed by local
    slot (padding slots are marked dead), so each shard's FEE lane mask
    needs only its own O(n_loc/32) words — the replicated global bitmap
    never reaches the devices.
    """
    if isinstance(vectors, tuple):
        coarse = build_sharded_db(vectors[0], dam, dtype, tombstone)
        resid = build_sharded_db(vectors[1], dam, dtype)
        return dataclasses.replace(
            coarse, vectors=(coarse.vectors, resid.vectors))
    c = dam.n_channels
    n_loc = max(len(ids) for ids in dam.local_ids)
    d = vectors.shape[1]
    if dtype is None:
        dtype = (vectors.dtype if np.issubdtype(vectors.dtype, np.integer)
                 else np.float32)
    vs = np.zeros((c, n_loc, d), dtype)
    ids = np.full((c, n_loc), -1, np.int32)
    for ch, gl in enumerate(dam.local_ids):
        vs[ch, : len(gl)] = vectors[gl]
        ids[ch, : len(gl)] = gl
    pa = np.stack(dam.part_adj)  # (C, N, Mc)
    tomb = None
    if tombstone is not None:
        tombstone = np.asarray(tombstone, np.uint32)
        w_loc = -(-n_loc // 32)
        tomb = np.zeros((c, w_loc), np.uint32)
        slot = np.arange(n_loc)
        for ch, gl in enumerate(dam.local_ids):
            dead = np.ones(n_loc, bool)                  # padding slots: dead
            g = np.asarray(gl, np.int64)
            bit = (tombstone[g >> 5] >> (g & 31).astype(np.uint32)) & 1
            dead[: len(g)] = bit.astype(bool)
            idx = slot[dead]
            np.bitwise_or.at(tomb[ch], idx >> 5,
                             np.uint32(1) << (idx & 31).astype(np.uint32))
    return ShardedDB(vs, ids, pa, tomb)


def db_shardings(mesh: Mesh):
    model = "model" if "model" in mesh.axis_names else mesh.axis_names[-1]
    return ShardedDB(
        vectors=NamedSharding(mesh, P(model, None, None)),
        local_ids=NamedSharding(mesh, P(model, None)),
        part_adj=NamedSharding(mesh, P(model, None, None)),
        tombstone=NamedSharding(mesh, P(model, None)),
    )


def collective_payload(cfg: SearchConfig, mc: int, c: int) -> dict:
    """Per-query per-hop collective payload accounting (8B = id + dist lane).

    ``flat_*`` is the legacy topology this module replaced: every shard
    all-gathers its full padded L-lane batch to *every* shard.  ``hier_*``
    is the owner-sharded topology: each shard ships its lossless top-r
    (r = min(L, ef)) to the query's owner only, plus the tiny frontier
    broadcast (E node ids + 1 threshold to C-1 shards).
    """
    e = max(1, min(cfg.expand, cfg.ef))
    l = search_mod.compact_width(mc, e, cfg.compact)
    r = min(l, cfg.ef)
    frontier_bytes = 4 * (c - 1) * (e + 1)
    return dict(
        n_shards=c, expand=e, local_lanes=l, reduce_width=r,
        flat_lanes_per_query=c * l,        # lanes landing on EVERY shard
        owner_lanes_per_query=c * r,       # lanes landing on the owner only
        flat_fabric_bytes_per_query=8 * c * (c - 1) * l,
        hier_fabric_bytes_per_query=8 * (c - 1) * r + frontier_bytes,
        frontier_bytes_per_query=frontier_bytes,
    )


def make_sharded_searcher(mesh: Mesh, cfg: SearchConfig, n_total: int,
                          fee: FeeParams | dict | None = None, *,
                          dfloat_cfg: dfl.DfloatConfig | None = None,
                          tombstone=None, overlap: bool = False):
    """Returns search(db: ShardedDB, queries (Q, d), entries (Q,)) — a jit'd
    shard_map program for ``mesh`` (axes: optional pod, data, model).

    ``fee`` takes a typed :class:`FeeParams`.  With ``cfg.storage ==
    "packed"`` the ShardedDB holds packed uint32 rows and each shard scores
    its local partition straight from the bitstream (``dfloat_cfg`` supplies
    the static layout).  With ``cfg.storage == "tiered"`` the ShardedDB
    holds a (coarse, residual) row pair and ``dfloat_cfg`` is the matching
    (coarse_cfg, resid_cfg) tuple; both tiers are sharded by the same row
    map, so residual words are only ever touched by the shard that owns
    them — the frontier broadcast and the owner-targeted all_to_all carry
    exactly the same payload as the packed path (ids + distances, never
    residual bytes).  ``tombstone`` is a flag: truthy means the ShardedDB
    carries per-shard dead-slot words (``build_sharded_db(...,
    tombstone=...)``) that fold into each shard's FEE lane mask.
    ``overlap=True`` selects the double-buffered pipeline (stale-threshold
    scoring, one-hop-deferred merge; recall-equivalent, not bit-identical).

    Queries are padded by the wrapper to a multiple of (data x model) so
    every model shard owns an equal chunk; results come back in input order.
    """
    model_axis = "model" if "model" in mesh.axis_names else mesh.axis_names[-1]
    data_axes = tuple(n for n in mesh.axis_names if n != model_axis)
    c = mesh.shape[model_axis]
    d_total = int(np.prod([mesh.shape[a] for a in data_axes]))
    fp = FeeParams.coerce(fee)
    if cfg.use_fee and fp is None:
        raise ValueError("cfg.use_fee=True requires fee=FeeParams(...)")
    packed = cfg.storage == "packed"
    tiered = cfg.storage == "tiered"
    if packed and dfloat_cfg is None:
        raise ValueError('cfg.storage="packed" requires dfloat_cfg=DfloatConfig')
    if tiered and not (isinstance(dfloat_cfg, tuple) and len(dfloat_cfg) == 2):
        raise ValueError('cfg.storage="tiered" requires dfloat_cfg='
                         "(coarse_cfg, resid_cfg)")
    has_tomb = bool(tombstone is not None and tombstone is not False)
    e = min(cfg.expand, cfg.ef)

    def _slot_of(ids_loc, gid):
        """Local slot of global id ``gid`` on this shard, -1 if not resident."""
        slot = jnp.argmax(ids_loc == gid)
        return jnp.where(ids_loc[slot] == gid, slot, -1)

    def _gather_rows(vec_loc, idx):
        """Row gather that transparently spans both tiers for tiered storage."""
        if tiered:
            return (vec_loc[0][idx], vec_loc[1][idx])
        return vec_loc[idx]

    def _decode_row(vec_loc, slot):
        """This shard's f32 row for a local slot (0 when not resident)."""
        safe = jnp.maximum(slot, 0)
        if tiered:
            row = kops.dfloat_unpack_tiered_rows(
                vec_loc[0][safe][None], vec_loc[1][safe][None],
                dfloat_cfg[0], dfloat_cfg[1], backend=cfg.fee_backend)[0]
        else:
            row = vec_loc[safe]
            if packed:
                row = kops.dfloat_unpack_rows(row[None], dfloat_cfg,
                                              backend=cfg.fee_backend)[0]
        return jnp.where(slot >= 0, row, 0.0)

    def _score_lanes(q, tgt, exit_thr, admit_thr, alive):
        """(dist, admit) for one shard's gathered lanes — FEE exit against
        ``exit_thr`` (stale in overlap mode), admit against ``admit_thr``."""
        if cfg.use_fee:
            dist, admit, _segs = kops.fee_distance_stale(
                q, tgt, exit_thr, admit_thr, fp.alpha, fp.beta, fp.margin,
                seg=cfg.seg, metric=cfg.metric, backend=cfg.fee_backend,
                lane_mask=alive,
                dfloat_cfg=dfloat_cfg if (packed or tiered) else None)
            return dist, admit
        if tiered:
            tgt = kops.dfloat_unpack_tiered_rows(tgt[0], tgt[1],
                                                 dfloat_cfg[0], dfloat_cfg[1],
                                                 backend=cfg.fee_backend)
        elif packed:
            tgt = kops.dfloat_unpack_rows(tgt, dfloat_cfg,
                                          backend=cfg.fee_backend)
        dist = fee_mod.exact_distance(q, tgt, metric=cfg.metric)
        admit = dist < admit_thr
        if alive is not None:
            admit &= alive
        return dist, admit

    def body(vectors, local_ids, part_adj, tomb, queries, entries):
        # block shapes: vectors (1, n_loc, d); queries (Q_loc, d) — queries
        # ride the data axes and are *replicated* over model; this shard owns
        # the contiguous chunk [j*Q_own, (j+1)*Q_own) of them.
        vec_loc = (tuple(v[0] for v in vectors) if tiered else vectors[0])
        ids_loc, padj_loc = local_ids[0], part_adj[0]
        tomb_loc = None if tomb is None else tomb[0]
        n_loc, mc = ids_loc.shape[0], padj_loc.shape[1]
        w_loc = -(-n_loc // 32)
        l = search_mod.compact_width(mc, e, cfg.compact)
        r = min(l, cfg.ef)
        q_loc = queries.shape[0]
        q_own = q_loc // c
        j = jax.lax.axis_index(model_axis)

        # ---- seed: entry rows via one masked psum (each gid is resident on
        # exactly one shard); per-shard exact visited bitmap marks the entry
        slots0 = jax.vmap(partial(_slot_of, ids_loc))(entries)       # (Q_loc,)
        rows0 = jax.lax.psum(jax.vmap(partial(_decode_row, vec_loc))(slots0),
                             model_axis)                             # (Q_loc, d)
        safe0 = jnp.maximum(slots0, 0)
        bit0 = jnp.where(slots0 >= 0,
                         jnp.uint32(1) << (safe0 & 31).astype(jnp.uint32),
                         jnp.uint32(0))
        visited = jnp.zeros((q_loc, w_loc), jnp.uint32)
        visited = visited.at[jnp.arange(q_loc), safe0 >> 5].add(bit0)
        if has_tomb:
            dead_bit = (tomb_loc[safe0 >> 5] & bit0) != 0
            entry_dead = jax.lax.psum(dead_bit.astype(jnp.int32),
                                      model_axis) > 0                # (Q_loc,)

        # ---- owner-only beam state for this shard's query chunk
        my_q = jax.lax.dynamic_slice_in_dim(queries, j * q_own, q_own, 0)
        my_ent = jax.lax.dynamic_slice_in_dim(entries, j * q_own, q_own, 0)
        my_rows0 = jax.lax.dynamic_slice_in_dim(rows0, j * q_own, q_own, 0)
        d0 = jax.vmap(lambda qv, rv: fee_mod.exact_distance(
            qv, rv[None], metric=cfg.metric)[0])(my_q, my_rows0)
        beam_ids = jnp.full((q_own, cfg.ef), -1, jnp.int32).at[:, 0].set(my_ent)
        beam_d = jnp.full((q_own, cfg.ef), BIG).at[:, 0].set(d0)
        expanded = jnp.ones((q_own, cfg.ef), bool).at[:, 0].set(False)

        def score_local(q, nodes_q, sel_q, thr_q, vis_q):
            """One query's local partition scoring -> shard-local top-r."""
            slots = padj_loc[jnp.maximum(nodes_q, 0)].reshape(e * mc)
            valid = (slots >= 0) & jnp.repeat(sel_q, mc)
            safe = jnp.maximum(slots, 0)
            w = safe >> 5
            bit = jnp.uint32(1) << (safe & 31).astype(jnp.uint32)
            seen = (vis_q[w] & bit) != 0
            # exact local-slot dedup/visited — no hashing, no false visits
            fresh = valid & ~seen & first_occurrence_mask(slots, valid)
            if e > 1:
                # fresh-first compaction: same stable partition as the local
                # hop, applied per shard (L = max(Mc, E*Mc*compact))
                _, keep = jax.lax.top_k(fresh.astype(jnp.float32), l)
                slots, safe, fresh = slots[keep], safe[keep], fresh[keep]
                w = safe >> 5
                bit = jnp.uint32(1) << (safe & 31).astype(jnp.uint32)
            vis_q = vis_q.at[w].add(jnp.where(fresh, bit, jnp.uint32(0)))
            alive = (None if tomb_loc is None
                     else (tomb_loc[w] & bit) == 0)
            dist, admit = _score_lanes(q, _gather_rows(vec_loc, safe),
                                       thr_q, thr_q, alive)
            cand_d = jnp.where(fresh & admit, dist, BIG)
            gids = jnp.where(cand_d < BIG, ids_loc[safe], -1)
            return *search_mod.local_topk_reduce(gids, cand_d, r), vis_q

        def local_pass(nodes, sel, thr, visited):
            """Broadcast the frontier, score local partitions everywhere,
            deliver each shard's top-r to the owner (one all_to_all)."""
            nodes_all = jax.lax.all_gather(nodes, model_axis).reshape(q_loc, e)
            sel_all = jax.lax.all_gather(sel, model_axis).reshape(q_loc, e)
            thr_all = jax.lax.all_gather(thr, model_axis).reshape(q_loc)
            gids_r, d_r, visited = jax.vmap(score_local)(
                queries, nodes_all, sel_all, thr_all, visited)
            # owner-targeted delivery: shard j's lanes for owner i's queries
            # go to shard i — O(C*r) lanes per owned query, not C*L everywhere
            arr_ids = jax.lax.all_to_all(gids_r.reshape(c, q_own, r),
                                         model_axis, 0, 0)
            arr_d = jax.lax.all_to_all(d_r.reshape(c, q_own, r),
                                       model_axis, 0, 0)
            return (arr_ids.transpose(1, 0, 2).reshape(q_own, c * r),
                    arr_d.transpose(1, 0, 2).reshape(q_own, c * r), visited)

        def go_flag(beam_d, expanded, pend_d=None):
            active = ((~expanded) & (beam_d < BIG)).any()
            if pend_d is not None:
                active |= (pend_d < BIG).any()
            return jax.lax.psum(active.astype(jnp.int32), model_axis) > 0

        if not overlap:
            def hop(state):
                beam_ids, beam_d, expanded, visited, _ = state
                nodes, sel, expanded = jax.vmap(
                    lambda bi, bd, ex: search_mod.pop_frontier(bi, bd, ex, e)
                )(beam_ids, beam_d, expanded)
                thr = beam_d[:, -1]
                arr_ids, arr_d, visited = local_pass(nodes, sel, thr, visited)
                beam_ids, beam_d, expanded = jax.vmap(search_mod.merge_beam)(
                    beam_ids, beam_d, expanded, arr_ids, arr_d)
                return (beam_ids, beam_d, expanded, visited,
                        go_flag(beam_d, expanded))

            state = (beam_ids, beam_d, expanded, visited,
                     go_flag(beam_d, expanded))
            state = jax.lax.while_loop(lambda s: s[-1], hop, state)
            beam_ids, beam_d = state[0], state[1]
        else:
            def hop(state):
                beam_ids, beam_d, expanded, visited, p_ids, p_d, _ = state
                # pop + broadcast from the *stale* beam (last hop's arrivals
                # are still pending) — the collective below is independent of
                # this hop's merge, so the two overlap
                nodes, sel, expanded = jax.vmap(
                    lambda bi, bd, ex: search_mod.pop_frontier(bi, bd, ex, e)
                )(beam_ids, beam_d, expanded)
                thr = beam_d[:, -1]                      # stale threshold
                # merge hop t-1's arrivals while hop t's collective flies;
                # the top-k merge is the arrival re-filter — lanes the stale
                # threshold over-admitted fall out here
                beam_ids, beam_d, expanded = jax.vmap(search_mod.merge_beam)(
                    beam_ids, beam_d, expanded, p_ids, p_d)
                p_ids, p_d, visited = local_pass(nodes, sel, thr, visited)
                return (beam_ids, beam_d, expanded, visited, p_ids, p_d,
                        go_flag(beam_d, expanded, p_d))

            pend_ids = jnp.full((q_own, c * r), -1, jnp.int32)
            pend_d = jnp.full((q_own, c * r), BIG)
            state = (beam_ids, beam_d, expanded, visited, pend_ids, pend_d,
                     go_flag(beam_d, expanded))
            state = jax.lax.while_loop(lambda s: s[-1], hop, state)
            beam_ids, beam_d = state[0], state[1]

        if has_tomb:
            # scoring already drops dead candidates before the beam; only the
            # seeded entry can be a dead beam resident (it must stay
            # navigable) — push it out with one top_k, like exclude_dead
            my_dead = jax.lax.dynamic_slice_in_dim(entry_dead, j * q_own,
                                                   q_own, 0)
            dead = ((beam_ids == my_ent[:, None]) & my_dead[:, None]
                    & (beam_ids >= 0))
            neg_d, order = jax.lax.top_k(-jnp.where(dead, BIG, beam_d), cfg.ef)
            beam_ids = jnp.take_along_axis(beam_ids, order, axis=1)
            beam_ids = jnp.where(jnp.take_along_axis(dead, order, axis=1),
                                 -1, beam_ids)
            beam_d = -neg_d
        return beam_ids[:, : cfg.k], beam_d[:, : cfg.k]

    dp = data_axes if len(data_axes) > 1 else data_axes[0]
    out_p = P((*data_axes, model_axis), None)
    in_specs = [P(model_axis, None, None), P(model_axis, None),
                P(model_axis, None, None)]
    in_specs.append(P(model_axis, None) if has_tomb else P())
    in_specs += [P(dp, None), P(dp)]
    if not has_tomb:
        # keep the block signature uniform; None threads through shard_map
        # as a static empty pytree
        wrapped = body
        body_in = lambda v, i, p, q, en: wrapped(v, i, p, None, q, en)
        mapped = jax.shard_map(
            body_in, mesh=mesh,
            in_specs=tuple(in_specs[:3] + in_specs[4:]),
            out_specs=(out_p, out_p), check_vma=False)
    else:
        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(out_p, out_p), check_vma=False)

    jitted = jax.jit(mapped)
    q_mult = d_total * c

    def _args(db: ShardedDB):
        base = (db.vectors, db.local_ids, db.part_adj)
        if has_tomb:
            if db.tombstone is None:
                raise ValueError("searcher built with tombstone=True needs a "
                                 "ShardedDB carrying per-shard tombstone words")
            return base + (db.tombstone,)
        return base

    def search(db: ShardedDB, queries, entries):
        queries = jnp.asarray(queries)
        entries = jnp.asarray(entries)
        q0 = queries.shape[0]
        pad = (-q0) % q_mult
        if pad:
            queries = jnp.concatenate(
                [queries, jnp.broadcast_to(queries[:1], (pad, queries.shape[1]))])
            entries = jnp.concatenate(
                [entries, jnp.broadcast_to(entries[:1], (pad,))])
        ids, dists = jitted(*_args(db), queries, entries)
        return (ids[:q0], dists[:q0]) if pad else (ids, dists)

    def _lower(db: ShardedDB, queries, entries):
        q0 = queries.shape[0]
        pad = (-q0) % q_mult
        if pad:
            queries = jax.ShapeDtypeStruct((q0 + pad, queries.shape[1]),
                                           queries.dtype)
            entries = jax.ShapeDtypeStruct((q0 + pad,), entries.dtype)
        return jitted.lower(*_args(db), queries, entries)

    search.lower = _lower
    return search

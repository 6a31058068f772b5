"""Pluggable execution backends behind ``Index.searcher(backend=...)``.

Every factory returns ``run(queries) -> SearchResult`` with the same call
signature; only construction-time options differ:

  local    jit/vmap beam search on this process's default device
  sharded  shard_map DaM retrieval over a (data, model) mesh (paper Fig. 12)
  ndpsim   trace-driven DIMM-NDP timing model (paper §VI-A) — runs the local
           searcher with tracing on, then attaches the SimResult projection

Queries are always *raw* (un-rotated) vectors; each backend applies the
index's sPCA transform and hierarchy descent itself.

``SearchParams.expand`` (multi-expansion frontier batching),
``SearchParams.fee_backend`` (FEE kernel dispatch) and
``SearchParams.storage`` (dense f32 rows vs the packed Dfloat bitstream)
thread through ``SearchParams.to_config`` into every backend: the local
jit/vmap loop, the sharded DaM hop (where popping ``expand`` nodes per hop
amortizes the cross-shard all-gather and packed shards hold ~3x more vectors
per device), and the traced search that feeds the ndpsim engine (which
consumes per-hop multi-node traces).

The hierarchy descent scores f32 rows of the upper levels only: emulated
Dfloat rows for the Dfloat stores (the full ``db_q`` array is never
materialized on host or device), ``db_rot`` rows otherwise.  Those levels
are uploaded once per index (``Index.device_levels``), and every batch
descends all of them in one device program whose entries go straight to
the search program, with no copy back to the host in between.

Streaming-mutation snapshots (``repro.streaming.MutableIndex.freeze``) carry
a tombstone bitmap and a generation counter; every backend masks tombstoned
rows out of scoring/results and stamps ``SearchResult.generation``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import graph as gmod
from repro.core import search as search_mod
from repro.core.fee import FeeParams
from repro.index.types import SearchParams, SearchResult
from repro.obs import default_registry, tracer


def _record_search(res: SearchResult, dim: int, bytes_per_dim: float) -> None:
    """Feed one batch's :class:`SearchResult` counters into the process-wide
    telemetry registry (``repro.obs.default_registry``): program executions
    (``search.batches``), queries served, hops, lanes evaluated, feature dims
    touched vs touchable (the FEE exit fraction is derivable as
    ``1 - dims_touched/dims_possible``), residual-tier fetches and
    approximate payload bytes streamed from the base-vector store.

    ``search.hop_slots`` adds ``len(hops) * max(hops)`` per batch: the
    vmapped while-loop runs every lane until the batch's slowest lane stops,
    so ``search.hops / search.hop_slots`` is the useful share of the hop
    iterations the device ran."""
    reg = default_registry()
    reg.counter("search.batches").inc()
    reg.counter("search.queries").inc(len(res.ids))
    if res.hops is not None:
        reg.counter("search.hops").inc(float(np.sum(res.hops)))
        if len(res.hops):
            reg.counter("search.hop_slots").inc(
                float(len(res.hops) * np.max(res.hops)))
    if res.n_eval is not None:
        reg.counter("search.lanes_evaluated").inc(float(np.sum(res.n_eval)))
    if res.dims is not None:
        dims = float(np.sum(res.dims))
        reg.counter("search.dims_touched").inc(dims)
        reg.counter("search.payload_bytes").inc(dims * bytes_per_dim)
        if res.n_eval is not None:
            reg.counter("search.dims_possible").inc(
                float(np.sum(res.n_eval)) * dim)
    if res.n_resid is not None:
        reg.counter("search.residual_fetches").inc(float(np.sum(res.n_resid)))

BACKENDS = ("local", "sharded", "ndpsim")


def make(index, backend: str, params: SearchParams, **opts):
    if backend == "local":
        return local_searcher(index, params, **opts)
    if backend == "sharded":
        return sharded_searcher(index, params, **opts)
    if backend == "ndpsim":
        return ndpsim_searcher(index, params, **opts)
    raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")


def _base_vectors(index, params: SearchParams):
    """Host array (or (coarse, residual) pair for tiered) the chosen storage
    mode scores against."""
    if params.storage == "packed":
        return index.db_packed
    if params.storage == "tiered":
        return index.tier_arrays()
    return index.db_q if params.use_dfloat else index.db_rot


def _dfloat_cfg(index, params: SearchParams):
    if params.storage == "packed":
        return index.dfloat_cfg
    if params.storage == "tiered":
        return index.tier_cfgs()
    return None


def _fee(index, params: SearchParams, fee=None) -> FeeParams | None:
    if not params.use_fee:
        return None
    return FeeParams.coerce(fee) if fee is not None else index.fee.params


def local_searcher(index, params: SearchParams, *, fee=None):
    """jit/vmap single-host searcher; the jitted executable is built once and
    reused across query batches.  The DB/adjacency device arrays come from the
    index-level cache, so searchers for different params share one copy."""
    import jax.numpy as jnp

    cfg = params.to_config(index.metric, index.seg)
    searcher = search_mod.make_searcher(
        index.device_db(params.use_dfloat, params.storage),
        index.device_adjacency(), cfg, fee=_fee(index, params, fee),
        trace=params.trace, dfloat_cfg=_dfloat_cfg(index, params),
        tombstone=index.device_tombstone())
    levels = index.device_levels(params.use_dfloat)

    # bytes actually streamed per feature dim under this storage mode: the
    # packed/tiered bitstream moves total_bits/dim bits, dense f32 moves 4 B
    dcfg = _dfloat_cfg(index, params)
    if params.storage == "tiered":
        bits = sum(c.total_bits() for c in dcfg)
        bpd = bits / 8.0 / max(sum(c.dim for c in dcfg), 1)
    elif params.storage == "packed":
        bpd = dcfg.total_bits() / 8.0 / max(dcfg.dim, 1)
    else:
        bpd = 4.0

    def run(queries) -> SearchResult:
        # the rotated queries go to the device once, for the descent and
        # the search program both
        with tracer.span("search.pca"):
            qr = jnp.asarray(index.transform_queries(np.asarray(queries)))
        # the descent and the search program are enqueued back to back; the
        # entries stay on the device, and the wait blocks on both programs
        # and the copy back
        entries = search_mod.descend(levels, qr, index.metric)
        with tracer.span("search.dispatch"):
            raw = searcher(qr, entries)
        with tracer.span("search.wait"):
            res = SearchResult.from_raw(raw)
        res.generation = index.generation
        with tracer.span("search.count"):
            _record_search(res, index.dim, bpd)
        return res

    run.lower = searcher.lower   # (rotated queries, entries) -> Lowered
    return run


def sharded_searcher(index, params: SearchParams, *, mesh=None,
                     n_shards: int | None = None, owner_policy: str = "shuffle",
                     seed: int = 0, fee=None,
                     owner=None, overlap: bool = False):
    """Query-owner-sharded DaM retrieval (paper Fig. 12): vectors row-sharded
    over the ``model`` axis, neighbor lists pre-partitioned by owner, queries
    over ``data`` with each query's beam resident on exactly one model shard.
    With ``mesh=None`` a (1, n_devices) mesh is created.

    ``owner`` overrides the row->shard map (a streaming index passes its
    stable capacity-wide map so appends never reshuffle resident rows);
    ``overlap=True`` selects the double-buffered stale-threshold pipeline.
    The returned ``run`` exposes the per-hop collective payload model as
    ``run.payload`` (see ``distributed.retrieval.collective_payload``) and
    the device-placed :class:`~repro.distributed.retrieval.ShardedDB` as
    ``run.db``."""
    import jax
    import jax.numpy as jnp

    from repro.distributed import retrieval as rt

    if params.trace:
        raise ValueError("sharded backend does not emit traces; use "
                         "backend='local' (trace=True) or 'ndpsim'")
    if mesh is None:
        ndev = len(jax.devices())
        n_shards = n_shards or ndev
        if ndev % n_shards:
            raise ValueError(f"n_shards={n_shards} must divide the available "
                             f"device count ({ndev}); pass an explicit mesh "
                             "to use a device subset")
        mesh = jax.make_mesh((ndev // n_shards, n_shards), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    else:
        model_axis = "model" if "model" in mesh.axis_names else mesh.axis_names[-1]
        n_shards = mesh.shape[model_axis]

    vectors = _base_vectors(index, params)
    if owner is None:
        owner = gmod.map_owners(index.n, n_shards, owner_policy, seed=seed)
    dam = gmod.build_dam(index.graph.base_adjacency, owner, n_shards)
    cfg = params.to_config(index.metric, index.seg)
    tomb = index.tombstone
    with jax.set_mesh(mesh):
        searcher = rt.make_sharded_searcher(mesh, cfg, index.n,
                                            fee=_fee(index, params, fee),
                                            dfloat_cfg=_dfloat_cfg(index, params),
                                            tombstone=tomb is not None,
                                            overlap=overlap)
        sh = rt.db_shardings(mesh)
        sdb = rt.build_sharded_db(vectors, dam, tombstone=tomb)
        fields = ("vectors", "local_ids", "part_adj")
        if tomb is not None:
            fields += ("tombstone",)
        sdb = rt.ShardedDB(*(jax.device_put(getattr(sdb, f), getattr(sh, f))
                             for f in fields))
    levels = index.device_levels(params.use_dfloat)

    def run(queries) -> SearchResult:
        qr = index.transform_queries(np.asarray(queries))
        entries = np.asarray(search_mod.descend(levels, qr, index.metric))
        with jax.set_mesh(mesh):
            ids, dists = searcher(sdb, jnp.asarray(qr), jnp.asarray(entries))
        return SearchResult(ids=np.asarray(ids), dists=np.asarray(dists),
                            generation=index.generation)

    run.payload = rt.collective_payload(cfg, max(p.shape[1] for p in dam.part_adj),
                                        n_shards)
    run.db = sdb
    return run


def ndpsim_searcher(index, params: SearchParams, *, hw=None, flags=None,
                    owner_policy: str = "shuffle", seed: int = 0, fee=None):
    """Trace-driven DIMM-NDP projection: local search with tracing forced on,
    replayed through ``ndpsim.simulate_ndp``; the SimResult rides on
    ``SearchResult.sim``."""
    from repro.core.dfloat import fp32_config
    from repro.ndpsim import SimFlags, simulate_ndp

    if hw is None:
        from repro.ndpsim.timing import NASZIP_2CH

        hw = NASZIP_2CH
    flags = flags or SimFlags()
    traced = dataclasses.replace(params, trace=True)
    # no custom fee -> go through the index cache so an already-compiled
    # traced local searcher is reused instead of jitting a duplicate
    local = (index.searcher("local", traced) if fee is None
             else local_searcher(index, traced, fee=fee))
    owner = gmod.map_owners(index.n, hw.n_subchannels, owner_policy, seed=seed)
    dfloat_cfg = (index.dfloat_cfg if params.use_dfloat
                  else fp32_config(index.dim))
    tier_cfgs = index.tier_cfgs() if params.storage == "tiered" else None

    def run(queries) -> SearchResult:
        res = local(queries)
        res.sim = simulate_ndp(res, owner, index.graph.base_adjacency, hw,
                               flags, dfloat_cfg, index.seg,
                               tier_cfgs=tier_cfgs)
        mut = (index.timings or {}).get("mutation")
        if mut:
            # streaming snapshot: append/repair traffic rides along as
            # write-burst accounting next to the read-side projection
            from repro.ndpsim.engine import account_writes

            res.sim.writes = account_writes(
                mut, index.dfloat_cfg, hw,
                index.graph.base_adjacency.shape[1])
        return res

    return run

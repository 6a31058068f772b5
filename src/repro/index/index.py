"""The unified naszip index: one typed build/search/persist surface.

Offline (paper Fig. 6 upper):  PCA-rotate DB -> alpha from eigenvalues ->
Var_k from sampled (query, vector) pairs -> beta from the Chebyshev budget ->
Dfloat config search (Alg. 1) -> bit-packed DB + graph index.

Online (Fig. 6 lower):  hierarchy descent -> FEE-sPCA beam search, executed by
any of the pluggable backends (``local`` jit/vmap, ``sharded`` shard_map DaM,
``ndpsim`` timing model) behind one ``searcher(backend=...)`` call.

Storage model (packed-native, format v3): the burst-aligned Dfloat bitstream
``db_packed`` is the canonical index payload.  The f32 quantized view ``db_q``
is *derived* — reconstructed on demand via ``dfloat.emulate_db`` (bit-identical
to decoding the bitstream) and cached; it is no longer persisted, which cuts
the on-disk artifact and the host/device footprint by the full f32 copy.
For ``storage="tiered"`` the row splits into a resident coarse tier (the
high-variance PCA-leading segment prefix) and a residual tier fetched only for
lanes that survive the coarse-tier exit; a v3 artifact with ``spec.tier_split``
set persists both tier bitstreams (checksummed), otherwise they are derived
lazily from ``db_rot``.

Persistence: ``Index.save(path)`` writes ``<path>/spec.json`` (build spec +
Dfloat layout + graph metadata) and ``<path>/arrays.npz`` (rotation, fee fit,
graph levels, rotated/packed DB); ``Index.load(path)`` restores a
bit-identical index, and still accepts format-v1 artifacts that carried the
redundant ``db_q`` copy.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import numpy as np

from repro.core import dfloat as dfl
from repro.core import graph as graph_mod
from repro.core import pca as pca_mod
from repro.core import search as search_mod
from repro.data.synthetic import VecDB, exact_topk, recall_at_k
from repro.index import backends as backends_mod
from repro.index.types import FeeFit, IndexSpec, SearchParams, SearchResult
from repro.resilience import CorruptArtifactError
from repro.resilience import checksum as cks
from repro.resilience import faults

FORMAT_VERSION = 3          # v3 persists the (coarse, residual) tier split;
                            # v2 dropped the persisted db_q copy
DELTA_FORMAT_VERSION = 3    # streaming-mutation delta segments (WAL) reuse the
                            # number, but live under <index>/delta/ with a
                            # manifest.json — an index dir always has spec.json
KNOWN_FORMATS = (1, 2, 3)


@dataclasses.dataclass
class Index:
    """A built naszip index: spec + all offline artifacts.

    ``db_packed`` (the burst-aligned uint32 bitstream) is the canonical
    payload; the quantized f32 view is available as the derived ``db_q``
    property (reconstructed lazily, cached).
    """

    spec: IndexSpec
    spca: pca_mod.SPCA
    fee: FeeFit
    dfloat_cfg: dfl.DfloatConfig
    graph: graph_mod.GraphIndex
    db_rot: np.ndarray            # PCA-rotated DB (f32, pre-quantization)
    db_packed: np.ndarray         # real bitstream (uint32) — canonical payload
    timings: dict = dataclasses.field(default_factory=dict)
    # dead-row bitmap ((ceil(n/32),) uint32, bit = tombstoned or unallocated
    # capacity-tail slot).  None for an ordinary immutable index; set on
    # snapshots frozen out of a ``repro.streaming.MutableIndex``.
    tombstone: np.ndarray | None = None
    # snapshot generation of a streaming MutableIndex (None = not a snapshot)
    generation: int | None = None
    # allocated prefix length of a capacity-array snapshot: rows >= n_rows are
    # unwritten tail slots (always tombstoned).  None = every row is real.
    # The serving tier's generation-aware device upload (index.device) uses it
    # to ship only the appended tail on a snapshot hot-swap.
    n_rows: int | None = None
    _db_q: np.ndarray | None = dataclasses.field(default=None, repr=False,
                                                 compare=False)
    # cached (coarse, residual) packed tiers for storage="tiered"; derived
    # lazily from db_rot unless the artifact persisted them (format v3 with
    # spec.tier_split set) or a streaming freeze seeded them
    _tiers: tuple | None = dataclasses.field(default=None, repr=False,
                                             compare=False)
    _searchers: dict = dataclasses.field(default_factory=dict, repr=False,
                                         compare=False)
    _device: dict = dataclasses.field(default_factory=dict, repr=False,
                                      compare=False)

    MAX_CACHED_SEARCHERS = 16

    # -- trivia -------------------------------------------------------------
    @property
    def metric(self) -> str:
        return self.spec.metric

    @property
    def seg(self) -> int:
        return self.spec.seg

    @property
    def n(self) -> int:
        return self.db_rot.shape[0]

    @property
    def n_alive(self) -> int:
        """Rows that can appear in results (``n`` minus tombstoned/tail)."""
        if self.tombstone is None:
            return self.n
        # popcount over the bitmap words (O(n/32)), masking bits >= n
        words = self.tombstone[: -(-self.n // 32)].copy()
        tail_bits = self.n & 31
        if tail_bits:
            words[-1] &= np.uint32((1 << tail_bits) - 1)
        return self.n - int(np.bitwise_count(words).sum())

    @property
    def dim(self) -> int:
        return self.db_rot.shape[1]

    def transform_queries(self, q: np.ndarray) -> np.ndarray:
        return self.spca.transform(q)

    @property
    def db_q(self) -> np.ndarray:
        """Derived f32 view of the quantized DB (what the hardware decodes).

        Reconstructed on demand from ``db_rot`` + the Dfloat layout — identical
        bit-for-bit to decoding ``db_packed`` — and cached.  Packed-storage
        searches never materialize it."""
        if self._db_q is None:
            self._db_q = dfl.emulate_db(self.db_rot, self.dfloat_cfg)
        return self._db_q

    @property
    def tier_split(self) -> int:
        """Resolved coarse-tier size in FEE segments for ``storage="tiered"``:
        ``spec.tier_split`` when set, else the energy-based auto split."""
        n_segs = self.dim // self.seg
        if self.spec.tier_split is not None:
            ts = self.spec.tier_split
            if not 0 <= ts <= n_segs:
                raise ValueError(
                    f"spec.tier_split={ts} outside [0, {n_segs}] for "
                    f"dim={self.dim}, seg={self.seg}")
            return ts
        return pca_mod.suggest_tier_split(self.spca.eigvals, self.seg)

    def tier_cfgs(self) -> tuple[dfl.DfloatConfig, dfl.DfloatConfig]:
        """(coarse, residual) Dfloat layouts at the resolved tier split."""
        return dfl.split_config(self.dfloat_cfg, self.tier_split * self.seg)

    def tier_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(coarse, residual) packed tier bitstreams — field-for-field the
        same bits as ``db_packed`` re-grouped at the tier boundary.  Derived
        from ``db_rot`` and cached when the artifact didn't persist them."""
        if self._tiers is None:
            self._tiers = dfl.pack_tiers(self.db_rot, self.dfloat_cfg,
                                         self.tier_split * self.seg)
        return self._tiers

    def emulated_rows(self, ids: np.ndarray) -> np.ndarray:
        """Quantized f32 rows for ``ids`` without materializing full ``db_q``
        (per-row emulation; used by the upper-layer greedy descent)."""
        if self._db_q is not None:
            return self._db_q[ids]
        return dfl.emulate_db(self.db_rot[ids], self.dfloat_cfg)

    def device_db(self, use_dfloat: bool = True, storage: str = "f32"):
        """Device copy of the DB in the requested representation, shared by
        every cached searcher so repeated ``searcher()`` calls don't re-upload
        the vectors.  ``storage="packed"`` uploads the uint32 bitstream
        (~3x smaller than the f32 view for typical Dfloat configs)."""
        import jax.numpy as jnp

        key = ("db", storage, bool(use_dfloat))
        if key not in self._device:
            if storage == "tiered":
                xc, xr = self.tier_arrays()
                self._device[key] = (jnp.asarray(xc), jnp.asarray(xr))
            else:
                if storage == "packed":
                    arr = self.db_packed
                else:
                    arr = self.db_q if use_dfloat else self.db_rot
                self._device[key] = jnp.asarray(arr)
        return self._device[key]

    def device_adjacency(self):
        import jax.numpy as jnp

        if "adj" not in self._device:
            self._device["adj"] = jnp.asarray(self.graph.base_adjacency,
                                              jnp.int32)
        return self._device["adj"]

    def device_tombstone(self):
        import jax.numpy as jnp

        if self.tombstone is None:
            return None
        if "tombstone" not in self._device:
            self._device["tombstone"] = jnp.asarray(self.tombstone, jnp.uint32)
        return self._device["tombstone"]

    def device_levels(self, use_dfloat: bool = True):
        """The upper graph levels on the device for the entry descent
        (:class:`repro.core.search.DeviceLevels`), uploaded once per index —
        so once per serving generation, each snapshot being its own
        ``Index``.  The rows are the quantized f32 rows the Dfloat stores
        decode to (``use_dfloat``), else ``db_rot``'s."""
        key = ("levels", bool(use_dfloat))
        if key not in self._device:
            rows = (self.emulated_rows if use_dfloat
                    else (lambda ids: self.db_rot[ids]))
            self._device[key] = search_mod.upload_levels(self.graph, rows)
        return self._device[key]

    def seed_device(self, key, arr) -> None:
        """Pre-populate the device-array cache (keys: ``("db", storage,
        use_dfloat)``, ``"adj"``, ``"tombstone"``).  The serving tier's
        :class:`repro.index.device.DeviceCache` seeds snapshots with
        prefix-aliased uploads so a generation swap never re-ships the full
        payload; ``searcher()`` picks the seeded arrays up transparently."""
        self._device[key] = arr

    def drop_device(self) -> None:
        """Release this index's device arrays and compiled-searcher cache
        (a retired serving generation whose buffers may have been donated)."""
        self._device.clear()
        self._searchers.clear()

    # -- build --------------------------------------------------------------
    @classmethod
    def build(cls, db: VecDB, spec: IndexSpec | None = None, *,
              cache_key: str | None = None, **overrides) -> "Index":
        """Run the full offline pipeline for ``db`` under ``spec``.

        ``overrides`` are IndexSpec field overrides applied on top of ``spec``
        (or of ``IndexSpec.for_db(db)`` when no spec is given).
        """
        if spec is None:
            spec = IndexSpec.for_db(db, **overrides)
        elif overrides:
            spec = dataclasses.replace(spec, **overrides)
        if spec.metric != db.metric:
            raise ValueError(f"spec.metric={spec.metric!r} but db is {db.metric!r}")
        x = db.vectors
        d = x.shape[1]
        if d % spec.seg:
            raise ValueError(f"seg={spec.seg} must divide dim={d}")
        t = {}

        t0 = time.perf_counter()
        spca = pca_mod.fit_spca(x, spec.metric)
        db_rot = spca.transform(x)
        tq_rot = spca.transform(db.train_queries)
        t["pca_offline_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        fee = FeeFit.from_dict(pca_mod.fit_beta(
            db_rot, tq_rot, spca.eigvals, spec.seg, metric=spec.metric,
            p_target=spec.p_target, seed=spec.seed))
        t["beta_fit_s"] = time.perf_counter() - t0

        # graph built on the rotated DB (distances identical to original space)
        t0 = time.perf_counter()
        key = cache_key or f"{db.name}/n{db.n}"
        graph = graph_mod.build_graph(db_rot, m=spec.m, metric=spec.metric,
                                      prune=spec.prune, cache_key=key,
                                      seed=spec.seed)
        t["graph_build_s"] = time.perf_counter() - t0

        # Dfloat search (Alg. 1) with a recall proxy on sampled train queries
        t0 = time.perf_counter()
        if spec.dfloat_recall_target is not None:
            sample_q = tq_rot[: min(64, len(tq_rot))]
            gt = exact_topk(db_rot, sample_q, spec.recall_k, spec.metric)

            if spec.dfloat_proxy:
                # fast inner-loop proxy (our speed adaptation of the paper's
                # mask-emulation evaluation): top-k ordering agreement under
                # exact quantized distances — no graph traversal per config
                def recall_fn(db_emul):
                    found = exact_topk(db_emul, sample_q, spec.recall_k, spec.metric)
                    return recall_at_k(found, gt, spec.recall_k)
            else:
                def recall_fn(db_emul):
                    cfg = search_mod.SearchConfig(
                        ef=spec.ef_fit, k=spec.recall_k, metric=spec.metric,
                        seg=spec.seg, use_fee=True)
                    out = search_mod.search_graph(db_emul, graph, sample_q, cfg,
                                                  fee=fee.params)
                    return recall_at_k(out["ids"], gt, spec.recall_k)

            dfloat_cfg, _log = dfl.search_config(db_rot, recall_fn,
                                                 spec.dfloat_recall_target)
        else:
            dfloat_cfg = dfl.fp32_config(d)
        db_packed = dfl.pack_db(db_rot, dfloat_cfg)
        t["dfloat_search_s"] = time.perf_counter() - t0

        return cls(spec=spec, spca=spca, fee=fee, dfloat_cfg=dfloat_cfg,
                   graph=graph, db_rot=db_rot, db_packed=db_packed, timings=t)

    # -- persistence --------------------------------------------------------
    def save(self, path: str | Path) -> Path:
        """Write ``<path>/spec.json`` + ``<path>/arrays.npz``."""
        path = Path(path)
        path.mkdir(parents=True, exist_ok=True)
        meta = dict(
            format_version=FORMAT_VERSION,
            spec=dataclasses.asdict(self.spec),
            fee=dict(seg=self.fee.seg, p_target=self.fee.p_target,
                     metric=self.fee.metric),
            dfloat=dict(
                burst_bits=self.dfloat_cfg.burst_bits,
                devices_per_subchannel=self.dfloat_cfg.devices_per_subchannel,
                segments=[dataclasses.asdict(s) for s in self.dfloat_cfg.segments],
            ),
            graph=dict(m=self.graph.m, entry=self.graph.entry,
                       n_levels=len(self.graph.levels)),
            timings=self.timings,
        )
        if self.generation is not None:
            meta["generation"] = self.generation
        if self.n_rows is not None:
            meta["n_rows"] = self.n_rows
        arrays = dict(
            spca_mean=self.spca.mean, spca_components=self.spca.components,
            spca_eigvals=self.spca.eigvals,
            fee_alpha=self.fee.alpha, fee_beta=self.fee.beta,
            fee_margin=self.fee.margin, fee_var_k=self.fee.var_k,
            # db_q is NOT persisted (format v2): it is derived, bit-exactly,
            # from db_rot + the Dfloat layout (or by decoding db_packed)
            db_rot=self.db_rot, db_packed=self.db_packed,
        )
        if self.tombstone is not None:
            # readers without streaming support simply see an extra optional
            # array (dead rows then reappear in results)
            arrays["tombstone"] = self.tombstone
        if self.spec.tier_split is not None:
            # tier-native artifact: persist both tier bitstreams (checksummed
            # below with everything else) plus the resolved split so load()
            # serves storage="tiered" without repacking
            xc, xr = self.tier_arrays()
            arrays["db_coarse"], arrays["db_resid"] = xc, xr
            meta["tier_split"] = self.tier_split
        for i, (ids, adj) in enumerate(self.graph.levels):
            arrays[f"g_ids{i}"] = ids
            arrays[f"g_adj{i}"] = adj
        # per-array checksums ride in the manifest (still format v2: an
        # additive optional field) so load() detects a flipped bit or torn
        # tail instead of serving garbage neighbors
        meta["checksums"] = cks.manifest_checksums(arrays)
        (path / "spec.json").write_text(json.dumps(meta, indent=1))
        np.savez_compressed(path / "arrays.npz", **arrays)
        return path

    @classmethod
    def load(cls, path: str | Path) -> "Index":
        path = Path(path)
        if not (path / "spec.json").exists():
            hint = (" (found manifest.json — this looks like a checkpoint or "
                    "streaming delta segment, not an index directory; delta "
                    "segments are replayed via repro.streaming.MutableIndex"
                    ".load on the *base* index directory)"
                    if (path / "manifest.json").exists() else "")
            raise ValueError(f"{path} is not a naszip index directory: "
                             f"no spec.json{hint}")
        meta = json.loads((path / "spec.json").read_text())
        version = meta.get("format_version")
        if version not in KNOWN_FORMATS:
            raise ValueError(
                f"unsupported index format v{version} at {path}: this build "
                f"reads formats {KNOWN_FORMATS} — written by a newer naszip; "
                "upgrade this package to read it.  (Streaming delta segments "
                "also stamp a format_version, but they live under "
                "<index>/delta/ with a manifest.json, never a spec.json — "
                "replay them via repro.streaming.MutableIndex.load on the "
                "base index directory.)")
        spec = IndexSpec(**meta["spec"])
        try:
            with np.load(path / "arrays.npz", allow_pickle=False) as z:
                a = {k: faults.corrupt("index.read_arrays", z[k])
                     for k in z.files}
        except Exception as e:   # truncated/torn zip containers raise variously
            raise CorruptArtifactError(
                f"{path}: unreadable arrays.npz ({e}) — torn write or "
                "truncated artifact") from e
        # verify every persisted array against the manifest's recorded
        # checksums (absent on pre-checksum artifacts: nothing to verify)
        cks.verify_arrays(a, meta.get("checksums"), path)
        spca = pca_mod.SPCA(mean=a["spca_mean"], components=a["spca_components"],
                            eigvals=a["spca_eigvals"], metric=spec.metric)
        fee = FeeFit(alpha=a["fee_alpha"], beta=a["fee_beta"],
                     margin=a["fee_margin"], var_k=a["fee_var_k"],
                     seg=int(meta["fee"]["seg"]),
                     p_target=float(meta["fee"]["p_target"]),
                     metric=str(meta["fee"]["metric"]))
        dmeta = meta["dfloat"]
        dfloat_cfg = dfl.DfloatConfig(
            segments=tuple(dfl.DfloatSegment(**s) for s in dmeta["segments"]),
            burst_bits=int(dmeta["burst_bits"]),
            devices_per_subchannel=int(dmeta["devices_per_subchannel"]))
        levels = [(a[f"g_ids{i}"], a[f"g_adj{i}"])
                  for i in range(int(meta["graph"]["n_levels"]))]
        graph = graph_mod.GraphIndex(levels=levels,
                                     entry=int(meta["graph"]["entry"]),
                                     m=int(meta["graph"]["m"]))
        return cls(spec=spec, spca=spca, fee=fee, dfloat_cfg=dfloat_cfg,
                   graph=graph, db_rot=a["db_rot"], db_packed=a["db_packed"],
                   timings=meta.get("timings", {}),
                   tombstone=a.get("tombstone"),
                   generation=meta.get("generation"),
                   n_rows=meta.get("n_rows"),
                   # v1 artifacts carried the derived copy; seed the cache
                   _db_q=a.get("db_q"),
                   # v3 tier-native artifacts carry both tier bitstreams
                   _tiers=((a["db_coarse"], a["db_resid"])
                           if "db_coarse" in a else None))

    # -- search -------------------------------------------------------------
    def searcher(self, backend: str = "local",
                 params: SearchParams | None = None, **opts):
        """Return ``run(queries) -> SearchResult`` for the chosen backend.

        Searchers without backend-specific options are cached on the index, so
        repeated query batches reuse one compiled executable.
        """
        params = params or SearchParams()
        key = (backend, params) if not opts else None
        if key is not None and key in self._searchers:
            return self._searchers[key]
        fn = backends_mod.make(self, backend, params, **opts)
        if key is not None:
            while len(self._searchers) >= self.MAX_CACHED_SEARCHERS:
                self._searchers.pop(next(iter(self._searchers)))
            self._searchers[key] = fn
        return fn

    @staticmethod
    def _params(params: SearchParams | None, kw: dict) -> SearchParams:
        if params is not None and kw:
            raise TypeError(f"pass either params= or field overrides, not both: {kw}")
        return params or SearchParams(**kw)

    def search(self, queries: np.ndarray, params: SearchParams | None = None,
               **kw) -> SearchResult:
        """Local-backend convenience: ``search(q, ef=64, k=10, trace=True)``."""
        return self.searcher("local", self._params(params, kw))(queries)

    def evaluate(self, db: VecDB, params: SearchParams | None = None,
                 **kw) -> dict:
        """Recall (and, when tracing, hop/eval/dims statistics) on db.queries."""
        params = self._params(params, kw)
        res = self.search(db.queries, params)
        out = dict(recall=recall_at_k(res.ids, db.gt, params.k),
                   ef=params.ef, k=params.k)
        if params.trace:
            out.update(
                hops=float(np.mean(res.hops)),
                dist_evals=float(np.mean(res.n_eval)),
                dims_per_eval=float(res.dims.sum() / max(1, res.n_eval.sum())),
                dims_total=float(np.mean(res.dims)),
            )
        return out

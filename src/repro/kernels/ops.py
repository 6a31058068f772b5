"""Public jit'd wrappers for the Pallas kernels.

On TPU these run compiled (interpret=False); on this CPU container they run
in interpret mode (kernel body executed in Python), which is the validation
target per the build spec.  ``backend="jnp"`` selects the pure-jnp oracle —
used both as the reference in tests and as the fast path for CPU benchmarks.
``backend="pallas_skip_dma"`` selects the manual-DMA kernels: feature blocks
(or packed word spans) are fetched from HBM with async copies inside the
tile-exit gate, which is asked once per group of
``fee_distance.GATE_BLOCKS`` blocks, so exited tiles skip the memory traffic
of the remaining groups, not just the compute.
"""
from __future__ import annotations

import jax

from repro.core import dfloat as dfl
from repro.kernels import ref as ref_ops
from repro.kernels.dfloat_unpack import dfloat_unpack_pallas
from repro.kernels.fee_distance import (fee_distance_packed_pallas,
                                        fee_distance_pallas,
                                        fee_distance_skipdma_pallas,
                                        fee_distance_tiered_pallas)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_ref(backend: str) -> bool:
    return backend == "jnp" or (backend == "auto" and not _on_tpu())


def _fold_lane_mask(out, lane_mask):
    """Fold an alive-lane mask into the FEE exit outputs.

    On the real VPE the tombstone bitmap is resident on-chip and is ANDed into
    the exit flags before the first burst is issued, so a dead lane streams
    zero bursts; here that contract is expressed on the kernel outputs —
    dead lanes come back rejected with ``segs_used == 0`` (the value the
    traffic/energy models account), whatever the backend computed.
    """
    if lane_mask is None:
        return out
    import jax.numpy as jnp

    dist, rejected, segs_used = out
    return (dist, rejected | ~lane_mask,
            jnp.where(lane_mask, segs_used, 0).astype(segs_used.dtype))


def fee_distance(q, x, threshold, alpha, beta, margin, *, seg: int,
                 metric: str = "l2", backend: str = "auto", tile_c: int = 128,
                 lane_mask=None):
    """VPE datapath: early-exit distance of candidates ``x`` vs query ``q``.

    Returns (dist, rejected, segs_used); dist is partial for rejected lanes.
    ``lane_mask`` (bool (C,), False = tombstoned lane) joins the exit mask
    before any segment is charged.
    """
    if _use_ref(backend):
        out = ref_ops.fee_distance_ref(q, x, threshold, alpha, beta, margin,
                                       seg=seg, metric=metric)
    elif backend == "pallas_skip_dma":
        out = fee_distance_skipdma_pallas(q, x, threshold, alpha, beta,
                                          margin, seg=seg, metric=metric,
                                          tile_c=tile_c,
                                          interpret=not _on_tpu())
    else:
        out = fee_distance_pallas(q, x, threshold, alpha, beta, margin,
                                  seg=seg, metric=metric, tile_c=tile_c,
                                  interpret=not _on_tpu())
    return _fold_lane_mask(out, lane_mask)


def fee_distance_packed(q, xp, threshold, alpha, beta, margin, *,
                        dfloat_cfg: dfl.DfloatConfig, seg: int,
                        metric: str = "l2", backend: str = "auto",
                        tile_c: int = 128, lane_mask=None):
    """Fused Dfloat-decode + early-exit distance straight from the packed
    uint32 bitstream (``xp`` (C, W)) — the packed-native scoring hot path.

    Bit-compatible with :func:`fee_distance` over ``dfloat.emulate_db`` data.
    ``lane_mask`` behaves as in :func:`fee_distance`.
    """
    if _use_ref(backend):
        out = ref_ops.fee_distance_packed_ref(q, xp, threshold, alpha, beta,
                                              margin, dfloat_cfg=dfloat_cfg,
                                              seg=seg, metric=metric)
    else:
        out = fee_distance_packed_pallas(q, xp, threshold, alpha, beta,
                                         margin, dfloat_cfg=dfloat_cfg,
                                         seg=seg, metric=metric,
                                         tile_c=tile_c,
                                         interpret=not _on_tpu(),
                                         skip_dma=backend == "pallas_skip_dma")
    return _fold_lane_mask(out, lane_mask)


def fee_distance_tiered(q, xc, xr, threshold, alpha, beta, margin, *,
                        coarse_cfg: dfl.DfloatConfig,
                        resid_cfg: dfl.DfloatConfig, seg: int,
                        metric: str = "l2", backend: str = "auto",
                        tile_c: int = 128, lane_mask=None):
    """Tiered fused decode + early-exit distance: the resident coarse-tier
    rows ``xc`` (C, Wc) make the exit decision; residual-tier rows ``xr``
    (C, Wr) are fetched per candidate tile (128 lanes by default): on the
    Pallas path a tile with a live lane at the tier boundary starts async
    copies of its whole residual span there, and a tile whose lanes all
    exited inside the coarse tier fetches none of it.

    Bit-identical to :func:`fee_distance_packed` over the parent layout's
    rows for any split point (``dfloat.split_config`` preserves per-feature
    formats).  A lane *uses* the residual tier iff ``segs_used >
    coarse_cfg.dim // seg`` — the accounting of the traffic models, in
    which exited lanes never pay residual bytes; the kernel's copies move
    whole tiles.
    """
    if _use_ref(backend):
        out = ref_ops.fee_distance_tiered_ref(
            q, xc, xr, threshold, alpha, beta, margin, coarse_cfg=coarse_cfg,
            resid_cfg=resid_cfg, seg=seg, metric=metric)
    else:
        out = fee_distance_tiered_pallas(
            q, xc, xr, threshold, alpha, beta, margin, coarse_cfg=coarse_cfg,
            resid_cfg=resid_cfg, seg=seg, metric=metric, tile_c=tile_c,
            interpret=not _on_tpu())
    return _fold_lane_mask(out, lane_mask)


def fee_distance_stale(q, x, exit_threshold, admit_threshold, alpha, beta,
                       margin, *, seg: int, metric: str = "l2",
                       backend: str = "auto", tile_c: int = 128,
                       lane_mask=None, dfloat_cfg: dfl.DfloatConfig | None = None):
    """Threshold-carrying FEE variant for the sharded / double-buffered hop.

    The VPE streams and early-exits against ``exit_threshold`` — in the
    overlap pipeline that is the *previous* hop's beam bound, which is always
    >= the current one, so exiting against it can only admit extra lanes,
    never drop one the synchronous hop would keep (the exit test
    ``est >= threshold`` is monotone in the threshold).  ``admit_threshold``
    is then applied to the surviving lanes' full distances: a lane with
    ``dist >= admit_threshold`` cannot displace anything in a full beam whose
    worst entry is ``admit_threshold`` (and an underfull beam carries
    ``admit_threshold == BIG``, which drops nothing), so filtering it here —
    before the shard-local top-k and the cross-shard collective — is exact
    while keeping dead weight out of the reduced payload.

    Returns ``(dist, admit, segs_used)``: ``admit`` is True for lanes that
    survived both thresholds (note the *positive* polarity, vs. the
    ``rejected`` flag of :func:`fee_distance`).  With ``dfloat_cfg`` the
    candidates ``x`` are packed uint32 rows scored via
    :func:`fee_distance_packed`; a *tuple* ``dfloat_cfg`` of (coarse,
    residual) tier configs selects the tiered path (``x`` is then the
    matching (coarse_rows, residual_rows) pair — both shard-local, so the
    cross-shard collective never carries residual words).
    """
    import jax.numpy as jnp

    if dfloat_cfg is None:
        dist, rejected, segs_used = fee_distance(
            q, x, exit_threshold, alpha, beta, margin, seg=seg, metric=metric,
            backend=backend, tile_c=tile_c, lane_mask=lane_mask)
    elif isinstance(dfloat_cfg, tuple):
        dist, rejected, segs_used = fee_distance_tiered(
            q, x[0], x[1], exit_threshold, alpha, beta, margin,
            coarse_cfg=dfloat_cfg[0], resid_cfg=dfloat_cfg[1], seg=seg,
            metric=metric, backend=backend, tile_c=tile_c,
            lane_mask=lane_mask)
    else:
        dist, rejected, segs_used = fee_distance_packed(
            q, x, exit_threshold, alpha, beta, margin, dfloat_cfg=dfloat_cfg,
            seg=seg, metric=metric, backend=backend, tile_c=tile_c,
            lane_mask=lane_mask)
    return dist, ~rejected & (dist < admit_threshold), segs_used


def dfloat_unpack_rows(packed, cfg: dfl.DfloatConfig, *,
                       backend: str = "auto", tile_c: int = 128):
    """Traceable packed-row decode: (C, W) uint32 -> (C, D) f32, bit-exact.

    Unlike :func:`dfloat_unpack` this is safe inside jit/vmap (no host numpy),
    so the search loop can derive f32 views of packed rows on demand.
    """
    if _use_ref(backend) or backend == "pallas_skip_dma":
        return dfl.unpack_rows_jnp(packed, cfg)
    return dfloat_unpack_pallas(packed, cfg, tile_c=tile_c,
                                interpret=not _on_tpu())


def dfloat_unpack_tiered_rows(xc, xr, coarse_cfg: dfl.DfloatConfig,
                              resid_cfg: dfl.DfloatConfig, *,
                              backend: str = "auto", tile_c: int = 128):
    """Decode a (coarse, residual) tier-row pair back to (C, D) f32 —
    bit-exact vs ``dfloat_unpack_rows`` on the parent layout's rows."""
    import jax.numpy as jnp

    parts = []
    if coarse_cfg.dim:
        parts.append(dfloat_unpack_rows(xc, coarse_cfg, backend=backend,
                                        tile_c=tile_c))
    if resid_cfg.dim:
        parts.append(dfloat_unpack_rows(xr, resid_cfg, backend=backend,
                                        tile_c=tile_c))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def dfloat_unpack(packed, cfg, *, backend: str = "auto", tile_c: int = 128):
    """Dfloat process module: packed uint32 rows -> f32 features (bit-exact)."""
    if _use_ref(backend):
        import jax.numpy as jnp
        import numpy as np
        return jnp.asarray(ref_ops.dfloat_unpack_ref(np.asarray(packed), cfg))
    return dfloat_unpack_pallas(packed, cfg, tile_c=tile_c,
                                interpret=not _on_tpu())

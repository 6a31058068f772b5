"""Pallas TPU kernels: FEE-sPCA early-exit distance (the VPE datapath, Fig. 10c/f).

TPU adaptation of the paper's per-burst early exit: candidates are tiled
(TILE_C per grid step) and the feature axis is walked in ``seg``-wide blocks
(one block = the TPU analogue of one DRAM access group).  After each block
the estimated full distance

    est = alpha_s * acc / beta_s - margin_s

is compared against the beam threshold; lanes that exit stop accumulating,
and once an entire candidate tile has exited the remaining feature blocks'
*compute* is skipped (`pl.when`).  Whether the tile is still live is asked
once every ``GATE_BLOCKS`` blocks, so a tile that dies inside a group
finishes that group with every lane masked.

The kernels are feature-major: candidates fill the 128 lanes and features
(or packed words) run down the sublanes, so a ``seg``-feature block is a
sublane slice of whole (8, 128) tiles, the per-lane accumulators are one
lane-dense row, and no layout assumes ``D % 128 == 0``.  The wrappers take
row-major (C, D) / (C, W) candidates and transpose them on the way in.

Three variants share the accumulate/exit logic:

  * ``fee_distance_pallas``        — f32 features, automatic block pipelining
    (exited tiles skip compute, but the BlockSpec pipeline still streams
    their whole feature column from HBM);
  * ``fee_distance_skipdma_pallas``— f32 features kept in HBM (`pl.ANY`); each
    feature block is fetched with a manual ``make_async_copy`` inside the
    tile-exit gate, so exited tiles skip the HBM traffic of every later
    group — the paper's actual win (the DIMM stops issuing bursts on exit);
  * ``fee_distance_packed_pallas`` — the Dfloat process module fused into the
    VPE datapath (Fig. 10d->10c): candidates arrive as the packed uint32
    bitstream and are decoded in VMEM with static barrel-shifter offsets, so
    only packed bytes ever cross HBM.  ``skip_dma=True`` additionally keeps
    the bitstream in HBM and manually DMAs only the word range of each
    feature block of a live group.

The tiered kernel (``fee_distance_tiered_pallas``) runs the packed datapath
over a resident coarse tier and fetches the residual tier of the tiles that
are live at the tier boundary, all of it at once.

Grid: (Q, C // TILE_C), one step per (query, candidate tile).  The leading
query axis is what ``jax.vmap`` over the single-query wrappers turns into
(the search loop vmaps them over its query batch).  Inside a step the FEE
blocks run as a statically unrolled loop — each block's decode offsets are
compile-time constants — so the per-lane accumulators live in scratch for
one step only, and both grid axes are "parallel".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dfloat as dfl
from repro.kernels.dfloat_unpack import decode_rows

BIG = 3.0e38
SUBLANES = 8              # rows of one (8, 128) 32-bit VMEM tile
# FEE blocks per tile-exit check.  The check reduces the tile's lanes to one
# scalar branch that every later block waits on (≈ 0.3 µs a check on a v5e),
# so it is made once per group of blocks; exited lanes are masked anyway
GATE_BLOCKS = 8


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def _part_distance(x, q, metric: str):
    """x (seg, TILE_C) features, q (seg, 1) -> (1, TILE_C) partial score."""
    if metric == "l2":
        return ((x - q) ** 2).sum(axis=0, keepdims=True)
    return -(x * q).sum(axis=0, keepdims=True)


def _tile_alive(alive):
    return alive[:].max() > 0


def _scorer(q_ref, thr_ref, alpha_ref, beta_ref, margin_ref, acc, alive,
            nseg, *, metric: str, seg: int, n_segs: int):
    """Reset the tile's per-lane state and return ``score(blocks)``.

    ``blocks`` is a list of ``(k, load)``: FEE block ``k`` whose (seg,
    TILE_C) features ``load()`` gives.  They are scored into the live lanes
    in groups of ``GATE_BLOCKS``; each group runs, with any fetch its loads
    issue, only while some lane of the tile is live, and carries the lane
    state in registers from block to block.  ``before(group)``, where given,
    runs ahead of each group's gate.
    """
    thr = thr_ref[pl.program_id(0)]
    acc[:] = jnp.zeros_like(acc)
    alive[:] = jnp.ones_like(alive)
    nseg[:] = jnp.zeros_like(nseg)

    def run(group):
        a, live, n = acc[:], alive[:] > 0, nseg[:]
        for k, load in group:
            part = _part_distance(load(), q_ref[pl.ds(k * seg, seg), :], metric)
            a = a + jnp.where(live, part, 0.0)
            n = n + jnp.where(live, 1, 0)
            # exits only before the last segment (paper Fig. 6: at the last
            # access the full distance is available anyway)
            if k < n_segs - 1:
                est = alpha_ref[k] * a / beta_ref[k] - margin_ref[k]
                live = live & ~(est >= thr)
        acc[:] = a
        alive[:] = live.astype(jnp.int32)
        nseg[:] = n

    def score(blocks, before=None):
        for g in range(0, len(blocks), GATE_BLOCKS):
            group = blocks[g : g + GATE_BLOCKS]
            if before is not None:
                before(group)
            pl.when(_tile_alive(alive))(functools.partial(run, group))

    return score


def _emit_outputs(dist_ref, rej_ref, segs_ref, acc, alive, nseg):
    dist_ref[:, :] = acc[:]
    rej_ref[:, :] = jnp.where(alive[:] > 0, 0, 1).astype(jnp.int32)
    segs_ref[:, :] = nseg[:]


def _lanes(tile_c: int):
    """The lane window of this step's candidate tile."""
    return pl.ds(pl.multiple_of(pl.program_id(1) * tile_c, tile_c), tile_c)


def _word_span(w0: int, w1: int, n_words: int) -> tuple[int, int]:
    """Widen a word range ``[w0, w1)`` to whole sublane tiles (capped at the
    row's ``n_words``) so its DMA moves whole (8, 128) tiles."""
    a = w0 - w0 % SUBLANES
    return a, min(-(-w1 // SUBLANES) * SUBLANES, n_words)


def _feature_major(x, tile_c: int):
    """(Q, C, F) candidate rows -> (Q, F, Cp): candidates on the lanes,
    padded to whole tiles."""
    pad = (-x.shape[1]) % tile_c
    return jnp.pad(jnp.swapaxes(x, 1, 2), ((0, 0), (0, 0), (0, pad)))


def _fee_call(kern, q, xs, x_specs, thr, alpha, beta, margin, *, seg: int,
              tile_c: int, scratch, interpret: bool):
    """Launch one FEE kernel over a query batch.

    ``q`` (Q, D); ``xs`` the feature-major candidate operands (Q, F, Cp)
    with their ``x_specs``; ``thr`` (Q,).  Returns (dist, rejected,
    segs_used), each (Q, Cp).
    """
    nq, d = q.shape
    n_segs = d // seg
    assert n_segs * seg == d, (d, seg)
    cp = xs[0].shape[-1]
    lane_row = pl.BlockSpec((None, 1, tile_c), lambda b, i: (b, 0, i))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    dist, rej, segs = pl.pallas_call(
        functools.partial(kern, seg=seg, n_segs=n_segs),
        grid=(nq, cp // tile_c),
        in_specs=[
            pl.BlockSpec((None, d, 1), lambda b, i: (b, 0, 0)),  # q column
            *x_specs,
            smem, smem, smem, smem,             # threshold, alpha, beta, margin
        ],
        out_specs=[lane_row, lane_row, lane_row],
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, cp), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, cp), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1, cp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, tile_c), jnp.float32),   # acc
            pltpu.VMEM((1, tile_c), jnp.int32),     # alive
            pltpu.VMEM((1, tile_c), jnp.int32),     # nseg
            *scratch,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(q[:, :, None], *xs, thr.astype(jnp.float32), alpha.astype(jnp.float32),
      beta.astype(jnp.float32), margin.astype(jnp.float32))
    return dist[:, 0], rej[:, 0] > 0, segs[:, 0]


def _one_query(batched_fn, n_batched: int):
    """Single-query entry point for ``batched_fn``, whose first ``n_batched``
    operands carry a leading query axis and whose outputs are (Q, Cp).

    A ``jax.vmap`` over the returned function folds the mapped axis into that
    query axis — the kernel's outermost grid dimension — rather than letting
    the generic ``pallas_call`` batching rule add a grid axis the kernel
    cannot see (a manual DMA from a ``pl.ANY`` ref has to index the query
    itself).  The FEE parameters must not be mapped.
    """
    @jax.custom_batching.custom_vmap
    def call(*args):
        return batched_fn(*args)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(in_batched[n_batched:]):
            raise NotImplementedError("FEE parameters cannot be vmapped")
        lead = [a if mapped else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, mapped in zip(args[:n_batched], in_batched)]
        nq = lead[0].shape[1]
        outs = call(*(a.reshape(axis_size * nq, *a.shape[2:]) for a in lead),
                    *args[n_batched:])
        return (tuple(o.reshape(axis_size, nq, *o.shape[1:]) for o in outs),
                (True,) * len(outs))

    def one(*args):
        outs = call(*(a[None] for a in args[:n_batched]), *args[n_batched:])
        return tuple(o[0] for o in outs)

    return one


# ---------------------------------------------------------------------------
# f32 candidates
# ---------------------------------------------------------------------------


def _kernel(q_ref, x_ref, thr_ref, alpha_ref, beta_ref, margin_ref,
            dist_ref, rej_ref, segs_ref, acc, alive, nseg,
            *, metric: str, seg: int, n_segs: int):
    score = _scorer(q_ref, thr_ref, alpha_ref, beta_ref, margin_ref, acc,
                    alive, nseg, metric=metric, seg=seg, n_segs=n_segs)
    score([(k, lambda k=k: x_ref[pl.ds(k * seg, seg), :])
           for k in range(n_segs)])
    _emit_outputs(dist_ref, rej_ref, segs_ref, acc, alive, nseg)


def _skipdma_kernel(q_ref, x_hbm, thr_ref, alpha_ref, beta_ref, margin_ref,
                    dist_ref, rej_ref, segs_ref, acc, alive, nseg, buf, sem,
                    *, metric: str, seg: int, n_segs: int):
    b, lanes = pl.program_id(0), _lanes(buf.shape[1])
    score = _scorer(q_ref, thr_ref, alpha_ref, beta_ref, margin_ref, acc,
                    alive, nseg, metric=metric, seg=seg, n_segs=n_segs)

    def fetch(k):
        # the burst stream for this feature block is issued only while the
        # tile is live — this is the skip_dma contract
        dma = pltpu.make_async_copy(x_hbm.at[b, pl.ds(k * seg, seg), lanes],
                                    buf, sem)
        dma.start()
        dma.wait()
        return buf[:, :]

    score([(k, functools.partial(fetch, k)) for k in range(n_segs)])
    _emit_outputs(dist_ref, rej_ref, segs_ref, acc, alive, nseg)


@functools.lru_cache(maxsize=None)
def _f32_fn(seg: int, metric: str, tile_c: int, interpret: bool,
            skip_dma: bool):
    def batched(q, x, thr, alpha, beta, margin):
        xt = _feature_major(x, tile_c)                      # (Q, D, Cp)
        if skip_dma:
            kern = functools.partial(_skipdma_kernel, metric=metric)
            x_spec = pl.BlockSpec(memory_space=pl.ANY)
            scratch = [pltpu.VMEM((seg, tile_c), jnp.float32),  # landing buf
                       pltpu.SemaphoreType.DMA]
        else:
            kern = functools.partial(_kernel, metric=metric)
            x_spec = pl.BlockSpec((None, xt.shape[1], tile_c),
                                  lambda b, i: (b, 0, i))
            scratch = []
        return _fee_call(kern, q, [xt], [x_spec], thr, alpha, beta, margin,
                         seg=seg, tile_c=tile_c, scratch=scratch,
                         interpret=interpret)
    return _one_query(batched, 3)


@functools.partial(jax.jit, static_argnames=("seg", "metric", "tile_c", "interpret"))
def fee_distance_pallas(q, x, threshold, alpha, beta, margin, *,
                        seg: int, metric: str = "l2", tile_c: int = 128,
                        interpret: bool = True):
    """q (D,), x (C, D) -> (dist (C,), rejected (C,) bool, segs_used (C,)).

    ``dist`` is the exact full score for survivors and the partial
    accumulated score for rejected lanes (unused by the search, matching the
    hardware which stops the burst stream on exit).
    """
    c = x.shape[0]
    fn = _f32_fn(seg, metric, tile_c, interpret, False)
    return tuple(o[:c] for o in fn(q, x, threshold, alpha, beta, margin))


@functools.partial(jax.jit, static_argnames=("seg", "metric", "tile_c", "interpret"))
def fee_distance_skipdma_pallas(q, x, threshold, alpha, beta, margin, *,
                                seg: int, metric: str = "l2", tile_c: int = 128,
                                interpret: bool = True):
    """Same contract as :func:`fee_distance_pallas`, but ``x`` stays in HBM and
    feature blocks are fetched with manual async copies inside the tile-exit
    gate: a fully-exited tile issues no DMA from the next group of
    ``GATE_BLOCKS`` blocks on, so the remaining bursts are never read."""
    c = x.shape[0]
    fn = _f32_fn(seg, metric, tile_c, interpret, True)
    return tuple(o[:c] for o in fn(q, x, threshold, alpha, beta, margin))


# ---------------------------------------------------------------------------
# packed-input variants: dfloat_unpack fused into the FEE datapath
# ---------------------------------------------------------------------------


def _block_positions(cfg: dfl.DfloatConfig, seg: int):
    """Per-FEE-block static decode positions and word ranges.

    Returns ``blocks[k] = (positions, w0, w1)``: ``positions`` is the
    (word, bit-offset, segment) list of the block's features, ``[w0, w1)`` the
    word span that covers them (including the carry word of fields that span
    a 32-bit word boundary — never a burst boundary, by layout rule 1),
    widened to whole sublane tiles for the manual DMAs.
    """
    pos, w_words = dfl.feature_positions(cfg)
    d = cfg.dim
    assert d % seg == 0, (d, seg)
    blocks = []
    for k in range(d // seg):
        p = pos[k * seg : (k + 1) * seg]
        hi = max(wi + (1 if ofs + s.width > 32 else 0) for wi, ofs, s in p)
        w0, w1 = _word_span(min(wi for wi, _, _ in p), hi + 1, w_words)
        blocks.append((tuple(p), w0, w1))
    return blocks, w_words


def _decoded(src, positions, w0: int, dec):
    """Decode one block's fields from ``src`` (row ``wi - w0`` holds word
    ``wi``) into ``dec`` and return them."""
    decode_rows(src, positions, w0, dec)
    return dec[:, :]


def _fetch_decode(src_hbm, b, lanes, positions, w0: int, w1: int, buf, sem,
                  dec):
    """Manual DMA of one block's word span of (query ``b``, tile ``lanes``)
    from HBM, then decode it into ``dec``."""
    dma = pltpu.make_async_copy(src_hbm.at[b, pl.ds(w0, w1 - w0), lanes],
                                buf.at[pl.ds(0, w1 - w0), :], sem)
    dma.start()
    dma.wait()
    return _decoded(buf, positions, w0, dec)


def _packed_kernel(q_ref, xp_ref, thr_ref, alpha_ref, beta_ref, margin_ref,
                   dist_ref, rej_ref, segs_ref, acc, alive, nseg, dec,
                   *, metric: str, seg: int, n_segs: int, blocks):
    score = _scorer(q_ref, thr_ref, alpha_ref, beta_ref, margin_ref, acc,
                    alive, nseg, metric=metric, seg=seg, n_segs=n_segs)
    score([(k, functools.partial(_decoded, xp_ref, positions, 0, dec))
           for k, (positions, _w0, _w1) in enumerate(blocks)])
    _emit_outputs(dist_ref, rej_ref, segs_ref, acc, alive, nseg)


def _packed_skipdma_kernel(q_ref, xp_hbm, thr_ref, alpha_ref, beta_ref,
                           margin_ref, dist_ref, rej_ref, segs_ref,
                           acc, alive, nseg, dec, buf, sem,
                           *, metric: str, seg: int, n_segs: int, blocks):
    b, lanes = pl.program_id(0), _lanes(buf.shape[1])
    score = _scorer(q_ref, thr_ref, alpha_ref, beta_ref, margin_ref, acc,
                    alive, nseg, metric=metric, seg=seg, n_segs=n_segs)
    score([(k, functools.partial(_fetch_decode, xp_hbm, b, lanes, positions,
                                 w0, w1, buf, sem, dec))
           for k, (positions, w0, w1) in enumerate(blocks)])
    _emit_outputs(dist_ref, rej_ref, segs_ref, acc, alive, nseg)


def _resid_copies(blocks):
    """Split the residual words the blocks read into disjoint copies, in
    block order: copy ``c`` covers words ``[w0, w1)`` that no earlier block
    reads.  Returns the copies and, per block, the copy it is the first to
    need (``None`` when earlier copies already hold all of its words)."""
    copies, first_need, end = [], [], 0
    for _, w0, w1 in blocks:
        if w1 > end:
            first_need.append(len(copies))
            copies.append((max(w0, end), w1))
            end = w1
        else:
            first_need.append(None)
    return tuple(copies), tuple(first_need)


def _tiered_kernel(q_ref, xc_ref, xr_hbm, thr_ref, alpha_ref, beta_ref,
                   margin_ref, dist_ref, rej_ref, segs_ref,
                   acc, alive, nseg, dec, buf, sem,
                   *, metric: str, seg: int, n_segs: int, c_blocks, r_blocks):
    """Two-tier fused decode+FEE: resident coarse blocks + gated residual DMA.

    Blocks ``k < len(c_blocks)`` decode from the VMEM-resident coarse-tier
    tile (the hot prefix that makes the exit decision).  At the tier boundary
    a tile that still has a live lane starts every residual copy at once
    (disjoint word spans, one semaphore each, landing at their own rows of
    ``buf``); each residual block then waits only for the copy that brings
    its last words, so the fetch overlaps the decode of the blocks before
    it.  A tile whose lanes all exited inside the coarse tier starts no
    copy; one that dies inside the residual tier stops decoding but still
    waits for the copies it started.
    """
    b, lanes = pl.program_id(0), _lanes(buf.shape[1])
    score = _scorer(q_ref, thr_ref, alpha_ref, beta_ref, margin_ref, acc,
                    alive, nseg, metric=metric, seg=seg, n_segs=n_segs)
    score([(k, functools.partial(_decoded, xc_ref, positions, 0, dec))
           for k, (positions, _w0, _w1) in enumerate(c_blocks)])

    spans, first_need = _resid_copies(r_blocks)
    copies = [pltpu.make_async_copy(xr_hbm.at[b, pl.ds(w0, w1 - w0), lanes],
                                    buf.at[pl.ds(w0, w1 - w0), :], sem.at[c])
              for c, (w0, w1) in enumerate(spans)]
    fetch = _tile_alive(alive)

    @pl.when(fetch)
    def _start():
        for dma in copies:
            dma.start()

    def wait(group):
        # a started copy is waited for whether or not the tile is still live
        need = [copies[c] for k, _ in group
                if (c := first_need[k - n_coarse]) is not None]
        if need:
            @pl.when(fetch)
            def _wait():
                for dma in need:
                    dma.wait()

    n_coarse = len(c_blocks)
    score([(n_coarse + j, functools.partial(_decoded, buf, positions, 0, dec))
           for j, (positions, _w0, _w1) in enumerate(r_blocks)], before=wait)
    _emit_outputs(dist_ref, rej_ref, segs_ref, acc, alive, nseg)


def _landing_buf(blocks, tile_c: int):
    return pltpu.VMEM((max(w1 - w0 for _, w0, w1 in blocks), tile_c),
                      jnp.uint32)


@functools.lru_cache(maxsize=None)
def _packed_fn(cfg: dfl.DfloatConfig, seg: int, metric: str, tile_c: int,
               interpret: bool, skip_dma: bool):
    blocks, w = _block_positions(cfg, seg)
    common = dict(metric=metric, blocks=tuple(blocks))
    dec = pltpu.VMEM((seg, tile_c), jnp.float32)            # decoded block

    def batched(q, xp, thr, alpha, beta, margin):
        assert xp.shape[2] == w, (xp.shape, w)
        if skip_dma:
            kern = functools.partial(_packed_skipdma_kernel, **common)
            xp_spec = pl.BlockSpec(memory_space=pl.ANY)
            scratch = [dec, _landing_buf(blocks, tile_c),
                       pltpu.SemaphoreType.DMA]
        else:
            kern = functools.partial(_packed_kernel, **common)
            xp_spec = pl.BlockSpec((None, w, tile_c), lambda b, i: (b, 0, i))
            scratch = [dec]
        return _fee_call(kern, q, [_feature_major(xp, tile_c)], [xp_spec],
                         thr, alpha, beta, margin, seg=seg, tile_c=tile_c,
                         scratch=scratch, interpret=interpret)
    return _one_query(batched, 3)


@functools.lru_cache(maxsize=None)
def _tiered_fn(coarse_cfg: dfl.DfloatConfig, resid_cfg: dfl.DfloatConfig,
               seg: int, metric: str, tile_c: int, interpret: bool):
    c_blocks, wc = _block_positions(coarse_cfg, seg)
    r_blocks, wr = _block_positions(resid_cfg, seg)
    n_copies = len(_resid_copies(r_blocks)[0])
    kern = functools.partial(_tiered_kernel, metric=metric,
                             c_blocks=tuple(c_blocks), r_blocks=tuple(r_blocks))

    def batched(q, xc, xr, thr, alpha, beta, margin):
        assert xc.shape[2] == wc and xr.shape[2] == wr, (xc.shape, xr.shape)
        x_specs = [
            pl.BlockSpec((None, wc, tile_c), lambda b, i: (b, 0, i)),  # coarse
            pl.BlockSpec(memory_space=pl.ANY),                  # resid (HBM)
        ]
        scratch = [pltpu.VMEM((seg, tile_c), jnp.float32),         # decoded
                   pltpu.VMEM((wr, tile_c), jnp.uint32),   # residual words
                   pltpu.SemaphoreType.DMA((n_copies,))]
        return _fee_call(kern, q, [_feature_major(xc, tile_c),
                                   _feature_major(xr, tile_c)], x_specs,
                         thr, alpha, beta, margin, seg=seg, tile_c=tile_c,
                         scratch=scratch, interpret=interpret)
    return _one_query(batched, 4)


@functools.partial(jax.jit, static_argnames=("coarse_cfg", "resid_cfg", "seg",
                                             "metric", "tile_c", "interpret"))
def fee_distance_tiered_pallas(q, xc, xr, threshold, alpha, beta, margin, *,
                               coarse_cfg: dfl.DfloatConfig,
                               resid_cfg: dfl.DfloatConfig, seg: int,
                               metric: str = "l2", tile_c: int = 128,
                               interpret: bool = True):
    """q (D,) f32, xc (C, Wc) / xr (C, Wr) packed uint32 tier rows ->
    (dist, rejected, segs_used).

    Same contract as :func:`fee_distance_packed_pallas` over the parent
    (unsplit) layout — ``dfloat.split_config`` preserves per-feature formats,
    so outputs are bit-identical for any split.  The coarse tier is streamed
    through the automatic BlockSpec pipeline (it is the resident payload);
    the residual tier stays in HBM and moves only through manual DMAs, per
    candidate tile: a tile with a live lane at the tier boundary fetches its
    whole residual row span, a tile whose lanes all exited inside the coarse
    tier fetches none of it.  Degenerate splits (one tier empty) collapse to
    the single-tier packed kernel on the non-empty bitstream.
    """
    if coarse_cfg.dim == 0:
        return fee_distance_packed_pallas(
            q, xr, threshold, alpha, beta, margin, dfloat_cfg=resid_cfg,
            seg=seg, metric=metric, tile_c=tile_c, interpret=interpret,
            skip_dma=True)
    if resid_cfg.dim == 0:
        return fee_distance_packed_pallas(
            q, xc, threshold, alpha, beta, margin, dfloat_cfg=coarse_cfg,
            seg=seg, metric=metric, tile_c=tile_c, interpret=interpret)
    c = xc.shape[0]
    fn = _tiered_fn(coarse_cfg, resid_cfg, seg, metric, tile_c, interpret)
    return tuple(o[:c] for o in fn(q, xc, xr, threshold, alpha, beta, margin))


@functools.partial(jax.jit, static_argnames=("dfloat_cfg", "seg", "metric",
                                             "tile_c", "interpret", "skip_dma"))
def fee_distance_packed_pallas(q, xp, threshold, alpha, beta, margin, *,
                               dfloat_cfg: dfl.DfloatConfig, seg: int,
                               metric: str = "l2", tile_c: int = 128,
                               interpret: bool = True, skip_dma: bool = False):
    """q (D,) f32, xp (C, W) packed uint32 -> (dist, rejected, segs_used).

    The Dfloat decode is fused into the FEE accumulate loop, so only packed
    bytes cross HBM; decoded features exist only in VMEM, one block at a time.
    Results are bit-compatible with ``fee_distance_pallas`` over
    ``dfloat.emulate_db`` data.  ``skip_dma=True`` keeps the bitstream in HBM
    and fetches each block's word span with a manual async copy inside the
    tile-exit gate — exited tiles skip the packed bursts of every later group
    of ``GATE_BLOCKS`` blocks.
    """
    assert dfloat_cfg.dim == q.shape[0], (dfloat_cfg.dim, q.shape)
    c = xp.shape[0]
    fn = _packed_fn(dfloat_cfg, seg, metric, tile_c, interpret, skip_dma)
    return tuple(o[:c] for o in fn(q, xp, threshold, alpha, beta, margin))

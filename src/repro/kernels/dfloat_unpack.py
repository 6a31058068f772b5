"""Pallas TPU kernel: Dfloat bitstream decode (the Dfloat process module,
paper Fig. 10d) — packed uint32 words -> f32 features.

Because the layout is burst-aligned (fields never straddle a 128-bit burst),
every field position within a row is static: for each feature, (word index,
bit offset) are compile-time constants (``dfloat.feature_positions``).  The
kernels work feature-major — candidates fill the 128 lanes and packed word
``w`` of every candidate in a tile is sublane row ``w`` — so a field decode
is a handful of static shifts/masks on lane-dense rows (the software
analogue of the preset offset register driving the barrel shifter).  The
unpack kernel decodes one local phase of a segment's bursts per strided row
op; the FEE kernels decode one block's fields row by row (``decode_rows``).

Grid: (C // TILE_C,); the whole packed tile (a few hundred bytes per
candidate) sits in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dfloat as dfl


def decode_rows(src, positions, w0: int, dst) -> None:
    """Decode fields from feature-major packed words into rows of ``dst``.

    ``src`` is a (words, TILE_C) uint32 ref whose row ``wi - w0`` holds word
    ``wi`` of every candidate; ``positions`` is a (word, bit-offset, segment)
    list (``dfloat.feature_positions``) and field ``j`` lands in row ``j`` of
    ``dst`` as f32.  All shifts and masks are static scalars.
    """
    words = {}

    def word(wi):
        if wi not in words:
            words[wi] = src[pl.ds(wi - w0, 1), :]
        return words[wi]

    for j, (wi, ofs, s) in enumerate(positions):
        v = word(wi) >> jnp.uint32(ofs)
        if ofs + s.width > 32:
            v = v | (word(wi + 1) << jnp.uint32(32 - ofs))
        fld = v & jnp.uint32((1 << s.width) - 1)
        dst[pl.ds(j, 1), :] = dfl.decode_field_jnp(fld, s.n_exp, s.n_man, s.bias)


def _kernel(p_ref, out_ref, *, layout, wpb: int):
    # one strided row op per (segment, local phase): phase l of every burst
    # of a segment sits at the same static word/bit offset, ``wpb`` words
    # apart, and lands every ``per``-th output row
    for s, word0, _nb, per in layout:
        for l in range(min(per, s.n_dims)):
            n = -(-(s.n_dims - l) // per)          # bursts that hold phase l
            bit = l * s.width
            wi, ofs = word0 + (bit >> 5), bit & 31
            v = p_ref[pl.ds(wi, n, stride=wpb), :] >> jnp.uint32(ofs)
            if ofs + s.width > 32:
                v = v | (p_ref[pl.ds(wi + 1, n, stride=wpb), :]
                         << jnp.uint32(32 - ofs))
            fld = v & jnp.uint32((1 << s.width) - 1)
            out_ref[pl.ds(s.start + l, n, stride=per), :] = \
                dfl.decode_field_jnp(fld, s.n_exp, s.n_man, s.bias)


@functools.partial(jax.jit, static_argnames=("cfg", "tile_c", "interpret"))
def dfloat_unpack_pallas(packed, cfg: dfl.DfloatConfig, *, tile_c: int = 128,
                         interpret: bool = True):
    """packed (C, W) uint32 -> (C, D) f32, bit-exact vs dfloat.unpack_db."""
    c, w = packed.shape
    layout, w_words = dfl.burst_layout(cfg)
    assert w == w_words, (w, w_words)
    pad_c = (-c) % tile_c
    packed_t = jnp.pad(packed.T, ((0, 0), (0, pad_c)))       # (W, Cp)
    cp = c + pad_c
    out = pl.pallas_call(
        functools.partial(_kernel, layout=tuple(layout),
                          wpb=cfg.burst_bits // 32),
        grid=(cp // tile_c,),
        in_specs=[pl.BlockSpec((w, tile_c), lambda i: (0, i))],
        out_specs=pl.BlockSpec((cfg.dim, tile_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((cfg.dim, cp), jnp.float32),
        interpret=interpret,
    )(packed_t)
    return out[:, :c].T

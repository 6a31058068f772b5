"""Tiered residual Dfloat: coarse-tier FEE with gated residual fetch.

Splitting the packed row at a segment boundary preserves every per-feature
Dfloat format, so tiered scoring must be *bit-identical* to packed-native
scoring at any split — the degenerate splits (0 = all-residual, n_segs =
all-coarse) are the sharpest version of that claim.  Beyond parity, the
tests pin the survivor-fetch invariant (an exited lane never pays residual
bytes), the tombstone/mutation interplay on all three backends, and the
format-v3 round-trip of tier-native artifacts.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import dfloat as dfl
from repro.index import Index, IndexSpec, SearchParams

PARAMS = SearchParams(ef=48, k=10, use_dfloat=True, storage="tiered")
PACKED = dataclasses.replace(PARAMS, storage="packed")


def _build(db, tier_split=None, dfloat=0.80):
    return Index.build(db, IndexSpec.for_db(db, m=8, ef_fit=32,
                                            dfloat_recall_target=dfloat,
                                            tier_split=tier_split))


# ---------------------------------------------------------------------------
# bit parity with packed: degenerate and interior splits
# ---------------------------------------------------------------------------


def test_degenerate_splits_bit_identical_to_packed(unit_db, unit_index_dfloat):
    """tier_split=0 (everything residual) and tier_split=n_segs (everything
    coarse) must both reproduce packed-native ids and dists bitwise."""
    idx = unit_index_dfloat
    n_segs = idx.dim // idx.seg
    ref = idx.search(unit_db.queries, PACKED)
    for split in (0, n_segs):
        tiered = Index.build(
            unit_db, dataclasses.replace(idx.spec, tier_split=split))
        got = tiered.search(unit_db.queries, PARAMS)
        np.testing.assert_array_equal(got.ids, ref.ids, err_msg=f"split={split}")
        np.testing.assert_array_equal(got.dists, ref.dists,
                                      err_msg=f"split={split}")


def test_all_interior_splits_bit_identical_to_packed(unit_db,
                                                     unit_index_dfloat):
    """split_config preserves per-feature formats, so parity holds at every
    interior split too (same index, split chosen at search time via spec)."""
    idx = unit_index_dfloat
    n_segs = idx.dim // idx.seg
    ref = idx.search(unit_db.queries[:32], PACKED)
    for split in range(1, n_segs):
        tiered = Index.build(
            unit_db, dataclasses.replace(idx.spec, tier_split=split))
        got = tiered.search(unit_db.queries[:32], PARAMS)
        np.testing.assert_array_equal(got.ids, ref.ids, err_msg=f"split={split}")


# ---------------------------------------------------------------------------
# the tiered kernel against its oracle
# ---------------------------------------------------------------------------


def _tile_pattern_inputs(rng, c, d, split, tile_c, metric):
    """Candidates whose tiles exit in turn: every lane of the first tile
    inside the coarse tier, of the second inside the residual tier (past
    the first exit-check group there), and none of the rest.  The query is
    zero under l2 and all -1 under ip, so that a lane's score grows with
    its features either way."""
    x = rng.uniform(0.05, 0.15, (c, d)).astype(np.float32)
    x[:tile_c, :split] += 10.0
    x[tile_c : 2 * tile_c, split:] += 1.0
    q = np.full(d, 0.0 if metric == "l2" else -1.0, np.float32)
    return x, q, np.float32(100.0)


@pytest.mark.parametrize("case,metric", [
    pytest.param(case, metric, id=case if metric == "l2" else f"{case}-ip")
    for metric in ("l2", "ip")
    for case in ("interior_split", "ragged_tiles", "tile_exit_pattern")])
def test_tiered_kernel_matches_ref(case, metric):
    import jax.numpy as jnp

    from repro.kernels import ref as ref_ops
    from repro.kernels.fee_distance import (GATE_BLOCKS,
                                            fee_distance_tiered_pallas)

    seg = 16
    c, d, split, tile_c = {"interior_split": (40, 128, 48, 128),
                           "ragged_tiles": (150, 192, 64, 64),
                           "tile_exit_pattern": (91, 256, 32, 32)}[case]
    n_segs, n_coarse = d // seg, split // seg
    rng = np.random.default_rng(d + c)
    if case == "tile_exit_pattern":
        x, q, thr = _tile_pattern_inputs(rng, c, d, split, tile_c, metric)
        alpha = beta = np.ones(n_segs, np.float32)
    else:
        # a PCA-like decaying spectrum, so that the leading blocks decide
        scale = (1.0 / np.arange(1, d + 1) ** 0.7).astype(np.float32)
        x = rng.standard_normal((c, d)).astype(np.float32) * scale
        q = x[0] + 0.3 * rng.standard_normal(d).astype(np.float32) * scale
        score = ((x - q) ** 2).sum(1) if metric == "l2" else -(x @ q)
        thr = np.float32(np.quantile(score, 0.3))
        alpha = (1.0 + 1.0 / np.arange(1, n_segs + 1)).astype(np.float32)
        beta = (1.0 + 0.2 / np.arange(1, n_segs + 1)).astype(np.float32)
    cfg = dfl.make_config(d, [(18, 6, d // 2), (12, 4, d // 2)], x)
    ccfg, rcfg = dfl.split_config(cfg, split)
    xc, xr = (jnp.asarray(a) for a in dfl.pack_tiers(x, cfg, split))
    args = (jnp.asarray(q), xc, xr, jnp.float32(thr), jnp.asarray(alpha),
            jnp.asarray(beta), jnp.zeros(n_segs, jnp.float32))
    kw = dict(coarse_cfg=ccfg, resid_cfg=rcfg, seg=seg, metric=metric)
    want = ref_ops.fee_distance_tiered_ref(*args, **kw)
    got = fee_distance_tiered_pallas(*args, tile_c=tile_c, **kw)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5, atol=2e-4)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))
    rej, segs = np.asarray(got[1]), np.asarray(got[2])
    if case == "tile_exit_pattern":
        assert rej[:tile_c].all() and (segs[:tile_c] <= n_coarse).all()
        mid = slice(tile_c, 2 * tile_c)
        assert rej[mid].all() and (segs[mid] > n_coarse).all()
        assert (segs[mid] < n_coarse + GATE_BLOCKS).all()
        assert not rej[2 * tile_c :].any()
    else:
        assert rej.any() and not rej.all()


def test_recall_matches_packed_operating_point(unit_db, unit_index_dfloat):
    """At the bench operating point the tiered recall must sit within 0.1 pt
    of packed (it is in fact bit-identical ids, so the delta is exactly 0)."""
    from repro.data.synthetic import recall_at_k

    idx = _build(unit_db)          # auto tier_split
    q = unit_db.queries
    r_packed = recall_at_k(idx.search(q, PACKED).ids, unit_db.gt, 10)
    r_tiered = recall_at_k(idx.search(q, PARAMS).ids, unit_db.gt, 10)
    assert abs(r_tiered - r_packed) <= 0.001


def test_auto_split_is_interior(unit_index_dfloat):
    n_segs = unit_index_dfloat.dim // unit_index_dfloat.seg
    assert 1 <= unit_index_dfloat.tier_split <= n_segs - 1


# ---------------------------------------------------------------------------
# survivor-fetch invariant: exited lanes never pay residual bytes
# ---------------------------------------------------------------------------


def test_survivor_fetch_counters(unit_db):
    """``n_resid`` counts exactly the evaluated lanes whose FEE sequence ran
    past the coarse tier: bounded by n_eval, zero at the all-coarse split,
    total at the all-residual split, and equal to the per-hop trace count of
    lanes with segs_used > tier_split in between."""
    q = unit_db.queries[:32]
    probe = _build(unit_db)
    n_segs = probe.dim // probe.seg
    for split, check in ((None, "mid"), (0, "all"), (n_segs, "none")):
        idx = probe if split is None else _build(unit_db, tier_split=split)
        out = idx.search(q, PARAMS)
        assert out.n_eval is not None and out.n_resid is not None
        assert (out.n_resid >= 0).all() and (out.n_resid <= out.n_eval).all()
        rf = out.residual_fetch_fraction
        if check == "none":
            assert rf == 0.0, "all-coarse split must never fetch residual"
        elif check == "all":
            assert rf == 1.0, "all-residual split fetches for every eval"
        else:
            assert 0.0 < rf < 1.0

        tr = idx.search(q, dataclasses.replace(PARAMS, trace=True))
        # the traced per-hop segs agree with the counters: a lane fetched
        # residual iff its FEE sequence used more than tier_split segments
        # (the trace zeroes segs on non-live lanes, so segs>0 <=> evaluated)
        segs = tr.trace["segs"]
        np.testing.assert_array_equal(
            tr.n_resid, (segs > idx.tier_split).sum(axis=(1, 2)))
        np.testing.assert_array_equal(tr.n_eval, (segs > 0).sum(axis=(1, 2)))


def test_tier_bytes_below_packed(unit_db):
    """The gather-bytes model: coarse-everywhere + residual-for-survivors is
    strictly below packed whenever any lane exits within the coarse tier."""
    idx = _build(unit_db)
    out = idx.search(unit_db.queries, PARAMS)
    ccfg, rcfg = idx.tier_cfgs()
    pb = idx.dfloat_cfg.packed_row_bytes()
    assert ccfg.packed_row_bytes() + rcfg.packed_row_bytes() == pb
    n_eval = float(out.n_eval.sum())
    n_resid = float(out.n_resid.sum())
    tiered_bytes = n_eval * ccfg.packed_row_bytes() \
        + n_resid * rcfg.packed_row_bytes()
    assert tiered_bytes < n_eval * pb


# ---------------------------------------------------------------------------
# mutation / tombstone interplay on all three backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["local", "sharded", "ndpsim"])
def test_mutated_index_no_tombstone_leaks(unit_db, backend):
    """Append + delete under storage="tiered": appended rows pack both tiers,
    deleted rows are masked before any residual fetch — no deleted id may
    surface from any backend."""
    from repro.streaming import MutableIndex

    idx = _build(unit_db, tier_split=1)
    mi = MutableIndex(idx, ef_build=48)
    rng = np.random.default_rng(0)
    new = unit_db.vectors[rng.integers(0, unit_db.n, mi.sub_batch)] \
        + 0.05 * rng.standard_normal((mi.sub_batch, unit_db.dim)) \
        .astype(np.float32)
    mi.append(new.astype(np.float32))
    dels = rng.choice(unit_db.n, 40, replace=False)
    mi.delete(dels)
    frozen = mi.freeze()
    q = unit_db.queries[:16]
    kw = {}
    if backend == "sharded":
        kw["mesh"] = jax.make_mesh((1, 1), ("data", "model"))
    out = frozen.searcher(backend, PARAMS, **kw)(q)
    assert not np.isin(out.ids, dels).any(), backend
    # appended rows are reachable through the tiered path
    out2 = frozen.searcher("local", PARAMS)(np.asarray(new[:4]))
    appended = np.arange(unit_db.n, unit_db.n + mi.sub_batch)
    assert np.isin(out2.ids, appended).any()


def test_streaming_tiers_match_repack(unit_db):
    """The incrementally-maintained tier arrays of a mutated index must be
    bit-identical to packing the frozen rotated DB from scratch."""
    from repro.streaming import MutableIndex

    idx = _build(unit_db, tier_split=1)
    mi = MutableIndex(idx, ef_build=48)
    rng = np.random.default_rng(1)
    mi.append(rng.standard_normal((mi.sub_batch, unit_db.dim))
              .astype(np.float32))
    frozen = mi.freeze()
    xc, xr = frozen.tier_arrays()
    want_c, want_r = dfl.pack_tiers(frozen.db_rot, frozen.dfloat_cfg,
                                    frozen.tier_split * frozen.seg)
    np.testing.assert_array_equal(xc, want_c)
    np.testing.assert_array_equal(xr, want_r)


# ---------------------------------------------------------------------------
# persistence: format v3 round-trips tier-native artifacts
# ---------------------------------------------------------------------------


def test_save_load_roundtrip_tiered(unit_db, tmp_path):
    idx = _build(unit_db, tier_split=2)
    path = idx.save(tmp_path / "tiered.naszip")
    meta = json.loads((path / "spec.json").read_text())
    assert meta["format_version"] == 3
    assert meta["tier_split"] == 2
    with np.load(path / "arrays.npz") as z:
        assert "db_coarse" in z.files and "db_resid" in z.files

    loaded = Index.load(path)
    for a, b in zip(loaded.tier_arrays(), idx.tier_arrays()):
        np.testing.assert_array_equal(a, b)
    ref = idx.search(unit_db.queries[:16], PARAMS)
    got = loaded.search(unit_db.queries[:16], PARAMS)
    np.testing.assert_array_equal(got.ids, ref.ids)


def test_save_without_tier_split_omits_tiers(unit_index_dfloat, tmp_path):
    """spec.tier_split=None keeps the artifact tier-free (tiers re-derive
    lazily from db_rot on demand)."""
    path = unit_index_dfloat.save(tmp_path / "plain.naszip")
    with np.load(path / "arrays.npz") as z:
        assert "db_coarse" not in z.files


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------


def test_unknown_storage_names_valid_set():
    with pytest.raises(ValueError) as ei:
        SearchParams(storage="tierd")
    msg = str(ei.value)
    for name in ("f32", "packed", "tiered"):
        assert name in msg


def test_tiered_requires_dfloat():
    with pytest.raises(ValueError):
        SearchParams(storage="tiered", use_dfloat=False)


def test_out_of_range_tier_split_rejected(unit_db):
    idx = _build(unit_db, tier_split=None)
    bad = Index.build(unit_db, dataclasses.replace(idx.spec, tier_split=99))
    with pytest.raises(ValueError):
        bad.tier_split

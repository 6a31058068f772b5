"""Compile rehearsal for the TPU: every Pallas kernel ``kernels/ops.py`` can
dispatch to is compiled — not run — for one chip of a described v5e:2x2
topology, at the real widths of the sift/bigann (D=128) and gist (D=960)
shapes, vmapped over a query batch as the search loop calls it.  The whole
local search program is compiled once per storage and metric as well.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, unsupported in-kernel layout
casts, VMEM overflow.  These tests can, with no chip attached.

The compiled search program also keeps the stage scopes a device trace is
attributed by (``hop.*``, ``search.init``, ``descent.level``) and the FEE
kernel instruction name the benchmark's trace reduction matches.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and it keeps
it until it exits.  Keep every such test in this one file.
"""
import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import dfloat as dfl
from repro.core import search as search_mod
from repro.core.fee import FeeParams
from repro.kernels import fee_distance as fd
from repro.kernels import ops as kops
from repro.kernels.dfloat_unpack import dfloat_unpack_pallas

SEG = 16
QUERIES = 32                  # the largest serving batch bucket
LANES = search_mod.compact_width(16, 4)    # frontier lanes per hop at M=16
# Dfloat layouts of the shapes Algorithm 1 picks: several widths per row,
# fields that straddle 32-bit words (21, 18 and 14 bits)
LAYOUTS = {128: [(21, 6, 48), (14, 5, 80)],
           960: [(18, 6, 320), (14, 5, 320), (12, 4, 320)]}
# jax.named_scope stages of one hop and of the search's set-up
SEARCH_SCOPES = ("search.init", "hop.frontier", "hop.gather", "hop.score",
                 "hop.merge")
HOP_SCOPES = SEARCH_SCOPES[1:]
TRACE_REDUCE = Path(__file__).resolve().parents[1] / "bench" / "trace_reduce.py"
STORAGES = ("f32", "packed", "tiered")
# every storage under each metric; the l2 cases keep their plain ids
STORE_METRIC = [*(pytest.param(s, "l2", id=s) for s in STORAGES),
                *(pytest.param(s, "ip", id=f"{s}-ip") for s in STORAGES)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _dfloat(d):
    cfg = dfl.make_config(d, LAYOUTS[d])
    return cfg, dfl.split_config(cfg, d // 2)


def _kernel_case(variant, d, metric):
    """(single-query kernel fn, candidate operand shapes/dtypes)."""
    cfg, (ccfg, rcfg) = _dfloat(d)
    w = dfl.packed_words(cfg)
    kw = dict(seg=SEG, metric=metric, interpret=False)
    if variant == "f32":
        return (lambda q, x, *f: fd.fee_distance_pallas(q, x, *f, **kw),
                [((LANES, d), jnp.float32)])
    if variant == "f32_skip_dma":
        return (lambda q, x, *f: fd.fee_distance_skipdma_pallas(q, x, *f, **kw),
                [((LANES, d), jnp.float32)])
    if variant in ("packed", "packed_skip_dma"):
        skip = variant == "packed_skip_dma"
        return (lambda q, x, *f: fd.fee_distance_packed_pallas(
                    q, x, *f, dfloat_cfg=cfg, skip_dma=skip, **kw),
                [((LANES, w), jnp.uint32)])
    assert variant == "tiered"
    return (lambda q, xc, xr, *f: fd.fee_distance_tiered_pallas(
                q, xc, xr, *f, coarse_cfg=ccfg, resid_cfg=rcfg, **kw),
            [((LANES, dfl.packed_words(ccfg)), jnp.uint32),
             ((LANES, dfl.packed_words(rcfg)), jnp.uint32)])


@pytest.mark.parametrize("d", sorted(LAYOUTS))
@pytest.mark.parametrize("variant,metric", [
    *(pytest.param(v, "l2", id=v) for v in ("f32", "f32_skip_dma", "packed",
                                            "packed_skip_dma", "tiered")),
    *(pytest.param(v, "ip", id=f"{v}-ip") for v in STORAGES)])
def test_fee_kernel_compiles_for_tpu(one_chip, variant, metric, d):
    fn, xs = _kernel_case(variant, d, metric)
    n_segs = d // SEG

    def batch(q, *args):
        # threshold per query, FEE parameters shared (as in _search_batch)
        *cands, thr, alpha, beta, margin = args
        return jax.vmap(fn, in_axes=(0,) * (len(cands) + 2) + (None,) * 3)(
            q, *cands, thr, alpha, beta, margin)

    args = [_sds(one_chip, (QUERIES, d), jnp.float32),
            *(_sds(one_chip, (QUERIES, *s), t) for s, t in xs),
            _sds(one_chip, (QUERIES,), jnp.float32),
            *(_sds(one_chip, (n_segs,), jnp.float32) for _ in range(3))]
    compiled = jax.jit(batch).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", sorted(LAYOUTS))
def test_dfloat_unpack_compiles_for_tpu(one_chip, d):
    cfg, _ = _dfloat(d)
    fn = jax.vmap(lambda p: dfloat_unpack_pallas(p, cfg, interpret=False))
    compiled = jax.jit(fn).lower(
        _sds(one_chip, (QUERIES, LANES, dfl.packed_words(cfg)), jnp.uint32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_search(one_chip, storage, metric):
    """The whole jitted local search (vmap over queries of the hop
    while_loop) with ``fee_backend="auto"`` dispatching to the kernels,
    compiled for one chip; returns the compiled HLO text."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "_on_tpu", lambda: True)
        return _lower_search(one_chip, storage, metric).compile().as_text()


@pytest.fixture(scope="module")
def search_hlo(one_chip):
    """``(storage, metric) -> compiled HLO text``, each pair compiled once."""
    cache = {}

    def get(storage, metric):
        if (storage, metric) not in cache:
            cache[storage, metric] = _compile_search(one_chip, storage, metric)
        return cache[storage, metric]

    return get


def _scoped(hlo: str, scope: str) -> bool:
    """Some instruction's ``op_name`` holds ``scope`` as a path element
    (``jit(f)/vmap(hop.score)/...`` under vmap)."""
    pattern = r'op_name="[^"]*[/(]' + re.escape(scope) + r'[/)]'
    return re.search(pattern, hlo) is not None


def _dims(text: str, key: str) -> list[int]:
    m = re.search(key + r"=\{([\d,]*)\}", text)
    return [int(v) for v in m.group(1).split(",") if v] if m else []


def _element_ops(hlo: str) -> list[tuple[str, list[str]]]:
    """Every gather whose slice sizes are all 1, and every scatter whose
    update window is all 1 (the TPU runs these one index at a time), with
    the ``op_name``s that speak for it: its own and, inside a fused
    computation, those of that computation and of the fusions calling it."""
    shapes, names_in, callers, found, comp = {}, {}, {}, [], None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        inst = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", line)
        if not inst:
            continue
        shapes[inst.group(1)] = [int(v) for v in inst.group(2).split(",") if v]
        names = re.findall(r'op_name="([^"]*)"', line)
        names_in.setdefault(comp, []).extend(names)
        for callee in re.findall(r"calls=%([\w.\-]+)", line):
            callers.setdefault(callee, []).extend(names)
        if " gather(" in line:
            window = _dims(line, "slice_sizes")
        elif " scatter(" in line:
            operands = re.findall(r"%([\w.\-]+)",
                                  line.split(" scatter(", 1)[1].split(")")[0])
            update = shapes[operands[-1]]
            window = [update[d] for d in _dims(line, "update_window_dims")]
        else:
            continue
        if all(v == 1 for v in window):
            found.append((inst.group(1), comp, names))
    return [(op, names + (names_in[comp] + callers[comp]
                          if comp in callers else []))
            for op, comp, names in found]


@pytest.mark.parametrize("storage,metric", STORE_METRIC)
def test_hop_body_has_no_element_gathers(search_hlo, storage, metric):
    """No gather or scatter of single elements in the hop's stages: beam
    merge, compaction, frontier pop and the visited bitmap run as vector
    work (one-hot selects, 128-word bitmap rows); row gathers pass."""
    hop_ops = [(op, names) for op, names in _element_ops(
                   search_hlo(storage, metric))
               if any(_scoped(f'op_name="{n}"', scope) for n in names
                      for scope in HOP_SCOPES)]
    assert not hop_ops, hop_ops


@pytest.mark.parametrize("storage,metric", STORE_METRIC)
def test_search_program_compiles_for_tpu(search_hlo, storage, metric):
    """The whole jitted local search (vmap over queries of the hop
    while_loop) with ``fee_backend="auto"`` dispatching to the kernels."""
    assert "tpu_custom_call" in search_hlo(storage, metric)


@pytest.mark.parametrize("storage,metric", STORE_METRIC)
def test_search_program_carries_stage_scopes(search_hlo, storage, metric):
    """The compiled program's metadata names each hop stage, and its FEE
    kernel keeps the instruction name the trace reduction matches."""
    spec = importlib.util.spec_from_file_location("_bench_trace_reduce",
                                                  TRACE_REDUCE)
    trace_reduce = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = trace_reduce      # its dataclass looks it up
    spec.loader.exec_module(trace_reduce)
    hlo = search_hlo(storage, metric)
    for scope in SEARCH_SCOPES:
        assert _scoped(hlo, scope), scope
    assert any(trace_reduce.FEE_KERNEL.search(line.strip())
               for line in hlo.splitlines())


def test_descent_level_carries_its_scope(one_chip):
    """The one descent program, over the three upper levels of a 40,000-row
    sift graph (2,500, 156 and 24 rows, 20 neighbors each), compiles for
    the chip and names each level's greedy loop ``descent.level``."""
    d, m = 128, 20
    levels = tuple((_sds(one_chip, (n, d), jnp.float32),
                    _sds(one_chip, (n, m), jnp.int32),
                    _sds(one_chip, (n,), jnp.int32)) for n in (24, 156, 2500))
    compiled = search_mod._descend_levels.lower(
        levels, _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (QUERIES, d), jnp.float32), metric="l2").compile()
    assert _scoped(compiled.as_text(), "descent.level")


def _lower_search(one_chip, storage, metric):
    n, d, m = 4099, 128, 16
    cfg, tiers = _dfloat(d)
    if storage == "f32":
        vectors, dcfg = _sds(one_chip, (n, d), jnp.float32), None
    elif storage == "packed":
        vectors = _sds(one_chip, (n, dfl.packed_words(cfg)), jnp.uint32)
        dcfg = cfg
    else:
        vectors = tuple(_sds(one_chip, (n, dfl.packed_words(c)), jnp.uint32)
                        for c in tiers)
        dcfg = tiers
    seg_vec = _sds(one_chip, (d // SEG,), jnp.float32)
    return search_mod._search_batch.lower(
        vectors, _sds(one_chip, (n, m), jnp.int32),
        FeeParams(seg_vec, seg_vec, seg_vec), None,
        _sds(one_chip, (QUERIES, d), jnp.float32),
        _sds(one_chip, (QUERIES,), jnp.int32),
        cfg=search_mod.SearchConfig(ef=64, k=10, seg=SEG, use_fee=True,
                                    storage=storage, metric=metric),
        trace=False, dfl_cfg=dcfg)

"""The reduction from a profiler trace to device numbers."""
import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import roofline, trace_reduce


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def _plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert trace_reduce._union_ns(iv) == 26
    assert trace_reduce._gaps(iv, 0, 40) == [(15, 20), (31, 40)]


def test_reduce_profile_on_a_synthetic_trace():
    ops = [_ev("%while.7 = (s32[32,64]) while(...)", 0, 300),
           _ev("%fusion.1 = u32[2560] fusion(...)", 0, 100),
           _ev("%fee_distance_packed_pallas.3 = (f32[32,1,128], s32[32,1,128])"
               " custom-call(...)", 150, 100),
           _ev("%copy.2 = f32[32,960] copy(...)", 400, 100)]
    mods = [_ev("jit__search_batch(7)", 0, 300), _ev("jit__greedy_level(9)",
                                                    400, 100)]
    host = [_ev("PjitFunction(_greedy_level)", 200, 250)]
    pd = NS(planes=[_plane("/device:TPU:0", **{"XLA Ops": ops,
                                               "XLA Modules": mods}),
                    _plane("/host:CPU", python=host)])
    # while.7 [0, 300) holds fusion.1 and the kernel; idle gap [300, 400)
    r = trace_reduce.reduce_profile(pd, window_s=1e-6)
    assert r.busy_s == pytest.approx(400e-9)
    assert r.search_runs == 1 and r.search_s == pytest.approx(300e-9)
    assert r.fee_s == pytest.approx(100e-9)
    assert r.fee_calls == {32: 1}
    assert dict(r.breakdown["device_ops"]) == pytest.approx({
        "%while.7": 100e-9, "%fusion.1": 100e-9, "%copy.2": 100e-9,
        "%fee_distance_packed_pallas.3": 100e-9})
    assert r.breakdown["idle_gaps"] == [["PjitFunction(_greedy_level)",
                                         100e-9]]


def test_fee_bytes_from_shapes():
    cfg = {"dim": 128, "search": {"expand": 4, "compact": 0.5}}
    shapes = {"adj_width": 20, "row_words": 64}
    assert roofline.lanes(20, 4, 0.5) == 40
    assert roofline.fee_call_bytes(cfg, 32, shapes) == \
        32 * (4 * 128 + 40 * 256 + 4)


FIXTURE = Path(__file__).resolve().parent / "data" / "closed64_sift.xplane.pb.gz"


def test_reduce_a_trace_recorded_on_the_chip():
    """A 60 ms trace of sift128-packed.closed64 on one TPU v5 lite."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(gzip.decompress(FIXTURE.read_bytes()))
    r = trace_reduce.reduce_profile(pd, window_s=0.06)
    assert 0.0 < r.busy_s <= 0.06
    assert r.search_runs >= 1 and r.search_s > 0.0
    assert set(r.fee_calls) == {32} and r.fee_calls[32] >= 1
    assert 0.0 < r.fee_s < r.busy_s
    names = [name for name, _ in r.breakdown["device_ops"]]
    assert len(names) == 10 and all(n.startswith("%") for n in names)
    assert r.breakdown["idle_gaps"]

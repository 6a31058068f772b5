"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU each chip is a plane
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation that
ran, its ``XLA Modules`` line one per program execution.  This module takes
from it:

  busy_s       union of the operation intervals, averaged over the chips
  window_s     length of the traced window (host clock, start to stop)
  search_*     the ``_search_batch`` program: seconds and executions
  fee_*        the FEE kernels: seconds, and calls by batch size
  breakdown    the ten costliest operations, and the ten longest idle
               gaps named by what the host was doing in them

Everything is matched by name only: program names come from the jitted
function (``jit__search_batch(<id>)``), FEE kernel names from the jitted
entry point that wraps each ``pallas_call`` (``fee_distance_*_pallas``).
Operation events nest (a ``while`` op spans the operations of its body), so
busy time is the union of intervals and the breakdown ranks operations by
self time: duration less the operations nested inside.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SEARCH_PROGRAM = re.compile(r"_search_batch")
# the Pallas calls of kernels/fee_distance.py carry the name of the jitted
# entry point: "%fee_distance_tiered_pallas.6 = (f32[32,1,128]...) custom-call"
FEE_KERNEL = re.compile(r"^%?fee_distance\w*_pallas\b")


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    search_s: float
    search_runs: int
    fee_s: float
    fee_calls: dict           # batch -> calls
    breakdown: dict


def _union_ns(intervals: list) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals: list, lo: int, hi: int) -> list:
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _short(name: str) -> str:
    """``%fusion.74 = u32[2560] fusion(...)`` -> ``%fusion.74``."""
    return name.split(" = ", 1)[0][:120]


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _batch_of(name: str) -> int | None:
    """The query batch of one FEE call: the leading dimension of its first
    result, ``(f32[32,1,128]{...}, ...)``."""
    m = re.search(r" = \(\w+\[(\d+),", name)
    return int(m.group(1)) if m else None


def _self_ns(ops: list) -> dict:
    """Self time by short name: each event's duration less the events
    nested inside it (one line's events nest like a call stack)."""
    out, stack = {}, []          # [end, short name, nested ns, duration]
    for a, b, e in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            end, name, child, dur = stack.pop()
            out[name] = out.get(name, 0) + dur - child
        if stack:
            stack[-1][2] += b - a
        stack.append([b, _short(e.name), 0, b - a])
    for end, name, child, dur in stack:
        out[name] = out.get(name, 0) + dur - child
    return out


def reduce_profile(pd, window_s: float) -> Reduced:
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    busy, op_ns, mod_ns, mod_runs = [], {}, {}, {}
    fee_ns, fee_calls = 0, {}
    first_ops, host = None, []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e)
               for e in (lines["XLA Ops"].events if "XLA Ops" in lines else [])]
        busy.append(_union_ns([(a, b) for a, b, _ in ops]))
        if first_ops is None:
            first_ops = [(a, b) for a, b, _ in ops]
        for name, ns in _self_ns(ops).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        for a, b, e in ops:
            if FEE_KERNEL.search(e.name):
                fee_ns += b - a
                batch = _batch_of(e.name)
                fee_calls[batch] = fee_calls.get(batch, 0) + 1
        for e in (lines["XLA Modules"].events if "XLA Modules" in lines
                  else []):
            name = _base(e.name)
            mod_ns[name] = mod_ns.get(name, 0) + e.duration_ns
            mod_runs[name] = mod_runs.get(name, 0) + 1
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for ln in plane.lines:
                if ln.name == "python":      # the serving and client threads
                    host += [(e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in ln.events]
    search = [k for k in mod_ns if SEARCH_PROGRAM.search(k)]
    n = max(len(planes), 1)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(
        busy_s=sum(busy) / n / 1e9,
        window_s=window_s,
        search_s=sum(mod_ns[k] for k in search) / n / 1e9,
        search_runs=sum(mod_runs[k] for k in search) // n,
        fee_s=fee_ns / n / 1e9,
        fee_calls={b: c // n for b, c in fee_calls.items() if b is not None},
        breakdown={"device_ops": [[k, v / 1e9] for k, v in top_ops],
                   "idle_gaps": _idle_gaps(first_ops or [], host)})


def _idle_gaps(ops: list, host: list) -> list:
    """The ten longest device-idle gaps (first plane's clock), each named
    by the host event that overlaps it most."""
    if not ops:
        return []
    lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    gaps = sorted(_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:10]
    out = []
    for a, b in gaps:
        best, name = 0, "no host event"
        for s, e, nm in host:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, nm
        out.append([name, (b - a) / 1e9])
    return out


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def reduce_dir(trace_dir: Path, trace_t) -> Reduced:
    """Reduce the trace under ``trace_dir``; ``trace_t`` holds the host
    clock's (before start, after start, before stop, after stop)."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None or trace_t is None:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    return reduce_profile(ProfileData.from_file(str(path)),
                          trace_t[2] - trace_t[1])

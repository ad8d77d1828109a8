"""From a profiler trace (``*.xplane.pb``) to the numbers of the layers.

What a TPU v5e trace holds, as JAX 0.9's profiler writes it: one plane
per chip named ``/device:TPU:<n>``, whose ``XLA Ops`` line has one event
per executed HLO op and whose ``XLA Modules`` line has one event per
executed program (``jit_<function>(<id>)``); and a ``/host:CPU`` plane
with one line per host thread, which carries the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench.*``) on the same clock.

- A kernel op is a Mosaic custom call: an op whose HLO text names the
  ``tpu_custom_call`` target. It is matched by that, never by a name.
  On a v5e the op event's name is its HLO text (``%<op> = <shape>
  custom-call(...), custom_call_target="tpu_custom_call", ...``); its
  stats hold only times.
- An op belongs to the program whose module event contains it; programs
  whose function name starts with ``bench_`` are the benchmark's own.
- Busy time is the union of op intervals inside the window (the host
  span ``bench.window``), averaged over the chips that ran anything.
- An idle gap is a stretch of the window with no op on a chip; it is
  labelled with the benchmark span the host spent most of it in.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: HLO custom-call target of a Mosaic (Pallas) kernel.
MOSAIC_TARGET = "tpu_custom_call"
BENCH_PROGRAM = "jit_bench_"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    module: str
    start_ns: float
    end_ns: float
    kernel: bool


@dataclasses.dataclass
class Trace:
    """The parts of a trace the reduction reads: per chip its ops, and the
    host's ``bench.*`` spans as (name, start_ns, end_ns)."""
    ops: Dict[str, List[Op]]
    host_spans: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Reduction:
    chips: int          # device planes on which an op ran in the window
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_calls: int
    bench_s: float
    library_s: float
    device_ops: List[list]
    idle_gaps: List[list]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def is_mosaic(name: str, stats: Dict[str, object]) -> bool:
    """Whether an op event is a Mosaic custom call, from its HLO text: the
    event's name, or a string stat where a profiler puts it there."""
    return MOSAIC_TARGET in name or any(
        isinstance(v, str) and MOSAIC_TARGET in v for v in stats.values())


def load(path: str, prefix: str = "bench.") -> Trace:
    """Read ``path`` with JAX's own reader (``jax.profiler.ProfileData``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in lines.get(MODULES_LINE, []))
            out = []
            for ev in lines.get(OPS_LINE, []):
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                # the op's HLO name, without the rest of its HLO text
                out.append(Op(ev.name.split(" = ", 1)[0],
                              _module_of(mods, start, end), start, end,
                              is_mosaic(ev.name, _stats(ev))))
            ops[plane.name] = out
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(prefix):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(ops=ops, host_spans=spans)


def _module_of(mods, start, end) -> str:
    """The program (module event) whose interval holds [start, end]."""
    lo, hi = 0, len(mods)
    while lo < hi:                      # last module starting <= start
        mid = (lo + hi) // 2
        if mods[mid][0] <= start:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][1] >= end:
        return mods[lo - 1][2]
    return ""


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(spans, a, b, window: str) -> str:
    """The host span (other than the window) overlapping [a, b] most;
    among equals, the shortest (innermost)."""
    best, best_key = "host: no benchmark span", None
    for name, s, e in spans:
        if name == window:
            continue
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce_trace(trace: Trace, window: str = "bench.window") -> Reduction:
    wins = [(s, e) for n, s, e in trace.host_spans if n == window]
    if not wins:
        raise ValueError(f"the trace holds no {window!r} host span")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    busy, kernel_ns, calls, bench_ns, lib_ns = [], 0.0, 0, 0.0, 0.0
    per_op: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for ops in trace.ops.values():
        inside = []
        for op in ops:
            a, b = _clip(op.start_ns, op.end_ns, lo, hi)
            if b <= a:
                continue
            inside.append((a, b))
            d = b - a
            if op.kernel:
                kernel_ns += d
                calls += 1
            elif op.module.startswith(BENCH_PROGRAM):
                bench_ns += d
            else:
                lib_ns += d
            per_op[f"{op.module or '?'}/{op.name}"] += d
        if not inside:
            continue
        u = _union(inside)
        busy.append(sum(b - a for a, b in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps.extend((edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k])
    nchips = max(1, len(busy))
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Reduction(
        chips=len(busy),
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / nchips * 1e-9,
        kernel_s=kernel_ns / nchips * 1e-9,
        kernel_calls=calls // nchips,
        bench_s=bench_ns / nchips * 1e-9,
        library_s=lib_ns / nchips * 1e-9,
        device_ops=[[name, ns * 1e-9 / nchips] for name, ns in top_ops],
        idle_gaps=[[_label(trace.host_spans, a, b, window), (b - a) * 1e-9]
                   for a, b in top_gaps])


def reduce(path: str, window: str = "bench.window") -> Reduction:
    return reduce_trace(load(path), window=window)


def describe(path: str, per_line: int = 3) -> str:
    """A plain listing of a trace's planes, lines and a few events with
    their stats: what to read before changing the reduction."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name!r}")
        for ln in plane.lines:
            evs = list(ln.events)
            out.append(f"  LINE {ln.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                out.append(f"    {ev.name!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} stats={_stats(ev)}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1], per_line=int(sys.argv[2]) if len(sys.argv) > 2
                   else 3))

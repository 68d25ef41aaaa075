"""Reduce a traced window's ``.xplane.pb`` to what the benchmark reports.

Three things: the device's busy and idle time in the window, the device
operations that took most time, and the longest idle gaps of the device,
each named by what the host was doing in it.

The window is the host event named ``bench.window`` (a
``jax.profiler.TraceAnnotation`` the harness puts around its measured
window). Busy time is the union of the intervals of the operations on the
device's ``XLA Ops`` line, clipped to the window, averaged over the device
planes that ran anything. Host activity is every event on the host plane's
threads (JAX's own dispatch events, the harness's annotations) plus any
intervals the caller adds (the program's telemetry spans, mapped onto the
trace's clock).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_ID = re.compile(r"\(\d+\)$")
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns)


def _op_names(ops, modules):
    """Name each op ``<jitted program>:<HLO op>``: the program is the
    ``XLA Modules`` event the op starts in, without its fingerprint, and
    the op is the HLO instruction's name (``%fusion.3``)."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = MODULE_ID.sub("", modules[i][0]) if i >= 0 and s < modules[i][2] else "?"
        out.append((f"{mod}:{name.split(' = ')[0]}", s, e))
    return out


def read_planes(path: str):
    """(device op events per device plane, host events) from an xplane
    file: ``{plane: [(name, start_ns, end_ns)]}``, ``[(thread, name, start,
    end)]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ev for ln in plane.lines if ln.name == OPS_LINE for ev in _events(ln)]
            mods = [ev for ln in plane.lines if ln.name == MODULES_LINE for ev in _events(ln)]
            devices[plane.name] = _op_names(ops, mods)
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host += [(ln.name, n, s, e) for n, s, e in _events(ln)]
    return devices, host


def attribute(gap: Interval, host: Sequence[Tuple[str, str, float, float]],
              starts: Sequence[float], longest: float) -> str:
    """What the host was doing in a gap: the host event that overlaps it
    most (the shorter, more specific one on a tie)."""
    g0, g1 = gap
    best, key = "host: nothing traced", (0.0, 0.0)
    # events starting after the gap cannot overlap; those starting more
    # than the longest event's length before it cannot either
    lo = bisect.bisect_left(starts, g0 - longest)
    hi = bisect.bisect_right(starts, g1)
    for thread, name, s, e in host[lo:hi]:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0 or name == WINDOW:
            continue
        k = (ov, -(e - s))
        if k > key:
            best, key = name, k
    return best


def window_of(host: Sequence[Tuple[str, str, float, float]]) -> Interval:
    """The harness's ``bench.window`` annotation (the longest, if several)."""
    marks = [(s, e) for _t, n, s, e in host if n == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return max(marks, key=lambda m: m[1] - m[0])


def reduce(devices, host, extra_host: Sequence[Tuple[str, float, float]] = (),
           window: Optional[Interval] = None, top: int = 10) -> Dict:
    """The reduction of one traced window (see :func:`read_planes` for the
    inputs). ``extra_host`` adds named host intervals (trace nanoseconds)
    for gap attribution; ``window`` overrides the ``bench.window``
    annotation. Returns ``busy_s``, ``window_s``, ``idle_share``,
    ``device_ops`` and ``idle_gaps`` (lists of ``[name, seconds]``, longest
    first) and the window in trace nanoseconds."""
    w0, w1 = window if window is not None else window_of(host)
    host = list(host) + [("spans", n, s, e) for n, s, e in extra_host]
    host.sort(key=lambda h: h[2])
    starts = [h[2] for h in host]
    longest = max((e - s for _t, _n, s, e in host), default=0.0)

    op_time: Dict[str, float] = {}
    busy_per_dev, merged_per_dev = [], []
    for _plane, evs in sorted(devices.items()):
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]
        if not inside:
            continue
        for n, s, e in inside:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
        merged = union((s, e) for _n, s, e in inside)
        merged_per_dev.append(merged)
        busy_per_dev.append(sum(e - s for s, e in merged))
    window_ns = w1 - w0
    busy_ns = sum(busy_per_dev) / len(busy_per_dev) if busy_per_dev else 0.0
    # the idle gaps of the first device that ran anything
    first = merged_per_dev[0] if merged_per_dev else []
    idle = sorted(gaps(first, w0, w1), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_ns": (w0, w1),
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": (1.0 - busy_ns / window_ns) if window_ns > 0 else None,
        "devices": len(busy_per_dev),
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[attribute(g, host, starts, longest), (g[1] - g[0]) * 1e-9]
                      for g in idle],
    }


def reduce_trace(path: str, **kw) -> Dict:
    """:func:`reduce` of the ``.xplane.pb`` file at ``path``."""
    return reduce(*read_planes(path), **kw)

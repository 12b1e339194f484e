"""Reduction of the profiler's trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`read` keeps two kinds of events from it, on the trace's own clock
(nanoseconds):

* device ops: the events of each ``/device:...`` plane's ``XLA Ops``
  line;
* host spans: the ``bench:<layer>[:<label>]`` annotations the benchmark
  writes around its calls into each layer (:class:`bench.harness.Spans`).

Busy time is the union of a plane's op intervals; the idle share is one
minus busy over the traced window.  Device time inside a span is the part
of that union the span covers, so the work a span waited for counts once
however many ops overlap.

On a TPU the device plane's clock is offset from the host's by about a
millisecond (device ops appear to start before the host span that
launched them).  :func:`read` shifts each device plane onto the host clock
by the offset that puts the most device time inside the benchmark's host
spans, the middle of the range of offsets that do equally well: every
device op of a run is launched from inside one of them.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"


def find(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def read(path: str) -> Dict[str, object]:
    """``{"device": {plane: [(name, start_ns, end_ns), ...]},
    "spans": [(name, start_ns, end_ns), ...]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            # An op's name is its HLO instruction's: "%fusion.3 = ..." -> "fusion.3"
            evs = [(e.name.split(" = ")[0].lstrip("%"), float(e.start_ns),
                    float(e.end_ns))
                   for ln in lines if ln.name == OP_LINE
                   for e in ln.events if e.duration_ns > 0]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns)))
    spans.sort(key=lambda s: s[1])
    hosts = union([(a, b) for _, a, b in spans])
    for plane, evs in device.items():
        d = clock_offset(union([(a, b) for _, a, b in evs]), hosts)
        device[plane] = [(n, a - d, b - d) for n, a, b in evs]
    return {"device": device, "spans": spans}


def clock_offset(dev: Sequence[Interval], hosts: Sequence[Interval],
                 reach: float = 5e6, step: float = 1e4) -> float:
    """The offset (ns, device clock less host clock) within ``reach`` that
    puts the most of ``dev`` inside ``hosts``; the middle of the offsets
    within 0.1% of the best."""
    if not dev or not hosts:
        return 0.0
    hs = np.array([a for a, _ in hosts])
    lens = np.array([b - a for a, b in hosts])
    before = np.concatenate([[0.0], np.cumsum(lens)[:-1]])

    def covered_upto(t):
        i = np.searchsorted(hs, t, side="right") - 1
        j = np.maximum(i, 0)
        inside = before[j] + np.clip(t - hs[j], 0.0, lens[j])
        return np.where(i < 0, 0.0, inside)

    a = np.array([x for x, _ in dev])
    b = np.array([y for _, y in dev])
    grid = np.arange(-reach, reach + step, step)
    cov = np.array([(covered_upto(b - d) - covered_upto(a - d)).sum()
                    for d in grid])
    best = grid[cov >= cov.max() * (1 - 1e-3)]
    return float(np.median(best))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Cover:
    """Length of ``[a, b]`` covered by sorted disjoint intervals."""

    def __init__(self, merged: Sequence[Interval]):
        self.merged = list(merged)
        self.starts = [a for a, _ in self.merged]

    def __call__(self, a: float, b: float) -> float:
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        tot = 0.0
        m = self.merged
        while i < len(m) and m[i][0] < b:
            lo, hi = max(a, m[i][0]), min(b, m[i][1])
            if hi > lo:
                tot += hi - lo
            i += 1
        return tot


def busy_by_plane(events) -> Dict[str, List[Interval]]:
    return {p: union([(a, b) for _, a, b in evs])
            for p, evs in events["device"].items()}


def busy_and_window(events, t0: float, t1: float) -> Tuple[float, float]:
    """(busy seconds averaged over the device planes, window seconds)."""
    planes = busy_by_plane(events)
    window = float(t1 - t0)
    if not planes:
        return 0.0, window
    busy = sum(sum(b - a for a, b in iv) for iv in planes.values())
    return float(busy / len(planes) / 1e9), window


def span_device_time(events, layer: str) -> List[Tuple[str, float, float]]:
    """Each ``bench:<layer>[:<label>]`` span as (label, span seconds,
    device-busy seconds inside it), busy taken on the first device plane."""
    planes = busy_by_plane(events)
    if not planes:
        return []
    cover = Cover(planes[sorted(planes)[0]])
    head = SPAN_PREFIX + layer
    out = []
    for name, a, b in events["spans"]:
        if name == head or name.startswith(head + ":"):
            label = name[len(head) + 1:]
            out.append((label, (b - a) / 1e9, cover(a, b) / 1e9))
    return out


def breakdown(events, top: int = 10) -> Dict[str, List[List[object]]]:
    """The device ops that took most time, and the device's idle time by
    the innermost benchmark span open on the host (``host`` where none)."""
    planes = events["device"]
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    first = sorted(planes)[0]
    by_op: Dict[str, float] = defaultdict(float)
    for name, a, b in planes[first]:
        by_op[name] += (b - a) / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    merged = union([(a, b) for _, a, b in planes[first]])
    spans = events["spans"]
    idle: Dict[str, float] = defaultdict(float)
    j, open_ = 0, []
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid = 0.5 * (e0 + s1)
        while j < len(spans) and spans[j][1] <= mid:
            open_.append(spans[j])
            j += 1
        open_ = [s for s in open_ if s[2] >= mid]
        label = "host"
        if open_:
            name = min(open_, key=lambda s: s[2] - s[1])[0]
            parts = name[len(SPAN_PREFIX):].split(":")
            label = ":".join(p for p in parts if not p.isdigit())
        idle[label] += (s1 - e0) / 1e9
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}

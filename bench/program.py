"""The program's own spans (``repro.core.spans``) as the readers of one
traced run see them.

The program records a span at each of its layer boundaries while a
profiler session collects, on ``perf_counter_ns``.  :func:`spans` drains
the recorder once into the run's record, so every reader of the run sees
the same spans; a program that records none (it has no
``repro.core.spans``) gives every reader nothing to read.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from bench import xplane

Interval = Tuple[float, float]

# The device's busy time must lie inside the spans that launched it for
# the program's clock to count as aligned with the trace's.
MIN_COVER = 0.95


def spans(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"origin_ns", "spans", "dropped"}`` of the traced window, or None
    where the record has no traced window or the program no spans."""
    if not rec.get("traced_s"):
        return None
    if "program" not in rec:
        try:
            from repro.core import spans as recorder
        except ImportError:
            rec["program"] = None
        else:
            rec["program"] = recorder.take()
    prog = rec["program"]
    return prog if prog and prog["spans"] else None


def named(prog: Dict[str, Any], name: str) -> List[Any]:
    return [s for s in prog["spans"] if s.name == name]


def seconds(spans_: Sequence[Any]) -> float:
    return sum(s.t1_ns - s.t0_ns for s in spans_) / 1e9


def per_window_ms(rec: Dict[str, Any], name: str) -> Optional[float]:
    """Milliseconds of ``name`` spans per window the online analyzer
    consumed (``online.consume``)."""
    prog = spans(rec)
    if prog is None:
        return None
    windows, parts = named(prog, "online.consume"), named(prog, name)
    if not windows or not parts:
        return None
    return 1e3 * seconds(parts) / len(windows)


def ancestor(s: Any, name: str, by_id: Dict[int, Any]) -> Optional[Any]:
    """The nearest enclosing span of ``s`` called ``name``."""
    p = by_id.get(s.parent)
    while p is not None and p.name != name:
        p = by_id.get(p.parent)
    return p


def self_time(s: Any, children: Sequence[Any]) -> List[Interval]:
    """The parts of span ``s`` that none of its children covers."""
    out, cur = [], float(s.t0_ns)
    for a, b in xplane.union([(c.t0_ns, c.t1_ns) for c in children]):
        if a > cur:
            out.append((cur, float(a)))
        cur = max(cur, float(b))
    if s.t1_ns > cur:
        out.append((cur, float(s.t1_ns)))
    return out


def covered(busy: Sequence[Interval], hosts: Sequence[Interval],
            d: float) -> float:
    """Share of ``busy`` (device clock) inside ``hosts`` after shifting it
    by ``-d`` onto the hosts' clock."""
    total = sum(b - a for a, b in busy)
    if total <= 0:
        return 0.0
    cover = xplane.Cover(xplane.union(hosts))
    return sum(cover(a - d, b - d) for a, b in busy) / total


def bridged(intervals: Sequence[Interval], gap: float) -> List[Interval]:
    """Sorted disjoint ``intervals`` with the gaps under ``gap`` closed."""
    out: List[List[float]] = []
    for a, b in intervals:
        if out and a - out[-1][1] < gap:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def fit_offset(busy: Sequence[Interval], hosts: Sequence[Interval],
               reach: float = 2e9, coarse: float = 1e6, fine: float = 1e4
               ) -> Tuple[float, float]:
    """(offset ns, share covered): the offset of the device's clock from
    the hosts' that puts the most of ``busy`` (sorted, disjoint) inside
    ``hosts``.  ``xplane.clock_offset`` searches ``reach`` at a ``coarse``
    step and then around that at a ``fine`` one, each time over the busy
    intervals with the gaps under its step closed, which leaves the fit
    to the calls and keeps it fast over a trace of many small ops."""
    hosts = xplane.union(hosts)
    d0 = xplane.clock_offset(bridged(busy, coarse), hosts, reach=reach,
                             step=coarse)
    near = [(a - d0, b - d0) for a, b in bridged(busy, fine)]
    d = d0 + xplane.clock_offset(near, hosts, reach=4 * coarse, step=fine)
    return d, covered(busy, hosts, d)

"""Program spans, recorded while a JAX profiler session is collecting.

Tracing is on exactly while ``jax.profiler.start_trace`` (or
``jax.profiler.trace``) is collecting: an operator profiles a live
analyzer or serving run the way they profile any JAX program, and each
span then appears in the profiler's trace as a ``repro:<name>``
annotation, on the clock of the device ops, so every idle gap of the
device sits under the program span that was open.  With no session,
:func:`span` costs one ``is_enabled()`` call and records nothing.

Each span is also kept in memory as a :class:`Span` on
``time.perf_counter_ns``, its parent taken from a per-thread stack, for a
reader in the same process (:func:`take`).  Counts of the work a span did
(pairs compared, bytes read, tokens) are its attributes, set where the
work happens::

    with spans.span("spool.load") as s:
        trace = load(path)
        s.set(bytes=nbytes)

A callee that does not know which span its caller opened counts its work
into it with :func:`annotate` (``RegionTrace.save`` and ``load`` count
their stored and inflated bytes so).  A span never goes inside a
per-pair or per-element loop: count the loop's work and set it after the
loop.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "repro:"
# Spans kept between two takes; past it a span is counted as dropped.
CAP = 1 << 18


class Span(NamedTuple):
    name: str
    id: int
    parent: Optional[int]
    t0_ns: int
    t1_ns: int
    attrs: Dict[str, Any]


class _Off:
    """What :func:`span` returns with no profiler session: nothing to
    record, nothing to set."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Open:
    __slots__ = ("rec", "name", "attrs", "id", "parent", "t0", "ann")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        stack = self.rec._stack()
        self.id = next(self.rec._ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self.ann = TraceAnnotation(PREFIX + self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        self.rec._stack().pop()
        self.rec._keep(Span(self.name, self.id, self.parent, self.t0, t1,
                            self.attrs))
        return False


class Recorder:
    """Spans recorded since the last :meth:`take`, at most :data:`CAP`."""

    def __init__(self):
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._origin: Optional[int] = None
        self._dropped = 0

    def _stack(self) -> List["_Open"]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _keep(self, s: Span) -> None:
        with self._lock:
            if self._origin is None or s.t0_ns < self._origin:
                self._origin = s.t0_ns
            if len(self._spans) < CAP:
                self._spans.append(s)
            else:
                self._dropped += 1

    def span(self, name: str, **attrs):
        """A context manager around one unit of a layer's work, recorded
        while a profiler session is collecting."""
        if not TraceAnnotation.is_enabled():
            return _OFF
        return _Open(self, name, attrs)

    def annotate(self, **attrs) -> None:
        """Set attributes on the innermost span open in this thread, if
        any: how a callee counts its work into whatever span its caller
        opened around it."""
        stack = self._stack()
        if stack:
            stack[-1].set(**attrs)

    def take(self) -> Dict[str, Any]:
        """``{"origin_ns", "spans", "dropped"}`` since the last take, and
        clear them.  ``origin_ns`` is the ``perf_counter_ns`` reading at
        the first span (None when there was none)."""
        with self._lock:
            out = {"origin_ns": self._origin, "spans": self._spans,
                   "dropped": self._dropped}
            self._spans, self._origin, self._dropped = [], None, 0
        return out


_RECORDER = Recorder()
span = _RECORDER.span
annotate = _RECORDER.annotate
take = _RECORDER.take

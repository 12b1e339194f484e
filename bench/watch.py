"""The watcher as both drivers run it, and the comparison of its verdicts
with the plain reference."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .reference import analyzer as ref


def recording_analyzer(schema, faults=(), **kw):
    """An ``AutoAnalyzer`` of the program that keeps each window's result
    (and, in tests, plants a fault in it)."""
    from repro.core import AutoAnalyzer, tree_from_schema
    from repro.core.trace import RegionTrace

    class RecordingAnalyzer(AutoAnalyzer):
        def __init__(self):
            super().__init__(tree_from_schema(schema), **kw)
            self.last = None
            self._first = None

        def analyze_trace(self, trace, window=None):
            if "half_batch" in faults:
                half = trace.n_processes // 2
                trace = RegionTrace(
                    region_ids=list(trace.region_ids), n_processes=half,
                    n_steps=trace.n_steps, n_repeats=trace.n_repeats,
                    schema=list(trace.schema), meta=dict(trace.meta),
                    data={k: v[:, :, :half] for k, v in trace.data.items()})
            res = super().analyze_trace(trace, window)
            if "stale" in faults:
                self._first = self._first or res
                res = self._first
            if "answer" in faults:
                res = dataclasses.replace(res, verdict=dataclasses.replace(
                    res.verdict, disparity_paths=()))
            self.last = res
            return res

    return RecordingAnalyzer()


def numbers_of(res) -> Dict[str, Any]:
    return {"values": dict(res.disparity.values),
            "severity": float(res.dissimilarity.severity)}


def compare(spool_dir: str, consumed: List[Tuple[int, int, Any]],
            dtype=np.float64) -> Dict[str, float]:
    """Verdict mismatches and the widest value gap over every consumed
    window ``(start, stop, result or None)``, the reference computed once
    per distinct window."""
    refs: Dict[Tuple[int, int], Any] = {}
    mismatches, gap = 0, 0.0
    for start, stop, res in consumed:
        if (start, stop) not in refs:
            refs[start, stop] = ref.analyze_window(spool_dir, start, stop,
                                                   dtype)
        doc, nums = refs[start, stop]
        if res is None or res.verdict.doc() != doc:
            mismatches += 1
            continue
        gap = max(gap, ref.value_gap(numbers_of(res), nums))
    return {"verdict_mismatches": float(mismatches), "value_gap": gap}


def control_readings(spool_dir: str, consumed) -> Dict[str, float]:
    """The control: the reference computed in float32 put in the program's
    place, read against the float64 reference over the same windows."""
    mismatches, gap = 0, 0.0
    for start, stop in sorted({(a, b) for a, b, _ in consumed}):
        doc, nums = ref.analyze_window(spool_dir, start, stop)
        doc32, nums32 = ref.analyze_window(spool_dir, start, stop,
                                           np.float32)
        mismatches += doc32 != doc
        gap = max(gap, ref.value_gap(nums32, nums))
    return {"verdict_mismatches": float(mismatches), "value_gap": gap}


def first_mismatch(spool_dir: str, consumed) -> Optional[str]:
    """A readable diff of the first window whose verdict differs."""
    for start, stop, res in consumed:
        doc, _ = ref.analyze_window(spool_dir, start, stop)
        got = None if res is None else res.verdict.doc()
        if got != doc:
            return f"window [{start}, {stop}): program {got} reference {doc}"
    return None

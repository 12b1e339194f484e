"""The readers of the spool's member encoding: the share of the bytes that
a window's segment loads had to inflate, and the load time read in the
imbalanced cell."""
import pytest

from bench import harness
from bench.tests import small
from repro.core.spans import Span

MS = 1e6
ORIGIN = 7.5e12


def _span(name, i, parent, a, b, **attrs):
    return Span(name, i, parent, int(ORIGIN + a), int(ORIGIN + b), attrs)


def test_spool_readers_on_traced_runs():
    """Both cells' segments hold float64 noise, which is stored: the
    loads inflate only part of what they read."""
    for traffic in ("imbalanced", "balanced"):
        doc, _ = small.run(*small.st_cell(traffic), trace=True)
        m = {k: v["value"] for k, v in doc["metrics"].items()}
        assert m[f"spool_load_ms.{traffic}"] > 0
    assert 0 < m["spool_inflated_share.balanced"] < 100


def test_spool_readers_on_recorded_spans():
    """Two windows, each loading one segment: 20 and 10 ms, of which 55
    and 50 of 100 member bytes were inflated.  Loads that count no member
    bytes, as the parent format's do, give the share nothing to read."""
    sp = [_span("online.consume", 1, None, 0, 40 * MS),
          _span("spool.window", 2, 1, 0, 30 * MS),
          _span("spool.load", 3, 2, 0, 20 * MS, bytes=80,
                inflated_bytes=55, raw_bytes=100),
          _span("online.consume", 4, None, 50 * MS, 90 * MS),
          _span("spool.window", 5, 4, 50 * MS, 70 * MS),
          _span("spool.load", 6, 5, 50 * MS, 60 * MS, bytes=90,
                inflated_bytes=50, raw_bytes=100)]
    rec = {"traced_s": 0.1, "spans": {},
           "program": {"origin_ns": ORIGIN, "spans": sp, "dropped": 0}}
    read = harness.metric_reader
    assert read("spool_load_ms.imbalanced")(rec) == pytest.approx(15.0)
    assert read("spool_inflated_share.balanced")(rec) == pytest.approx(52.5)
    for s in sp:
        for k in ("inflated_bytes", "raw_bytes"):
            s.attrs.pop(k, None)
    assert read("spool_inflated_share.balanced")(rec) is None
    assert read("spool_load_ms.imbalanced")(rec) == pytest.approx(15.0)

"""The readers of the program's own spans: each finds its number in a
traced run of its cell, within the outside reading of the same layer, and
the engine's idle share puts the program's clock on the trace's."""
import pytest

from bench import costs, harness, program, xplane
from bench.tests import small
from repro.core.spans import Span

MS = 1e6
ORIGIN = 7.5e12


def test_analyzer_readers_on_traced_runs():
    doc, _ = small.run(*small.st_cell("imbalanced"), trace=True)
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    assert 0 < m["discernibility_ms.imbalanced"] \
        <= m["rootcause_ms.imbalanced"]
    assert 0 < m["device_wait_ms.imbalanced"] <= m["clustering_ms.imbalanced"]
    doc, _ = small.run(*small.st_cell("balanced"), trace=True)
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    assert 0 < m["spool_load_ms.balanced"] <= m["spool_read_ms.balanced"]


def test_engine_readers_on_a_traced_run(monkeypatch):
    """The CPU has no device plane: the XLA CPU client's execution line
    stands in for it, and the chip's peaks for the CPU's."""
    from jax.profiler import ProfileData

    read, peaks = xplane.read, costs.peaks

    def with_cpu_device(path):
        ev = read(path)
        ev["device"]["/device:CPU:0"] = [
            (e.name, float(e.start_ns), float(e.end_ns))
            for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for ln in p.lines
            if ln.name.startswith("tf_XLAPjRtCpuClient")
            for e in ln.events if e.duration_ns > 0]
        return ev

    monkeypatch.setattr(xplane, "read", with_cpu_device)
    monkeypatch.setattr(costs, "peaks", lambda kind: peaks("TPU v5 lite"))
    doc, _ = small.run(*small.chat_cell(), trace=True)
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    assert m["step_host_ms.serve"] > 0
    assert 0 < m["idle_engine_share.serve"] <= m["device_idle.serve"]


def _span(name, i, parent, a, b, **attrs):
    return Span(name, i, parent, int(ORIGIN + a), int(ORIGIN + b), attrs)


def _engine_rec(shift, stray=()):
    """One engine step (ms on the program's clock): schedule 0-1, execute
    1-9 holding a dispatch 2-3 and a wait 3-7, spool append 9-10; the
    device busy 2-7, on a clock ``shift`` ns ahead."""
    sp = [_span("serve.schedule", 2, 1, 0, 1 * MS),
          _span("serve.dispatch", 4, 3, 2 * MS, 3 * MS, kind="decode"),
          _span("serve.wait", 5, 3, 3 * MS, 7 * MS, kind="decode"),
          _span("serve.execute", 3, 1, 1 * MS, 9 * MS),
          _span("spool.append", 6, 1, 9 * MS, 10 * MS),
          _span("serve.step", 1, None, 0, 10 * MS)]
    ops = [("fusion", 2 * MS + shift, 7 * MS + shift)]
    ops += [("stray", a + shift, b + shift) for a, b in stray]
    return {"traced_s": 0.02, "spans": {},
            "program": {"origin_ns": ORIGIN, "spans": sp, "dropped": 0},
            "profile": {"device": {"/device:TPU:0": ops}, "spans": []}}


def test_engine_readers_on_synthetic_spans():
    rec = _engine_rec(137.25 * MS)
    read = harness.metric_reader
    assert read("step_host_ms.serve")(rec) == pytest.approx(6.0)
    # idle under engine work: schedule 1, execute's own 1-2 and 7-9,
    # spool append 1: 5 ms of the 20 ms window
    assert read("idle_engine_share.serve")(rec) == pytest.approx(25.0)


def test_offset_fit_recovers_a_known_offset():
    # calls of varied lengths; the device starts 20 us into each call and
    # runs to its end, on a clock 137.25 ms ahead of the program's
    calls, t = [], 0.0
    for k in range(40):
        n = (1 + k % 7) * 0.3 * MS
        calls.append((t, t + n))
        t += n + (0.2 + k % 3 * 0.5) * MS
    shift = 137.25 * MS
    busy = [(a + 20e3 + shift, b + shift) for a, b in calls]
    d, share = program.fit_offset(busy, calls)
    assert abs(d - shift) <= 20e3
    assert share == pytest.approx(1.0)


def test_reads_nothing_below_the_cover_limit():
    read = harness.metric_reader("idle_engine_share.serve")
    # 1 ms of device work outside every call: 5 of 6 ms covered
    assert read(_engine_rec(0.0, stray=[(12 * MS, 13 * MS)])) is None
    assert read(_engine_rec(0.0)) == pytest.approx(25.0)


def test_a_program_without_spans_gives_nothing(monkeypatch):
    import sys

    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    rec = {"traced_s": 1.0, "spans": {}}
    for name in ("discernibility_ms.imbalanced", "device_wait_ms.imbalanced",
                 "spool_load_ms.balanced", "step_host_ms.serve",
                 "idle_engine_share.serve"):
        assert harness.metric_reader(name)(rec) is None
    assert rec["program"] is None

"""The trace reduction: busy/idle union, device time inside host spans,
the breakdown lists, and reading a trace the profiler recorded."""
import numpy as np
import pytest

from bench import xplane


def events():
    dev = [("fusion.1", 0, 10), ("fusion.2", 5, 20), ("dot", 40, 50),
           ("fusion.1", 90, 100)]
    spans = [("bench:call:decode:7", 0, 30), ("bench:clustering", 35, 60),
             ("bench:watch", 60, 95)]
    return {"device": {"/device:TPU:0": dev}, "spans": spans}


def test_union_and_busy():
    ev = events()
    assert xplane.union([(a, b) for _, a, b in ev["device"]["/device:TPU:0"]]) \
        == [(0, 20), (40, 50), (90, 100)]
    busy, window = xplane.busy_and_window(ev, 0.0, 1e-7)
    assert busy == pytest.approx(40e-9)
    assert window == pytest.approx(1e-7)


def test_device_time_inside_spans():
    ev = events()
    got = xplane.span_device_time(ev, "call")
    assert got == [("decode:7", pytest.approx(30e-9), pytest.approx(20e-9))]
    (_, span, dev), = xplane.span_device_time(ev, "clustering")
    assert dev == pytest.approx(10e-9)
    (_, _, dev), = xplane.span_device_time(ev, "watch")
    assert dev == pytest.approx(5e-9)


def test_breakdown():
    bd = xplane.breakdown(events())
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]
    assert [k for k, _ in bd["device_ops"]] == ["fusion.1", "fusion.2", "dot"]
    # idle 20-40 (its middle, 30, still inside the decode call's span)
    # and 50-90 (middle 70, inside the watcher's)
    idle = dict((k, v) for k, v in bd["idle_gaps"])
    assert idle["call:decode"] == pytest.approx(20e-9)
    assert idle["watch"] == pytest.approx(40e-9)
    assert sum(idle.values()) == pytest.approx(60e-9)


def test_empty_trace():
    ev = {"device": {}, "spans": []}
    assert xplane.busy_and_window(ev, 0.0, 2.0) == (0.0, 2.0)
    assert xplane.span_device_time(ev, "call") == []
    assert xplane.breakdown(ev) == {"device_ops": [], "idle_gaps": []}


def test_clock_offset_recovers_a_shift():
    # host spans launch device work that starts 50 and ends 30 after the
    # span's ends on the host clock; the device clock reads 1.1 ms early
    hosts = [(t, t + 1000.0) for t in np.arange(0, 200000, 5000.0)]
    dev = [(a + 50 - 1.1e6, b - 30 - 1.1e6) for a, b in hosts]
    d = xplane.clock_offset(dev, hosts, step=10.0, reach=2e6)
    assert -1.1e6 - 50 <= d <= -1.1e6 + 30


def test_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:call:decode:3"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = xplane.read(xplane.find(str(tmp_path)))
    names = [n for n, _, _ in ev["spans"]]
    assert names == ["bench:call:decode:3"]
    (_, a, b), = ev["spans"]
    assert b > a


def test_reads_a_tpu_trace():
    """A trace recorded on one TPU v5e: a 512x512 matmul under
    ``bench:call:decode:<i>`` and a reduction under ``bench:clustering``,
    three times each.  On the chip the device clock runs about 1 ms ahead
    of the spans; after alignment every op lies inside its span."""
    import os
    ev = xplane.read(os.path.join(os.path.dirname(__file__),
                                  "tpu_v5e_probe.xplane.pb"))
    (plane, ops), = ev["device"].items()
    assert plane == "/device:TPU:0"
    assert {n for n, _, _ in ops} == {"copy-start", "copy-done", "fusion",
                                       "add_reduce_fusion"}
    assert len(ev["spans"]) == 6
    cover = xplane.Cover(xplane.union([(a, b) for _, a, b in ev["spans"]]))
    assert all(cover(a, b) == b - a for _, a, b in ops)
    calls = xplane.span_device_time(ev, "call")
    assert [label for label, _, _ in calls] == ["decode:0", "decode:1",
                                                "decode:2"]
    busy = sum(d for _, _, d in calls) + sum(
        d for _, _, d in xplane.span_device_time(ev, "clustering"))
    assert busy == pytest.approx(
        sum(b - a for a, b in xplane.busy_by_plane(ev)[plane]) / 1e9)
    bd = xplane.breakdown(ev)
    assert bd["device_ops"][0][0] == "add_reduce_fusion"

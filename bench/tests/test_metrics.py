"""The per-layer readers: each finds its number in what a traced run
recorded, and returns nothing where there is nothing to read."""
import pytest

from bench import costs, harness
from bench.tests.test_costs import TINY

READERS = [m["name"] for m in harness.load_benchmark()["per_layer"]]


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_an_empty_record(name):
    assert harness.metric_reader(name)({"spans": {}}) is None


def test_span_readers():
    rec = {"windows": 4, "window_s": 2.0, "traced_s": 2.0, "busy_s": 0.5,
           "spans": {"rootcause": 2.0, "clustering": 0.4, "spool_read": 0.2,
                     "analyze": 1.0}, "watch_s": 0.1}
    read = harness.metric_reader
    assert read("rootcause_ms.imbalanced")(rec) == pytest.approx(500.0)
    assert read("clustering_ms.imbalanced")(rec) == pytest.approx(100.0)
    assert read("spool_read_ms.balanced")(rec) == pytest.approx(50.0)
    assert read("analyze_ms.balanced")(rec) == pytest.approx(250.0)
    assert read("watch_share.serve")(rec) == pytest.approx(5.0)
    for cell in ("imbalanced", "balanced", "serve"):
        assert read(f"device_idle.{cell}")(rec) == pytest.approx(75.0)


def test_device_readers():
    pk = costs.peaks("TPU v5 lite")
    # one decode call at position 9 that kept the device busy 1 ms
    ev = {"device": {"/device:TPU:0": [("fusion", 0.0, 1e6)]},
          "spans": [("bench:call:decode:9", -1e3, 1.001e6),
                    ("bench:clustering", 2e6, 4e6)]}
    rec = {"profile": ev, "device_kind": "TPU v5 lite", "config": TINY,
           "window_flops": 197e12 * 0.5, "window_s": 1.0,
           "d2_work": {"flops": 1e6, "bytes": 1e6}}
    need = costs.roofline_s(costs.token_flops(TINY, 9),
                            costs.decode_bytes(TINY, 9), pk)
    read = harness.metric_reader
    assert read("decode_hbm_roofline")(rec) == pytest.approx(
        100 * need / 1e-3)
    assert read("serve_mfu")(rec) == pytest.approx(50.0)
    # no device time inside the clustering span: nothing to read
    assert read("distance_roofline.imbalanced")(rec) is None
    ev["device"]["/device:TPU:0"].append(("rows", 2e6, 3e6))
    assert read("distance_roofline.imbalanced")(rec) == pytest.approx(
        100 * costs.roofline_s(1e6, 1e6, pk) / 1e-3)

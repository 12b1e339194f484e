"""Each driver runs a tiny window in this process and prints a result line
with the contract's keys; the command itself refuses a host without a
TPU."""
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import small

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell", ["imbalanced", "balanced", "chat"])
def test_driver_runs_a_window(cell):
    args = small.chat_cell() if cell == "chat" else small.st_cell(cell)
    doc, err = small.run(*args)
    assert set(doc) == KEYS
    assert list(doc)[-1] == "checks"
    assert set(doc["device"]) == DEVICE
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 0
    bench = harness.load_benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if harness.applies(m, args[0]["name"])}
    assert set(doc["metrics"]) == want
    for name, m in doc["metrics"].items():
        assert m["value"] > 0, name
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(doc["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_traced_run_reads_host_spans():
    doc, _ = small.run(*small.st_cell("imbalanced"), trace=True)
    assert {"busy_s", "window_s"} <= set(doc["device"])
    assert "breakdown" in doc
    assert {"rootcause_ms.imbalanced", "clustering_ms.imbalanced"} \
        <= set(doc["metrics"])


def test_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "st-fine-m2048.imbalanced", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr

"""Cells of the benchmark cut to a size the CPU holds, and an in-process
run of one that returns its result line."""
from __future__ import annotations

import io
import json
import time

from bench import harness


def st_cell(traffic: str = "imbalanced", m: int = 32):
    config = harness.load_json(f"{harness.BENCH_DIR}/configs/st-fine-m2048.json")
    config["n_processes"] = m
    tr = harness.load_json(f"{harness.BENCH_DIR}/traffic/{traffic}.json")
    tr.update(windows=2, warm_windows=1)
    return {"name": f"st-fine-m2048.{traffic}", "chips": 1}, config, tr


def chat_cell(dtype: str = "bfloat16"):
    config = harness.load_json(
        f"{harness.BENCH_DIR}/configs/h2o-danube3-4b.json")
    config.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=128,
                  vocab_size=256, torch_dtype=dtype)
    tr = harness.load_json(f"{harness.BENCH_DIR}/traffic/chat.json")
    tr.update(prompt_median=48, prompt_multiple=16, output_median=8,
              max_positions=256, prefill_chunk=16, lanes=2, n_requests=400,
              warm_steps=8, check_requests=2)
    return {"name": "h2o-danube3-4b.chat", "chips": 1}, config, tr


def run(cell, config, traffic, seed=2 ** 31 + 9, seconds=1.0, trace=False,
        faults=()):
    """(result line as a dict, standard error) of one run on this host."""
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(cell, config, traffic, seed, seconds, trace,
                          time.perf_counter(), harness.load_benchmark(),
                          require_tpu=False, faults=faults, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()

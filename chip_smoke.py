#!/usr/bin/env python3
"""Bring-up smoke run of the system's main path on one TPU chip.

    python3 chip_smoke.py [--out DIR] [--seed N]

Runs, in this one process, on the chip JAX finds:

  (a) device   — fail unless JAX's first device is a TPU;
  (b) serve    — h2o-danube-3-4b at full width (24 layers, d_model 3840,
                 GQA 32/8, bf16, random weights from ``--seed``) through
                 ``repro.launch.serve`` (ServeEngine + JitBackend): 2 lanes,
                 4 requests, prompt bucket 64, prefill chunk 8, 16 generated
                 tokens each, spooled to a TraceSpool under ``--out``;
  (c) online   — OnlineAnalyzer over that spool with the Pallas distance
                 backend and with exact numpy: no degraded window, and
                 every window's verdict identical;
  (d) analyzer — Algorithm 2 at m=65536 shards x n=128 regions (a flat
                 tree, one planted straggler block), Pallas vs numpy:
                 identical results, the device lockstep path taken,
                 and the distance kernel compiled to a TPU custom call;
  (e) corpus   — every synthetic corpus verdict under the Pallas backend
                 bit-identical to the committed VERDICTS_synthetic.json.

Any failed check raises and exits non-zero.  Rates and phase times are
those of a smoke run on the named device, not a benchmark.  The last line
of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVE_ARGS = ["--lanes", "2", "--requests", "4", "--prompt-len", "64",
              "--chunk", "8", "--gen", "16"]
ALGO2_SHAPE = (65536, 128)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def serve_phase(spool: str, arch: str, smoke: bool, seed: int):
    """(b) Serve generated traffic through the launcher's path and check
    what came out; returns the engine's throughput dict."""
    from repro.core import WALL_TIME
    from repro.launch import serve as serve_mod
    from repro.serve.engine import DECODE, PREFILL

    shutil.rmtree(spool, ignore_errors=True)
    argv = ["--arch", arch, "--seed", str(seed), "--spool-dir", spool,
            *SERVE_ARGS] + (["--smoke"] if smoke else [])
    args = serve_mod.parse_args(argv)
    engine, backend, traffic = serve_mod.serve(args)
    cfg = backend.cfg

    require(engine.completed == len(traffic) == args.requests,
            f"{engine.completed}/{args.requests} requests completed")
    for r in traffic:
        toks = backend.outputs[r.rid]
        require(len(toks) == args.gen,
                f"request {r.rid}: {len(toks)} tokens, want {args.gen}")
        require(all(0 <= t < cfg.vocab for t in toks),
                f"request {r.rid}: token outside the vocabulary")

    require(backend.nonfinite_samples == 0,
            f"{backend.nonfinite_samples} sampled logits rows not finite")

    wall = engine.trace.metric(WALL_TIME).sum(axis=(0, 1))   # (lanes, n)
    root = backend.tree.root.name
    cols = {ph: engine.trace.col(backend.tree.by_path(f"{root}/{ph}")
                                 .region_id) for ph in (PREFILL, DECODE)}
    lanes = sorted({rec.lane for rec in engine.records.values()})
    for lane in lanes:
        for ph, j in cols.items():
            require(wall[lane, j] > 0, f"lane {lane}: {ph} wall is 0")
    return engine.throughput()


def online_phase(spool: str, window: int = 8):
    """(c) Analyze the serving spool window by window on the Pallas lane
    and the exact numpy lane; returns the number of windows."""
    from repro.stream import OnlineAnalyzer, SpooledTrace

    docs = {}
    for backend in ("pallas", "numpy"):
        online = OnlineAnalyzer(window_steps=window,
                                distance_backend=backend)
        windows = online.poll(SpooledTrace(spool))
        require(bool(windows), "the spool held no complete window")
        bad = [w.reason for w in windows if w.degraded]
        require(not bad, f"{backend}: degraded windows {bad}")
        docs[backend] = [w.verdict.doc() for w in windows]
    require(docs["pallas"] == docs["numpy"],
            "pallas window verdicts differ from numpy")
    return len(docs["numpy"])


def _algo2_workload(m: int, n: int):
    """Flat n-region tree and an (m, n) near-balanced matrix whose first
    m/8 shards straggle 6x in one region: Algorithm 2 walks every
    depth-1 region and pins one CCR."""
    import numpy as np

    from repro.core import RegionTree

    tree = RegionTree("bench")
    for j in range(1, n + 1):
        tree.add(f"cr{j}")
    T = 1.0 + 0.05 * np.random.default_rng(0).random((m, n))
    T[: max(1, m // 8), n // 3] *= 6.0
    return tree, T, list(range(1, n + 1))


def analyzer_phase(m: int, n: int):
    """(d) Algorithm 2 on the Pallas lane against the exact numpy lane;
    returns (pallas report, numpy report, pallas s, numpy s)."""
    from repro.core import find_dissimilarity_bottlenecks

    tree, T, rids = _algo2_workload(m, n)
    t0 = time.perf_counter()
    fast = find_dissimilarity_bottlenecks(tree, T, rids, backend="pallas")
    t1 = time.perf_counter()
    ref = find_dissimilarity_bottlenecks(tree, T, rids)
    t2 = time.perf_counter()
    for field in ("exists", "ccrs", "cccrs", "severity", "composite_s"):
        require(getattr(fast, field) == getattr(ref, field),
                f"algo2 m={m}: {field} differs from numpy")
    require(fast.baseline.n_clusters == ref.baseline.n_clusters
            and fast.baseline.same_partition(ref.baseline),
            f"algo2 m={m}: baseline partition differs from numpy")
    require(fast.fetch_stats["device_calls"] > 0,
            "the device lockstep path was not taken")
    return fast, ref, t1 - t0, t2 - t1


def kernel_text(m: int, n: int) -> str:
    """Compiled text of the batched distance kernel at (m, n), 8 seeds,
    as the Pallas backend calls it."""
    import jax.numpy as jnp

    from repro.core import get_distance_backend
    from repro.kernels import distance as dist

    backend = get_distance_backend("pallas")
    W = jnp.ones((m, n), jnp.float32)
    return dist.multi_seed_rows.lower(
        W, jnp.ones((m,), jnp.float32), jnp.zeros((8,), jnp.int32),
        interpret=backend._interpret).compile().as_text()


def corpus_phase(seed: int = 0) -> None:
    """(e) scripts/snapshot_verdicts.py --check VERDICTS_synthetic.json
    --distance-backend pallas, in this process."""
    spec = importlib.util.spec_from_file_location(
        "snapshot_verdicts", os.path.join(ROOT, "scripts",
                                          "snapshot_verdicts.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.check(os.path.join(ROOT, "VERDICTS_synthetic.json"), seed,
                   "pallas")
    require(rc == 0, "pallas verdicts drifted from VERDICTS_synthetic.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for the serving spool")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    args = ap.parse_args(argv)

    import jax

    # (a) device
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    kind, count = dev.device_kind, len(devices)
    print(f"(a) device: {kind}, {count} device(s)", flush=True)

    from repro.compile_cache import use_compile_cache
    from repro.core import get_distance_backend

    print(f"compile cache: {use_compile_cache()}", flush=True)
    require(get_distance_backend("pallas")._interpret is False,
            "Pallas would run in interpret mode on the chip")
    label = f"smoke run on {kind}, not a benchmark"

    t0 = time.perf_counter()
    spool = os.path.join(args.out, "serve_spool")
    tp = serve_phase(spool, "h2o-danube-3-4b", smoke=False, seed=args.seed)
    print(f"(b) serve h2o-danube-3-4b full width: "
          f"{int(tp['requests_completed'])} requests, "
          f"{int(tp['tokens_prefill'])} prefill + "
          f"{int(tp['tokens_decode'])} decode tokens; {label}: "
          f"prefill {tp['prefill_tok_per_s']} tok/s, "
          f"decode {tp['decode_tok_per_s']} tok/s; "
          f"phase {time.perf_counter() - t0} s with compiles", flush=True)

    t0 = time.perf_counter()
    n_windows = online_phase(spool)
    print(f"(c) online: {n_windows} windows, pallas == numpy, none "
          f"degraded; phase {time.perf_counter() - t0} s", flush=True)

    t0 = time.perf_counter()
    m, n = ALGO2_SHAPE
    fast, ref, t_fast, t_ref = analyzer_phase(m, n)
    require("tpu_custom_call" in kernel_text(m, n),
            "multi_seed_rows did not compile to a TPU custom call")
    print(f"(d) algo2 m={m} n={n}: pallas == numpy (ccrs {ref.ccrs}, "
          f"{ref.baseline.n_clusters} clusters), "
          f"{fast.fetch_stats['device_calls']} device row fetches, kernel "
          f"is a tpu_custom_call; {label}: pallas {t_fast} s with "
          f"compiles, numpy {t_ref} s; phase {time.perf_counter() - t0} s",
          flush=True)

    t0 = time.perf_counter()
    corpus_phase()
    print(f"(e) corpus: pallas verdicts bit-identical to "
          f"VERDICTS_synthetic.json; phase {time.perf_counter() - t0} s",
          flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

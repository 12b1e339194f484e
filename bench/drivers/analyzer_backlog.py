"""Driver ``analyzer_backlog``: an online analyzer working through a
finished or lagging spool of a large SPMD job.

Set-up writes the traffic's windows of the configured job from the seed
(``TraceSpool``, one segment per window): ``warm_windows`` to a spool of
their own, analyzed once to compile and warm every device shape, and
``windows`` more to the spool the measured window reads.  The window then
drives ``OnlineAnalyzer.pending_bounds``/``consume`` over that
``SpooledTrace`` in a closed loop, as a watcher does on a backlog; should
it reach the end, it passes through the spool again with a fresh consumer
and a fresh analyzer, so nothing an analyzer keeps outlives one pass.  A
verdict's latency is the ``consume`` call that takes its window.
Afterwards every consumed window is compared with the plain reference
(``bench/reference/analyzer.py``).
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from bench import costs, harness, st_job, watch

# Limits of the numbers compared with the reference (PERF.md gives the
# readings each was set from).
LIMITS = {"verdict_mismatches": 0.0, "value_gap": 1e-9}


def _write_spool(ctx, directory: str, windows: int,
                 rng: np.random.Generator) -> None:
    from repro.core.trace import RegionTrace
    from repro.stream import TraceSpool

    cfg, tr = ctx.config, ctx.traffic
    steps = int(cfg["window_steps"])
    spool = TraceSpool(directory, chunk_steps=steps)
    schema, rids = st_job.schema(cfg), st_job.region_ids(cfg)
    for _ in range(windows):
        spool.append(RegionTrace(
            region_ids=rids, n_processes=int(cfg["n_processes"]),
            n_steps=steps, schema=schema,
            data=st_job.window_data(cfg, tr, rng, steps),
            meta={"collector": "synthetic"}))
    spool.close()


def _install_spans(ctx) -> None:
    import repro.core.analyzer as an
    import repro.core.search as se
    from repro.core.roughset import DecisionTable
    from repro.stream import SpooledTrace

    sp = ctx.spans
    sp.wrap(SpooledTrace, "window", "spool_read")
    sp.wrap(an.AutoAnalyzer, "analyze_trace", "analyze")
    for mod, fn in ((an, "find_dissimilarity_bottlenecks"),
                    (an, "optics_cluster"), (an, "kmeans_severity"),
                    (se, "kmeans_severity")):
        sp.wrap(mod, fn, "clustering")
    sp.wrap(DecisionTable, "reducts", "rootcause")
    sp.wrap(DecisionTable, "object_reducts", "rootcause")


def _numpy_rows(directory: str, bounds) -> int:
    """Seed rows the exact float64 lane fetches for these windows."""
    from repro.core import find_dissimilarity_bottlenecks, tree_from_schema
    from repro.stream import SpooledTrace

    spooled = SpooledTrace(directory)
    tree = tree_from_schema(spooled.schema)
    rows = 0
    for start, stop in bounds:
        rm = spooled.window(start, stop).reduce()
        rids = list(rm.region_ids)
        rep = find_dissimilarity_bottlenecks(
            tree, rm.vectors("cpu_time", rids), rids, backend="numpy")
        rows += int((rep.fetch_stats or {}).get("rows", 0))
    return rows


def run(ctx):
    import jax
    from repro.stream import OnlineAnalyzer, SpooledTrace

    cfg, tr = ctx.config, ctx.traffic
    rng = np.random.default_rng(ctx.seed)
    warm_dir = os.path.join(ctx.scratch, "warm")
    directory = os.path.join(ctx.scratch, "spool")
    _write_spool(ctx, warm_dir, int(tr["warm_windows"]), rng)
    _write_spool(ctx, directory, int(tr["windows"]), rng)
    spooled = SpooledTrace(directory)
    kw = dict(spooled.meta.get("analyzer_kw", {}))
    kw["distance_backend"] = cfg["distance_backend"]

    def consumer():
        analyzer = watch.recording_analyzer(spooled.schema, ctx.faults, **kw)
        return analyzer, OnlineAnalyzer(window_steps=int(cfg["window_steps"]),
                                        analyzer=analyzer)

    warm_spool = SpooledTrace(warm_dir)
    _, warm = consumer()
    for start, stop in warm.pending_bounds(warm_spool):
        warm.consume(warm_spool, start, stop)

    if ctx.trace:
        _install_spans(ctx)
    consumed, latency = [], []
    t_window = time.perf_counter()
    deadline = t_window + ctx.seconds
    with harness.CompileCounter() as compiles:
        if ctx.profile:
            ctx.profile.start()
        now = t_window
        while now < deadline:
            analyzer, c = consumer()
            for start, stop in c.pending_bounds(spooled):
                t0 = time.perf_counter()
                wv = c.consume(spooled, start, stop)
                now = time.perf_counter()
                latency.append(now - t0)
                consumed.append((start, stop,
                                 None if wv.degraded else analyzer.last))
                if now >= deadline:
                    break
        t_end = time.perf_counter()
        if ctx.profile:
            ctx.profile.stop()
    ctx.spans.restore()
    window_s = t_end - t_window
    peak = harness.memory_peak(jax.devices())

    record = {"windows": len(consumed), "window_s": window_s,
              "spans": {k: ctx.spans.total(k) for k in ctx.spans.intervals},
              "device_kind": jax.devices()[0].device_kind}
    if ctx.trace:
        m, n = int(cfg["n_processes"]), len(st_job.region_ids(cfg))
        distinct = sorted({(a, b) for a, b, _ in consumed})
        per = {b: _numpy_rows(directory, [b]) for b in distinct}
        rows = [per[(a, b)] for a, b, _ in consumed]
        work = [costs.d2_rows(m, n, r) for r in rows if r]
        record["d2_work"] = {"flops": sum(w["flops"] for w in work),
                             "bytes": sum(w["bytes"] for w in work)}

    cmp = watch.compare(directory, consumed)
    if cmp["verdict_mismatches"]:
        print(watch.first_mismatch(directory, consumed), file=sys.stderr)
    print(f"compiles in window: {compiles.count}", file=sys.stderr)
    checks = {k: {"value": cmp[k], "limit": LIMITS[k]} for k in LIMITS}
    controls = ({"analyzer.float32": watch.control_readings(directory,
                                                            consumed)}
                if ctx.control else {})
    return {
        "e2e": {"verdicts_per_s": len(consumed) / window_s,
                "verdict_p95_ms": 1e3 * harness.percentile(latency, 95),
                "setup_s": t_window - ctx.t_process},
        "attempted": len(consumed),
        "failed": sum(1 for *_, r in consumed if r is None),
        "memory_peak_bytes": peak,
        "checks": checks,
        "controls": controls,
        "record": record,
    }

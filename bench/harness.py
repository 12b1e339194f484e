"""The data-driven harness: finds a cell's files by name, runs its driver
once, and prints the result line.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); the mix names its driver
(``bench/drivers/<name>.py``), the code that sets the cell up, runs the
measured window and checks what the window produced against the plain
reference.  Each per-layer metric is a reader of its own
(``bench/metrics/<name>.py``) over what the traced run recorded.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve(bench: Dict[str, Any], workload: str
            ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    """The cell named ``workload`` with its configuration and traffic mix."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = load_json(os.path.join(BENCH_DIR, "configs",
                                    cell["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return load_module(os.path.join(BENCH_DIR, "drivers", name + ".py"),
                       f"bench_driver_{name}")


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_")).read


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation between order stats)."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


# -- host spans --------------------------------------------------------------
class Spans:
    """Host spans around the calls into each layer, recorded from the
    benchmark's side.  Only the outermost span of a layer is kept when
    calls nest.  With ``annotate`` each span is also written into the
    profiler's trace as ``bench:<layer>[:<label>]``, on the clock of the
    device events."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.intervals: Dict[str, List[Tuple[float, float]]] = {}
        self._depth: Dict[str, int] = {}
        self._patched: List[Tuple[Any, str, Any, bool]] = []

    @contextlib.contextmanager
    def span(self, layer: str, label: Optional[str] = None):
        depth = self._depth.get(layer, 0)
        self._depth[layer] = depth + 1
        ann = contextlib.nullcontext()
        if self.annotate and depth == 0:
            import jax
            ann = jax.profiler.TraceAnnotation(
                f"bench:{layer}" + (f":{label}" if label else ""))
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            t1 = time.perf_counter()
            self._depth[layer] = depth
            if depth == 0:
                self.intervals.setdefault(layer, []).append((t0, t1))

    def total(self, layer: str) -> float:
        return float(sum(b - a for a, b in self.intervals.get(layer, ())))

    def wrap(self, owner: Any, attr: str, layer: str,
             label: Optional[Callable[..., Optional[str]]] = None) -> None:
        """Replace ``owner.attr`` by a wrapper that records a span of
        ``layer`` around each call; :meth:`restore` puts it back."""
        # A module's function, a class's plain function (re-bound as a
        # method through the class) or an instance's bound method.
        inner = vars(owner).get(attr)
        orig = inner if isinstance(owner, type) else getattr(owner, attr)

        def wrapped(*args, **kw):
            with self.span(layer, label(*args, **kw) if label else None):
                return orig(*args, **kw)

        self._patched.append((owner, attr, inner))
        setattr(owner, attr, wrapped)

    def restore(self) -> None:
        while self._patched:
            owner, attr, inner = self._patched.pop()
            if inner is None:
                delattr(owner, attr)
            else:
                setattr(owner, attr, inner)


class CompileCounter:
    """Counts XLA backend compilations while it is open: the measured
    window must have none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self._on = False
        self._jax = jax

        def listener(name, *_a, **_kw):
            if self._on and name == self.EVENT:
                self.count += 1

        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        self._jax.monitoring.unregister_event_duration_listener(
            self._listener)
        return False


class Profile:
    """The profiler's trace of part of the window, read back as events."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.events = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax
        # No Python function tracing (it slows the host code being
        # measured several-fold), and of the host's events only the
        # benchmark's own annotations.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        from . import xplane
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.events = xplane.read(xplane.find(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)


# -- the run -----------------------------------------------------------------
class Context:
    """What a driver is given: the cell's data, the run's arguments, the
    span recorder and (traced runs) the profiler."""

    def __init__(self, cell, config, traffic, seed: int, seconds: float,
                 trace: bool, t_process: float, faults=None):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.t_process = t_process
        self.spans = Spans(annotate=trace)
        self.profile = Profile() if trace else None
        # Test-only: names of faults the driver plants in the timed path.
        self.faults = set(faults or ())
        # Control runs (bench/control.py) also read the controls' numbers,
        # which a driver returns under "controls".
        self.control = False
        self.scratch = tempfile.mkdtemp(prefix="bench-")

    def close(self) -> None:
        shutil.rmtree(self.scratch, ignore_errors=True)


def judge(checks: Dict[str, Dict[str, Any]]) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def with_control(checks: Dict[str, Dict[str, Any]],
                 readings: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    """The checks with a control's readings put in the program's place."""
    return {k: dict(v, value=readings.get(k, v["value"]))
            for k, v in checks.items()}


def control_verdicts(res: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each control of a driver's result judged by the rule that decides
    ``correct``, with its readings in the program's place."""
    out = {}
    for name, readings in res.get("controls", {}).items():
        sub = with_control(res["checks"], readings)
        out[name] = {"correct": judge(sub), "checks": sub}
    return out


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:       # backends without memory stats (the CPU)
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell, config, traffic, seed: int, seconds: float, trace: bool,
             t_process: float, bench: Dict[str, Any],
             require_tpu: bool = True, faults=None,
             out=sys.stdout, err=sys.stderr) -> int:
    """Run one cell once and print its result line.  Returns the exit
    code: 2 (and no result) when JAX finds no TPU or too few chips."""
    import jax
    if require_tpu:
        # Every program of the cell, however quick to compile, comes from
        # the checkout's cache after the first run.
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"bench: needs a TPU, JAX found {dev.platform!r}", file=err)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: {cell['name']} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=err)
        return 2
    used = devices[:int(cell["chips"])]
    ctx = Context(cell, config, traffic, seed, seconds, trace, t_process,
                  faults)
    try:
        res = driver(traffic["driver"]).run(ctx)
    finally:
        ctx.close()

    name = cell["name"]
    metrics: Dict[str, Dict[str, Any]] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line: Dict[str, Any] = {}
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, name):
                metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        from . import xplane
        rec = res["record"]
        ev = ctx.profile.events if ctx.profile else None
        if ev is not None:
            busy, window = xplane.busy_and_window(
                ev, ctx.profile.t0, ctx.profile.t1)
            device["busy_s"], device["window_s"] = busy, window
            rec["profile"] = ev
            rec["busy_s"], rec["traced_s"] = busy, window
            line["breakdown"] = xplane.breakdown(ev)
        for m in bench["per_layer"]:
            if applies(m, name):
                v = metric_reader(m["name"])(rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = res["checks"]
    correct = judge(checks)
    for cname, c in checks.items():
        print(f"check {cname}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}", file=err)
    doc = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    doc.update(line)
    doc["checks"] = checks
    err.flush()
    print(json.dumps(doc), file=out, flush=True)
    return 0

"""Program spans: recorded only while a profiler session collects, nested
by a per-thread stack, carried on the profiler's own host plane, and set
at the analyzer's and the serving engine's layer boundaries."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import spans
from repro.stream import OnlineAnalyzer, SpooledTrace, TraceSpool


@pytest.fixture
def recorder():
    spans.take()
    yield spans
    spans.take()


def _opts():
    # Python calls untraced, as an operator profiling a live run would.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _tree(spans_):
    by_id = {s.id: s for s in spans_}

    def parent(s):
        p = by_id.get(s.parent)
        return p.name if p else None
    return by_id, parent


def test_no_session_records_nothing(recorder, monkeypatch):
    made = []

    class Counting(spans.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    assert not Counting.is_enabled()
    with spans.span("a", x=1) as a:
        with spans.span("b") as b:
            b.set(n=2)
    assert a is b and not a
    assert made == []
    got = spans.take()
    assert got == {"origin_ns": None, "spans": [], "dropped": 0}


def test_cap_counts_what_it_drops(recorder, tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    with jax.profiler.trace(str(tmp_path), profiler_options=_opts()):
        for i in range(5):
            with spans.span("x", i=i):
                pass
    got = spans.take()
    assert [s.attrs["i"] for s in got["spans"]] == [0, 1, 2]
    assert got["dropped"] == 2
    assert got["origin_ns"] == got["spans"][0].t0_ns
    assert spans.take()["spans"] == []


def test_annotations_on_the_host_plane(recorder, tmp_path):
    """Each span is a ``repro:<name>`` event of the profiler's host plane,
    with the recorder's duration."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path), profiler_options=_opts()):
        with spans.span("outer", k=1):
            for _ in range(3):
                with spans.span("inner"):
                    f(x).block_until_ready()
    rec = sorted(spans.take()["spans"], key=lambda s: s.t0_ns)
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    evs = sorted(((e.name, e.start_ns, e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  if p.name.startswith("/host:")
                  for ln in p.lines for e in ln.events
                  if e.name.startswith(spans.PREFIX)), key=lambda e: e[1])
    assert [n for n, _, _ in evs] == ["repro:" + s.name for s in rec] \
        == ["repro:outer"] + ["repro:inner"] * 3
    for (_, _, dur), s in zip(evs, rec):
        assert abs(dur - (s.t1_ns - s.t0_ns)) <= 50e3
    # the offsets between spans agree too: one clock, shifted
    shift = [e[1] - s.t0_ns for e, s in zip(evs, rec)]
    assert max(shift) - min(shift) <= 50e3


def test_analyzer_spans_nest_by_window(recorder, tmp_path):
    from repro.scenarios.corpus import CORPUS

    # 16 steps of the ST job, dissimilar from step 8: the rough-set pass
    # runs on the last two windows
    _, coll = CORPUS["st/thermal-drift-onset"].build(0)
    trace = coll.collect_trace()
    spool = TraceSpool(str(tmp_path / "sp"), chunk_steps=3)
    for s in range(trace.n_steps):
        spool.append(trace.window(s, s + 1))
    spool.close()
    sp = SpooledTrace(str(tmp_path / "sp"))
    online = OnlineAnalyzer(window_steps=4, distance_backend="jax")
    online.poll(sp)                         # compile outside the session
    online = OnlineAnalyzer(window_steps=4, distance_backend="jax")
    with jax.profiler.trace(str(tmp_path / "tr"), profiler_options=_opts()):
        for start, stop in online.pending_bounds(sp):
            online.consume(sp, start, stop)
    got = spans.take()["spans"]
    by_id, parent = _tree(got)
    windows = [s for s in got if s.name == "online.consume"]
    assert [(s.attrs["start"], s.attrs["stop"]) for s in windows] == \
        [(0, 4), (4, 8), (8, 12), (12, 16)]
    assert all(s.parent is None for s in windows)
    want = {"spool.window": "online.consume", "spool.load": "spool.window",
            "spool.assemble": "spool.window",
            "analyzer.reduce": "online.consume",
            "analyzer.dissimilarity": "online.consume",
            "analyzer.disparity": "online.consume",
            "analyzer.rootcause": "online.consume",
            "lockstep.round": None}
    for s in got:
        if s.name in want and want[s.name]:
            assert parent(s) == want[s.name], s
    names = {s.name for s in got}
    assert set(want) | {"roughset.discernibility", "roughset.reducts",
                        "clustering.device_wait"} <= names
    # a 4-step window over 3-step segments loads two of them
    for w in (s for s in got if s.name == "spool.window"):
        loads = [s for s in got if s.name == "spool.load"
                 and s.parent == w.id]
        assert len(loads) == 2 and all(s.attrs["bytes"] > 0 for s in loads)
    # every span of a window lies inside it, and within its parent
    for s in got:
        p = by_id.get(s.parent)
        if p is not None:
            assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns
    for s in got:
        if s.name == "roughset.discernibility":
            assert s.attrs["objects"] > 0 and s.attrs["clauses"] >= 0
            assert 0 <= s.attrs["pairs"] <= \
                s.attrs["objects"] * (s.attrs["objects"] - 1) // 2
            assert 1 <= s.attrs["classes"] <= s.attrs["objects"]
            assert 0 <= s.attrs["class_pairs"] <= min(
                s.attrs["pairs"],
                s.attrs["classes"] * (s.attrs["classes"] - 1) // 2)
        if s.name == "lockstep.round":
            assert s.attrs["trials"] >= 1 and s.attrs["seeds"] >= 1
            assert any(c.parent == s.id and c.attrs["site"] == "lockstep"
                       for c in got if c.name == "clustering.device_wait")


def test_engine_spans_carry_the_request(recorder, tmp_path):
    from repro.configs import get_arch
    from repro.models import build
    from repro.scenarios.traffic import TrafficConfig, generate_traffic
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.runtime import JitBackend

    cfg = get_arch("st-100m").smoke
    api = build(cfg)
    params, _ = api.init(jax.random.key(0))
    traffic = generate_traffic(TrafficConfig(
        n_requests=3, arrival_rate=10.0, length_buckets=(8,),
        length_mix=(1.0,), gen_len=2, vocab=cfg.vocab), seed=0)
    backend = JitBackend(cfg, api, params, lanes=2, max_len=11,
                         prefill_chunk=8, seed=0)
    engine = ServeEngine(ServeConfig(
        lanes=2, max_len=11, prefill_chunk=8,
        trace_spool_dir=str(tmp_path / "sp"), trace_chunk_steps=2),
        traffic, backend)
    backend.warmup()
    engine.step()                           # compile the sampler
    before = engine.tokens_decode
    with jax.profiler.trace(str(tmp_path / "tr"), profiler_options=_opts()):
        while engine.step():
            pass
    got = spans.take()["spans"]
    by_id, parent = _tree(got)
    steps = [s for s in got if s.name == "serve.step"]
    assert len(steps) == engine.step_idx - 1
    assert sum(s.attrs["decode_tokens"] for s in steps) \
        == engine.tokens_decode - before
    for s in got:
        if s.name in ("serve.schedule", "serve.execute", "spool.append"):
            assert parent(s) == "serve.step"
        if s.name == "spool.flush":
            assert parent(s) == "spool.append" and s.attrs["bytes"] > 0
        if s.name in ("serve.dispatch", "serve.wait"):
            assert parent(s) == "serve.execute"
            rec = engine.records[s.attrs["rid"]]
            assert rec.lane == s.attrs["lane"]
            assert s.attrs["kind"] in ("prefill", "decode", "sample")
    calls = [s for s in got if s.name == "serve.dispatch"]
    waits = [s for s in got if s.name == "serve.wait"]
    assert [s.attrs for s in calls] == [s.attrs for s in waits]
    assert {s.attrs["kind"] for s in calls} == {"prefill", "decode",
                                                 "sample"}
    assert any(s.name == "spool.flush" for s in got)


def test_spool_spans_count_member_bytes(recorder, tmp_path):
    """``RegionTrace.save`` and ``load`` count their member bytes into the
    span around them: what a flush wrote stored is what a load of that
    segment did not inflate."""
    import numpy as np

    from repro.core import RegionTrace

    rng = np.random.default_rng(5)
    shape = (1, 1, 64, 3)
    spool = TraceSpool(str(tmp_path / "sp"), chunk_steps=2)
    with jax.profiler.trace(str(tmp_path / "tr"), profiler_options=_opts()):
        for _ in range(4):
            spool.append(RegionTrace(
                region_ids=[1, 2, 3], n_processes=64,
                data={"wall_time": rng.standard_normal(shape),
                      "vmem_pressure": np.full(shape, 0.5)}))
        sp = SpooledTrace(str(tmp_path / "sp"))
        sp.window(0, 4)
    spans.annotate(raw_bytes=1)             # no open span: nothing to set
    got = spans.take()["spans"]
    flushes = [s.attrs for s in got if s.name == "spool.flush"]
    loads = [s.attrs for s in got if s.name == "spool.load"]
    assert len(flushes) == len(loads) == 2
    for f, ld in zip(flushes, loads):
        assert 0 < f["stored_bytes"] < f["raw_bytes"] == ld["raw_bytes"]
        assert ld["inflated_bytes"] == f["raw_bytes"] - f["stored_bytes"]

"""The serving lane end-to-end: deterministic scheduler, cost-model
engine, serving fault corpus, spool round-trip (a finalized serving spool
is byte-identical to the in-memory artifact and replays offline through
analyze_trace.py to the in-process verdict), and the live-tail acceptance
pin — an OnlineAnalyzer tailing the engine's spool reports the injected
bottleneck's onset window while the traffic is still in flight."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import FLOPS, WALL_TIME, AutoAnalyzer
from repro.core.trace import RegionTrace
from repro.scenarios import (CORPUS, ServingFaultCollector, corpus_entries,
                             run_entry, saturated_sessions)
from repro.scenarios import faults as F
from repro.scenarios.traffic import TrafficConfig, generate_traffic
from repro.serve import (CostModelBackend, ServeConfig, ServeEngine,
                         ServeScheduler)
from repro.stream import OnlineAnalyzer, SpooledTrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVING = [e.name for e in corpus_entries(backend="serving")]
SEEDS = (0, 1, 7)


def _load_script(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"script_{name}", os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _event_key(ev):
    return (ev.lane, None if ev.request is None else ev.request.rid,
            ev.new_request, ev.prefill_tokens, ev.prefill_start,
            ev.decode_tokens, ev.decode_pos, ev.kv_tokens,
            ev.sample_tokens, ev.occupancy, ev.finished)


class TestScheduler:
    def test_request_lifecycle(self):
        """One P=16/G=6 request at chunk 8 occupies its lane for exactly
        ceil(P/chunk) + G = 8 steps: two prefill chunks, six decodes."""
        traffic = saturated_sessions(1, 1)
        sched = ServeScheduler(traffic, lanes=1, prefill_chunk=8, max_len=24)
        evs = []
        s = 0
        while not sched.done:
            evs.append(sched.step(s)[0])
            s += 1
        assert s == 8 and sched.completed == 1
        assert [e.prefill_tokens for e in evs] == [8, 8, 0, 0, 0, 0, 0, 0]
        assert [e.prefill_start for e in evs[:2]] == [0, 8]
        assert [e.decode_tokens for e in evs] == [0, 0, 1, 1, 1, 1, 1, 1]
        assert [e.decode_pos for e in evs[2:]] == [16, 17, 18, 19, 20, 21]
        assert all(e.kv_tokens == (8 if e.prefill_tokens else 1)
                   for e in evs)
        assert evs[0].new_request and not any(e.new_request for e in evs[1:])
        assert evs[-1].finished
        assert evs[0].occupancy == 8 / 24 and evs[-1].occupancy == 22 / 24
        rec = sched.records[0]
        assert (rec.start_step, rec.prefill_done_step, rec.finish_step,
                rec.lane) == (0, 1, 7, 0)

    def test_back_to_back_saturation(self):
        """A finishing lane frees at end of step and picks up the next
        session request the following step — 4 requests/lane drain in
        exactly 4 * 8 steps with no idle events."""
        sched = ServeScheduler(saturated_sessions(4, 4), lanes=4,
                               prefill_chunk=8, max_len=24)
        s = 0
        while not sched.done:
            evs = sched.step(s)
            assert all(e.request is not None for e in evs)
            s += 1
        assert s == 32 and sched.completed == 16

    def test_sticky_sessions_pin_lanes(self):
        sched = ServeScheduler(saturated_sessions(2, 2), lanes=2,
                               prefill_chunk=8, max_len=24)
        s = 0
        while not sched.done:
            sched.step(s)
            s += 1
        for rec in sched.records.values():
            assert rec.lane == rec.session % 2

    def test_sessionless_shared_fifo(self):
        reqs = [dataclasses.replace(r, session=None)
                for r in saturated_sessions(1, 3)]
        sched = ServeScheduler(reqs, lanes=2, prefill_chunk=8, max_len=24)
        evs = sched.step(0)
        # lowest free lane takes the head of the shared queue
        assert evs[0].request.rid == 0 and evs[1].request.rid == 1

    def test_deterministic_replay(self):
        """Same traffic -> the identical event stream (the property that
        lets the cost-model and jitted backends share one schedule)."""
        t = lambda: saturated_sessions(4, 3, stagger=1)
        a, b = (ServeScheduler(t(), 4, 8, 24) for _ in range(2))
        for s in range(200):
            if a.done:
                break
            assert [_event_key(e) for e in a.step(s)] == \
                   [_event_key(e) for e in b.step(s)]
        assert a.done and b.done

    def test_validation(self):
        with pytest.raises(ValueError):
            ServeScheduler(saturated_sessions(1, 1), 1, 8, max_len=16)
        with pytest.raises(ValueError):
            ServeScheduler([], lanes=0, prefill_chunk=8, max_len=24)
        with pytest.raises(ValueError):
            ServeConfig(lanes=0)


class TestCostModelEngine:
    def _run(self, traffic, steps=32, **bk):
        backend = CostModelBackend(lanes=4, seed=0, **bk)
        engine = ServeEngine(ServeConfig(lanes=4, max_len=24,
                                         prefill_chunk=8, max_steps=steps),
                             traffic, backend)
        engine.run()
        return engine

    def test_clean_baseline_is_flat(self):
        """Saturated synchronized sessions: no verdict of either kind on
        the whole run, and no persistent window verdict — the 0.9
        precision floor's foundation."""
        engine = self._run(saturated_sessions(4, 4))
        assert engine.trace.n_steps == 32
        v = AutoAnalyzer(engine.tree).analyze_trace(engine.trace).verdict
        assert not v.dissimilar and not v.disparity_paths
        online = OnlineAnalyzer(tree=engine.tree, window_steps=8, persist=2)
        online.process_trace(engine.trace)
        assert online.onset() is None

    def test_moe_routing_skew_is_emergent(self):
        """Hot-prompt traffic alone concentrates expert FLOPS: no fault
        injected, yet the hot expert carries ~17x a sibling's work —
        exactly the signal HotExpertRouting conditions on."""
        engine = self._run(saturated_sessions(4, 2, hot=True), steps=16,
                           moe_experts=4)
        tr = engine.trace
        flops = tr.metric(FLOPS)
        per_expert = [float(flops[:, :, :, tr.col(
            engine.tree.by_path(f"serve/moe/expert_{e}").region_id)].sum())
            for e in range(4)]
        assert per_expert[0] > 10 * max(per_expert[1:])

    def test_throughput_split_and_meta(self):
        engine = self._run(saturated_sessions(4, 2), steps=None)
        tp = engine.throughput()
        assert tp["requests_completed"] == 8
        assert tp["tokens_prefill"] == 8 * 16
        assert tp["tokens_decode"] == 8 * 6
        assert tp["prefill_tok_per_s"] > 0 and tp["decode_tok_per_s"] > 0
        meta = engine.trace.meta
        assert meta["collector"] == "serve"
        assert meta["requests_completed"] == 8
        assert meta["tokens_prefill"] == 128
        assert meta["tokens_decode"] == 48

    def test_throughput_is_tokens_over_the_run_wall(self):
        """Every rate divides by the same wall, the run's, so prefill
        and decode rates add up to the total rate."""
        engine = self._run(saturated_sessions(4, 2), steps=None)
        tp = engine.throughput()
        assert tp["wall_s"] == engine.wall_s > 0
        assert tp["decode_tok_per_s"] == pytest.approx(48 / engine.wall_s)
        assert tp["prefill_tok_per_s"] + tp["decode_tok_per_s"] \
            == pytest.approx(tp["tok_per_s"])


class TestServingCorpus:
    def test_registry_shape(self):
        assert len(SERVING) >= 4
        entries = [CORPUS[n] for n in SERVING]
        assert {e.truth.kind for e in entries} >= \
               {"dissimilarity", "disparity"}
        assert all(e.serving is not None for e in entries)

    @pytest.mark.parametrize("seed", SEEDS)
    @pytest.mark.parametrize("name", SERVING)
    def test_entry_recovers_ground_truth(self, name, seed):
        r = run_entry(CORPUS[name], seed=seed)
        assert r.recall == 1.0, (
            f"{name}@{seed}: missed {sorted(r.missed)}")
        assert r.cause_recall == 1.0, (
            f"{name}@{seed}: causes not recovered at the planted paths")
        assert r.precision >= r.entry.min_precision, (
            f"{name}@{seed}: precision {r.precision:.2f} "
            f"(spurious: {sorted(r.spurious)})")
        assert r.served, (
            f"{name}@{seed}: served {r.completed} < "
            f"{r.entry.serving.min_completed}")
        assert r.passed

    @pytest.mark.parametrize("name", SERVING)
    def test_entry_deterministic(self, name):
        """The cost-model backend has no wall-clock dependence: same seed
        -> bit-identical verdict and completion count."""
        a = run_entry(CORPUS[name], seed=7)
        b = run_entry(CORPUS[name], seed=7)
        assert a.verdict == b.verdict
        assert a.completed == b.completed


class TestServeSpoolRoundTrip:
    def test_finalized_spool_byte_identical_and_replays_offline(
            self, tmp_path, capsys):
        """The serving acceptance pin: a faulted serving run collected
        through the spool finalizes into the very bytes the in-memory
        merge path saves, and replaying the artifact through
        analyze_trace.py yields the in-process verdict exactly.

        The monolithic twin is rebuilt independently from the step traces
        captured at the spool boundary, so the comparison is genuinely
        streamed-vs-in-memory."""
        d = str(tmp_path / "spool")
        run = str(tmp_path / "run.npz")
        scfg = ServeConfig(lanes=4, max_len=24, prefill_chunk=8,
                           max_steps=32, trace_spool_dir=d,
                           trace_chunk_steps=4, trace_path=run,
                           trace_meta={"analyzer_kw": {}})
        collector = ServingFaultCollector(
            scfg, saturated_sessions(4, 4), (F.KVCacheThrash(),), seed=0)
        engine = collector.engine
        captured = []
        real_append = engine.spool.append
        engine.spool.append = lambda st: (captured.append(st),
                                          real_append(st))
        collector.collect_trace()
        assert engine.trace.n_steps == 32 and len(captured) == 32

        # in-memory twin, replayed on the captured step traces
        mono_trace = RegionTrace.merge(captured)
        mono_trace.meta = engine._final_meta(mono_trace.meta)
        mono = str(tmp_path / "mono.npz")
        mono_trace.save(mono)
        sp = SpooledTrace(d)
        assert sp.complete
        fin = str(tmp_path / "fin.npz")
        sp.finalize(fin)
        with open(run, "rb") as f:
            want = f.read()
        for other in (mono, fin):
            with open(other, "rb") as f:
                assert f.read() == want, f"{other} diverged from {run}"

        in_proc = AutoAnalyzer(collector.tree).analyze_collector(
            collector).verdict
        assert "serve/kv_append" in in_proc.disparity_paths

        # offline replay, the analyze_trace.py recipe byte-for-byte
        loaded = RegionTrace.load(run)
        kw = dict(loaded.meta.get("analyzer_kw", {}))
        from repro.core import tree_from_schema
        offline = AutoAnalyzer(tree_from_schema(loaded.schema),
                               **kw).analyze_trace(loaded).verdict
        assert offline == in_proc

        # and through the actual script surface
        mod = _load_script("analyze_trace")
        assert mod.main([run, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["verdict"] == in_proc.doc()

    def test_live_tail_reports_onset_in_flight(self, tmp_path):
        """Acceptance: an OnlineAnalyzer tailing the engine's spool
        localizes the step-16 KV-thrash onset to window 2 of the 8-step
        windows while the traffic run is still in flight — detection
        lands a third of the run before the spool closes."""
        d = str(tmp_path / "spool")
        scfg = ServeConfig(lanes=4, max_len=24, prefill_chunk=8,
                           trace_spool_dir=d, trace_chunk_steps=4,
                           trace_meta={"analyzer_kw": {}})
        collector = ServingFaultCollector(
            scfg, saturated_sessions(4, 6),
            (F.KVCacheThrash(onset_step=16),), seed=0)
        engine = collector.engine
        online = OnlineAnalyzer(tree=collector.tree, window_steps=8,
                                persist=2)
        sp = None
        detected_at = None
        while engine.step():
            if sp is None and engine.step_idx >= scfg.trace_chunk_steps:
                sp = SpooledTrace(d)
            if sp is not None and detected_at is None:
                online.poll(sp)
                if online.onset("disparity") is not None:
                    detected_at = engine.step_idx
        assert engine.step_idx == 48 and engine.completed == 24
        # 4 complete windows (32 flushed steps) suffice: onset reported
        # 16 steps before the run drains
        assert detected_at is not None and detected_at <= 36
        assert not engine.sched.done or detected_at < engine.step_idx
        assert online.onset("disparity") == 2
        assert "serve/kv_append" in online.log.windows[2].paths("disparity")
        # the pre-onset windows stayed clean
        assert not online.log.windows[0].flagged()
        assert not online.log.windows[1].flagged()
        engine.finalize_trace()
        online.poll(sp)
        assert online.onset("disparity") == 2
        assert len(online.log.windows) == 6


@pytest.mark.slow
class TestJitBackendSmoke:
    def test_jitted_serve_smoke(self):
        """The real jitted model through the same engine: chunked prefill,
        per-lane decode states, measured walls in the serving regions, and
        warmup-excluded split throughput."""
        import jax

        from repro.configs import get_arch
        from repro.models import build
        from repro.serve.runtime import JitBackend, supports_chunk

        cfg = get_arch("st-100m").smoke
        assert supports_chunk(cfg)
        api = build(cfg)
        params, _ = api.init(jax.random.key(0))
        traffic = generate_traffic(TrafficConfig(
            n_requests=3, arrival_rate=10.0, length_buckets=(8,),
            length_mix=(1.0,), gen_len=2, vocab=cfg.vocab), seed=0)
        backend = JitBackend(cfg, api, params, lanes=2, max_len=11,
                             prefill_chunk=8, seed=0)
        engine = ServeEngine(ServeConfig(lanes=2, max_len=11,
                                         prefill_chunk=8), traffic, backend)
        engine.run()
        assert engine.completed == 3
        assert sorted(backend.outputs) == [0, 1, 2]
        assert all(len(v) == 2 for v in backend.outputs.values())
        tr = engine.trace
        assert tr.meta["collector"] == "serve"
        assert tr.meta["derived"] is True and "cpu_tick" in tr.meta
        wall = tr.metric(WALL_TIME)
        for path in ("serve/prefill", "serve/decode", "serve/sample"):
            rid = backend.tree.by_path(path).region_id
            assert float(wall[:, :, :, tr.col(rid)].sum()) > 0.0, path
        tp = engine.throughput()
        assert tp["prefill_tok_per_s"] > 0 and tp["decode_tok_per_s"] > 0
        assert backend.nonfinite_samples == 0

    def test_nonfinite_logits_are_counted(self):
        """NaN weights still sample in-vocabulary tokens (argmax of a NaN
        row is an index); the backend counts every such sampled row."""
        import jax
        import jax.numpy as jnp

        from repro.configs import get_arch
        from repro.models import build
        from repro.serve.runtime import JitBackend

        cfg = get_arch("st-100m").smoke
        api = build(cfg)
        params, _ = api.init(jax.random.key(0))
        params = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan)
                              if jnp.issubdtype(x.dtype, jnp.floating)
                              else x, params)
        traffic = generate_traffic(TrafficConfig(
            n_requests=2, arrival_rate=10.0, length_buckets=(8,),
            length_mix=(1.0,), gen_len=2, vocab=cfg.vocab), seed=0)
        backend = JitBackend(cfg, api, params, lanes=2, max_len=11,
                             prefill_chunk=8, seed=0)
        ServeEngine(ServeConfig(lanes=2, max_len=11, prefill_chunk=8),
                    traffic, backend).run()
        assert all(0 <= t < cfg.vocab
                   for v in backend.outputs.values() for t in v)
        assert backend.nonfinite_samples == 4

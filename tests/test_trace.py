"""RegionTrace layer: round-trips, reductions, windowing, offline analysis.

The contract the trace layer must keep (ISSUE 4 / paper §4-§5 decoupling):
collection through the trace is *bit-identical* to the old fused path —
save -> load -> reduce() equals the direct in-memory RegionMetrics for all
three collector backends — and an offline analysis of a saved artifact
equals the in-process verdict exactly.
"""
import os
import zipfile

import numpy as np
import pytest

from repro.core import (CPU_TIME, RAW_METRICS, WALL_TIME, AutoAnalyzer,
                        RegionTrace, SyntheticWorkload, TimedRegionRunner,
                        schema_from_tree, st_region_tree,
                        static_trace_from_costs, tree_from_schema)
from repro.core.trace import RATE_METRICS
from repro.scenarios import faults as F
from repro.scenarios.corpus import (CORPUS, FaultedSyntheticCollector,
                                    baseline_st)


def _assert_metrics_equal(a, b):
    assert a.region_ids == b.region_ids
    assert a.n_processes == b.n_processes
    keys = set(a.data) | set(b.data)
    for k in keys:
        np.testing.assert_array_equal(a.metric(k), b.metric(k), err_msg=k)


class TestSchema:
    def test_tree_roundtrip(self):
        tree = st_region_tree()
        rebuilt = tree_from_schema(schema_from_tree(tree))
        assert schema_from_tree(rebuilt) == schema_from_tree(tree)
        # non-dense paper ids and nesting survive
        assert rebuilt.by_path("ST/cr14/cr11").region_id == 11
        assert rebuilt[14].children[0].region_id == 11

    def test_management_flag_survives(self):
        from repro.core import RegionTree
        tree = RegionTree("APP")
        tree.add("mgmt", management=True)
        tree.add("work")
        rebuilt = tree_from_schema(schema_from_tree(tree))
        assert rebuilt.by_path("APP/mgmt").management
        assert not rebuilt.by_path("APP/work").management


class TestSyntheticRoundTrip:
    def test_reduce_matches_fused_path(self):
        """collect() == collect_trace().reduce() bitwise (same rng use)."""
        tree, behaviors = baseline_st()
        a = SyntheticWorkload(tree, behaviors, 8, seed=5).collect()
        b = SyntheticWorkload(tree, behaviors, 8, seed=5) \
            .collect_trace().reduce()
        _assert_metrics_equal(a, b)

    def test_save_load_reduce_bit_identical(self, tmp_path):
        tree, behaviors = baseline_st()
        coll = FaultedSyntheticCollector(
            tree, behaviors,
            (F.ComputeStraggler("ST/cr5", procs=(6,), factor=5.0),), seed=3)
        trace = coll.collect_trace()
        path = str(tmp_path / "st.npz")
        trace.save(path)
        loaded = RegionTrace.load(path)
        assert loaded.schema == trace.schema
        assert loaded.meta == trace.meta
        _assert_metrics_equal(trace.reduce(), loaded.reduce())
        for k in trace.data:
            np.testing.assert_array_equal(trace.data[k], loaded.data[k])

    def test_faulted_collect_matches_trace_route(self):
        """The collector's trace route reproduces metric-level injection
        exactly for single-step traces (rng stream and arithmetic)."""
        tree, behaviors = baseline_st()
        fault = (F.IOHotspot("ST/cr8", extra_bytes=100e9, slowdown=6.0),)
        via_trace = FaultedSyntheticCollector(tree, behaviors, fault,
                                              seed=9).collect()
        rm = SyntheticWorkload(tree, behaviors, 8, seed=9).collect()
        direct = F.inject(tree, rm, list(fault), seed=9)
        _assert_metrics_equal(via_trace, direct)


class TestRuntimeRoundTrip:
    @pytest.fixture(scope="class")
    def rt(self):
        entry = CORPUS["runtime/compute-straggler"]
        tree, coll = entry.build(0)
        import jax
        import jax.numpy as jnp
        m = len(coll.iters)
        states = [jax.random.normal(jax.random.key(coll.seed * 131 + i),
                                    (coll.size, coll.size)) for i in range(m)]
        data = [(jax.random.normal(jax.random.key(coll.seed * 131 + 64 + i),
                                   (coll.size, coll.size)),
                 jnp.int32(coll.iters[i])) for i in range(m)]
        runner = TimedRegionRunner(tree, warmup=1, repeats=coll.repeats)
        return tree, runner.run_trace(states, data)

    def test_repeat_axis_and_tick_header(self, rt):
        _, trace = rt
        assert trace.n_repeats == 5
        assert trace.meta["cpu_tick"] > 0
        assert trace.meta["derived"]

    def test_save_load_reduce_bit_identical(self, rt, tmp_path):
        _, trace = rt
        path = str(tmp_path / "rt.npz")
        trace.save(path)
        _assert_metrics_equal(trace.reduce(), RegionTrace.load(path).reduce())

    def test_reduce_applies_min_of_repeats_and_snap(self, rt):
        _, trace = rt
        rm = trace.reduce()
        wall = trace.data[WALL_TIME].min(axis=1).sum(axis=0)
        np.testing.assert_array_equal(rm.metric(WALL_TIME), wall)
        # every region here is collective-free, so any sub-tick cpu delta
        # must have been snapped to wall
        tick = trace.meta["cpu_tick"]
        cpu = rm.metric(CPU_TIME)
        snap = (wall < tick) | (np.abs(cpu - wall) < tick)
        assert np.array_equal(cpu[snap], wall[snap])


class TestStaticRoundTrip:
    def test_save_load_reduce_bit_identical(self, tmp_path):
        from repro.core import RegionTree, static_metrics_from_costs
        tree = RegionTree("step")
        a = tree.add("embed")
        b = tree.add("mlp")
        costs = {a.region_id: {"wall_time": 0.2, "flops": 1e9, "bytes": 3e7},
                 b.region_id: {"wall_time": 0.5, "flops": 8e9, "bytes": 9e7}}
        rids = [a.region_id, b.region_id]
        trace = static_trace_from_costs(tree, rids, costs, n_processes=4)
        path = str(tmp_path / "static.npz")
        trace.save(path)
        _assert_metrics_equal(trace.reduce(), RegionTrace.load(path).reduce())
        # the classic entry point is the same reduction
        _assert_metrics_equal(
            trace.reduce(),
            static_metrics_from_costs(rids, costs, n_processes=4, tree=tree))


class TestWindowing:
    def _trace(self, n_steps=6, seed=2):
        tree, behaviors = baseline_st()
        wl = SyntheticWorkload(tree, behaviors, 8, seed=seed)
        return tree, wl.collect_trace(n_steps=n_steps)

    def test_merge_of_windows_reduces_identically(self):
        _, full = self._trace()
        merged = RegionTrace.merge([full.window(0, 2), full.window(2, 4),
                                    full.window(4)])
        assert merged.n_steps == full.n_steps
        _assert_metrics_equal(full.reduce(), merged.reduce())
        for k in full.data:
            np.testing.assert_array_equal(full.data[k], merged.data[k])

    def test_window_reduce_equals_reduce_window(self):
        _, full = self._trace()
        _assert_metrics_equal(full.window(1, 4).reduce(),
                              full.reduce(window=(1, 4)))

    def test_quantities_sum_rates_average_over_steps(self):
        _, full = self._trace(n_steps=4)
        rm = full.reduce()
        for k in RAW_METRICS:
            per_step = full.data[k].min(axis=1)
            want = (per_step.mean(axis=0) if k in RATE_METRICS
                    else per_step.sum(axis=0))
            np.testing.assert_array_equal(rm.metric(k), want, err_msg=k)

    def test_bad_windows_rejected(self):
        _, full = self._trace(n_steps=3)
        with pytest.raises(ValueError):
            full.window(2, 2)
        with pytest.raises(ValueError):
            full.window(0, 9)
        with pytest.raises(ValueError):
            full.reduce(window=(3, 3))
        with pytest.raises(ValueError):   # no silent clamp past the end
            full.reduce(window=(0, 9))
        with pytest.raises(ValueError):
            full.reduce(window=(-1, 2))

    def test_cpu_tick_snap_is_per_step(self):
        """The quantization snap must fire per step, pre-sum: per-step
        jiffy-phase noise accumulates O(S * tick) on the summed gap, which
        would escape a single-tick threshold on a long merged trace."""
        from repro.core import RegionTree
        tree = RegionTree("rt")
        tree.add("work")
        S, tick = 20, 0.01
        trace = RegionTrace.for_tree(tree, [1], 1, n_steps=S,
                                     meta={"cpu_tick": tick})
        wall = trace.metric(WALL_TIME)
        cpu = trace.metric(CPU_TIME)
        trace.metric("comm_bytes")   # zeros: a compute region
        rng = np.random.default_rng(0)
        wall[:, 0, 0, 0] = 0.05
        # each step's cpu reads within one tick of wall -> noise, not wait
        cpu[:, 0, 0, 0] = 0.05 + rng.uniform(0.5 * tick, 0.9 * tick, S)
        rm = trace.reduce()
        # summed gap ~ S * 0.7 tick >> tick, yet every step snapped
        assert rm.metric(CPU_TIME)[0, 0] == rm.metric(WALL_TIME)[0, 0]

    def test_merge_rejects_mismatched_schemas(self):
        _, a = self._trace(n_steps=2)
        tree, behaviors = baseline_st()
        del behaviors[13]
        b = SyntheticWorkload(tree, behaviors, 8, seed=2).collect_trace()
        with pytest.raises(ValueError):
            RegionTrace.merge([a, b])


class TestThermalThrottleDrift:
    def test_ramp_is_time_varying_and_ancestor_propagating(self):
        tree, behaviors = baseline_st()
        wl = SyntheticWorkload(tree, behaviors, 8, seed=0, jitter=0.0)
        trace = wl.collect_trace(n_steps=10)
        before = trace.data[WALL_TIME].copy()
        F.inject_trace(tree, trace,
                       [F.ThermalThrottleDrift("ST/cr14/cr11", procs=(2,),
                                               peak_factor=3.0)], seed=0)
        after = trace.data[WALL_TIME]
        j11, j14 = trace.col(11), trace.col(14)
        ratio = after[:, 0, 2, j11] / before[:, 0, 2, j11]
        # linear ramp: strictly increasing, reaching peak at the last step
        assert np.all(np.diff(ratio) > 0)
        assert ratio[-1] == pytest.approx(3.0)
        assert ratio[0] == pytest.approx(1.0 + 2.0 / 10)
        # inclusive parent sees the additive delta, step by step
        np.testing.assert_allclose(
            after[:, 0, 2, j14] - before[:, 0, 2, j14],
            after[:, 0, 2, j11] - before[:, 0, 2, j11])
        # untouched processes unchanged
        np.testing.assert_array_equal(after[:, 0, 0, :], before[:, 0, 0, :])

    def test_cpu_and_wall_stretch_but_flops_do_not(self):
        from repro.core import FLOPS
        tree, behaviors = baseline_st()
        trace = SyntheticWorkload(tree, behaviors, 8, seed=0) \
            .collect_trace(n_steps=6)
        flops_before = trace.data[FLOPS].copy()
        F.inject_trace(tree, trace,
                       [F.ThermalThrottleDrift("ST/cr5", procs=(1,))], seed=0)
        np.testing.assert_array_equal(trace.data[FLOPS], flops_before)
        rm = trace.reduce()
        j = rm.col(5)
        assert rm.metric(CPU_TIME)[1, j] > 2.0 * rm.metric(CPU_TIME)[0, j]


class TestOfflineAnalysis:
    def test_offline_verdict_equals_in_process(self, tmp_path):
        """The deployment story: save the artifact, rebuild the tree from
        its header on the 'analysis machine', get the same verdict."""
        entry = CORPUS["st/thermal-throttle-cr5"]
        tree, coll = entry.build(0)
        analyzer = AutoAnalyzer(tree, **dict(entry.analyzer_kw))
        in_process = analyzer.analyze_collector(coll).verdict

        path = str(tmp_path / "artifact.npz")
        coll.collect_trace().save(path)
        loaded = RegionTrace.load(path)
        offline = AutoAnalyzer(tree_from_schema(loaded.schema),
                               **dict(entry.analyzer_kw)) \
            .analyze_trace(loaded).verdict
        assert offline == in_process
        assert "ST/cr5" in offline.dissimilarity_paths

    def test_analyze_trace_script_json(self, tmp_path, capsys):
        import json
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "scripts"))
        try:
            import analyze_trace
        finally:
            sys.path.pop(0)
        entry = CORPUS["st/compute-straggler-cr5"]
        tree, coll = entry.build(0)
        path = str(tmp_path / "artifact.npz")
        trace = coll.collect_trace()
        trace.meta["analyzer_kw"] = dict(entry.analyzer_kw)
        trace.save(path)
        assert analyze_trace.main([path, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["verdict"]["dissimilar"]
        assert "ST/cr5" in doc["verdict"]["dissimilarity_paths"]


@pytest.mark.slow
class TestTrainBackend:
    def test_smoke_train_entry_and_offline_replay(self, tmp_path):
        """The train corpus entry passes, the trainer's artifact replays
        offline to the exact in-process verdict, and the straggler
        monitor was fed from the trace (shard dissimilarity observed)."""
        from repro.scenarios.corpus import run_entry_robust, score_verdict
        entry = CORPUS["train/fwdbwd-straggler-smoke"]
        tree, coll = entry.build(0)
        analyzer = AutoAnalyzer(tree, **dict(entry.analyzer_kw))
        res = analyzer.analyze_collector(coll)
        r = score_verdict(entry, res.verdict)
        if not r.passed:   # one retry, as the corpus gate allows
            r = run_entry_robust(entry, seed=1)
            assert r.passed
            return
        assert r.recall == 1.0
        # The dissimilar process must be the *injected* straggler (shard
        # 3), alone in its cluster — not a shard-0 compile artifact.
        labels = list(res.dissimilarity.baseline.labels)
        assert labels.count(labels[3]) == 1, labels

        trainer = coll.trainer
        assert trainer.trace is not None
        assert trainer.trace.n_steps == 2
        # StragglerMonitor observations came from the trace samples
        assert any(e["kind"] == "shard-dissimilarity"
                   for e in trainer.monitor.events)
        hist = trainer.history
        assert len(hist) == 2 and "per_shard_seconds" in hist[0]

        path = str(tmp_path / "train.npz")
        trainer.trace.save(path)
        loaded = RegionTrace.load(path)
        assert loaded.meta["collector"] == "train"
        offline = AutoAnalyzer(tree_from_schema(loaded.schema),
                               **loaded.meta["analyzer_kw"]) \
            .analyze_trace(loaded).verdict
        assert offline == res.verdict

    def test_healthy_traced_run_not_dissimilar(self):
        """With no injected fault the traced trainer must read healthy —
        the gate above is meaningful only if a clean run passes clean
        (e.g. no compile spike mistaken for a shard-0 straggler).
        Collected in measurement mode (repeats=3): min-of-repeats absorbs
        the scheduler bursts a loaded host throws at ~6ms regions, as
        docs/traces.md prescribes for sweeps; one retry on top."""
        from repro.scenarios.corpus import _TRAIN_KW, _train
        for attempt in range(2):
            tree, coll = _train(iters_per_shard=(1, 1, 1, 1),
                                repeats=3)(attempt)
            res = AutoAnalyzer(tree, **dict(_TRAIN_KW)) \
                .analyze_collector(coll)
            if not res.dissimilarity.exists:
                return
        assert not res.dissimilarity.exists, \
            res.verdict.dissimilarity_paths


def mixed_trace(seed=0, m=512):
    """A trace of the ST tree with noisy float64 timings and constant
    rates, each member larger than the deflate probe."""
    tree = st_region_tree()
    rids = [r.region_id for r in tree.regions()]
    rng = np.random.default_rng(seed)
    shape = (2, 1, m, len(rids))
    return RegionTrace(
        region_ids=rids, n_processes=m, n_steps=2,
        schema=schema_from_tree(tree), meta={"collector": "test"},
        data={WALL_TIME: 1.0 + 0.005 * rng.standard_normal(shape),
              CPU_TIME: 0.9 + 0.005 * rng.standard_normal(shape),
              "vmem_pressure": np.full(shape, 0.02),
              "comm_bytes": np.zeros(shape)})


class TestMemberEncoding:
    """Each member is deflated only where a probe of its bytes shrinks to
    half; noisy timings are stored, constant metrics deflated."""

    def test_noisy_stored_constant_deflated_bit_exact(self, tmp_path):
        trace = mixed_trace()
        path = str(tmp_path / "mixed.npz")
        trace.save(path)
        with zipfile.ZipFile(path) as z:
            kinds = {i.filename: i.compress_type for i in z.infolist()}
        assert kinds == {
            "__header__.npy": zipfile.ZIP_DEFLATED,
            f"metric:{CPU_TIME}.npy": zipfile.ZIP_STORED,
            f"metric:{WALL_TIME}.npy": zipfile.ZIP_STORED,
            "metric:comm_bytes.npy": zipfile.ZIP_DEFLATED,
            "metric:vmem_pressure.npy": zipfile.ZIP_DEFLATED}
        loaded = RegionTrace.load(path)
        assert loaded.meta == trace.meta and loaded.schema == trace.schema
        for k in trace.data:
            assert loaded.data[k].tobytes() == trace.data[k].tobytes(), k
        with np.load(path) as z:  # a plain .npz to any numpy reader
            np.testing.assert_array_equal(z[f"metric:{WALL_TIME}"],
                                          trace.data[WALL_TIME])

    def test_same_trace_same_bytes(self, tmp_path):
        a, b = str(tmp_path / "a.npz"), str(tmp_path / "b.npz")
        mixed_trace(seed=3).save(a)
        mixed_trace(seed=3).save(b)
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read()

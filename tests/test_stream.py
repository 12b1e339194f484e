"""Streaming layer: spool round-trips, window reassembly, online verdicts.

The contracts this file pins (ISSUE 5):

* ``SpooledTrace.finalize()`` is **byte-identical** to the monolithic
  ``RegionTrace.save`` of the same run — synthetic and train backends;
* window reassembly from segments reduces bit-identically to the same
  window of the monolithic trace, so per-window online verdicts equal an
  offline ``analyze_trace.py --per-window`` replay exactly;
* the onset detector localizes the thermal-drift corpus entry at its
  planted window across seeds {0, 7};
* the CPU-clock selection prefers the per-thread clock only when it is
  finer *and* attributable, keeping the measured-tick fallback otherwise.
"""
import hashlib
import json
import os
import struct
import subprocess
import sys
import zipfile

import numpy as np
import pytest

from repro.core import AutoAnalyzer, RegionTrace, TimedRegionRunner
from repro.core import collector as collector_mod
from repro.core.analyzer import Verdict
from repro.scenarios.corpus import CORPUS
from repro.core.trace import TraceFormatError
from repro.stream import (OnlineAnalyzer, SpooledTrace, TraceSpool,
                          WindowVerdict, WindowVerdictLog, verify_segment)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def drift_trace(seed=0):
    """The thermal-drift onset entry's trace: 16 steps, drift from step 8."""
    entry = CORPUS["st/thermal-drift-onset"]
    tree, coll = entry.build(seed)
    return entry, tree, coll.collect_trace()


def step_traces(trace):
    return [trace.window(s, s + 1) for s in range(trace.n_steps)]


def spool_up(trace, directory, chunk_steps, meta=None):
    spool = TraceSpool(directory, chunk_steps=chunk_steps)
    for st in step_traces(trace):
        spool.append(st)
    spool.close(meta=meta)
    return SpooledTrace(directory)


class TestSpool:
    def test_segmentation_and_manifest(self, tmp_path):
        _, _, trace = drift_trace()
        sp = spool_up(trace, str(tmp_path / "sp"), chunk_steps=5)
        assert sp.n_steps == 16
        assert sp.complete
        # 5 + 5 + 5 + tail 1
        assert sp.n_segments == 4
        assert [t.n_steps for t in sp.segments()] == [5, 5, 5, 1]
        assert sp.schema == trace.schema

    def test_finalize_byte_identical_synthetic(self, tmp_path):
        """The acceptance pin: streamed segments reassemble into the very
        bytes the monolithic save would have written."""
        _, _, trace = drift_trace()
        mono = str(tmp_path / "mono.npz")
        trace.save(mono)
        for chunk in (1, 5, 16):
            sp = spool_up(trace, str(tmp_path / f"sp{chunk}"),
                          chunk_steps=chunk)
            fin = str(tmp_path / f"fin{chunk}.npz")
            sp.finalize(fin)
            with open(mono, "rb") as a, open(fin, "rb") as b:
                assert a.read() == b.read(), f"chunk_steps={chunk}"

    def test_final_meta_applied(self, tmp_path):
        _, _, trace = drift_trace()
        final = {"collector": "synthetic", "note": "closed"}
        sp = spool_up(trace, str(tmp_path / "sp"), chunk_steps=4,
                      meta=final)
        assert sp.meta == final
        assert sp.to_trace().meta == final
        # ... and the monolithic twin with the same meta matches bytes
        trace.meta = dict(final)
        mono = str(tmp_path / "mono.npz")
        trace.save(mono)
        fin = str(tmp_path / "fin.npz")
        sp.finalize(fin)
        with open(mono, "rb") as a, open(fin, "rb") as b:
            assert a.read() == b.read()

    def test_window_reassembly_bit_identical(self, tmp_path):
        _, _, trace = drift_trace()
        sp = spool_up(trace, str(tmp_path / "sp"), chunk_steps=3)
        for (a, b) in [(0, 3), (2, 7), (5, 16), (0, 16), (15, 16)]:
            got = sp.window(a, b).reduce()
            want = trace.reduce(window=(a, b))
            for k in want.data:
                np.testing.assert_array_equal(got.metric(k),
                                              want.metric(k),
                                              err_msg=f"[{a},{b}) {k}")

    def test_live_tail_sees_flushed_steps(self, tmp_path):
        _, _, trace = drift_trace()
        spool = TraceSpool(str(tmp_path / "sp"), chunk_steps=2)
        steps = step_traces(trace)
        for st in steps[:5]:
            spool.append(st)
        # two chunks flushed, one step still buffered in the writer
        reader = SpooledTrace(str(tmp_path / "sp"))
        assert reader.n_steps == 4
        assert not reader.complete
        with pytest.raises(ValueError):
            reader.finalize(str(tmp_path / "early.npz"))
        for st in steps[5:]:
            spool.append(st)
        spool.close()
        reader.reload()
        assert reader.complete and reader.n_steps == 16

    def test_writer_guards(self, tmp_path):
        _, _, trace = drift_trace()
        d = str(tmp_path / "sp")
        spool = TraceSpool(d, chunk_steps=4)
        steps = step_traces(trace)
        spool.append(steps[0])
        bad = trace.window(0, 1)
        bad.region_ids = bad.region_ids[:-1]
        bad.schema = bad.schema[:-1]
        with pytest.raises(ValueError, match="disagree"):
            spool.append(RegionTrace(
                region_ids=bad.region_ids, n_processes=bad.n_processes,
                schema=bad.schema,
                data={k: v[:, :, :, :-1] for k, v in bad.data.items()}))
        spool.close()
        with pytest.raises(ValueError, match="closed"):
            spool.append(steps[1])
        with pytest.raises(ValueError, match="already contains"):
            TraceSpool(d)
        with pytest.raises(ValueError, match="no spool manifest"):
            SpooledTrace(str(tmp_path / "nowhere"))


def _verdict(flag: bool) -> Verdict:
    return Verdict(dissimilar=flag,
                   dissimilarity_paths=("X/r",) if flag else (),
                   dissimilarity_ccr_paths=(), disparity_paths=(),
                   disparity_ccr_paths=(), cause_attributes=frozenset(),
                   dissimilarity_cause_attributes=frozenset(),
                   per_path_causes=())


def _log(pattern: str, persist: int) -> WindowVerdictLog:
    log = WindowVerdictLog(persist=persist)
    for i, c in enumerate(pattern):
        log.append(WindowVerdict(index=i, start=i, stop=i + 1,
                                 verdict=_verdict(c == "T")))
    return log


class TestOnsetDetector:
    def test_persist_filters_single_blips(self):
        assert _log("FTFTTTT", persist=2).onset() == 3
        assert _log("FTFTTTT", persist=1).onset() == 1
        assert _log("FTFTFTF", persist=2).onset() is None
        assert _log("TTTT", persist=4).onset() == 0
        assert _log("TTT", persist=4).onset() is None   # not yet persisted

    def test_kind_filter(self):
        log = _log("TT", persist=2)
        assert log.onset("dissimilarity") == 0
        assert log.onset("disparity") is None

    def test_report_shape(self):
        rep = _log("FTT", persist=2).onset_report()
        assert rep["onset_window"] == 1
        assert rep["kinds"] == ["dissimilarity"]
        assert rep["paths"] == ["X/r"]
        assert _log("FFF", persist=2).onset_report() is None

    def test_out_of_order_append_rejected(self):
        log = WindowVerdictLog()
        with pytest.raises(ValueError, match="out of order"):
            log.append(WindowVerdict(index=3, start=0, stop=1,
                                     verdict=_verdict(False)))


class TestOnlineAnalyzer:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_drift_onset_window(self, seed):
        """The acceptance pin: the drifting fault is localized in time at
        its planted onset window, for both gate seeds."""
        entry, tree, trace = drift_trace(seed)
        online = OnlineAnalyzer(tree=tree, window_steps=4, persist=2)
        online.process_trace(trace)
        assert online.onset("dissimilarity") == 2
        rep = online.onset_report("dissimilarity")
        assert rep["onset_step"] == 8
        assert rep["paths"] == ["ST/cr5"]
        # the pre-onset windows are genuinely clean of dissimilarity
        assert [w.flagged("dissimilarity")
                for w in online.log.windows] == [False, False, True, True]

    def test_poll_equals_process_trace_equals_offline(self, tmp_path):
        """Streaming (poll over a growing spool), in-memory process_trace
        and the offline per-window replay agree verdict-for-verdict."""
        entry, tree, trace = drift_trace()
        offline = AutoAnalyzer(tree)
        want = [offline.analyze_trace(trace, window=(s, min(s + 4, 16)))
                .verdict for s in range(0, 16, 4)]

        mem = OnlineAnalyzer(tree=tree, window_steps=4)
        assert [w.verdict for w in mem.process_trace(trace).windows] == want

        spool = TraceSpool(str(tmp_path / "sp"), chunk_steps=3)
        online = OnlineAnalyzer(window_steps=4)   # tree from the schema
        seen = []
        reader = None
        for st in step_traces(trace):
            spool.append(st)
            try:
                reader = reader or SpooledTrace(str(tmp_path / "sp"))
            except ValueError:
                continue                           # nothing flushed yet
            seen += online.poll(reader)
        spool.close()
        seen += online.poll(reader)
        assert [w.verdict for w in seen] == want
        assert [(w.start, w.stop) for w in seen] == \
            [(0, 4), (4, 8), (8, 12), (12, 16)]

    def test_stride_and_trailing_partial(self):
        _, tree, trace = drift_trace()
        online = OnlineAnalyzer(tree=tree, window_steps=5)
        log = online.process_trace(trace)
        assert [(w.start, w.stop) for w in log.windows] == \
            [(0, 5), (5, 10), (10, 15), (15, 16)]
        hop = OnlineAnalyzer(tree=tree, window_steps=8, stride=4)
        assert [(w.start, w.stop)
                for w in hop.process_trace(trace).windows] == \
            [(0, 8), (4, 12), (8, 16), (12, 16)]

    def test_live_tail_resolves_provisional_analyzer_kw(self, tmp_path):
        """A live (not yet closed) spool carries the producer's run-level
        meta provisionally, so the online analyzer resolves analyzer_kw
        from the very first poll — identical to the post-close replay."""
        _, tree, trace = drift_trace()
        spool = TraceSpool(str(tmp_path / "sp"), chunk_steps=4,
                           meta={"analyzer_kw": {"threshold_frac": 9.0}})
        for st in step_traces(trace)[:8]:
            spool.append(st)
        reader = SpooledTrace(str(tmp_path / "sp"))
        assert not reader.complete
        assert reader.meta == {"analyzer_kw": {"threshold_frac": 9.0}}
        online = OnlineAnalyzer(window_steps=4, persist=1)
        online.poll(reader)
        # absurd threshold from the provisional meta mutes everything,
        # proving the live analyzer was built from it
        assert len(online.log.windows) == 2
        assert online.onset("dissimilarity") is None
        # close() replaces the provisional meta with the definitive one
        for st in step_traces(trace)[8:]:
            spool.append(st)
        spool.close(meta={"collector": "synthetic", "final": True})
        reader.reload()
        assert reader.meta == {"collector": "synthetic", "final": True}

    def test_analyzer_kw_resolution_matches_header(self, tmp_path):
        """Header analyzer_kw is the default, explicit kwargs override —
        the same contract as scripts/analyze_trace.py."""
        _, tree, trace = drift_trace()
        trace.meta["analyzer_kw"] = {"threshold_frac": 9.0}  # absurd: mute
        online = OnlineAnalyzer(window_steps=4, persist=2)
        online.process_trace(trace)
        assert online.onset("dissimilarity") is None   # muted by header kw
        override = OnlineAnalyzer(window_steps=4, persist=2,
                                  analyzer_kw={"threshold_frac": 0.10})
        override.process_trace(trace)
        assert override.onset("dissimilarity") == 2

    def test_analyzer_exception_propagates(self, monkeypatch):
        """An analyzer that fails — a device backend that cannot import
        or compile, a bug — is a fault of the watcher, not of the window:
        it raises instead of logging a degraded window."""
        _, tree, trace = drift_trace()

        def boom(self, trace, window=None):
            raise RuntimeError("backend down")

        monkeypatch.setattr(AutoAnalyzer, "analyze_trace", boom)
        online = OnlineAnalyzer(tree=tree, window_steps=4)
        with pytest.raises(RuntimeError, match="backend down"):
            online.process_trace(trace)
        assert online.log.windows == []

    def test_non_finite_samples_still_degrade(self):
        """Bad data stays a degraded window: the stream goes on."""
        _, tree, trace = drift_trace()
        metric = sorted(trace.data)[0]
        trace.data[metric][5] = np.nan
        online = OnlineAnalyzer(tree=tree, window_steps=4)
        log = online.process_trace(trace)
        assert [w.degraded for w in log.windows] == [False, True, False,
                                                     False]
        assert log.windows[1].reason == "non-finite samples"


class TestWatchTrainCLI:
    def test_analyzer_exception_exits_nonzero(self, tmp_path):
        """A window the analyzer fails on ends the watcher with a nonzero
        exit, not a DEGRADED line and exit 0."""
        _, _, trace = drift_trace()
        d = str(tmp_path / "sp")
        spool_up(trace, d, chunk_steps=4)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/watch_train.py"),
             d, "--analyzer-kw", '{"threshold_frac": "x"}'],
            capture_output=True, text=True,
            env={**os.environ,
                 "PYTHONPATH": os.path.join(REPO, "src")})
        assert out.returncode not in (0, 3, 4)
        assert "TypeError" in out.stderr
        assert "DEGRADED" not in out.stdout

    def test_json_stream_and_finalize(self, tmp_path):
        _, _, trace = drift_trace()
        d = str(tmp_path / "sp")
        spool_up(trace, d, chunk_steps=4)
        fin = str(tmp_path / "fin.npz")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/watch_train.py"),
             d, "--window", "4", "--kind", "dissimilarity", "--json",
             "--finalize", fin],
            capture_output=True, text=True,
            env={**os.environ,
                 "PYTHONPATH": os.path.join(REPO, "src")})
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        assert doc["complete"] and doc["n_steps"] == 16
        assert len(doc["windows"]) == 4
        assert doc["onset"]["onset_window"] == 2
        assert doc["onset"]["paths"] == ["ST/cr5"]
        # finalized artifact byte-identical to the monolithic save
        mono = str(tmp_path / "mono.npz")
        trace.save(mono)
        with open(mono, "rb") as a, open(fin, "rb") as b:
            assert a.read() == b.read()

    def test_incomplete_spool_exits_nonzero(self, tmp_path):
        _, _, trace = drift_trace()
        spool = TraceSpool(str(tmp_path / "sp"), chunk_steps=2)
        for st in step_traces(trace)[:6]:
            spool.append(st)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/watch_train.py"),
             str(tmp_path / "sp")],
            capture_output=True, text=True,
            env={**os.environ,
                 "PYTHONPATH": os.path.join(REPO, "src")})
        assert out.returncode == 3
        assert "still in progress" in out.stderr


class TestCpuClockSelection:
    @pytest.fixture(autouse=True)
    def reset_cache(self):
        saved = TimedRegionRunner._cpu_clock
        TimedRegionRunner._cpu_clock = None
        yield
        TimedRegionRunner._cpu_clock = saved

    def test_thread_clock_needs_finer_tick_and_attribution(self, monkeypatch):
        import time as time_mod
        fake_thread = lambda: 0.0
        monkeypatch.setattr(time_mod, "clock_gettime",
                            lambda _id: fake_thread(), raising=False)
        monkeypatch.setattr(time_mod, "CLOCK_THREAD_CPUTIME_ID", 3,
                            raising=False)
        monkeypatch.setattr(time_mod, "clock_getres", lambda _id: 1e-9,
                            raising=False)
        monkeypatch.setattr(collector_mod, "_cpu_clock_tick", lambda: 0.01)
        ticks = {"thread": 1e-6}
        monkeypatch.setattr(collector_mod, "_measure_tick",
                            lambda clock, res: ticks["thread"])
        # finer AND attributable -> thread
        monkeypatch.setattr(collector_mod, "_thread_clock_attributes_jax",
                            lambda clock, tick: True)
        _, tick, name = collector_mod._pick_cpu_clock()
        assert (name, tick) == ("thread", 1e-6)
        # finer but NOT attributable (XLA worker threads) -> process
        monkeypatch.setattr(collector_mod, "_thread_clock_attributes_jax",
                            lambda clock, tick: False)
        assert collector_mod._pick_cpu_clock()[2] == "process"
        # coarser-or-equal tick -> process without probing
        ticks["thread"] = 0.01
        monkeypatch.setattr(collector_mod, "_thread_clock_attributes_jax",
                            lambda clock, tick: pytest.fail("probed"))
        assert collector_mod._pick_cpu_clock()[2] == "process"

    def test_runner_records_chosen_clock(self, monkeypatch):
        """The selection lands in the trace header; the measured-tick
        fallback (None tick) keeps the advertised resolution and is not
        cached, so it is re-attempted next run."""
        import time as time_mod
        monkeypatch.setattr(
            collector_mod, "_pick_cpu_clock",
            lambda: (time_mod.process_time, None, "process"))
        from repro.core import RegionTree
        tree = RegionTree("t")
        tree.add("r", fn=lambda s, d: s)
        runner = TimedRegionRunner(tree, warmup=0, repeats=1)
        trace = runner.run_trace([0.0], [0.0])
        assert trace.meta["cpu_clock"] == "process"
        assert trace.meta["cpu_tick"] == \
            time_mod.get_clock_info("process_time").resolution
        assert TimedRegionRunner._cpu_clock is None   # retried next time

    def test_ambient_selection_is_cached_and_sane(self):
        clock, tick, name = collector_mod._pick_cpu_clock()
        assert name in ("thread", "process")
        assert tick is None or tick > 0
        x = clock()
        assert isinstance(x, float)


@pytest.mark.slow
class TestTrainSpoolEndToEnd:
    def test_spooled_smoke_train_finalize_byte_identical(self, tmp_path):
        """The train-backend acceptance pin: a real region-instrumented
        run collected through the spool finalizes into the very bytes the
        in-memory merge path would have saved, and the per-step online
        window stream flags the straggler from window 0.

        The monolithic twin is built *independently* from the exact step
        traces the trainer appended (captured at the spool boundary), so
        the comparison is genuinely streamed-vs-in-memory — not two reads
        of the same reassembly."""
        from repro.configs import get_arch
        from repro.data import DataConfig
        from repro.optim import AdamWConfig
        from repro.train import Trainer, TrainerConfig
        cfg = get_arch("st-100m").smoke
        d = str(tmp_path / "spool")
        t = Trainer(
            cfg, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
            DataConfig(seq_len=32, global_batch=8, vocab=cfg.vocab),
            TrainerConfig(steps=3, ckpt_dir=None, ckpt_every=0, seed=0,
                          trace_shards=4, trace_iters=(1, 1, 1, 12),
                          trace_spool_dir=d, trace_chunk_steps=2,
                          trace_path=str(tmp_path / "run.npz"),
                          trace_meta={"analyzer_kw":
                                      {"threshold_frac": 0.45}}))
        captured = []
        real_append = t.spool.append
        t.spool.append = lambda st: (captured.append(st), real_append(st))
        t.run()
        assert t.trace.n_steps == 3 and len(captured) == 3
        # the in-memory path, replayed on the captured step traces
        mono_trace = RegionTrace.merge(captured)
        mono_trace.meta = t._final_meta(mono_trace.meta)
        mono = str(tmp_path / "mono.npz")
        mono_trace.save(mono)
        sp = SpooledTrace(d)
        assert sp.complete and sp.n_segments == 2
        fin = str(tmp_path / "fin.npz")
        sp.finalize(fin)
        for other in (str(tmp_path / "run.npz"), mono):
            with open(other, "rb") as a, open(fin, "rb") as b:
                assert a.read() == b.read(), other
        online = OnlineAnalyzer(window_steps=1, persist=2)
        online.poll(sp)
        assert online.onset("dissimilarity") == 0


class TestOnsetBisection:
    """Step-granular onset (ISSUE 6 satellite): with overlapping windows
    (stride < window_steps) the report bisects the onset *step* inside
    the first flagged window instead of reporting the window boundary."""

    @pytest.mark.parametrize("stride", [1, 2, 3])
    def test_refines_to_planted_step(self, stride):
        """Drift planted at step 8: tumbling windows can only say
        "window [8, 12)"; overlapping ones must pin the step to 8 (or 9 —
        a single drifting step may sit below the detection threshold)."""
        _, tree, trace = drift_trace()
        online = OnlineAnalyzer(tree=tree, window_steps=4, stride=stride,
                                persist=2)
        online.process_trace(trace)
        rep = online.onset_report("dissimilarity")
        assert rep is not None
        assert 8 <= rep["onset_step"] <= 9
        # the refined step stays inside the flagged window
        assert rep["window"][0] <= rep["onset_step"] < rep["window"][1]

    def test_tumbling_keeps_window_boundary(self):
        """No overlap, no refinement: the report's onset_step stays the
        window start (exactly what the log itself says)."""
        _, tree, trace = drift_trace()
        online = OnlineAnalyzer(tree=tree, window_steps=4, persist=2)
        online.process_trace(trace)
        rep = online.onset_report("dissimilarity")
        assert rep["onset_step"] == 8 == rep["window"][0]
        assert online.log.onset_report("dissimilarity")["onset_step"] == 8

    def test_spool_backed_bisection(self, tmp_path):
        """The refinement works identically when the source is a spool:
        the onset window is reassembled from its segments for the prefix
        re-analysis."""
        _, tree, trace = drift_trace()
        sp = spool_up(trace, str(tmp_path / "sp"), chunk_steps=3)
        online = OnlineAnalyzer(tree=tree, window_steps=4, stride=2,
                                persist=2)
        online.poll(sp)
        rep = online.onset_report("dissimilarity")
        assert rep is not None
        assert 8 <= rep["onset_step"] <= 9


def noisy_spool(directory, m=256):
    """Two 4-step segments of a trace whose timings are float64 noise, so
    each segment holds stored members beside deflated ones."""
    _, _, base = drift_trace()
    rids = base.region_ids
    rng = np.random.default_rng(11)
    shape = (8, 1, m, len(rids))
    trace = RegionTrace(
        region_ids=rids, n_processes=m, n_steps=8,
        schema=base.schema, meta={"collector": "test"},
        data={"wall_time": 1.0 + 0.005 * rng.standard_normal(shape),
              "cpu_time": 0.9 + 0.005 * rng.standard_normal(shape),
              "vmem_pressure": np.full(shape, 0.02)})
    return trace, spool_up(trace, directory, chunk_steps=4)


def stored_data_offset(path, member):
    """File offset of the middle of a ``ZIP_STORED`` member's data."""
    with zipfile.ZipFile(path) as z:
        info = z.getinfo(member)
    assert info.compress_type == zipfile.ZIP_STORED
    with open(path, "rb") as f:
        f.seek(info.header_offset)
        local = f.read(30)
    name_len, extra_len = struct.unpack("<HH", local[26:30])
    return info.header_offset + 30 + name_len + extra_len \
        + info.file_size // 2


def flip_byte(path, offset):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


class TestSegmentEncoding:
    def test_legacy_deflated_segment_still_loads(self, tmp_path):
        """A segment written by the old writer, every member deflated by
        ``np.savez_compressed`` under the same header, reads through
        ``SpooledTrace.window`` to the same samples."""
        trace, sp = noisy_spool(str(tmp_path / "sp"))
        seg = sp.segment_records[1]
        path = os.path.join(sp.directory, seg["file"])
        with np.load(path) as z:
            header = str(z["__header__"])
        new = sp.segment(1)
        with open(path, "wb") as f:
            np.savez_compressed(f, __header__=header, **{
                f"metric:{k}": new.data[k] for k in sorted(new.data)})
        with zipfile.ZipFile(path) as z:
            assert {i.compress_type for i in z.infolist()} \
                == {zipfile.ZIP_DEFLATED}
        with open(path, "rb") as f:
            seg["sha256"] = hashlib.sha256(f.read()).hexdigest()
        seg["bytes"] = os.path.getsize(path)
        assert verify_segment(sp.directory, seg) is None
        got = sp.window(2, 8)
        for k in trace.data:
            np.testing.assert_array_equal(got.data[k], trace.data[k][2:8])

    @pytest.mark.parametrize("record", ["legacy", "sha256"])
    def test_flip_in_stored_member_is_caught(self, tmp_path, record):
        """A flipped byte inside a stored member's data: the manifest's
        sha256 names it, and without one (a legacy record) zipfile's
        CRC-32 does, as a load failure."""
        _, sp = noisy_spool(str(tmp_path / "sp"))
        seg = dict(sp.segment_records[0])
        path = os.path.join(sp.directory, seg["file"])
        flip_byte(path, stored_data_offset(path, "metric:wall_time.npy"))
        if record == "legacy":
            for key in ("sha256", "bytes"):
                seg.pop(key)
            assert verify_segment(sp.directory, seg) is not None
            with pytest.raises(TraceFormatError) as e:
                sp.segment(0)
            assert e.value.member == "metric:wall_time"
        else:
            assert verify_segment(sp.directory, seg) == "sha256 mismatch"

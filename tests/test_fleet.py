"""Fleet-scale ingest (ISSUE 8): fault-isolated multi-run analysis with
backpressure and a crash-safe cross-run verdict index.

The contracts this file pins:

* ``VerdictIndex`` killed at **any** journal/snapshot fault point and
  reopened, then re-fed every record (at-least-once delivery), rebuilds
  the exact dedup report of an uninterrupted run;
* with >= 8 concurrent runs, corrupting one tenant quarantines *that*
  run while every healthy run's per-window verdicts stay bit-identical
  (``Verdict.doc()``) to a solo OnlineAnalyzer poll of the same spool;
* backpressure sheds the *oldest* queued window as a structured
  ``ShedEvent`` + ``DegradedWindow`` — the log stays contiguous and
  complete, nothing is fabricated and nothing silently vanishes;
* a dead producer is stall-detected on the injected clock, recovered,
  and its salvaged tail drained to ``done``;
* the fleet corpus entries pass deterministically at seeds {0, 1, 7};
* the CLI surfaces (``fleet_watch.py``, ``watch_train.py --recover``,
  ``run_corpus.py --jobs``) hold their documented exit codes/output.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Verdict, verdict_fingerprint
from repro.core import faultpoints as FP
from repro.core.faultpoints import InjectedCrash
from repro.fleet import (FleetConfig, FleetIngest, VerdictIndex)
from repro.scenarios.corpus import CORPUS, corpus_entries, run_entry
from repro.stream import OnlineAnalyzer, SpooledTrace, TraceSpool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.path.join(REPO, "src")
       + os.pathsep + os.environ.get("PYTHONPATH", "")}


# -- fixtures -------------------------------------------------------------


def make_verdict(paths=("ST/cr5",), disparity=(), causes=("flops",)):
    return Verdict(
        dissimilar=bool(paths), dissimilarity_paths=tuple(paths),
        dissimilarity_ccr_paths=tuple(paths),
        disparity_paths=tuple(disparity),
        disparity_ccr_paths=tuple(disparity),
        cause_attributes=frozenset(causes),
        dissimilarity_cause_attributes=frozenset(causes),
        per_path_causes=())


def fleet_trace(run: int, n_steps: int = 16, seed: int = 0):
    """One run of the fleet scenario: ST + a compute straggler active on
    every step (same planted fault per run, distinct per-run seed)."""
    _, coll = CORPUS["fleet/one-tenant-corruption"].build(seed)
    return coll.make_trace(run, n_steps)


def spool_up(trace, directory, chunk_steps=2, upto=None, close=True):
    spool = TraceSpool(directory, chunk_steps=chunk_steps,
                       meta=dict(trace.meta))
    for s in range(upto if upto is not None else trace.n_steps):
        spool.append(trace.window(s, s + 1))
    if close:
        spool.close(meta=dict(trace.meta))
    return spool


def flip_bytes(path, n_flips=8, seed=3):
    rng = np.random.default_rng(seed)
    size = os.path.getsize(path)
    with open(path, "rb+") as f:
        for off in rng.choice(size, size=min(n_flips, size), replace=False):
            f.seek(int(off))
            b = f.read(1)
            f.seek(int(off))
            f.write(bytes([b[0] ^ 0xFF]))


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def tick_until_done(fleet, clock, max_ticks=400):
    for _ in range(max_ticks):
        if fleet.done:
            return True
        clock.now += 1.0
        fleet.tick()
    return fleet.done


# -- verdict fingerprint (satellite 2) ------------------------------------


class TestVerdictFingerprint:
    def test_fingerprint_is_doc_equality(self):
        a, b = make_verdict(), make_verdict()
        assert a.doc() == b.doc()
        assert a.fingerprint() == b.fingerprint()
        c = make_verdict(paths=("ST/cr6",))
        assert a.doc() != c.doc()
        assert a.fingerprint() != c.fingerprint()

    def test_kind_prefix(self):
        assert make_verdict().fingerprint().startswith("dissim:")
        assert make_verdict(paths=(), disparity=("ST/cr2",)) \
            .fingerprint().startswith("disp:")
        assert make_verdict(disparity=("ST/cr2",)) \
            .fingerprint().startswith("both:")
        assert make_verdict(paths=(), causes=()) \
            .fingerprint().startswith("none:")

    def test_function_and_method_agree(self):
        v = make_verdict()
        assert verdict_fingerprint(v) == v.fingerprint()


# -- VerdictIndex ---------------------------------------------------------


def feed(index, records):
    for run, v, start, stop in records:
        index.record(run, v, start, stop)


def sample_records():
    va = make_verdict()                      # one recurring signature...
    vb = make_verdict(paths=("ST/cr6",))     # ...and a rarer second one
    recs = []
    for run in ("run-0", "run-1", "run-2"):
        for w in range(3):
            recs.append((run, va, w * 4, w * 4 + 4))
    recs.append(("run-1", vb, 0, 4))
    return recs


class TestVerdictIndex:
    def test_dedup_report(self, tmp_path):
        idx = VerdictIndex(str(tmp_path / "idx"), snapshot_every=4)
        feed(idx, sample_records())
        rows = idx.report()
        assert len(rows) == 2
        top = rows[0]               # widest blast radius first
        assert top["n_runs"] == 3 and top["n_windows"] == 9
        assert top["paths"] == ["ST/cr5"]
        assert rows[1]["n_runs"] == 1
        assert idx.seen_in(top["fingerprint"]) == 3

    def test_record_is_idempotent(self, tmp_path):
        idx = VerdictIndex(str(tmp_path / "idx"))
        feed(idx, sample_records())
        before = idx.report()
        feed(idx, sample_records())         # at-least-once delivery
        assert idx.report() == before

    def test_reopen_rebuilds_from_journal(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=1000)   # journal only
        feed(idx, sample_records())
        rows = idx.report()
        del idx
        again = VerdictIndex(d)
        assert again.report() == rows
        assert again.recovered_event["torn_tail"] is None

    def test_close_snapshots_and_reopen_replays_nothing(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=1000)
        feed(idx, sample_records())
        idx.close()
        again = VerdictIndex(d)
        assert again.recovered_event["replayed"] == 0
        assert again.report() == idx.report()

    def test_torn_tail_is_preserved_not_fatal(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=1000)
        feed(idx, sample_records())
        rows = idx.report()
        with open(os.path.join(d, "journal.jsonl"), "a") as f:
            f.write('{"run": "run-9", "fp": "tru')     # killed mid-append
        again = VerdictIndex(d)
        assert again.report() == rows       # unacknowledged -> old state
        assert again.recovered_event["torn_tail"].startswith('{"run"')

    def test_corrupt_nonfinal_line_is_fatal(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=1000)
        feed(idx, sample_records())
        path = os.path.join(d, "journal.jsonl")
        lines = open(path).read().splitlines(keepends=True)
        lines[1] = "GARBAGE\n"
        open(path, "w").write("".join(lines))
        with pytest.raises(ValueError, match="corrupt journal record"):
            VerdictIndex(d)

    def test_foreign_snapshot_rejected(self, tmp_path):
        d = str(tmp_path / "idx")
        os.makedirs(d)
        with open(os.path.join(d, "snapshot.json"), "w") as f:
            json.dump({"format": "something-else"}, f)
        with pytest.raises(ValueError, match="not a verdict-index"):
            VerdictIndex(d)


class TestVerdictIndexKillSchedule:
    """Tentpole gate: kill the index at every journal/snapshot boundary;
    reopen + re-feed (at-least-once) must rebuild the exact dedup
    counts of an uninterrupted run — for every single (point, nth)."""

    def test_every_boundary_rebuilds_exact_counts(self, tmp_path):
        recs = sample_records()
        with FP.hits() as schedule:
            clean = VerdictIndex(str(tmp_path / "clean"), snapshot_every=3)
            feed(clean, recs)
            clean.close()
        want = clean.report()
        points = sorted(k for k in schedule if k.startswith("vindex."))
        assert {"vindex.journal.pre_append", "vindex.journal.appended",
                "vindex.snapshot.written",
                "vindex.snapshot.renamed"} <= set(points)
        swept = 0
        for point in points:
            for nth in range(1, schedule[point] + 1):
                d = str(tmp_path / f"{point}-{nth}")
                with FP.armed(point, nth=nth):
                    with pytest.raises(InjectedCrash):
                        idx = VerdictIndex(d, snapshot_every=3)
                        feed(idx, recs)
                        idx.close()
                # crash-recover: reopen never raises on crash residue,
                # re-feeding every record is a no-op for survivors
                again = VerdictIndex(d, snapshot_every=3)
                feed(again, recs)
                assert again.report() == want, f"{point}#{nth}"
                again.close()
                final = VerdictIndex(d)
                assert final.report() == want, f"{point}#{nth} reopened"
                assert final.recovered_event["replayed"] == 0
                swept += 1
        assert swept >= 8       # the sweep is a real schedule, not trivia


class TestVerdictIndexRetention:
    """Carry-over: bounded index growth.  Aggregates age out past the
    ``retain_runs`` horizon and the journal collapses behind snapshots,
    but idempotence keys are never dropped and live counts never move."""

    def test_aged_out_runs_drop_from_report(self, tmp_path):
        idx = VerdictIndex(str(tmp_path / "idx"), retain_runs=2)
        feed(idx, sample_records())     # run-0, run-1, run-2 in order
        rows = idx.report()
        runs = {r for row in rows for r in row["runs"]}
        assert runs == {"run-1", "run-2"}   # run-0 aged out
        assert idx.evicted_runs == 1
        top = rows[0]
        assert top["n_runs"] == 2 and top["n_windows"] == 6

    def test_eviction_survives_refeed(self, tmp_path):
        """An evicted run's records stay dead on at-least-once redelivery
        — the idempotence keys outlive the aggregates."""
        idx = VerdictIndex(str(tmp_path / "idx"), retain_runs=2)
        recs = sample_records()
        feed(idx, recs)
        before = idx.report()
        feed(idx, (r for r in recs if r[0] == "run-0"))   # redeliver
        assert idx.report() == before
        # ...but a genuinely NEW window re-admits the run (fresh recency)
        idx.record("run-0", make_verdict(), 100, 104)
        runs = {r for row in idx.report() for r in row["runs"]}
        assert "run-0" in runs and len(runs) == 2

    def test_empty_fingerprints_disappear(self, tmp_path):
        """A signature whose every contributing run ages out leaves the
        report entirely."""
        idx = VerdictIndex(str(tmp_path / "idx"), retain_runs=1)
        # feed order: run-0 (3x va), run-1 (3x va), run-2 (3x va),
        # run-1 (1x vb) — the trailing vb record re-admits run-1 and
        # evicts run-2, so va loses its last contributor and vanishes
        feed(idx, sample_records())
        rows = idx.report()
        assert len(rows) == 1
        assert rows[0]["paths"] == ["ST/cr6"]
        assert rows[0]["runs"] == {"run-1": 1}

    def test_retained_state_replays_from_journal(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=1000, retain_runs=2)
        feed(idx, sample_records())
        rows = idx.report()
        again = VerdictIndex(d, retain_runs=2)     # journal-only replay
        assert again.report() == rows

    def test_tightened_horizon_on_reopen_evicts(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d)
        feed(idx, sample_records())
        idx.close()
        again = VerdictIndex(d, retain_runs=1)
        runs = {r for row in again.report() for r in row["runs"]}
        assert runs == {"run-1"}    # the last run to contribute a window

    def test_journal_truncation_bounds_growth(self, tmp_path):
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=2, journal_max_records=4)
        feed(idx, sample_records())     # 10 records
        rows = idx.report()
        lines = [json.loads(ln) for ln in
                 open(os.path.join(d, "journal.jsonl")) if ln.strip()]
        assert "_base" in lines[0]
        # marker + the tail past the last truncation, never all 10
        assert len(lines) <= 1 + 4 + 2
        again = VerdictIndex(d)
        assert again.report() == rows
        assert again.n_records == 10

    def test_marker_past_snapshot_is_fatal(self, tmp_path):
        """A truncation marker claiming records the snapshot does not
        cover means data loss — refuse to open, never undercount."""
        d = str(tmp_path / "idx")
        idx = VerdictIndex(d, snapshot_every=1000)
        feed(idx, sample_records())
        del idx
        with open(os.path.join(d, "journal.jsonl"), "w") as f:
            f.write('{"_base": 99}\n')
        with pytest.raises(ValueError, match="unrecoverable"):
            VerdictIndex(d)

    def test_kill_sweep_never_loses_live_counts(self, tmp_path):
        """The tentpole-grade gate for retention: kill at every journal,
        snapshot AND truncation boundary; reopen + re-feed must rebuild
        exactly the retained report of an uninterrupted run."""
        recs = sample_records()
        kw = dict(snapshot_every=3, retain_runs=2, journal_max_records=4)
        with FP.hits() as schedule:
            clean = VerdictIndex(str(tmp_path / "clean"), **kw)
            feed(clean, recs)
            clean.close()
        want = clean.report()
        points = sorted(k for k in schedule if k.startswith("vindex."))
        assert {"vindex.journal.truncate.written",
                "vindex.journal.truncated"} <= set(points)
        swept = 0
        for point in points:
            for nth in range(1, schedule[point] + 1):
                d = str(tmp_path / f"{point}-{nth}")
                with FP.armed(point, nth=nth):
                    with pytest.raises(InjectedCrash):
                        idx = VerdictIndex(d, **kw)
                        feed(idx, recs)
                        idx.close()
                again = VerdictIndex(d, **kw)
                feed(again, recs)
                assert again.report() == want, f"{point}#{nth}"
                again.close()
                final = VerdictIndex(d, **kw)
                assert final.report() == want, f"{point}#{nth} reopened"
                swept += 1
        assert swept >= 10


# -- fleet ingest ---------------------------------------------------------


class TestFleetIsolation:
    def test_corrupt_tenant_cannot_perturb_siblings(self, tmp_path):
        """>= 8 concurrent runs; one tenant's segments rot; the sick run
        quarantines and every healthy run's windows stay bit-identical
        (Verdict.doc()) to a solo analysis of the same spool."""
        n_runs, victim = 8, 3
        dirs = []
        for r in range(n_runs):
            d = str(tmp_path / f"run-{r}")
            spool_up(fleet_trace(r), d)
            dirs.append(d)
        for seg in (1, 3, 5):       # 3 bad segments -> breaker trips
            flip_bytes(os.path.join(dirs[victim],
                                    f"segment-{seg:05d}.npz"), seed=seg)
        clock = FakeClock()
        idx = VerdictIndex(str(tmp_path / "idx"))
        fleet = FleetIngest(FleetConfig(), index=idx, time_fn=clock)
        for r, d in enumerate(dirs):
            fleet.add_run(f"run-{r}", d)
        assert tick_until_done(fleet, clock)

        sick = fleet.runs[f"run-{victim}"]
        assert sick.state == "quarantined"
        assert sick.integrity_failures >= 3
        assert not [w for w in sick.windows if not w.degraded], \
            "no verdict may be fabricated from corrupt bytes"
        kinds = [e.kind for e in sick.events]
        assert "integrity" in kinds and "quarantine" in kinds

        for r in range(n_runs):
            if r == victim:
                continue
            sup = fleet.runs[f"run-{r}"]
            assert sup.state == "done"
            solo = OnlineAnalyzer(window_steps=4, persist=2) \
                .poll(SpooledTrace(dirs[r]))
            assert len(sup.windows) == len(solo) == 4
            for got, want in zip(sup.windows, solo):
                assert not got.degraded and not want.degraded
                assert (got.start, got.stop) == (want.start, want.stop)
                assert got.verdict.doc() == want.verdict.doc()

        # the healthy runs' shared signature dedups to "seen in 7 runs"
        top = idx.report()[0]
        assert top["n_runs"] == n_runs - 1

    def test_internal_error_quarantines_run_not_fleet(self, tmp_path):
        d0, d1 = str(tmp_path / "a"), str(tmp_path / "b")
        spool_up(fleet_trace(0), d0)
        spool_up(fleet_trace(1), d1)
        clock = FakeClock()
        fleet = FleetIngest(FleetConfig(), time_fn=clock)
        fleet.add_run("a", d0)
        fleet.add_run("b", d1)

        def boom(*a, **k):
            raise RuntimeError("supervision bug")
        fleet.runs["a"].discover = boom
        assert tick_until_done(fleet, clock)
        assert fleet.runs["a"].state == "quarantined"
        assert "supervision bug" in fleet.runs["a"].error
        assert fleet.runs["b"].state == "done"
        assert len(fleet.runs["b"].windows) == 4


class TestBackpressure:
    def test_sheds_oldest_keeps_log_contiguous(self, tmp_path):
        d = str(tmp_path / "run")
        spool_up(fleet_trace(0, n_steps=24), d)
        clock = FakeClock()
        cfg = FleetConfig(queue_windows=2, max_workers=1)
        fleet = FleetIngest(cfg, time_fn=clock)
        fleet.add_run("run", d)
        assert tick_until_done(fleet, clock)
        sup = fleet.runs["run"]
        log = sup.windows
        assert [w.index for w in log] == list(range(6))
        shed = [w for w in log if w.degraded
                and w.reason == "shed: backpressure"]
        assert len(shed) == 4               # 6 discovered - 2 kept
        assert [w.index for w in shed] == [0, 1, 2, 3], \
            "shedding must drop the oldest first"
        kept = [w for w in log if not w.degraded]
        assert [(w.start, w.stop) for w in kept] == [(16, 20), (20, 24)]
        events = [e for e in sup.events if e.kind == "shed"]
        assert len(events) == 4
        assert all(e.doc()["event"] == "shed" for e in events)

    def test_default_budget_never_sheds(self, tmp_path):
        d = str(tmp_path / "run")
        spool_up(fleet_trace(0, n_steps=24), d)
        clock = FakeClock()
        fleet = FleetIngest(FleetConfig(), time_fn=clock)
        fleet.add_run("run", d)
        assert tick_until_done(fleet, clock)
        assert fleet.runs["run"].shed == 0
        assert len(fleet.runs["run"].windows) == 6


class TestStallRecovery:
    def test_dead_producer_is_recovered_and_drained(self, tmp_path):
        d = str(tmp_path / "run")
        spool_up(fleet_trace(0), d, upto=10, close=False)   # dies at 10
        clock = FakeClock()
        fleet = FleetIngest(FleetConfig(max_stall=3.0), time_fn=clock)
        fleet.add_run("run", d)
        assert tick_until_done(fleet, clock)
        sup = fleet.runs["run"]
        assert sup.state == "done"
        kinds = [e.kind for e in sup.events]
        assert "stall" in kinds and "recover" in kinds
        # salvaged tail drained: [0,4), [4,8), then the partial [8,10)
        assert [(w.start, w.stop) for w in sup.windows] == \
            [(0, 4), (4, 8), (8, 10)]
        assert not any(w.degraded for w in sup.windows)

    def test_unreadable_manifest_retries_then_quarantines(self, tmp_path):
        d = str(tmp_path / "run")
        spool_up(fleet_trace(0), d)
        man = os.path.join(d, "spool.json")
        good = open(man).read()
        open(man, "w").write("NOT JSON")
        clock = FakeClock()
        fleet = FleetIngest(FleetConfig(), time_fn=clock)
        fleet.add_run("run", d)
        for _ in range(80):
            if fleet.done:
                break
            clock.now += 1.0
            fleet.tick()
        sup = fleet.runs["run"]
        assert sup.state == "quarantined"
        retries = [e for e in sup.events if e.kind == "retry"]
        assert len(retries) >= 3            # exponential backoff attempts
        assert retries[1].retry_tick - retries[0].retry_tick >= 1
        assert "unreadable" in sup.quarantine_reason \
            or "integrity" in sup.quarantine_reason

        # and a transient error heals: restore the manifest mid-backoff
        d2 = str(tmp_path / "run2")
        spool_up(fleet_trace(1), d2)
        man2 = os.path.join(d2, "spool.json")
        good2 = open(man2).read()
        open(man2, "w").write("NOT JSON")
        clock2 = FakeClock()
        fleet2 = FleetIngest(FleetConfig(), time_fn=clock2)
        fleet2.add_run("run", d2)
        clock2.now += 1.0
        fleet2.tick()                       # first failed read
        open(man2, "w").write(good2)
        assert tick_until_done(fleet2, clock2)
        assert fleet2.runs["run"].state == "done"
        assert len(fleet2.runs["run"].windows) == 4
        assert good                         # (unused restore for run 1)


# -- fleet corpus gates ---------------------------------------------------


FLEET = sorted(e.name for e in corpus_entries(backend="fleet"))


class TestFleetCorpus:
    def test_registry_has_all_archetypes(self):
        assert FLEET == ["fleet/analysis-lag-flood",
                         "fleet/concurrent-producer-kill",
                         "fleet/one-tenant-corruption"]

    @pytest.mark.parametrize("seed", (0, 1, 7))
    @pytest.mark.parametrize("name", FLEET)
    def test_fleet_entry_passes(self, name, seed):
        r = run_entry(CORPUS[name], seed=seed)
        assert r.chaos_ok, f"{name}@{seed}: {r.chaos_failures}"
        assert r.passed, (
            f"{name}@{seed}: recall={r.recall} precision={r.precision}")
        o = r.chaos_outcome
        assert o.survived
        assert o.matched == o.comparable

    def test_fleet_outcome_deterministic(self):
        name = "fleet/one-tenant-corruption"
        a = run_entry(CORPUS[name], seed=0).chaos_outcome
        b = run_entry(CORPUS[name], seed=0).chaos_outcome
        assert (a.quarantined, a.degraded, a.shed, a.matched,
                a.comparable) == (b.quarantined, b.degraded, b.shed,
                                  b.matched, b.comparable)
        assert a.verdict.fingerprint() == b.verdict.fingerprint()


# -- CLI surfaces (subprocess; slow lane) ---------------------------------


def run_cli(*argv, cwd=REPO):
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=ENV,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.slow
class TestFleetWatchCLI:
    def test_corrupt_tenant_report_and_resume(self, tmp_path):
        root = tmp_path / "fleet"
        for r in range(4):
            spool_up(fleet_trace(r, n_steps=8),
                     str(root / f"run-{r}"))
        for seg in range(3):
            flip_bytes(str(root / "run-3" / f"segment-{seg:05d}.npz"),
                       seed=seg)
        idx = str(tmp_path / "idx")
        p = run_cli("scripts/fleet_watch.py", "--root", str(root),
                    "--index", idx)
        assert p.returncode == 4, p.stderr       # a run quarantined
        assert "quarantined" in p.stdout
        assert re.search(r"seen in 3 runs\s+6 windows", p.stdout), p.stdout
        # rerun against the persisted index: idempotent counts (the sick
        # run was recovered on disk, so this pass exits 0)
        p2 = run_cli("scripts/fleet_watch.py", "--root", str(root),
                     "--index", idx)
        assert p2.returncode == 0, p2.stderr
        assert re.search(r"seen in 3 runs\s+6 windows", p2.stdout)

    def test_json_and_no_runs(self, tmp_path):
        spool_up(fleet_trace(0, n_steps=8), str(tmp_path / "f" / "a"))
        p = run_cli("scripts/fleet_watch.py", "--root",
                    str(tmp_path / "f"), "--json")
        assert p.returncode == 0, p.stderr
        doc = json.loads(p.stdout)
        assert doc["runs"][0]["state"] == "done"
        assert doc["index"][0]["n_runs"] == 1
        empty = tmp_path / "empty"
        empty.mkdir()
        p = run_cli("scripts/fleet_watch.py", "--root", str(empty))
        assert p.returncode == 3


@pytest.mark.slow
class TestWatchTrainRecoverCLI:
    def test_recover_adopts_and_analyzes(self, tmp_path):
        d = str(tmp_path / "spool")
        trace = fleet_trace(0)
        with FP.armed("spool.segment.renamed", nth=6):
            with pytest.raises(InjectedCrash):
                spool_up(trace, d)
        p = run_cli("scripts/watch_train.py", d, "--recover")
        assert p.returncode == 0, p.stderr
        assert "recover: adopted segment-00005.npz" in p.stdout
        assert "recover: sealed at 12 steps" in p.stdout
        assert "window   2" in p.stdout      # the salvaged tail analyzed

    def test_recover_nothing_salvageable_exits_3(self, tmp_path):
        d = tmp_path / "empty-spool"
        d.mkdir()
        p = run_cli("scripts/watch_train.py", str(d), "--recover")
        assert p.returncode == 3
        assert p.stderr.strip()


@pytest.mark.slow
class TestRunCorpusJobs:
    ENTRIES = ["st/compute-straggler-cr5", "st/data-skew-cr11",
               "st/memory-pressure-cr9"]

    def test_jobs_output_matches_sequential(self):
        argv = ["scripts/run_corpus.py"] + \
            [a for e in self.ENTRIES for a in ("--entry", e)]
        seq = run_cli(*argv)
        par = run_cli(*argv, "--jobs", "2")
        assert seq.returncode == par.returncode == 0, (seq.stderr,
                                                       par.stderr)
        # identical apart from wall seconds
        norm = lambda s: re.sub(r"\d+\.\d{3}", "W", s)
        assert norm(seq.stdout) == norm(par.stdout)
        # the pool's workers are pinned to the CPU, and the run says so
        assert "pool: 2 workers, JAX_PLATFORMS=cpu" in par.stderr

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_jobs_refused_with_device_lane(self, backend):
        """A pool of workers would each claim the accelerator: --jobs > 1
        with a device distance backend is a usage error, before any
        entry runs."""
        p = run_cli("scripts/run_corpus.py", "--jobs", "2",
                    "--distance-backend", backend,
                    "--entry", self.ENTRIES[0])
        assert p.returncode == 2
        assert "use --jobs 1" in p.stderr
        assert p.stdout == ""

    def test_jobs_fleet_backend(self):
        p = run_cli("scripts/run_corpus.py", "--backend", "fleet",
                    "--jobs", "3")
        assert p.returncode == 0, p.stdout + p.stderr
        assert "3/3 entries passed" in p.stdout

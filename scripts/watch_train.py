#!/usr/bin/env python
"""Tail a trace spool — live or finished — and stream per-window verdicts.

    PYTHONPATH=src python scripts/watch_train.py SPOOL_DIR
    PYTHONPATH=src python scripts/watch_train.py SPOOL_DIR --follow
    PYTHONPATH=src python scripts/watch_train.py SPOOL_DIR --window 8 --json
    PYTHONPATH=src python scripts/watch_train.py SPOOL_DIR --finalize out.npz

The collection side (a Trainer with ``trace_spool_dir`` set, or anything
appending to a :class:`repro.stream.TraceSpool`) flushes step segments as
the run goes; this script re-reads the spool manifest, runs the full
AutoAnalyzer on each completed tumbling window, prints one verdict line
per window, and reports the **onset**: the first window whose bottleneck
verdict persisted ``--persist`` consecutive windows — so a drifting fault
is localized in time while the run is still going.  With overlapping
windows (``--stride`` smaller than ``--window``) the reported onset step
is additionally bisected *inside* the first flagged window, down to the
exact step whose inclusion first flips the verdict.

Analyzer keyword arguments default to the ``analyzer_kw`` the collector
recorded in the trace header (same resolution as ``analyze_trace.py``)
and can be overridden with ``--analyzer-kw '{"threshold_frac": 0.2}'``.

``--follow`` keeps polling until the producer closes the spool; without it
the script processes everything flushed so far and exits (nonzero if the
spool is still incomplete, so CI can assert it saw a whole run).
``--follow --max-stall SEC`` bounds the wait: when the spool makes no
progress for SEC seconds the producer is presumed dead and the script
exits rather than tailing a corpse forever (exit code 4 below; rerun
with ``--recover`` to salvage and re-analyze).
``--recover`` runs :meth:`TraceSpool.recover` before tailing — torn
``.tmp`` residue is quarantined, a crash-orphaned trailing segment is
adopted, and the quarantine/adopt/lost-range event log is printed —
then analyzes the sealed manifest like any complete spool.
``--finalize PATH`` converts the complete spool into the classic
single-``.npz`` artifact — byte-identical to the monolithic save of the
same run.

Windows the analyzer could not judge (a quarantined segment's range, a
non-finite sample burst) print as ``DEGRADED`` with the reason — they are
reported, never silently skipped, and never count toward onset.

Exit codes: 0 — complete run analyzed; 2 — usage error (argparse);
3 — spool missing/invalid, or run still in progress without ``--follow``;
4 — ``--max-stall`` exceeded, producer presumed dead.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def window_line(wv) -> str:
    if wv.degraded:
        return (f"window {wv.index:3d}  steps [{wv.start}:{wv.stop})  "
                f"{'DEGRADED':26s} {wv.reason}")
    kinds = ",".join(sorted(wv.kinds)) or "-"
    paths = ",".join(wv.paths()) or "-"
    return (f"window {wv.index:3d}  steps [{wv.start}:{wv.stop})  "
            f"{kinds:26s} {paths}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("spool", help="spool directory (contains spool.json)")
    ap.add_argument("--window", type=int, default=4, metavar="N",
                    help="tumbling window size in steps (default 4)")
    ap.add_argument("--stride", type=int, default=None, metavar="N",
                    help="window stride (default: window size; a stride "
                         "smaller than the window overlaps windows and "
                         "bisects the onset down to a step)")
    ap.add_argument("--persist", type=int, default=2, metavar="K",
                    help="consecutive flagged windows that define onset")
    ap.add_argument("--kind", choices=("dissimilarity", "disparity"),
                    default=None,
                    help="restrict onset detection to one bottleneck kind")
    ap.add_argument("--analyzer-kw", default=None, metavar="JSON",
                    help="AutoAnalyzer kwargs, overriding the trace header")
    ap.add_argument("--distance-backend", default=None,
                    choices=("numpy", "jax", "pallas"),
                    help="distance backend for the per-window analyzer "
                         "(default: exact numpy)")
    ap.add_argument("--follow", action="store_true",
                    help="keep polling until the producer closes the spool")
    ap.add_argument("--interval", type=float, default=1.0, metavar="SEC",
                    help="poll interval with --follow (default 1s)")
    ap.add_argument("--max-stall", type=float, default=None, metavar="SEC",
                    help="with --follow: exit 4 (producer presumed dead) "
                         "when the spool makes no progress for SEC seconds")
    ap.add_argument("--recover", action="store_true",
                    help="run TraceSpool.recover on the spool before "
                         "tailing (salvage a crashed producer's residue: "
                         "torn tmps quarantined, orphan segments adopted) "
                         "and print the quarantine/adopt event log")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON document instead of text lines")
    ap.add_argument("--finalize", default=None, metavar="PATH",
                    help="after a complete run, write the classic "
                         "single-.npz artifact here (byte-identical to "
                         "the monolithic save)")
    args = ap.parse_args(argv)
    if args.window < 1:
        ap.error("--window must be a positive step count")

    import os

    from repro.stream import (MANIFEST_NAME, OnlineAnalyzer,
                              ProducerStalledError, SpooledTrace,
                              StallDetector, TraceSpool)

    if args.recover:
        # salvage first, then tail the sealed manifest like any other
        # complete spool; the event log says exactly what was kept
        try:
            event = TraceSpool.recover(args.spool)
        except (ValueError, OSError) as e:
            print(str(e), file=sys.stderr)
            return 3
        for q in event["quarantined"]:
            print(f"recover: quarantined {q['file']} ({q['reason']})")
        for a in event["adopted"]:
            print(f"recover: adopted {a}")
        for lo, hi in event["lost_ranges"]:
            print(f"recover: lost steps [{lo}:{hi})")
        print(f"recover: sealed at {event['n_steps']} steps")

    # A live run has no manifest until its first chunk flushes; --follow
    # waits for it rather than dying at startup — but a producer that
    # died *before* its first flush must not be tailed forever either,
    # so --max-stall bounds this wait too.  A *present* but invalid
    # manifest (foreign file, newer version) still aborts.
    waited = 0.0
    while True:
        try:
            spooled = SpooledTrace(args.spool)
            break
        except ValueError as e:
            missing = not os.path.exists(
                os.path.join(args.spool, MANIFEST_NAME))
            if not (args.follow and missing):
                print(str(e), file=sys.stderr)
                return 3
            if args.max_stall is not None and waited >= args.max_stall:
                print(f"{args.spool}: no spool manifest after "
                      f"{waited:.1f}s — producer presumed dead",
                      file=sys.stderr)
                return 4
            time.sleep(args.interval)
            waited += args.interval
    kw = json.loads(args.analyzer_kw) if args.analyzer_kw else None
    online = OnlineAnalyzer(window_steps=args.window, stride=args.stride,
                            persist=args.persist, analyzer_kw=kw,
                            distance_backend=args.distance_backend)

    detector = (StallDetector(args.max_stall, base_interval=args.interval)
                if args.follow and args.max_stall is not None else None)
    while True:
        for wv in online.poll(spooled):
            if not args.json:
                print(window_line(wv), flush=True)
        if spooled.complete or not args.follow:
            break
        if detector is not None:
            try:
                delay = detector.observe(spooled)
            except ProducerStalledError as e:
                print(str(e), file=sys.stderr)
                return 4
            time.sleep(delay)
        else:
            time.sleep(args.interval)

    onset = online.onset_report(args.kind)
    if args.json:
        doc = {
            "spool": args.spool,
            "complete": spooled.complete,
            "n_steps": spooled.n_steps,
            "window_steps": args.window,
            "persist": args.persist,
            "windows": [
                ({"index": wv.index, "steps": [wv.start, wv.stop],
                  "degraded": True, "reason": wv.reason,
                  "detail": wv.detail}
                 if wv.degraded else
                 {"index": wv.index, "steps": [wv.start, wv.stop],
                  "kinds": sorted(wv.kinds),
                  "verdict": wv.verdict.doc()})
                for wv in online.log.windows],
            "onset": onset,
        }
        json.dump(doc, sys.stdout, indent=1, sort_keys=True)
        print()
    else:
        if onset is not None:
            print(f"onset: window {onset['onset_window']} (step "
                  f"{onset['onset_step']}; kinds "
                  f"{','.join(onset['kinds'])}; paths "
                  f"{','.join(onset['paths']) or '-'})")
        else:
            print(f"onset: none ({len(online.log.windows)} windows, "
                  f"persist {args.persist})")
    if not spooled.complete:
        print(f"{args.spool}: run still in progress "
              f"({spooled.n_steps} steps flushed)", file=sys.stderr)
        return 3
    if args.finalize:
        # stderr keeps --json stdout a single parseable document
        print(f"finalized: {spooled.finalize(args.finalize)}",
              file=sys.stderr if args.json else sys.stdout)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())

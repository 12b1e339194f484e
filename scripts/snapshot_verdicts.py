#!/usr/bin/env python
"""Dump full analyzer verdicts for every *synthetic* corpus entry to JSON,
or diff the current verdicts against a committed baseline.

    PYTHONPATH=src python scripts/snapshot_verdicts.py out.json [--seed N]
    PYTHONPATH=src python scripts/snapshot_verdicts.py --check VERDICTS.json

The corpus gate (scripts/run_corpus.py) only scores pass/fail; this dump
captures everything a verdict contains — partitions, CCR/CCCR paths, cause
attributes, per-path causes, dissimilarity severity, composite_s, disparity
severities — so a hot-path change can be proven output-preserving by
diffing two snapshots.  Runtime/train-backend entries are wall-clock noisy
and are excluded.

``--check`` compares the live verdicts against a baseline file (the repo
commits one at VERDICTS_synthetic.json): every baseline entry must still
exist and match bit-for-bit; entries added since the baseline are listed
but allowed (regenerate the baseline when adding entries or intentionally
changing the analyzer).
"""
from __future__ import annotations

import argparse
import json
import sys


def snapshot(seed: int, distance_backend: str = None) -> dict:
    from repro.core import AutoAnalyzer
    from repro.scenarios import corpus_entries

    out = {}
    for entry in corpus_entries(backend="synthetic"):
        tree, collector = entry.build(seed)
        kw = dict(entry.analyzer_kw)
        if distance_backend is not None:
            kw["distance_backend"] = distance_backend
        analyzer = AutoAnalyzer(tree, **kw)
        res = analyzer.analyze_collector(collector)
        out[entry.name] = {
            **res.verdict.doc(),
            "dissimilarity_severity": res.dissimilarity.severity,
            "composite_s": res.dissimilarity.composite_s,
            "baseline_n_clusters": res.dissimilarity.baseline.n_clusters,
            "baseline_partition": [list(g) for g in
                                   res.dissimilarity.baseline
                                   .partition_signature],
            "disparity_severities": {str(k): int(s) for k, s in
                                     sorted(res.disparity.severities.items())},
        }
    return out


def check(baseline_path: str, seed: int, distance_backend: str = None) -> int:
    with open(baseline_path) as f:
        baseline = json.load(f)
    current = snapshot(seed, distance_backend)
    drifted = []
    for name, want in sorted(baseline.items()):
        got = current.get(name)
        if got is None:
            drifted.append((name, "entry missing from current corpus"))
        elif got != want:
            detail = ", ".join(k for k in sorted(set(want) | set(got))
                               if got.get(k) != want.get(k))
            drifted.append((name, f"fields drifted: {detail}"))
    new = sorted(set(current) - set(baseline))
    if new:
        print(f"{len(new)} entries not in baseline (ok, regenerate to pin): "
              f"{new}")
    if drifted:
        print(f"VERDICT DRIFT vs {baseline_path} (seed {seed}):")
        for name, why in drifted:
            print(f"  {name}: {why}")
        return 1
    print(f"{len(baseline)} baseline entries bit-identical "
          f"(seed {seed}) vs {baseline_path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("out", nargs="?", default=None,
                    help="snapshot output path (omit with --check)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="diff live verdicts against this snapshot; exit "
                         "1 on any drift")
    ap.add_argument("--distance-backend", default=None,
                    choices=("numpy", "jax", "pallas"),
                    help="override every entry's distance backend; with "
                         "--check this proves the accelerated lane "
                         "verdict-equal to the exact baseline")
    args = ap.parse_args(argv)
    if args.check:
        if args.out:
            ap.error("--check does not write a snapshot; drop the output "
                     "path (regenerate first, then --check, if you want "
                     "both)")
        return check(args.check, args.seed, args.distance_backend)
    if not args.out:
        ap.error("either an output path or --check is required")
    doc = snapshot(args.seed, args.distance_backend)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out} ({len(doc)} entries)")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())

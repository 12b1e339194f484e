"""Serving launcher: batched prefill + interleaved decode through the
instrumented ServeEngine (docs/serving.md).

Generated traffic (skewed arrivals, bucketed prompt lengths, optional
hot-prompt repetition and sticky sessions) runs through the real jitted
model on per-lane decode states, every step emitting one serving region
trace row — so a spool directory makes the run live-tailable::

    PYTHONPATH=src python -m repro.launch.serve --arch st-100m --smoke \
        --lanes 2 --requests 8 --prompt-len 16 --gen 8 \
        --spool-dir /tmp/serve-spool &
    PYTHONPATH=src python scripts/watch_train.py /tmp/serve-spool --follow

Reported throughput excludes jit warmup/compile (the engine warms both
steady-state decode shapes before the timed section — the train corpus
``warmup=1`` convention) and splits prefill from decode: each phase's
tokens over that phase's own region wall.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Tuple

import jax

from repro.configs import get_arch
from repro.models import build
from repro.scenarios.traffic import (Request, TrafficConfig,
                                     generate_traffic)
from repro.serve import ServeConfig, ServeEngine
from repro.serve.runtime import JitBackend, supports_chunk


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="st-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--lanes", type=int, default=2,
                    help="concurrent batch lanes (trace process axis)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length bucket (single-bucket traffic)")
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk (clamped to 1 on families "
                         "without multi-token cache writes)")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="mean request arrivals per engine step")
    ap.add_argument("--hot-fraction", type=float, default=0.0,
                    help="fraction of requests replaying one hot prompt")
    ap.add_argument("--sessions", type=int, default=0,
                    help="sticky sessions (0 = none)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="save the serving RegionTrace artifact here "
                         "(replayable via scripts/analyze_trace.py)")
    ap.add_argument("--spool-dir", default=None, metavar="DIR",
                    help="stream per-step traces to a TraceSpool "
                         "(live-tailable via scripts/watch_train.py)")
    return ap.parse_args(argv)


def serve(args: argparse.Namespace
          ) -> Tuple[ServeEngine, JitBackend, List[Request]]:
    """Build the model from a seed, generate the traffic and drain it
    through the engine; returns the finished engine, its backend and the
    traffic it served."""
    entry = get_arch(args.arch)
    cfg = entry.smoke if args.smoke else entry.full
    api = build(cfg)
    # Jitted, each weight's float32 random draw fuses into its cast to
    # the parameter dtype; eager, it would sit beside it in device memory
    # (the largest stacked weight at full width is 3.8 GB in float32).
    params = jax.jit(lambda key: api.init(key)[0])(
        jax.random.key(args.seed))

    chunk = args.chunk if supports_chunk(cfg) else 1
    chunk = min(chunk, args.prompt_len)
    traffic = generate_traffic(TrafficConfig(
        n_requests=args.requests,
        arrival_rate=args.arrival_rate,
        length_buckets=(args.prompt_len,), length_mix=(1.0,),
        gen_len=args.gen,
        hot_fraction=args.hot_fraction,
        sessions=args.sessions,
        vocab=cfg.vocab), seed=args.seed)
    max_len = args.prompt_len + args.gen + 1

    embeds_fn = None
    if cfg.family in ("encdec", "vlm") and cfg.frontend:
        def embeds_fn(req):
            key = jax.random.key(args.seed * 131 + req.rid)
            return jax.random.normal(
                key, (1, cfg.frontend_tokens, cfg.d_model))

    backend = JitBackend(cfg, api, params, lanes=args.lanes,
                         max_len=max_len, prefill_chunk=chunk,
                         seed=args.seed, embeds_fn=embeds_fn)
    engine = ServeEngine(
        ServeConfig(lanes=args.lanes, max_len=max_len, prefill_chunk=chunk,
                    trace_path=args.trace, trace_spool_dir=args.spool_dir),
        traffic, backend)
    engine.run()
    return engine, backend, traffic


def main(argv=None) -> int:
    args = parse_args(argv)
    engine, backend, _ = serve(args)
    for rid in sorted(backend.outputs):
        print(f"request {rid}: {backend.outputs[rid]}")
    tp = engine.throughput()
    print(json.dumps({
        "steps": engine.step_idx,
        "requests_completed": int(tp["requests_completed"]),
        "tokens_generated": int(tp["tokens_decode"]),
        "tokens_prefill": int(tp["tokens_prefill"]),
        # warmup/compile excluded: the engine warms the decode shapes
        # before the timed section
        "wall_s": tp["wall_s"],
        "tok_per_s": tp["tok_per_s"],
        "prefill_tok_per_s": tp["prefill_tok_per_s"],
        "decode_tok_per_s": tp["decode_tok_per_s"],
    }))
    if args.trace:
        print(f"trace artifact: {args.trace}")
    if args.spool_dir:
        print(f"spool: {args.spool_dir}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())

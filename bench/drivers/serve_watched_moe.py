"""Driver ``serve_watched_moe``: a served mixture-of-experts model with
latent attention, at one chip's expert share, watched by its own analyzer.

The run is ``serve_watched``'s: weights drawn on the device from the seed
(``bench/reference/mla_moe_lm.py``), the program's ``JitBackend`` and
``ServeEngine`` over the traffic mix, every call shape compiled and the
first engine steps run with the watcher in set-up; then engine steps with
the watcher after each until the window's time is up.  Decode tokens per
second and the inter-token gap are measured as there.

The model is built from the configuration's published keys, with every
field they need (the held block of experts, the leading dense layer,
un-renormalised gates, YaRN): a program that lacks any of them fails
here, at construction, before a weight is drawn.

Afterwards a sample of the finished requests, drawn from the seed with the
longest prompt among them, goes through the float32 reference of the same
share; the widest gap by which a served token's logit lies below the
reference's best decides ``correct``, with the watcher's verdicts against
the analyzer reference.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time

import numpy as np

from bench import costs_mla_moe, harness, traffic, watch, xplane
from bench.drivers.serve_watched import Watcher, _check_layout
from bench.reference import mla_moe_lm

# Limits of the numbers compared with the references (PERF.md gives the
# readings each was set from).
LIMITS = {"logit_gap": 0.22, "nonfinite_samples": 0.0,
          "verdict_mismatches": 0.0, "value_gap": 1e-9}


def model_config(c):
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    YarnConfig)
    if c["routed_scaling_factor"] != 1:
        raise ValueError("the program's expert gates carry no "
                         "routed_scaling_factor")
    y = c["rope_scaling"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        yarn=YarnConfig(
            factor=float(y["factor"]),
            original_max_positions=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"]),
        activation=c["hidden_act"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        moe=MoEConfig(
            n_experts=c["n_routed_experts_published"],
            top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
            d_ff=c["moe_intermediate_size"], held=c["n_routed_experts"],
            first_held=c["first_held_expert"],
            norm_topk_prob=c["norm_topk_prob"],
            first_dense=c["first_k_dense_replace"]),
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                      q_lora_rank=c["q_lora_rank"] or 0,
                      rope_head_dim=c["qk_rope_head_dim"],
                      nope_head_dim=c["qk_nope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"])


def _plant(ctx, api, backend, vocab) -> None:
    """Test-only faults in the timed path: a token altered where it is
    produced, a decode step that returns its cache unchanged, and a decode
    step that leaves out the held experts' part (its own compile, traced
    with that part replaced by zeros: no copy of the weights)."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    sample, decode = backend._sample, backend._decode
    if "answer" in ctx.faults:
        def altered(logits):
            tok, finite = sample(logits)
            return (tok + 1) % vocab, finite
        backend._sample = altered
    if "stale" in ctx.faults:
        backend._decode = lambda p, s, t, pos: (decode(p, s, t, pos)[0], s)
    if "skip_experts" in ctx.faults:
        def traced(p, s, t, pos):
            parts = {n: getattr(moe, n)
                     for n in ("_per_expert", "_every_held", "_grouped")}
            for n in parts:
                setattr(moe, n, lambda x, *rest: jnp.zeros_like(x))
            try:
                return api.decode_step(p, s, t, pos)
            finally:
                for n, f in parts.items():
                    setattr(moe, n, f)
        skipping = jax.jit(traced)

        def skipped(p, s, t, pos):
            return (decode if t.shape[1] > 1 else skipping)(p, s, t, pos)
        backend._decode = skipped


def _stop_profile(profile) -> float:
    """Stop the profiler inside the window and leave the reading of its
    trace (many small device ops a decode call here: seconds of parsing)
    until the window has closed, where ``Profile.stop`` would read it at
    once.  Returns the seconds the stop held the engine."""
    import jax
    profile.t1 = time.perf_counter()
    jax.profiler.stop_trace()
    return time.perf_counter() - profile.t1


def _read_profile(profile) -> None:
    profile.events = xplane.read(xplane.find(profile.dir))
    shutil.rmtree(profile.dir, ignore_errors=True)


def run(ctx):
    import jax
    from repro.models import build
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.runtime import JitBackend

    c, tr = ctx.config, ctx.traffic
    mcfg = model_config(c)
    api = build(mcfg)
    params = mla_moe_lm.init_params(c, ctx.seed)
    _check_layout(api, params)
    reqs = traffic.generate(tr, c["vocab_size"], ctx.seed)
    max_len = int(tr["max_positions"])
    lanes, chunk = int(tr["lanes"]), int(tr["prefill_chunk"])
    backend = JitBackend(mcfg, api, params, lanes=lanes, max_len=max_len,
                         prefill_chunk=chunk, seed=ctx.seed)
    spool_dir = os.path.join(ctx.scratch, "spool")
    engine = ServeEngine(ServeConfig(
        lanes=lanes, max_len=max_len, prefill_chunk=chunk,
        trace_spool_dir=spool_dir,
        trace_chunk_steps=int(tr["spool_chunk_steps"])), reqs, backend)
    watcher = Watcher(spool_dir, int(tr["watch_window_steps"]),
                      tr["distance_backend"], ctx.faults)
    backend.warmup()
    _plant(ctx, api, backend, c["vocab_size"])
    for _ in range(int(tr["warm_steps"])):
        if not engine.step():
            raise RuntimeError("the traffic drained during warm-up")
        watcher.poll()

    events = []
    execute = backend.execute

    def noted(step, evs):
        events.extend((e.prefill_start, e.prefill_tokens, e.decode_pos)
                      for e in evs if e.request is not None)
        return execute(step, evs)
    backend.execute = noted
    if ctx.trace:
        def label(fn, *args):
            if fn is backend._sample:
                return "sample"
            toks, pos = args[2], np.asarray(args[3]).ravel()
            kind = "decode" if toks.shape[1] == 1 else "prefill"
            return f"{kind}:{int(pos[0])}"
        ctx.spans.wrap(backend, "_timed", "call", label)

    seen = {rid: len(t) for rid, t in backend.outputs.items()}
    times = {}
    trace_end = None
    stop_s = 0.0
    t_window = time.perf_counter()
    deadline = t_window + ctx.seconds
    tokens0 = engine.tokens_decode
    with harness.CompileCounter() as compiles:
        if ctx.profile:
            ctx.profile.start()
            trace_end = t_window + float(tr.get("trace_seconds") or
                                         ctx.seconds)
        now = t_window
        while now < deadline:
            if not engine.step():
                raise RuntimeError("the traffic drained inside the window")
            t_step = time.perf_counter()
            for rid, toks in backend.outputs.items():
                if len(toks) > seen.get(rid, 0):
                    times.setdefault(rid, []).append(t_step)
                    seen[rid] = len(toks)
            with ctx.spans.span("watch"):
                watcher.poll()
            now = time.perf_counter()
            if trace_end is not None and now >= trace_end:
                stop_s = _stop_profile(ctx.profile)
                trace_end = None
        t_end = time.perf_counter()
        if trace_end is not None:
            _stop_profile(ctx.profile)
    ctx.spans.restore()
    if ctx.profile:
        _read_profile(ctx.profile)
        ops = sum(len(v) for v in ctx.profile.events["device"].values())
        print(f"profiler stop: {stop_s:.3f} s, {ops} device ops traced",
              file=sys.stderr)
    window_s = t_end - t_window
    tokens = engine.tokens_decode - tokens0
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])]
    peak = harness.memory_peak(jax.devices())
    print(f"compiles in window: {compiles.count}", file=sys.stderr)

    # The window's per-layer readings leave out the profiler's stop inside
    # it, in which the engine stands still.
    record = {"window_s": window_s - stop_s,
              "device_kind": jax.devices()[0].device_kind, "config": c,
              "watch_s": ctx.spans.total("watch"),
              "window_flops": sum(
                  costs_mla_moe.span_flops(c, a, k) if k
                  else costs_mla_moe.decode_flops(c, p)
                  for a, k, p in events)}

    # -- what the window produced, against the references ------------------
    finished = sorted(rid for rid, rec in engine.records.items()
                      if rec.finish_step is not None)
    by_rid = {r.rid: r for r in reqs}
    rng = np.random.default_rng(ctx.seed)
    longest = max(finished, key=lambda r: (by_rid[r].prompt_len, -r))
    rest = [r for r in finished if r != longest]
    pick = [longest] + list(rng.choice(rest, size=min(
        len(rest), int(tr["check_requests"]) - 1), replace=False))
    seqs, scored = [], []
    for rid in pick:
        r = by_rid[rid]
        out = backend.outputs[rid]
        prompt = traffic.prompt_tokens(r, c["vocab_size"], ctx.seed)[0]
        seqs.append(np.concatenate([prompt, np.asarray(out, np.int32)]))
        scored.append(range(r.prompt_len - 1, r.prompt_len + len(out) - 1))
    nonfinite = backend.nonfinite_samples
    consumed = watcher.consumed
    del engine, backend, params, watcher
    gc.collect()
    for a in jax.live_arrays():
        a.delete()

    control = ("int8", "fp8") if ctx.control else ()
    gaps_ref = mla_moe_lm.forward_gaps(c, ctx.seed, seqs, scored,
                                       max_len - 1, control)
    logit_gap = max(float(g.max()) for g in gaps_ref["none"])
    cmp = watch.compare(spool_dir, consumed)
    if cmp["verdict_mismatches"]:
        print(watch.first_mismatch(spool_dir, consumed), file=sys.stderr)
    values = {"logit_gap": logit_gap, "nonfinite_samples": float(nonfinite),
              "verdict_mismatches": cmp["verdict_mismatches"],
              "value_gap": cmp["value_gap"]}
    checks = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    controls = {}
    if ctx.control:
        controls["analyzer.float32"] = watch.control_readings(spool_dir,
                                                              consumed)
        for q in control:
            controls["model." + q] = {"logit_gap": max(
                float(g.max()) for g in gaps_ref[q])}
    return {
        "e2e": {"decode_tok_per_s": tokens / window_s,
                "itl_p95_ms": 1e3 * harness.percentile(gaps, 95),
                "setup_s": t_window - ctx.t_process},
        "attempted": len(times),
        "failed": 0,
        "memory_peak_bytes": peak,
        "checks": checks,
        "controls": controls,
        "record": record,
    }

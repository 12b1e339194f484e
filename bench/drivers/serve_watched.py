"""Driver ``serve_watched``: a served model watched by its own analyzer.

Set-up draws the weights on the device from the seed
(``bench/reference/dense_lm.py``), builds the program's ``JitBackend`` and
``ServeEngine`` over the traffic mix, compiles the backend's call shapes,
and runs the first engine steps with the watcher, so that every shape the
window meets is compiled.  The engine spools its region trace every few
steps; after each engine step an ``OnlineAnalyzer`` on the device lane
takes whatever windows have landed, in the same process, as a user
watching their own serving run would.

The measured window runs engine steps until its time is up.  Decode tokens
per second are the tokens emitted over the whole window; an inter-token
gap is the host time between the ends of the steps that emitted two
consecutive tokens of one request, so it carries every step in between:
prefill chunks of other lanes, spool flushes and the watcher.

Afterwards a sample of the finished requests, drawn from the seed with the
longest prompt among them, is run through the float32 reference: the
widest gap by which a served token's logit lies below the reference's best
decides ``correct``, with the watcher's verdicts against the analyzer
reference.
"""
from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

from bench import costs, harness, traffic, watch
from bench.reference import dense_lm

# Limits of the numbers compared with the references (PERF.md gives the
# readings each was set from).
LIMITS = {"logit_gap": 0.13, "nonfinite_samples": 0.0,
          "verdict_mismatches": 0.0, "value_gap": 1e-9}


def model_config(c):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], window=c.get("sliding_window"),
        activation=c["hidden_act"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        param_dtype=c["torch_dtype"])


def _check_layout(api, params) -> None:
    """The weights must be laid out as the program's model takes them."""
    import jax
    want = jax.eval_shape(lambda k: api.init(k)[0], jax.random.key(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise RuntimeError(f"weight layout {got} is not the model's {want}")


class Watcher:
    """The in-process tail of the engine's spool."""

    def __init__(self, directory, window_steps, backend_name, faults):
        self.dir, self.steps = directory, window_steps
        self.kw = {"distance_backend": backend_name}
        self.faults = faults
        self.spooled = self.online = self.analyzer = None
        self.consumed = []

    def poll(self):
        from repro.stream import OnlineAnalyzer, SpooledTrace
        if self.spooled is None:
            if not os.path.exists(os.path.join(self.dir, "spool.json")):
                return
            self.spooled = SpooledTrace(self.dir)
            self.analyzer = watch.recording_analyzer(
                self.spooled.schema, self.faults, **self.kw)
            self.online = OnlineAnalyzer(window_steps=self.steps,
                                         analyzer=self.analyzer)
        for start, stop in self.online.pending_bounds(self.spooled):
            wv = self.online.consume(self.spooled, start, stop)
            self.consumed.append((start, stop, None if wv.degraded
                                  else self.analyzer.last))


def _plant(ctx, backend, vocab) -> None:
    """Test-only faults in the timed path: a token altered where it is
    produced, and a decode step that returns its cache unchanged."""
    sample, decode = backend._sample, backend._decode
    if "answer" in ctx.faults:
        def altered(logits):
            tok, finite = sample(logits)
            return (tok + 1) % vocab, finite
        backend._sample = altered
    if "stale" in ctx.faults:
        backend._decode = lambda p, s, t, pos: (decode(p, s, t, pos)[0], s)


def run(ctx):
    import jax
    from repro.models import build
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.runtime import JitBackend

    c, tr = ctx.config, ctx.traffic
    mcfg = model_config(c)
    api = build(mcfg)
    params = dense_lm.init_params(c, ctx.seed)
    _check_layout(api, params)
    reqs = traffic.generate(tr, c["vocab_size"], ctx.seed)
    # A mix that states its positions gets a cache of that size whatever
    # lengths the seed pairs, so every seed holds the same memory.
    max_len = int(tr.get("max_positions")
                  or max(r.prompt_len + r.gen_len for r in reqs) + 1)
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
    _plant(ctx, backend, c["vocab_size"])
    for _ in range(int(tr["warm_steps"])):
        if not engine.step():
            raise RuntimeError("the traffic drained during warm-up")
        watcher.poll()

    events = []
    if ctx.trace:
        execute = backend.execute

        def noted(step, evs):
            events.extend((e.prefill_start, e.prefill_tokens, e.decode_pos)
                          for e in evs if e.request is not None)
            return execute(step, evs)
        backend.execute = noted

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
                ctx.profile.stop()
                trace_end = None
        t_end = time.perf_counter()
        if trace_end is not None:
            ctx.profile.stop()
    ctx.spans.restore()
    window_s = t_end - t_window
    tokens = engine.tokens_decode - tokens0
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])]
    peak = harness.memory_peak(jax.devices())
    print(f"compiles in window: {compiles.count}", file=sys.stderr)

    record = {"window_s": window_s, "device_kind": jax.devices()[0].device_kind,
              "config": c, "watch_s": ctx.spans.total("watch")}
    if ctx.trace:
        record["window_flops"] = sum(
            costs.span_flops(c, a, k) if k else costs.token_flops(c, p)
            for a, k, p in events)

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
    gaps_ref = dense_lm.forward_gaps(c, ctx.seed, seqs, scored,
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

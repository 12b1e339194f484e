"""Training loop: step function, jit/pjit wiring, hooks.

The same ``make_train_step`` serves three callers:
  * CPU smoke runs (no mesh) — tests and examples;
  * the production dry-run (512-device mesh, abstract lowering);
  * real training (mesh + shardings + donation).

AutoAnalyzer is a first-class consumer: with ``TrainerConfig.trace`` set
the trainer runs a *region-instrumented* step — the real jitted forward/
backward and optimizer as leaves of a :class:`RegionTree`, executed once
per emulated SPMD shard on that shard's slice of the batch — and records
every step into a :class:`RegionTrace`.  The trace is the single source
of truth: :class:`StragglerMonitor` observations are derived from its
per-shard samples (not a private ``perf_counter`` path), ``run`` emits a
portable ``.npz`` artifact, and ``scripts/analyze_trace.py`` replays the
full analysis offline (the paper's collection/analysis split).

Long runs stream instead of accumulating: ``trace_spool_dir`` routes the
per-step traces through a :class:`repro.stream.TraceSpool` (peak
collection memory O(chunk), live-tailable by ``scripts/watch_train.py``,
finalized byte-identically to the monolithic save — docs/streaming.md).
On MoE configs ``trace_expert_iters`` adds per-expert probe regions to
the instrumented tree, so routing imbalance is genuinely executed
per-region work the analyzer can localize.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import (AutoAnalyzer, RegionTrace, RegionTree,
                        TimedRegionRunner, WALL_TIME, optics_cluster)
from repro.data import DataConfig, device_batch, host_batch
from repro.models import build
from repro.optim import AdamWConfig, apply_updates, init_opt_state
from repro.sharding import activation_sharding, rules_for, tree_shardings

from . import checkpoint as ckpt_mod


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig) -> Callable:
    api = build(cfg)

    def train_step(params, opt_state, batch):
        (total, info), grads = jax.value_and_grad(
            api.loss_fn, has_aux=True)(params, batch)
        new_params, new_opt, om = apply_updates(opt_cfg, params, grads,
                                                opt_state)
        metrics = {"loss": info["loss"], "total_loss": total, **om}
        if "expert_counts" in info:
            metrics["expert_counts"] = info["expert_counts"]
        return new_params, new_opt, metrics

    return train_step


def _expert_probe_leaf(cfg: ModelConfig, expert: int):
    """A per-expert instrumented region: run held expert ``expert``'s FFN
    (layer 0 weights from the live params) on the shard's probe-token
    tile, ``bundle["expert_iters"][expert]`` times — so a hot expert
    genuinely executes more jitted work, per shard, inside its own region.
    The per-iteration roll by the loop index plus the carried accumulator
    keep XLA's loop-invariant code motion from collapsing N iterations
    into one (same defence as the iterated fwd_bwd)."""
    import jax.numpy as jnp

    from repro.models.layers import _act

    def leaf(state, bundle):
        iters = bundle["expert_iters"][expert]
        toks = bundle["probe_tokens"]                       # (T, d_model)
        moe_p = state["params"]["layers"]["moe"]            # (L, E, ...)
        wi = moe_p["wi"][0, expert]
        wg = moe_p["wg"][0, expert]
        wo = moe_p["wo"][0, expert]

        def body(i, acc):
            x = jnp.roll(toks, i, axis=0)
            h = _act(x @ wg, cfg.activation) * (x @ wi)
            return acc + (h @ wo).sum()

        probe = jax.lax.fori_loop(0, iters, body, state["probe"])
        return {**state, "probe": probe}

    return leaf


def train_region_tree(cfg: ModelConfig, opt_cfg: AdamWConfig,
                      iterated: bool = False,
                      expert_probe: bool = False) -> RegionTree:
    """The real training step as a code-region tree (paper §2 applied to
    the train loop): ``train/{fwd_bwd, optimizer}`` leaves threading a
    stable ``{params, opt_state, grads, loss}`` state pytree, runnable by
    :class:`TimedRegionRunner` once per emulated shard.

    With ``iterated=True`` the forward/backward leaf is wrapped in
    :func:`repro.scenarios.faults.iterated_work`, so shard data arrives
    as ``(batch, iters)`` bundles and a shard carrying a larger ``iters``
    genuinely executes more jitted work — the corpus fault-injection
    hook on real model steps.

    With ``expert_probe=True`` (MoE configs only) the tree grows a
    ``moe/expert_<e>`` leaf per routed expert, each running that expert's
    FFN on a probe-token tile ``expert_iters[e]`` times — per-expert load
    becomes per-region instrumented work, so the analyzer can pin a hot
    expert in the region tree.  Shard data then arrives as a dict bundle
    ``{batch, iters, expert_iters, probe_tokens}``."""
    api = build(cfg)

    def fwd_bwd(state, batch):
        # Accumulate into the carried grads (zero on step entry; the
        # optimizer region resets them).  For a plain step this is
        # `grads = 0 + grads` — identical to overwriting — but it gives
        # iterated execution a carry dependency XLA cannot hoist out of
        # the fori_loop.
        (total, info), grads = jax.value_and_grad(
            api.loss_fn, has_aux=True)(state["params"], batch)
        acc = jax.tree.map(lambda a, g: a + g, state["grads"], grads)
        return {**state, "grads": acc, "loss": info["loss"]}

    def optimizer(state, batch):
        new_params, new_opt, _ = apply_updates(
            opt_cfg, state["params"], state["grads"], state["opt_state"])
        return {**state, "params": new_params, "opt_state": new_opt,
                "grads": jax.tree.map(jnp.zeros_like, state["grads"])}

    tree = RegionTree("train")
    if expert_probe and cfg.moe is None:
        raise ValueError(f"{cfg.name}: expert_probe needs an MoE config")
    if iterated:
        # Lazy import: scenarios.corpus imports repro.train for the train
        # backend, so the reverse edge must not exist at module scope.
        from repro.scenarios.faults import iterated_work

        def fwd_bwd_micro(state, bundle):
            # Each iteration grads a batch rolled by the loop index: the
            # values are permutation-invariant (mean over the batch dim)
            # but the computation is index-dependent, so loop-invariant
            # code motion cannot collapse N iterations into one.
            batch, i = bundle
            rolled = {k: jnp.roll(v, i, axis=0) for k, v in batch.items()}
            return fwd_bwd(state, rolled)

        fwd_bwd_iter = iterated_work(fwd_bwd_micro, indexed=True)

    if expert_probe:
        # Dict bundles: every region unpacks the piece it consumes.
        if iterated:
            def fwd_bwd_leaf(state, bundle):
                return fwd_bwd_iter(state, (bundle["batch"],
                                            bundle["iters"]))
        else:
            def fwd_bwd_leaf(state, bundle):
                return fwd_bwd(state, bundle["batch"])
        tree.add("fwd_bwd", fn=fwd_bwd_leaf)
        moe_parent = tree.add("moe")
        for e in range(cfg.moe.n_held):
            tree.add(f"expert_{cfg.moe.first_held + e}", parent=moe_parent,
                     fn=_expert_probe_leaf(cfg, e))

        def optimizer_leaf(state, bundle):
            return optimizer(state, bundle["batch"])
        tree.add("optimizer", fn=optimizer_leaf)
    elif iterated:
        tree.add("fwd_bwd", fn=fwd_bwd_iter)

        def optimizer_b(state, bundle):
            batch, _ = bundle
            return optimizer(state, batch)
        tree.add("optimizer", fn=optimizer_b)
    else:
        tree.add("fwd_bwd", fn=fwd_bwd)
        tree.add("optimizer", fn=optimizer)
    return tree


def make_eval_step(cfg: ModelConfig) -> Callable:
    api = build(cfg)

    def eval_step(params, batch):
        loss, info = api.loss_fn(params, batch)
        return info["loss"]

    return eval_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    analyze_every: int = 0         # 0 = off
    seed: int = 0
    straggler_threshold: float = 1.75  # step_time > thr × running median
    # -- region-instrumented (traced) mode --------------------------------
    trace: bool = False            # run the region-instrumented step
    trace_path: Optional[str] = None   # save the merged artifact here
    trace_shards: int = 4          # emulated SPMD shards
    trace_repeats: int = 1         # timing repeats per (region, shard)
    # Per-shard fwd_bwd iteration counts (fault-injection hook: a shard
    # with more iterations genuinely executes more jitted work).
    trace_iters: Optional[Tuple[int, ...]] = None
    trace_meta: Optional[Dict[str, Any]] = None  # merged into the header
    # -- streaming collection (docs/streaming.md) -------------------------
    # With a spool directory set, per-step traces stream to disk as
    # segment files instead of accumulating in memory: peak collection
    # memory is O(trace_chunk_steps), and a live OnlineAnalyzer /
    # watch_train.py can tail the run.  trace_path still works — the
    # closed spool finalizes into the same (byte-identical) artifact.
    trace_spool_dir: Optional[str] = None
    trace_chunk_steps: int = 8
    # -- MoE expert probe (expert regions in the instrumented tree) -------
    # Per-shard per-expert probe iteration counts ((n_shards, n_experts)):
    # each expert_<e> region runs its FFN expert_iters[shard][e] times, so
    # routing imbalance becomes genuinely executed per-region work.
    trace_expert_iters: Optional[Tuple[Tuple[int, ...], ...]] = None
    trace_probe_tokens: int = 64   # probe tile rows per expert iteration
    # -- closed-loop mitigation (train/mitigate.py, docs/mitigation.md) ----
    # A MitigationPolicy (duck-typed: observe(trainer)) consulted after
    # every traced step; persisted online verdicts trigger actions
    # (remesh / expert rebalance / checkpoint reschedule).
    mitigate: Optional[Any] = None
    # Trace-injection seam: called as trace_inject(trainer, step, trace)
    # right after the instrumented step produces its RegionTrace and
    # before anything consumes it (spool, monitor, mitigation policy).
    # May return a replacement trace (or mutate in place and return
    # None).  This is how infrastructure-level fault archetypes — e.g. a
    # checkpoint-write stall conditioned on the trainer's *current*
    # ckpt_every — are driven through the real training loop by the
    # recovery/chaos corpus.
    trace_inject: Optional[Callable[["Trainer", int, RegionTrace],
                                    Optional[RegionTrace]]] = None

    def __post_init__(self) -> None:
        if self.trace_path or self.trace_iters or self.trace_spool_dir \
                or self.trace_expert_iters or self.mitigate is not None:
            self.trace = True
        if self.trace_iters is not None and \
                len(self.trace_iters) != self.trace_shards:
            raise ValueError(
                f"trace_iters has {len(self.trace_iters)} entries for "
                f"{self.trace_shards} shards")
        if self.trace_expert_iters is not None and \
                len(self.trace_expert_iters) != self.trace_shards:
            raise ValueError(
                f"trace_expert_iters has {len(self.trace_expert_iters)} "
                f"entries for {self.trace_shards} shards")


class StragglerMonitor:
    """Dissimilarity-based straggler detection (paper §4.2.1 applied to the
    time dimension).  Per-shard step-time vectors are clustered with the
    simplified OPTICS algorithm when available; the scalar fallback flags
    steps slower than ``threshold ×`` the running median (restart/evict
    trigger for the fault-tolerance layer)."""

    def __init__(self, threshold: float = 1.75, window: int = 32):
        self.threshold = threshold
        self.window = window
        self.times: List[float] = []
        self.events: List[Dict] = []

    def observe_step(self, step: int, seconds: float,
                     per_shard: Optional[np.ndarray] = None) -> bool:
        self.times.append(seconds)
        hist = self.times[-self.window:]
        med = float(np.median(hist))
        flagged = len(hist) >= 8 and seconds > self.threshold * med
        if per_shard is not None and len(per_shard) > 1:
            res = optics_cluster(np.asarray(per_shard)[:, None])
            if res.n_clusters > 1:
                flagged = True
                self.events.append({"step": step, "kind": "shard-dissimilarity",
                                    "clusters": res.n_clusters})
        if flagged:
            self.events.append({"step": step, "kind": "slow-step",
                                "seconds": seconds, "median": med})
        return flagged


class Trainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: AdamWConfig,
                 data_cfg: DataConfig, tcfg: TrainerConfig,
                 mesh=None):
        self.cfg, self.opt_cfg, self.data_cfg, self.tcfg = (
            cfg, opt_cfg, data_cfg, tcfg)
        self.mesh = mesh
        self.api = build(cfg)
        self.monitor = StragglerMonitor(tcfg.straggler_threshold)
        self.history: List[Dict] = []
        self._build()

    def _build(self) -> None:
        key = jax.random.key(self.tcfg.seed)
        self.params, self.param_axes = self.api.init(key)
        self.opt_state = init_opt_state(self.params)
        step_fn = make_train_step(self.cfg, self.opt_cfg)
        self.train_step = jax.jit(step_fn, donate_argnums=(0, 1))
        self.step = 0
        self.trace: Optional[RegionTrace] = None
        self._step_traces: List[RegionTrace] = []
        self._last_step_trace: Optional[RegionTrace] = None
        self.spool = None
        if self.tcfg.trace_spool_dir:
            # Lazy import: repro.stream sits above the core trace layer.
            # trace_meta rides along provisionally so a live tail resolves
            # run-level configuration (analyzer_kw) before the run ends;
            # close() replaces it with the definitive final meta.
            from repro.stream import TraceSpool
            self.spool = TraceSpool(self.tcfg.trace_spool_dir,
                                    chunk_steps=self.tcfg.trace_chunk_steps,
                                    meta=self.tcfg.trace_meta)
        if self.tcfg.trace:
            if self.tcfg.trace_expert_iters is not None and self.cfg.moe:
                # shard count is checked in TrainerConfig; the expert
                # count needs the model config, so it is checked here
                # (train_region_tree rejects the non-MoE case itself)
                want = self.cfg.moe.n_held
                for i, row in enumerate(self.tcfg.trace_expert_iters):
                    if len(row) != want:
                        raise ValueError(
                            f"trace_expert_iters[{i}] has {len(row)} "
                            f"entries for {want} experts")
            self.region_tree = train_region_tree(
                self.cfg, self.opt_cfg,
                iterated=self.tcfg.trace_iters is not None,
                expert_probe=self.tcfg.trace_expert_iters is not None)
            # warmup=1: the first jitted call pays trace+compile (the
            # explicit lower().compile() does not seed jit's dispatch
            # cache), which would otherwise be recorded as shard 0's
            # step-0 sample — a ~500x artifact that reads as a shard-0
            # straggler.  Warmup outputs are discarded, so training
            # state still advances exactly once per step.
            self.runner = TimedRegionRunner(self.region_tree, warmup=1,
                                            repeats=self.tcfg.trace_repeats)
            zero_grads = jax.tree.map(jnp.zeros_like, self.params)
            # Replicated start: every emulated shard trains its own copy
            # of the same initial state on its slice of the global batch —
            # the single-host stand-in for per-rank SPMD execution that
            # TimedRegionRunner already uses.
            state = {"params": self.params, "opt_state": self.opt_state,
                     "grads": zero_grads, "loss": jnp.float32(0.0)}
            if self.tcfg.trace_expert_iters is not None:
                state["probe"] = jnp.float32(0.0)
                # Per-shard probe-token tiles, deterministic and constant
                # across steps (the per-iteration roll varies the work) —
                # built once, reused by every _traced_step.
                self._probe_tokens = [
                    jax.random.normal(
                        jax.random.key(self.tcfg.seed * 977 + i),
                        (self.tcfg.trace_probe_tokens, self.cfg.d_model),
                        dtype=jnp.float32)
                    for i in range(self.tcfg.trace_shards)]
            self._shard_states = [dict(state)
                                  for _ in range(self.tcfg.trace_shards)]

    def _traced_step(self, step: int) -> Dict[str, Any]:
        """One region-instrumented step over all emulated shards; appends
        the per-step trace and feeds the StragglerMonitor from it."""
        m = self.tcfg.trace_shards
        data = []
        for i in range(m):
            b = host_batch(self.data_cfg, step, n_shards=m, shard=i)
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            if self.tcfg.trace_expert_iters is not None:
                # iters defaults to 1 when the entry injects only through
                # the expert probe.
                iters = (self.tcfg.trace_iters[i]
                         if self.tcfg.trace_iters is not None else 1)
                data.append({
                    "batch": batch, "iters": jnp.int32(iters),
                    "expert_iters": jnp.asarray(
                        self.tcfg.trace_expert_iters[i], dtype=jnp.int32),
                    "probe_tokens": self._probe_tokens[i]})
            elif self.tcfg.trace_iters is not None:
                data.append((batch, jnp.int32(self.tcfg.trace_iters[i])))
            else:
                data.append(batch)
        step_trace = self.runner.run_trace(self._shard_states, data)
        self._shard_states = self.runner.final_states
        if self.tcfg.trace_inject is not None:
            replaced = self.tcfg.trace_inject(self, step, step_trace)
            if replaced is not None:
                step_trace = replaced
        self._last_step_trace = step_trace
        if self.spool is not None:
            self.spool.append(step_trace)
        else:
            self._step_traces.append(step_trace)
        rm = step_trace.reduce()
        per_shard = rm.metric(WALL_TIME).sum(axis=1)   # (m,) step seconds
        # SPMD semantics: the step ends when the slowest shard does.
        seconds = float(per_shard.max())
        self.monitor.observe_step(step, seconds, per_shard=per_shard)
        # Shard 0 is the canonical replica (checkpoints resume from it).
        self.params = self._shard_states[0]["params"]
        self.opt_state = self._shard_states[0]["opt_state"]
        return {"step": step,
                "loss": float(self._shard_states[0]["loss"]),
                "seconds": seconds,
                "per_shard_seconds": [float(x) for x in per_shard]}

    def _final_meta(self, base: Dict[str, Any]) -> Dict[str, Any]:
        """The merged artifact's header meta, built the same way (and in
        the same key order) for the in-memory and spooled paths — key
        order matters because spool finalization must reproduce the
        monolithic save byte-for-byte."""
        meta = dict(base)
        meta["collector"] = "train"
        meta.update(self.tcfg.trace_meta or {})
        meta["straggler_events"] = len(self.monitor.events)
        return meta

    def finalize_trace(self) -> Optional[RegionTrace]:
        """Merge the per-step traces into one artifact (saved to
        ``trace_path`` when set) and expose it as ``self.trace``.

        In spool mode the per-step traces already live on disk: the spool
        is closed with the final header meta, and the merged trace is
        reassembled from the segments — ``trace_path`` then receives the
        spool's ``finalize()`` output, byte-identical to what the
        in-memory path would have saved."""
        if self.spool is not None:
            if self.spool.n_steps == 0:
                return None
            from repro.stream import SpooledTrace
            if not self.spool.closed:
                self.spool.close(
                    meta=self._final_meta(self.spool.head_meta))
            self.trace = SpooledTrace(self.spool.directory).to_trace()
            if self.tcfg.trace_path:
                # == SpooledTrace.finalize(trace_path): to_trace() is the
                # finalize reassembly, saved once instead of twice.
                self.trace.save(self.tcfg.trace_path)
            return self.trace
        if not self._step_traces:
            return None
        self.trace = RegionTrace.merge(self._step_traces)
        self.trace.meta = self._final_meta(self.trace.meta)
        if self.tcfg.trace_path:
            self.trace.save(self.tcfg.trace_path)
        return self.trace

    # -- checkpoint/resume --------------------------------------------------
    def adopt_restore(self, step: int, trees: Dict[str, Any]) -> None:
        """Adopt a restored checkpoint as the live training state.  In
        traced mode the emulated shards' replicated states must be
        refreshed too — they were built from the *initial* params, and a
        resumed run that kept them would silently continue the shards
        from scratch while reporting the checkpoint's step."""
        self.params, self.opt_state = trees["params"], trees["opt_state"]
        self.step = step
        if self.tcfg.trace and hasattr(self, "_shard_states"):
            for s in self._shard_states:
                s["params"] = self.params
                s["opt_state"] = self.opt_state

    def maybe_resume(self) -> bool:
        d = self.tcfg.ckpt_dir
        if not d:
            return False
        latest = ckpt_mod.latest_step(d)
        if latest is None:
            return False
        templates = {"params": self.params, "opt_state": self.opt_state}
        try:
            # restore() verifies integrity and falls back to the newest
            # *verified* step on its own (docs/robustness.md).
            step, trees = ckpt_mod.restore(d, templates)
        except ckpt_mod.CheckpointCorruptError as e:
            # Every checkpoint is damaged: a fresh start beats a crash
            # loop, but never silently — the failure list is warned.
            import warnings
            warnings.warn(f"resume abandoned, starting fresh: {e}",
                          RuntimeWarning)
            return False
        self.adopt_restore(step, trees)
        return True

    def save(self) -> None:
        if self.tcfg.ckpt_dir:
            ckpt_mod.save(self.tcfg.ckpt_dir, self.step,
                          {"params": self.params,
                           "opt_state": self.opt_state},
                          meta={"config": self.cfg.name})

    # -- run -----------------------------------------------------------------
    def run(self, steps: Optional[int] = None,
            fail_at: Optional[int] = None) -> List[Dict]:
        """``fail_at`` injects a crash (fault-tolerance tests)."""
        steps = steps if steps is not None else self.tcfg.steps
        end = self.step + steps
        while self.step < end:
            if fail_at is not None and self.step == fail_at:
                raise RuntimeError(f"injected failure at step {self.step}")
            if self.tcfg.trace:
                rec = self._traced_step(self.step)
            else:
                batch = device_batch(self.data_cfg, self.step)
                t0 = time.perf_counter()
                self.params, self.opt_state, metrics = self.train_step(
                    self.params, self.opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                self.monitor.observe_step(self.step, dt)
                rec = {"step": self.step, "loss": loss, "seconds": dt,
                       "grad_norm": float(metrics["grad_norm"])}
                if "expert_counts" in metrics:
                    rec["expert_counts"] = np.asarray(
                        metrics["expert_counts"])
            self.history.append(rec)
            self.step += 1
            if self.tcfg.trace and self.tcfg.mitigate is not None:
                # Closed loop (train/mitigate.py): the policy windows the
                # step traces, analyzes, and may act — in place (expert
                # rebalance, ckpt reschedule) or by raising
                # MitigationRestart (remesh), which run_with_restarts
                # handles like any failure.
                self.tcfg.mitigate.observe(self)
            if self.tcfg.ckpt_every and self.step % self.tcfg.ckpt_every == 0:
                self.save()
        self.save()
        if self.tcfg.trace:
            self.finalize_trace()
        return self.history

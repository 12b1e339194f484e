"""JitBackend: the serving engine's real-model execution backend.

Runs the shared :class:`~repro.serve.engine.ServeScheduler` schedule
through the actual jitted model: per-lane batch=1 decode states (the KV
cache's ring index is shared across a batch, so lanes at different
positions cannot share one batched state), true chunked prefill on the
families whose attention cache accepts S>1 writes (dense / moe / vlm /
audio — ``supports_chunk``), per-token fallback elsewhere.

Measurement follows ``TimedRegionRunner`` conventions: perf_counter
walls, the calibrated CPU clock from ``repro.core.collector``
(``cpu_tick``/``cpu_clock``/``derived`` ride in the header meta so
``RegionTrace.reduce`` replays the quantization snap offline), and
flops/bytes attributed from the compiled executable's HLO cost analysis
per call *shape* — which is why bucketing-by-length matters: with prompt
buckets that are multiples of ``prefill_chunk`` the engine only ever
sees two decode-call shapes, ``(1, chunk)`` and ``(1, 1)``, so after
:meth:`JitBackend.warmup` (one untimed call per shape, the train-corpus
``warmup=1`` convention) nothing recompiles inside the timed region.

``kv_append`` records quantities rather than time: the KV write is fused
into the decode kernel on this path (there is no separately timeable
append), so the region carries the appended bytes
(slots x 2 x n_layers x n_kv_heads x head_dim x dtype) and the lane's
cache occupancy as VMEM_PRESSURE, with ~zero wall — exactly the signals
the KV archetypes condition on.  ``sample`` is a separately jitted,
separately timed argmax.

On MoE configs the tree has a ``moe`` parent with one ``expert_e`` child
per expert the layer holds, filled from the real routing: each prefill
or decode call returns its per-layer routed-token counts in the decode
state, pulled once the call's result is ready (no extra wait), and
``expert_e`` carries that expert's routes as FLOPS (the three expert
matmuls per route) and, as BYTES, one read of its weights per layer that
routed to it — quantities, like ``kv_append``, with no wall of their own
(the experts run fused inside the decode call).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (BYTES, CPU_TIME, FLOPS, RAW_METRICS, VMEM_PRESSURE,
                        WALL_TIME)
from repro.core.collector import _pick_cpu_clock
from repro.core.hlo import cost_analysis_of
from repro.core.spans import span
from repro.core.trace import RegionTrace
from repro.models import ModelApi, encdec
from repro.scenarios.traffic import prompt_tokens

from .engine import DECODE, KV_APPEND, MOE, PREFILL, SAMPLE, LaneEvent, \
    serve_region_tree

CHUNK_FAMILIES = ("dense", "moe", "vlm", "audio")


def supports_chunk(cfg) -> bool:
    """True when the family's attention cache accepts multi-token
    (S > 1) writes, i.e. true chunked prefill works."""
    return cfg.family in CHUNK_FAMILIES


class JitBackend:
    """Execute lane events against the real jitted model, measured."""

    _cpu_clock: Optional[Tuple[Callable[[], float], Optional[float], str]] \
        = None

    def __init__(self, cfg, api: ModelApi, params, lanes: int, max_len: int,
                 prefill_chunk: int, seed: int = 0,
                 embeds_fn: Optional[Callable[[Any], Any]] = None):
        if prefill_chunk > 1 and not supports_chunk(cfg):
            raise ValueError(
                f"family {cfg.family!r} has a per-token decode cache; "
                f"use prefill_chunk=1")
        self.cfg = cfg
        self.api = api
        self.params = params
        self.lanes = lanes
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.seed = seed
        self.embeds_fn = embeds_fn
        mo = cfg.moe
        self.tree = serve_region_tree(
            moe_experts=mo.n_held if mo else 0,
            first_expert=mo.first_held if mo else 0)
        self.region_ids = [r.region_id for r in self.tree.regions()]
        root = self.tree.root.name
        self._rid = {p: self.tree.by_path(f"{root}/{p}").region_id
                     for p in (PREFILL, DECODE, KV_APPEND, SAMPLE)
                     + ((MOE,) if mo else ())}
        self._expert_rids = [
            self.tree.by_path(f"{root}/{MOE}/expert_{e}").region_id
            for e in range(mo.first_held, mo.first_held + mo.n_held)
        ] if mo else []
        self._decode = jax.jit(
            lambda p, s, t, pos: api.decode_step(p, s, t, pos))
        # A lane's fresh decode state in one compiled call: built op by op
        # it costs tens of milliseconds of host dispatch per request.
        self._init_state = jax.jit(lambda: api.init_decode_state(1, max_len))
        # The sampler also reports whether the logits row it sampled was
        # finite: argmax of a row holding NaN still returns a token.
        self._sample = jax.jit(
            lambda logits: (jnp.argmax(logits[:, -1:], axis=-1)
                            .astype(jnp.int32),
                            jnp.isfinite(logits[:, -1]).all()))
        # What the current ``_timed`` call serves, for its spans.
        self._call: Dict[str, Any] = {}
        # Per-lane decode state.
        self._state: List[Any] = [None] * lanes
        self._pending_logits: List[Any] = [None] * lanes
        self._prompt: List[Optional[np.ndarray]] = [None] * lanes
        self.outputs: Dict[int, List[int]] = {}
        # Sampled logits rows that held a NaN or an infinity.
        self.nonfinite_samples = 0
        # (flops, bytes) per decode-call token count, from HLO cost
        # analysis of the compiled executable for that shape.
        self._decode_costs: Dict[int, Tuple[float, float]] = {}
        self._sample_cost: Optional[Tuple[float, float]] = None
        dt = np.dtype(cfg.activation_dtype())
        if cfg.mla is not None:     # the latent and the shared rope key
            self.kv_bytes_per_token = (cfg.n_layers * dt.itemsize * (
                cfg.mla.kv_lora_rank + cfg.mla.rope_head_dim))
        else:
            self.kv_bytes_per_token = (2 * cfg.n_layers * cfg.n_kv_heads
                                       * cfg.resolved_head_dim * dt.itemsize)
        # An expert's three matmuls: operations per route, weight bytes.
        self._expert_flops = 6.0 * cfg.d_model * mo.d_ff if mo else 0.0
        self._expert_bytes = (3.0 * cfg.d_model * mo.d_ff
                              * np.dtype(cfg.parameter_dtype()).itemsize
                              if mo else 0.0)
        self._dispatch = None
        if JitBackend._cpu_clock is None:
            JitBackend._cpu_clock = _pick_cpu_clock()
        self._clock, self._tick, self._clock_name = JitBackend._cpu_clock

    # -- state management --------------------------------------------------
    def _fresh_state(self, request) -> Any:
        if self.cfg.family == "encdec":
            embeds = self.embeds_fn(request) if self.embeds_fn else None
            enc_out = encdec.encode(self.params, self.cfg, embeds)
            return self.api.init_decode_state(1, self.max_len,
                                              params=self.params,
                                              enc_out=enc_out)
        return self._init_state()

    def _costs_for(self, tokens, pos, state) -> Tuple[float, float]:
        k = int(tokens.shape[1])
        if k not in self._decode_costs:
            compiled = self._decode.lower(self.params, state, tokens,
                                          pos).compile()
            self._decode_costs[k] = cost_analysis_of(compiled)
        return self._decode_costs[k]

    def warmup(self) -> None:
        """Compile (and discard) the fresh lane state, the two
        steady-state decode shapes and the sampler — excluded from every
        reported timing."""
        state = self._init_state() if self.cfg.family != "encdec" else None
        if state is None:
            return  # encdec compiles per request state; first call warms
        shapes = {1}
        if self.prefill_chunk > 1:
            shapes.add(self.prefill_chunk)
        logits = None
        for k in sorted(shapes):
            toks = jnp.zeros((1, k), jnp.int32)
            pos = jnp.arange(0, k, dtype=jnp.int32) if k > 1 \
                else jnp.int32(0)
            logits, _ = self._decode(self.params, state, toks, pos)
            self._costs_for(toks, pos, state)
        if logits is not None:
            jax.block_until_ready(self._sample(logits))
            if self._sample_cost is None:
                compiled = self._sample.lower(logits).compile()
                self._sample_cost = cost_analysis_of(compiled)

    # -- execution ---------------------------------------------------------
    def _timed(self, fn, *args):
        """Call ``fn``, wait for its result, and return it with the wall
        and CPU seconds taken.  Under a profiler session the call is split
        into ``serve.dispatch`` (until ``fn`` returns) and ``serve.wait``
        (until the device is done), labelled by :attr:`_call`."""
        t0w = time.perf_counter()
        t0c = self._clock()
        with span("serve.dispatch", **self._call) as self._dispatch:
            out = fn(*args)
        with span("serve.wait", **self._call):
            jax.block_until_ready(out)
        return out, time.perf_counter() - t0w, self._clock() - t0c

    def execute(self, s: int, events: Sequence[LaneEvent]) -> RegionTrace:
        tr = RegionTrace.for_tree(
            self.tree, self.region_ids, self.lanes, n_steps=1,
            metrics=RAW_METRICS,
            meta={"collector": "serve", "cpu_tick": self._tick,
                  "cpu_clock": self._clock_name, "derived": True})
        for ev in events:
            if ev.request is None:
                continue
            lane, req = ev.lane, ev.request
            if ev.new_request:
                self._state[lane] = self._fresh_state(req)
                self._pending_logits[lane] = None
                self._prompt[lane] = prompt_tokens(req, self.cfg.vocab,
                                                   self.seed)
                self.outputs.setdefault(req.rid, [])
            if ev.prefill_tokens:
                a, k = ev.prefill_start, ev.prefill_tokens
                toks = jnp.asarray(self._prompt[lane][:, a:a + k])
                pos = jnp.arange(a, a + k, dtype=jnp.int32) if k > 1 \
                    else jnp.int32(a)
                fl, by = self._costs_for(toks, pos, self._state[lane])
                self._call = {"kind": PREFILL, "lane": lane,
                              "rid": req.rid, "pos": a}
                (logits, new_state), dw, dc = self._timed(
                    self._decode, self.params, self._state[lane], toks, pos)
                self._state[lane] = new_state
                if a + k == req.prompt_len:
                    self._pending_logits[lane] = logits
                self._write(tr, PREFILL, lane, dw, dc, fl, by)
                self._experts(tr, lane, new_state, k)
            if ev.decode_tokens:
                # Sample the pending logits (its own timed region), then
                # feed the sampled token to produce the next logits.
                self._call = {"kind": SAMPLE, "lane": lane, "rid": req.rid,
                              "pos": ev.decode_pos}
                (tok, finite), dw, dc = self._timed(
                    self._sample, self._pending_logits[lane])
                sfl, sby = self._sample_cost or (0.0, 0.0)
                self._write(tr, SAMPLE, lane, dw, dc, sfl, sby)
                self.outputs[req.rid].append(int(tok[0, 0]))
                self.nonfinite_samples += not bool(finite)
                pos = jnp.int32(ev.decode_pos)
                fl, by = self._costs_for(tok, pos, self._state[lane])
                self._call = dict(self._call, kind=DECODE)
                (logits, new_state), dw, dc = self._timed(
                    self._decode, self.params, self._state[lane], tok, pos)
                self._state[lane] = new_state
                self._pending_logits[lane] = logits
                self._write(tr, DECODE, lane, dw, dc, fl, by)
                self._experts(tr, lane, new_state, 1)
            if ev.kv_tokens:
                # The KV write is fused into the decode kernel here, so
                # this region carries quantities, not time: appended
                # bytes and cache occupancy.
                j = tr.col(self._rid[KV_APPEND])
                tr.metric(BYTES)[0, 0, lane, j] = \
                    ev.kv_tokens * self.kv_bytes_per_token
                tr.metric(VMEM_PRESSURE)[0, 0, lane, j] = ev.occupancy
            if ev.finished:
                self._state[lane] = None
                self._pending_logits[lane] = None
                self._prompt[lane] = None
        return tr

    def _experts(self, tr: RegionTrace, lane: int, state, tokens: int
                 ) -> None:
        """The call's routed-token counts into the held experts' regions
        and onto its ``serve.dispatch`` span: ``routes_held`` (routes to
        held experts) and ``experts_hit`` (held experts with a route),
        summed over the expert layers."""
        if not self._expert_rids:
            return
        mo = self.cfg.moe
        counts = np.asarray(state["expert_counts"])      # (layers, E)
        held = counts[:, mo.first_held:mo.first_held + mo.n_held]
        routes, hit = held.sum(axis=0), (held > 0).sum(axis=0)
        self._dispatch.set(tokens=tokens, routes_held=int(routes.sum()),
                           experts_hit=int(hit.sum()))
        fl, by = tr.metric(FLOPS)[0, 0, lane], tr.metric(BYTES)[0, 0, lane]
        for rid, r, h in zip(self._expert_rids, routes, hit):
            fl[tr.col(rid)] += r * self._expert_flops
            by[tr.col(rid)] += h * self._expert_bytes
        j = tr.col(self._rid[MOE])
        fl[j] += routes.sum() * self._expert_flops
        by[j] += hit.sum() * self._expert_bytes

    def _write(self, tr: RegionTrace, phase: str, lane: int,
               wall: float, cpu: float, fl: float, by: float) -> None:
        j = tr.col(self._rid[phase])
        tr.metric(WALL_TIME)[0, 0, lane, j] += wall
        tr.metric(CPU_TIME)[0, 0, lane, j] += cpu
        tr.metric(FLOPS)[0, 0, lane, j] += fl
        tr.metric(BYTES)[0, 0, lane, j] += by

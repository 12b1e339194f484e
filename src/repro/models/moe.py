"""Routed mixture-of-experts, dropless, over a held share of the experts.

Design (DESIGN.md §6):
  * the router scores all ``n_experts`` and each token keeps its top-k
    (softmax gates, renormalised over the k unless ``norm_topk_prob`` is
    off), plus optional shared experts;
  * the layer holds a contiguous block of ``n_held`` experts (expert
    parallelism: each chip its own block, all of them by default) and
    computes only their part of the result, for every token routed to
    them: no capacity, no token dropped.  What the other blocks would add
    is the other chips' part;
  * the call's shape picks how the held part is computed.  With fewer
    routes than held experts (decode) each distinct held expert that a
    route hit is computed once under a ``lax.cond``, so only the weights
    of experts that were routed to are read.  Below ``GROUPED_TOKENS``
    tokens (a prompt chunk) every held expert is read anyway, and each
    takes every token, weighted by the token's gate for it.  From
    ``GROUPED_TOKENS`` on (a training batch) the routes are sorted by
    expert through grouped products (``lax.ragged_dot``), whose work does
    not grow with the experts held.  On one TPU v5e a 256-token prompt
    chunk of DeepSeek-V2-Lite at 16 of its 64 experts took 30.6 ms the
    first way and 51.5 ms the grouped way; a training pass of one layer
    holding all 64 took 32.6 ms against 27.7 ms at 1024 tokens, and
    92.1 ms against 48.9 ms at 4096 (PERF.md);
  * per-expert routed-token counts over all ``n_experts`` are returned —
    the per-"process" load vectors the AutoAnalyzer dissimilarity pass
    reads (the paper's ST load-imbalance scenario, DESIGN.md §4);
  * aux load-balancing loss (Switch-style) with configurable weight — the
    "dynamic load dispatching" fix of paper §6.1.1.

The expert weights may come stacked over layers, ``(L, n_held, ...)``,
with the layer to use: a decode scan then reads one expert's slice of
the stack in place, not a copy of the layer's whole block.

Sharding: 'ep' puts the expert dim on the model axis; 'tp' (for E <
model-axis) keeps experts replicated and shards each expert's hidden dim.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

from .layers import _act, make_param

Params = Dict[str, Any]

EXPERT_KEYS = ("wi", "wg", "wo")
GROUPED_TOKENS = 1024


def init_moe(key, cfg: ModelConfig, dtype) -> Tuple[Params, Params]:
    mo = cfg.moe
    d, ff, E = cfg.d_model, mo.d_ff, mo.n_held
    ks = jax.random.split(key, 5)
    p, a = {}, {}
    p["router"], a["router"] = make_param(
        ks[0], (d, mo.n_experts), ("embed", "expert_r"), dtype)
    p["wi"], a["wi"] = make_param(ks[1], (E, d, ff), ("expert", "embed", "mlp"), dtype)
    p["wg"], a["wg"] = make_param(ks[2], (E, d, ff), ("expert", "embed", "mlp"), dtype)
    p["wo"], a["wo"] = make_param(ks[3], (E, ff, d), ("expert", "mlp", "embed"), dtype)
    if mo.n_shared:
        sk = jax.random.split(ks[4], 3)
        p["shared_wi"], a["shared_wi"] = make_param(
            sk[0], (d, ff * mo.n_shared), ("embed", "mlp"), dtype)
        p["shared_wg"], a["shared_wg"] = make_param(
            sk[1], (d, ff * mo.n_shared), ("embed", "mlp"), dtype)
        p["shared_wo"], a["shared_wo"] = make_param(
            sk[2], (ff * mo.n_shared, d), ("mlp", "embed"), dtype)
    return p, a


def route(router, cfg: ModelConfig, x):
    """x (N, D) -> (probs (N, E) f32, gates (N, k) f32, ids (N, k))."""
    mo = cfg.moe
    logits = jnp.einsum("nd,de->ne", x, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, ids


def _expert(x, wg, wi, wo, activation: str):
    h = _act(x @ wg, activation) * (x @ wi)
    return h @ wo


def _per_expert(x, gates, local, w, layer, cfg: ModelConfig):
    """Held part for few routes: each distinct held expert a route hit,
    computed once for all N tokens under a ``lax.cond`` that reads its
    weights only when it was hit.  local (N, k): held index or n_held."""
    n_held = cfg.moe.n_held
    flat = jnp.sort(local.reshape(-1))
    first = jnp.concatenate([jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    out = jnp.zeros(x.shape, x.dtype)
    for j in range(flat.shape[0]):
        e = jnp.minimum(flat[j], n_held - 1)
        gate = jnp.sum(jnp.where(local == flat[j], gates, 0.0), axis=-1)

        def hit(o, e=e, gate=gate):
            ws = [lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(w[k], layer, 0, keepdims=False),
                e, 0, keepdims=False) for k in ("wg", "wi", "wo")]
            y = _expert(x, *ws, cfg.activation)
            return o + y * gate.astype(y.dtype)[:, None]

        out = lax.cond(first[j] & (flat[j] < n_held), hit, lambda o: o, out)
    return out


def _every_held(x, gates, local, w, layer, cfg: ModelConfig):
    """Held part for many routes: each held expert over every token,
    weighted by the token's gate for it (zero where the token did not
    route there)."""
    out = jnp.zeros(x.shape, x.dtype)
    for e in range(cfg.moe.n_held):
        gate = jnp.sum(jnp.where(local == e, gates, 0.0), axis=-1)
        y = _expert(x, *(w[k][layer, e] for k in ("wg", "wi", "wo")),
                    cfg.activation)
        out = out + y * gate.astype(y.dtype)[:, None]
    return out


def _grouped(x, gates, local, w, layer, cfg: ModelConfig):
    """Held part for many tokens: the routes sorted by held expert, each
    expert's run of them through its weights as one grouped product
    (``lax.ragged_dot``).  Routes to experts held elsewhere sort last,
    past every group, and add nothing."""
    N, k = local.shape
    n_held = cfg.moe.n_held
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tok = order // k
    sizes = jnp.bincount(flat, length=n_held + 1)[:n_held].astype(jnp.int32)
    xs = x[tok]
    wg, wi, wo = (w[n][layer] for n in ("wg", "wi", "wo"))
    h = _act(lax.ragged_dot(xs, wg, sizes), cfg.activation) \
        * lax.ragged_dot(xs, wi, sizes)
    y = lax.ragged_dot(h, wo, sizes)
    gate = jnp.where(flat[order] < n_held, gates.reshape(-1)[order], 0.0)
    return jnp.zeros(x.shape, x.dtype).at[tok].add(
        y * gate.astype(y.dtype)[:, None])


def moe_block(params: Params, cfg: ModelConfig, x, layer=None):
    """x: (B, S, D) -> (y, aux_loss, expert_counts (E,)).

    ``params["wi"|"wg"|"wo"]`` are the held experts' weights, ``(n_held,
    ...)``, or stacked over layers, ``(L, n_held, ...)``, with ``layer``
    the index into that stack.  ``expert_counts`` are the routes each of
    the ``n_experts`` received, held or not."""
    mo = cfg.moe
    B, S, D = x.shape
    E = mo.n_experts
    xf = x.reshape(B * S, D)
    probs, gates, ids = route(params["router"], cfg, xf)

    # Switch-style aux loss: E * sum_e f_e * p_e (f = fraction of top-1
    # dispatches, p = mean router prob).
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(jnp.argmax(probs, axis=-1), E,
                        dtype=jnp.float32).mean(axis=0)
    aux = mo.aux_loss_weight * E * jnp.sum(me * ce)
    counts = jnp.bincount(ids.reshape(-1), length=E)

    w = {n: params[n] for n in EXPERT_KEYS}
    if layer is None:
        w = {n: v[None] for n, v in w.items()}
        layer = 0
    local = ids - mo.first_held
    local = jnp.where((local >= 0) & (local < mo.n_held), local, mo.n_held)
    if ids.size < mo.n_held:
        part = _per_expert
    elif B * S < GROUPED_TOKENS:
        part = _every_held
    else:
        part = _grouped
    y = part(xf, gates, local, w, layer, cfg)

    out = y.reshape(B, S, D)
    if mo.n_shared:
        h = _act(jnp.einsum("bsd,df->bsf", x, params["shared_wg"]), cfg.activation)
        h = h * jnp.einsum("bsd,df->bsf", x, params["shared_wi"])
        out = out + jnp.einsum("bsf,fd->bsd", h, params["shared_wo"])
    return out, aux, counts

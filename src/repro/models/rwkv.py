"""RWKV-6 (Finch) blocks: time-mix with data-dependent decay + channel-mix.

The WKV-6 recurrence per head (state S in R^{dh x dh}):

    out_t = r_t · (S + (u ⊙ k_t) v_tᵀ)
    S     = diag(w_t) S + k_t v_tᵀ

with w_t = exp(-exp(w0 + lora(x_t))) the *data-dependent* decay (the Finch
novelty vs RWKV-5).  :func:`wkv6_reference` is a lax.scan over time;
:func:`wkv6_chunked` is the chunked matmul formulation of the same math,
validated against it.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

from .layers import make_param, zeros_param

Params = Dict[str, Any]


def init_rwkv_block(key, cfg: ModelConfig, dtype) -> Tuple[Params, Params]:
    d, ff = cfg.d_model, cfg.d_ff
    dh = cfg.recurrent.head_dim
    H = d // dh
    ks = jax.random.split(key, 12)
    p, a = {}, {}
    # time-mix interpolation params (token shift): one per projection
    for i, nm in enumerate(["mu_r", "mu_k", "mu_v", "mu_g", "mu_w"]):
        p[nm] = zeros_param((d,), dtype)
        a[nm] = ("embed",)
    p["wr"], a["wr"] = make_param(ks[0], (d, d), ("embed", "heads_x_dim"), dtype)
    p["wk"], a["wk"] = make_param(ks[1], (d, d), ("embed", "heads_x_dim"), dtype)
    p["wv"], a["wv"] = make_param(ks[2], (d, d), ("embed", "heads_x_dim"), dtype)
    p["wg"], a["wg"] = make_param(ks[3], (d, d), ("embed", "heads_x_dim"), dtype)
    p["wo"], a["wo"] = make_param(ks[4], (d, d), ("heads_x_dim", "embed"), dtype)
    # data-dependent decay lora: d -> 64 -> d, plus base decay w0 and bonus u
    p["w_lora_a"], a["w_lora_a"] = make_param(ks[5], (d, 64), ("embed", "lora"), dtype)
    p["w_lora_b"], a["w_lora_b"] = make_param(ks[6], (64, d), ("lora", "embed"), dtype)
    p["w0"] = zeros_param((d,), dtype); a["w0"] = ("embed",)
    p["u"], a["u"] = make_param(ks[7], (d,), ("embed",), dtype, scale=1.0)
    p["ln_x"] = zeros_param((d,), dtype); a["ln_x"] = ("embed",)  # group-norm weight
    # channel-mix
    p["mu_c"] = zeros_param((d,), dtype); a["mu_c"] = ("embed",)
    p["ck"], a["ck"] = make_param(ks[8], (d, ff), ("embed", "mlp"), dtype)
    p["cv"], a["cv"] = make_param(ks[9], (ff, d), ("mlp", "embed"), dtype)
    p["cr"], a["cr"] = make_param(ks[10], (d, d), ("embed", "heads_x_dim"), dtype)
    return p, a


def wkv6_reference(r, k, v, w, u):
    """Sequential WKV-6 oracle.  r,k,v,w: (B, T, H, dh); u: (H, dh).
    Returns (out (B,T,H,dh), final state (B,H,dh,dh))."""
    B, T, H, dh = r.shape
    S0 = jnp.zeros((B, H, dh, dh), jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp  # (B,H,dh)
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,dh,dh)
        out = jnp.einsum("bhk,bhkv->bhv", rt,
                         S + u[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, out

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, w))
    S, outs = lax.scan(step, S0, xs)
    return jnp.moveaxis(outs, 0, 1).astype(r.dtype), S


def wkv6_chunked(r, k, v, w, u, S0, chunk: int = 64, unroll: bool = False):
    """Chunked WKV-6 (same identity as wkv6_reference) in pure jnp:
    MXU-matmul formulation, optionally fully unrolled over chunks so the
    dry-run cost probes count true FLOPs.  r,k,v,w: (B,T,H,dh); u: (H,dh);
    S0: (B,H,dh,dh).  Returns (out, S_final)."""
    B, T, H, dh = r.shape
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        zp = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zp(r), zp(k), zp(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    Tp = T + pad
    nc = Tp // chunk
    resh = lambda t: jnp.moveaxis(
        t.astype(jnp.float32).reshape(B, nc, chunk, H, dh), 1, 0)
    rc, kc, vc, wc = resh(r), resh(k), resh(v), resh(w)
    uf = u.astype(jnp.float32)

    def step(S, inp):
        rx, kx, vx, wx = inp                      # (B,C,H,dh)
        logw = jnp.log(jnp.maximum(wx, 1e-30))
        cum = jnp.cumsum(logw, axis=1)
        q_t = rx * jnp.exp(cum - logw)
        k_t = kx * jnp.exp(-cum)
        scores = jnp.einsum("bthd,bshd->bhts", q_t, k_t)
        C = rx.shape[1]
        tri = jnp.tril(jnp.ones((C, C), jnp.float32), k=-1)
        scores = scores * tri[None, None]
        diag = jnp.einsum("bthd,bthd->bth", rx, uf[None, None] * kx)
        intra = jnp.einsum("bhts,bshd->bthd", scores, vx)
        intra = intra + diag[..., None] * vx
        inter = jnp.einsum("bthd,bhdv->bthv", q_t, S)
        out = intra + inter
        decay_all = jnp.exp(cum[:, -1])           # (B,H,dh)
        k_rem = kx * jnp.exp(cum[:, -1:][:, :, :] - cum)
        S = decay_all[..., None] * S + jnp.einsum("bshd,bshv->bhdv",
                                                  k_rem, vx)
        return S, out

    S, outs = lax.scan(step, S0.astype(jnp.float32), (rc, kc, vc, wc),
                       unroll=nc if unroll else 1)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tp, H, dh)[:, :T]
    return out.astype(r.dtype), S


def _shift(x, prev=None):
    """Token shift: x_{t-1} (zeros / `prev` carry at t=0). x: (B,T,D)."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    else:
        prev = prev[:, None, :] if prev.ndim == 2 else prev
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def rwkv_time_mix(params: Params, cfg: ModelConfig, x,
                  state: Optional[Dict] = None):
    """x: (B,T,D).  state: {"S": (B,H,dh,dh), "last": (B,D)} for decode."""
    B, T, D = x.shape
    dh = cfg.recurrent.head_dim
    H = D // dh
    last = state["last_tm"] if state is not None else None
    xs = _shift(x, last)

    def lerp(mu):
        return x + (xs - x) * mu

    r = jnp.einsum("btd,de->bte", lerp(params["mu_r"]), params["wr"])
    kk = jnp.einsum("btd,de->bte", lerp(params["mu_k"]), params["wk"])
    vv = jnp.einsum("btd,de->bte", lerp(params["mu_v"]), params["wv"])
    g = jnp.einsum("btd,de->bte", lerp(params["mu_g"]), params["wg"])
    wx = lerp(params["mu_w"])
    dd = params["w0"] + jnp.einsum(
        "btd,dl,le->bte", wx, params["w_lora_a"], params["w_lora_b"])
    w = jnp.exp(-jnp.exp(dd.astype(jnp.float32)))          # (B,T,D) in (0,1)

    hs = (B, T, H, dh)
    r4, k4, v4, w4 = (t.reshape(hs) for t in (r, kk, vv, w))
    u = params["u"].reshape(H, dh).astype(jnp.float32)
    S0 = state["S"] if state is not None else jnp.zeros((B, H, dh, dh), jnp.float32)

    if cfg.probe_unroll or T >= 512:
        # Chunked MXU formulation (the TPU production path).  Longer
        # sequences use a larger chunk: amortises state I/O and keeps the
        # unrolled probe HLO bounded (<=128 chunk steps).
        chunk = 32 if T <= 8192 else 256
        y4, S = wkv6_chunked(r4, k4, v4, w4, u, S0, chunk=chunk,
                             unroll=cfg.probe_unroll)
        y = y4.reshape(B, T, D).astype(jnp.float32)
    else:
        def step(S, inp):
            rt, kt, vt, wt = inp
            kv = kt[..., :, None] * vt[..., None, :]
            out = jnp.einsum("bhk,bhkv->bhv", rt,
                             S + u[None, :, :, None] * kv)
            S = wt[..., :, None] * S + kv
            return S, out

        xs_scan = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                        for t in (r4, k4, v4, w4))
        S, outs = lax.scan(step, S0, xs_scan)
        y = jnp.moveaxis(outs, 0, 1).reshape(B, T, D)
    # per-head group norm then gate
    yg = y.reshape(B, T, H, dh)
    mean = yg.mean(-1, keepdims=True)
    var = yg.var(-1, keepdims=True)
    yg = (yg - mean) * lax.rsqrt(var + 1e-5)
    y = (yg.reshape(B, T, D) * (1.0 + params["ln_x"].astype(jnp.float32)))
    y = y.astype(x.dtype) * jax.nn.silu(g)
    y = jnp.einsum("btd,de->bte", y, params["wo"])
    new_state = {"S": S, "last_tm": x[:, -1]}
    return y, new_state


def rwkv_channel_mix(params: Params, cfg: ModelConfig, x,
                     state: Optional[Dict] = None):
    last = state["last_cm"] if state is not None else None
    xs = _shift(x, last)
    xk = x + (xs - x) * params["mu_c"]
    k = jnp.einsum("btd,df->btf", xk, params["ck"])
    k = jnp.square(jax.nn.relu(k))
    v = jnp.einsum("btf,fd->btd", k, params["cv"])
    r = jax.nn.sigmoid(jnp.einsum("btd,de->bte", x, params["cr"]))
    return r * v, {"last_cm": x[:, -1]}

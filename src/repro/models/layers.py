"""Shared model layers, pure-functional JAX.

Parameters are nested dicts of arrays; every init function returns
``(params, axes)`` where ``axes`` mirrors the params tree with tuples of
*logical* axis names consumed by ``repro.sharding.rules``.

Attention comes in two flavours:
  * naive (materialised scores) — small seqs / oracle,
  * chunked flash-style scan (online softmax) — the memory-bounded pure-JAX
    path used in dry-runs and long sequences.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.sharding import constrain

Params = Dict[str, Any]
Axes = Dict[str, Any]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------
import contextlib
import threading


class _AbstractFlag(threading.local):
    on = False


_ABSTRACT = _AbstractFlag()


@contextlib.contextmanager
def abstract_init():
    """While active, init functions return ShapeDtypeStructs (no device
    allocation) — the dry-run path for full-size configs."""
    prev = _ABSTRACT.on
    _ABSTRACT.on = True
    try:
        yield
    finally:
        _ABSTRACT.on = prev


def is_abstract() -> bool:
    return _ABSTRACT.on


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    if _ABSTRACT.on:
        return jax.ShapeDtypeStruct(shape, dtype)
    fan_in = shape[0] if len(shape) > 1 else 1
    s = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (s * jax.random.normal(key, shape)).astype(dtype)


def zeros_param(shape, dtype):
    if _ABSTRACT.on:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def uniform_param(key, shape, dtype, minval=0.0, maxval=1.0):
    if _ABSTRACT.on:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.random.uniform(key, shape, minval=minval,
                              maxval=maxval).astype(dtype)


def make_param(key, shape, axes, dtype, scale=None):
    return dense_init(key, shape, dtype, scale), tuple(axes)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rms_norm(x, w, eps: float):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def init_rms_norm(d, dtype):
    return zeros_param((d,), dtype), ("embed",)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * np.log(factor) + 1.0


def rope_freqs(dim: int, theta: float, yarn) -> np.ndarray:
    """The dim/2 YaRN rotary frequencies (``yarn`` a YarnConfig): the
    original ones up to the beta_fast correction dim, the interpolated
    ones (/ factor) past the beta_slow one, and a linear ramp between."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * np.log(yarn.original_max_positions
                            / (rot * 2 * np.pi)) / (2 * np.log(theta))

    lo = max(int(np.floor(corr(yarn.beta_fast))), 0)
    hi = min(int(np.ceil(corr(yarn.beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (base / yarn.factor * ramp + base * (1 - ramp)).astype(np.float32)


def rope_angles(positions, dim: int, theta: float, yarn=None):
    """positions (..., S) -> cos/sin (..., S, dim/2), with YaRN's
    frequencies and its cos/sin factor where ``yarn`` is given."""
    if yarn is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                 / dim))
        ang = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(ang), jnp.sin(ang)
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(rope_freqs(dim, theta, yarn))
    m = np.float32(yarn_mscale(yarn.factor, yarn.mscale)
                   / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, positions, cfg: ModelConfig):
    """x: (..., S, H, Dh).  Styles:
    'half'        — llama rotate-half over the full head dim;
    'partial'     — chatglm 2d rope: only rope_fraction of dims, interleaved
                    pairs, remainder passed through;
    'interleaved' — gpt-neox interleaved pairs over the full dim.
    """
    dh = x.shape[-1]
    frac = cfg.rope_fraction if cfg.rope_style == "partial" else 1.0
    rot = int(dh * frac)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    pos = positions  # (..., S)
    cos, sin = rope_angles(pos, rot, cfg.rope_theta, cfg.yarn)  # (..., S, rot/2)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    if cfg.rope_style == "half":
        x1, x2 = jnp.split(xr, 2, axis=-1)
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.concatenate([o1, o2], axis=-1)
    else:  # interleaved pairs (also the chatglm partial style)
        x1 = xr[..., 0::2]
        x2 = xr[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1) if rot < dh \
        else out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype) -> Tuple[Params, Axes]:
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = make_param(ks[0], (d, H, dh), ("embed", "heads", "head_dim"), dtype)
    p["wk"], a["wk"] = make_param(ks[1], (d, KV, dh), ("embed", "kv_heads", "head_dim"), dtype)
    p["wv"], a["wv"] = make_param(ks[2], (d, KV, dh), ("embed", "kv_heads", "head_dim"), dtype)
    p["wo"], a["wo"] = make_param(ks[3], (H, dh, d), ("heads", "head_dim", "embed"), dtype)
    return p, a


def _soft_cap(scores, cap: Optional[float]):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def naive_attention(q, k, v, *, causal: bool, window: Optional[int],
                    q_positions, k_positions, softcap=None):
    """q (B,Q,H,dh), k/v (B,K,KV,dh) -> (B,Q,H,dh).  Materialises scores —
    for short sequences, single-token decode, and as the oracle for the
    chunked path.  Operands stay in their storage dtype with f32
    MXU accumulation (``preferred_element_type``) — pre-casting a 32k-long
    KV cache to f32 would double its HBM/collective traffic (§Perf)."""
    B, Q, H, dh = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, Q, KV, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) / np.sqrt(dh)
    scores = _soft_cap(scores, softcap)
    mask = jnp.ones((Q, k.shape[1]), dtype=bool)
    qp = q_positions[:, None]
    kp = k_positions[None, :]
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Q, H, dh).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      q_positions, k_positions, softcap=None,
                      q_block: int = 512, k_block: int = 1024,
                      unroll: bool = False):
    """Flash-style two-level blocked attention with online softmax.

    Memory is O(q_block × k_block) per step instead of O(Q × K); this is the
    path the dry-run lowers for long sequences.
    """
    B, Q, H, dh = q.shape
    K = k.shape[1]
    KV = k.shape[2]
    g = H // KV
    q_block = min(q_block, Q)
    k_block = min(k_block, K)
    # pad to multiples
    Qp = -(-Q // q_block) * q_block
    Kp = -(-K // k_block) * k_block
    qpad = jnp.pad(q, ((0, 0), (0, Qp - Q), (0, 0), (0, 0)))
    kpad = jnp.pad(k, ((0, 0), (0, Kp - K), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (0, Kp - K), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, (0, Qp - Q), constant_values=-1)
    kpos = jnp.pad(k_positions, (0, Kp - K), constant_values=2**30)
    nq, nk = Qp // q_block, Kp // k_block
    qb = qpad.reshape(B, nq, q_block, KV, g, dh)
    kb = kpad.reshape(B, nk, k_block, KV, dh)
    vb = vpad.reshape(B, nk, k_block, KV, dh)
    qposb = qpos.reshape(nq, q_block)
    kposb = kpos.reshape(nk, k_block)
    scale = 1.0 / np.sqrt(dh)

    def per_qblock(qi, qpos_i):
        # online softmax over k blocks
        acc0 = jnp.zeros((B, q_block, KV, g, dh), jnp.float32)
        m0 = jnp.full((B, KV, g, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, g, q_block), jnp.float32)

        def step(carry, inp):
            acc, m, l = carry
            kj, vj, kpos_j = inp
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            s = _soft_cap(s, softcap)
            msk = jnp.ones((q_block, k_block), bool)
            qp = qpos_i[:, None]
            kp = kpos_j[None, :]
            if causal:
                msk &= kp <= qp
            if window is not None:
                msk &= kp > qp - window
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bqkgd", p, vj.astype(jnp.float32))
            acc_new = acc * jnp.moveaxis(alpha, -1, 1)[..., None] + pv
            return (acc_new, m_new, l_new), None

        (acc, m, l), _ = lax.scan(step, (acc0, m0, l0),
                                  (jnp.moveaxis(kb, 1, 0),
                                   jnp.moveaxis(vb, 1, 0), kposb),
                                  unroll=nk if unroll else 1)
        l = jnp.maximum(l, 1e-30)
        out = acc / jnp.moveaxis(l, -1, 1)[..., None]
        return out.reshape(B, q_block, H, dh)

    _, out = lax.scan(
        lambda _, args: (None, per_qblock(*args)), None,
        (jnp.moveaxis(qb, 1, 0), qposb), unroll=nq if unroll else 1)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Qp, H, dh)[:, :Q]
    return out.astype(q.dtype)


def attention(params: Params, cfg: ModelConfig, x, positions,
              cache: Optional[Params] = None,
              kv_override: Optional[Tuple] = None):
    """Full attention sub-layer: projections + rope + SDPA (+ KV cache).

    ``cache``: {"k": (B,S,KV,dh), "v": ..., "idx": scalar} for decode.
    ``kv_override``: (k_in, v_in, k_positions) for cross-attention.
    Returns (out, new_cache).
    """
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
        k_positions = positions
    else:
        k, v, k_positions = kv_override
    new_cache = None
    if cache is not None:
        # Ring-buffer KV cache: slot positions are tracked explicitly so a
        # sliding window needs only `window` slots (paper-of-record SWA
        # decode).  Unwritten slots carry position 2**30 => masked by the
        # causal test.
        idx = cache["idx"]
        S = x.shape[1]
        max_len = cache["k"].shape[1]
        write = idx % max_len if S == 1 else idx
        k = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), write, axis=1)
        v = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), write, axis=1)
        pos1 = positions if positions.ndim == 1 else positions[0]
        pos = lax.dynamic_update_slice_in_dim(
            cache["pos"], pos1.astype(cache["pos"].dtype), write, axis=0)
        # pin the cache to its (batch × kv-head) sharding so the attention
        # einsum never gathers it, and an MHA cache fits HBM
        # (§Perf: gemma decode_32k iterations 1-2)
        k = constrain(k, ("batch", "seq", "act_kv", None))
        v = constrain(v, ("batch", "seq", "act_kv", None))
        new_cache = {"k": k, "v": v, "pos": pos, "idx": idx + S}
        k_positions = pos
    q_pos = positions if positions.ndim == 1 else positions[0]
    k_pos = k_positions if k_positions.ndim == 1 else k_positions[0]
    use_chunked = (x.shape[1] * k.shape[1] > 1024 * 1024)
    fn = chunked_attention if use_chunked else naive_attention
    out = fn(q, k, v, causal=cfg.causal and kv_override is None,
             window=cfg.window if kv_override is None else None,
             q_positions=q_pos, k_positions=k_pos,
             softcap=cfg.attn_logit_softcap,
             **({"q_block": cfg.attn_q_block, "k_block": cfg.attn_k_block,
                 "unroll": cfg.probe_unroll}
                if fn is chunked_attention else {}))
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, new_cache


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    KV, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.window is not None:
        max_len = min(max_len, cfg.window)
    return {
        "k": jnp.zeros((batch, max_len, KV, dh), dtype),
        "v": jnp.zeros((batch, max_len, KV, dh), dtype),
        "pos": jnp.full((max_len,), 2**30, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# --------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig, dtype) -> Tuple[Params, Axes]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = make_param(ks[0], (d, H, qd), ("embed", "heads", "head_dim"), dtype)
    p["wkv_a"], a["wkv_a"] = make_param(
        ks[1], (d, m.kv_lora_rank + m.rope_head_dim), ("embed", "kv_lora"), dtype)
    # RMSNorm on the latent before it is cached and decompressed
    # (DeepSeek's kv_a_layernorm).
    p["kv_norm"], a["kv_norm"] = zeros_param((m.kv_lora_rank,), dtype), \
        ("kv_lora",)
    p["wkv_b"], a["wkv_b"] = make_param(
        ks[2], (m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim),
        ("kv_lora", "heads", "head_dim"), dtype)
    p["wo"], a["wo"] = make_param(ks[3], (H, m.v_head_dim, d),
                                  ("heads", "head_dim", "embed"), dtype)
    return p, a


def mla_mscale2(cfg: ModelConfig) -> float:
    """YaRN's factor on the attention softmax scale, mscale ** 2."""
    y = cfg.yarn
    if y is None or not y.mscale_all_dim:
        return 1.0
    return float(yarn_mscale(y.factor, y.mscale_all_dim) ** 2)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope head dim) ** -0.5, times YaRN's mscale ** 2."""
    m = cfg.mla
    return (m.nope_head_dim + m.rope_head_dim) ** -0.5 * mla_mscale2(cfg)


def _mla_latent_decode(params: Params, cfg: ModelConfig, q_nope, q_rope,
                       c_kv, k_rope, q_pos):
    """One query position against the cached latent, in latent space:
    ``wkv_b``'s key half is absorbed into the query, which scores the
    normalised latent (plus the shared rope key); the weighted latent then
    goes through ``wkv_b``'s value half.  The cache is never decompressed.
    q_nope (B,1,H,dn), q_rope (B,1,H,dr), c_kv (B,T,r), k_rope (B,T,dr)."""
    m = cfg.mla
    wk = params["wkv_b"][..., :m.nope_head_dim]            # (r, H, dn)
    wv = params["wkv_b"][..., m.nope_head_dim:]            # (r, H, dv)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk,
                       preferred_element_type=jnp.float32).astype(c_kv.dtype)
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope,
                           preferred_element_type=jnp.float32))
    scores = scores * mla_softmax_scale(cfg)
    visible = jnp.arange(c_kv.shape[1]) <= q_pos[-1]
    scores = jnp.where(visible, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", w, c_kv,
                       preferred_element_type=jnp.float32).astype(c_kv.dtype)
    return jnp.einsum("bshr,rhk->bshk", o_lat, wv)


def _mla_decompressed(params: Params, cfg: ModelConfig, q_nope, q_rope,
                      c_kv, k_rope, q_pos, k_positions):
    """Queries against keys and values decompressed from the latent
    through ``wkv_b``, at full rank (the prompt path).  Returns the heads'
    values, (B, S, H, dv)."""
    m = cfg.mla
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, params["wkv_b"])
    k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
    # assemble full-rank q/k with the shared rope key broadcast over heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                (*k_nope.shape[:3], m.rope_head_dim))
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    # the attention kernels scale by qd ** -0.5; YaRN's mscale ** 2 rides
    # on the query
    if mla_mscale2(cfg) != 1.0:
        q_full = (q_full.astype(jnp.float32) * mla_mscale2(cfg)
                  ).astype(q_full.dtype)
    use_chunked = (q_full.shape[1] * k_full.shape[1] > 1024 * 1024)
    fn = chunked_attention if use_chunked else naive_attention
    # pad v to match head dims for the shared kernel, slice after
    pad = q_full.shape[-1] - v.shape[-1]
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    return fn(q_full, k_full, v_p, causal=cfg.causal, window=cfg.window,
              q_positions=q_pos, k_positions=k_positions,
              softcap=cfg.attn_logit_softcap,
              **({"q_block": cfg.attn_q_block, "k_block": cfg.attn_k_block,
                  "unroll": cfg.probe_unroll}
                 if fn is chunked_attention else {}))[..., :m.v_head_dim]


def mla_attention(params: Params, cfg: ModelConfig, x, positions,
                  cache: Optional[Params] = None):
    """MLA: KV compressed to a per-token latent (kv_lora_rank) + a shared
    rope key.  The decode cache stores only the (normalised) latent + rope
    key — the memory saving that is MLA's point.  A single query position
    against a cache attends in latent space (:func:`_mla_latent_decode`);
    a prompt, with or without a cache, decompresses keys and values
    through ``wkv_b``."""
    m = cfg.mla
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    rcfg = cfg.with_(rope_style="half", rope_fraction=1.0)
    q_rope = apply_rope(q_rope, positions, rcfg)
    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_kv, k_rope = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, rcfg)[:, :, 0]
    q_pos = positions if positions.ndim == 1 else positions[0]
    new_cache = None
    if cache is not None:
        idx = cache["idx"]
        c_kv = lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), idx, 1)
        k_rope = lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), idx, 1)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope, "idx": idx + S}
    if cache is not None and S == 1:
        out = _mla_latent_decode(params, cfg, q_nope, q_rope, c_kv, k_rope,
                                 q_pos)
    else:
        k_positions = jnp.arange(c_kv.shape[1]) if cache is not None \
            else q_pos
        out = _mla_decompressed(params, cfg, q_nope, q_rope, c_kv, k_rope,
                                q_pos, k_positions)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu_sq":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def init_mlp(key, d: int, ff: int, dtype) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 3)
    p, a = {}, {}
    p["wi"], a["wi"] = make_param(ks[0], (d, ff), ("embed", "mlp"), dtype)
    p["wg"], a["wg"] = make_param(ks[1], (d, ff), ("embed", "mlp"), dtype)
    p["wo"], a["wo"] = make_param(ks[2], (ff, d), ("mlp", "embed"), dtype)
    return p, a


def mlp(params: Params, x, activation: str):
    h = _act(jnp.einsum("bsd,df->bsf", x, params["wg"]), activation)
    h = h * jnp.einsum("bsd,df->bsf", x, params["wi"])
    return jnp.einsum("bsf,fd->bsd", h, params["wo"])


# --------------------------------------------------------------------------
# embedding / head
# --------------------------------------------------------------------------
def init_embed(key, cfg: ModelConfig, dtype) -> Tuple[Params, Axes]:
    p, a = {}, {}
    p["tokens"], a["tokens"] = make_param(
        key, (cfg.vocab, cfg.d_model), ("vocab", "embed"), dtype, scale=1.0)
    return p, a


def embed(params: Params, cfg: ModelConfig, tokens):
    x = params["tokens"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x.astype(cfg.activation_dtype())


def logits_from(params_embed, head, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params_embed["tokens"]).astype(jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)


def cross_entropy(logits, labels, mask=None):
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()

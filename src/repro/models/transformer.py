"""Decoder-only LM covering the dense / moe / vlm / ssm / hybrid families.

Layers are stacked (leading L dim) and driven by ``lax.scan`` so compile
time and HLO size are O(1) in depth; remat policy is a config knob.
Activation sharding constraints are inserted via repro.sharding.constrain
(no-ops outside an activation_sharding context).
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

from . import moe as moe_mod
from . import rglru as rglru_mod
from . import rwkv as rwkv_mod
from .layers import (attention, cross_entropy, embed, init_attention,
                     init_attention_cache, init_embed, init_mla,
                     init_mla_cache, init_mlp, init_rms_norm, logits_from,
                     make_param, mla_attention, mlp, rms_norm)

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def _init_dense_layer(key, cfg: ModelConfig, dtype, moe: bool = True):
    """One attention layer with the config's experts (``moe``) or with a
    SwiGLU of ``cfg.d_ff``."""
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["ln1"], a["ln1"] = init_rms_norm(cfg.d_model, dtype)
    p["ln2"], a["ln2"] = init_rms_norm(cfg.d_model, dtype)
    if cfg.mla is not None:
        p["attn"], a["attn"] = init_mla(ks[0], cfg, dtype)
    else:
        p["attn"], a["attn"] = init_attention(ks[0], cfg, dtype)
    if cfg.moe is not None and moe:
        p["moe"], a["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"], a["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p, a


def n_dense_layers(cfg: ModelConfig) -> int:
    """Leading dense layers that sit before an MoE config's expert stack
    (``params["dense"]``); the rest are ``params["layers"]``."""
    return cfg.moe.first_dense if cfg.moe is not None else 0


def _init_rwkv_layer(key, cfg: ModelConfig, dtype):
    p, a = {}, {}
    p["ln1"], a["ln1"] = init_rms_norm(cfg.d_model, dtype)
    p["ln2"], a["ln2"] = init_rms_norm(cfg.d_model, dtype)
    p["block"], a["block"] = rwkv_mod.init_rwkv_block(key, cfg, dtype)
    return p, a


def _init_hybrid_sublayer(key, cfg: ModelConfig, kind: str, dtype):
    ks = jax.random.split(key, 2)
    p, a = {}, {}
    p["ln1"], a["ln1"] = init_rms_norm(cfg.d_model, dtype)
    p["ln2"], a["ln2"] = init_rms_norm(cfg.d_model, dtype)
    if kind == "rec":
        p["mix"], a["mix"] = rglru_mod.init_rglru_block(ks[0], cfg, dtype)
    else:
        p["mix"], a["mix"] = init_attention(ks[0], cfg, dtype)
    p["mlp"], a["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p, a


def _stack_init(fn, key, n: int):
    """vmap an init over n keys -> params with leading layer dim."""
    from .layers import is_abstract
    if is_abstract():
        p1, axes = fn(key)
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), p1)
    else:
        keys = jax.random.split(key, n)
        params = jax.vmap(lambda k: fn(k)[0])(keys)
        _, axes = fn(keys[0])
    axes = jax.tree.map(lambda ax: ("layers",) + ax, axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    return params, axes


def hybrid_pattern(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    pat = cfg.recurrent.block_pattern
    n_blocks = cfg.n_layers // len(pat)
    n_tail = cfg.n_layers - n_blocks * len(pat)
    return n_blocks, pat[:n_tail]


def init(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    dtype = cfg.parameter_dtype()
    k_embed, k_layers, k_head, k_tail = jax.random.split(key, 4)
    p, a = {}, {}
    p["embed"], a["embed"] = init_embed(k_embed, cfg, dtype)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        n_dense = n_dense_layers(cfg)
        if n_dense:
            p["dense"], a["dense"] = _stack_init(
                lambda k: _init_dense_layer(k, cfg, dtype, moe=False),
                k_tail, n_dense)
        p["layers"], a["layers"] = _stack_init(
            lambda k: _init_dense_layer(k, cfg, dtype), k_layers,
            cfg.n_layers - n_dense)
    elif cfg.family == "ssm":
        p["layers"], a["layers"] = _stack_init(
            lambda k: _init_rwkv_layer(k, cfg, dtype), k_layers, cfg.n_layers)
    elif cfg.family == "hybrid":
        n_blocks, tail = hybrid_pattern(cfg)
        pat = cfg.recurrent.block_pattern

        def init_block(k):
            kk = jax.random.split(k, len(pat))
            bp, ba = {}, {}
            for i, kind in enumerate(pat):
                bp[f"sub{i}"], ba[f"sub{i}"] = _init_hybrid_sublayer(
                    kk[i], cfg, kind, dtype)
            return bp, ba

        p["blocks"], a["blocks"] = _stack_init(init_block, k_layers, n_blocks)
        if tail:
            kt = jax.random.split(k_tail, len(tail))
            p["tail"], a["tail"] = {}, {}
            for i, kind in enumerate(tail):
                p["tail"][f"sub{i}"], a["tail"][f"sub{i}"] = \
                    _init_hybrid_sublayer(kt[i], cfg, kind, dtype)
    else:
        raise ValueError(cfg.family)
    p["final_norm"], a["final_norm"] = init_rms_norm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["head"], a["head"] = make_param(
            k_head, (cfg.d_model, cfg.vocab), ("embed", "vocab"), dtype)
    if cfg.family == "vlm":
        # stub frontend projection: precomputed patch embeddings -> d_model
        p["vis_proj"], a["vis_proj"] = make_param(
            k_head, (cfg.d_model, cfg.d_model), ("embed", "act_embed"), dtype)
    return p, a


# --------------------------------------------------------------------------
# remat
# --------------------------------------------------------------------------
def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "full":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


# --------------------------------------------------------------------------
# forward (no cache): train / prefill
# --------------------------------------------------------------------------
def _dense_block(cfg: ModelConfig, carry, lp, positions):
    x, aux = carry
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        h, _ = mla_attention(lp["attn"], cfg, h, positions)
    else:
        h, _ = attention(lp["attn"], cfg, h, positions)
    x = constrain(x + h, ("batch", "seq", "act_embed"))
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        h, a, counts = moe_mod.moe_block(lp["moe"], cfg, h)
        aux = aux + a
    else:
        h = mlp(lp["mlp"], h, cfg.activation)
        counts = jnp.zeros((1,), jnp.int32)
    x = constrain(x + h, ("batch", "seq", "act_embed"))
    return (x, aux), counts


def _rwkv_block(cfg: ModelConfig, x, lp, state=None):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    h, tm_state = rwkv_mod.rwkv_time_mix(lp["block"], cfg, h, state)
    x = constrain(x + h, ("batch", "seq", "act_embed"))
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    h, cm_state = rwkv_mod.rwkv_channel_mix(lp["block"], cfg, h, state)
    x = constrain(x + h, ("batch", "seq", "act_embed"))
    return x, {**tm_state, **cm_state}


def _hybrid_sublayer(cfg: ModelConfig, x, sp, kind: str, positions,
                     state=None):
    h = rms_norm(x, sp["ln1"], cfg.norm_eps)
    if kind == "rec":
        h, new_state = rglru_mod.rglru_block(sp["mix"], cfg, h, state)
    else:
        h, new_state = attention(sp["mix"], cfg, h, positions, cache=state)
    x = constrain(x + h, ("batch", "seq", "act_embed"))
    h = mlp(sp["mlp"], rms_norm(x, sp["ln2"], cfg.norm_eps), cfg.activation)
    x = constrain(x + h, ("batch", "seq", "act_embed"))
    return x, new_state


def forward(params: Params, cfg: ModelConfig, tokens,
            embeds=None, last_only: bool = False,
            return_hidden: bool = False):
    """tokens (B, S_text); embeds (B, P, d) for vlm/audio stubs.
    Returns (logits, info) with info = {'aux', 'expert_counts'};
    ``return_hidden`` skips the head (chunked-CE path)."""
    x = embed(params["embed"], cfg, tokens)
    if cfg.family == "vlm" and embeds is not None:
        vis = jnp.einsum("bpd,de->bpe", embeds.astype(x.dtype),
                         params["vis_proj"])
        x = jnp.concatenate([vis, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)
    x = constrain(x, ("batch", "seq", "act_embed"))
    aux0 = jnp.zeros((), jnp.float32)
    info: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        block = _maybe_remat(
            lambda c, lp: _dense_block(cfg, c, lp, positions), cfg)
        carry = (x, aux0)
        if "dense" in params:
            carry, _ = lax.scan(block, carry, params["dense"],
                                unroll=cfg.probe_unroll)
        (x, aux), counts = lax.scan(block, carry, params["layers"],
                                    unroll=cfg.probe_unroll)
        info["aux"] = aux
        info["expert_counts"] = counts  # (L, E) per-layer expert loads
    elif cfg.family == "ssm":
        block = _maybe_remat(
            lambda xx, lp: _rwkv_block(cfg, xx, lp), cfg)
        x, _ = lax.scan(lambda xx, lp: block(xx, lp), x, params["layers"],
                        unroll=cfg.probe_unroll)
        info["aux"] = aux0
    elif cfg.family == "hybrid":
        pat = cfg.recurrent.block_pattern

        def blockfn(xx, bp):
            for i, kind in enumerate(pat):
                xx, _ = _hybrid_sublayer(cfg, xx, bp[f"sub{i}"], kind,
                                         positions)
            return xx, None

        x, _ = lax.scan(_maybe_remat(blockfn, cfg), x, params["blocks"],
                        unroll=cfg.probe_unroll)
        if "tail" in params:
            _, tailpat = hybrid_pattern(cfg)
            for i, kind in enumerate(tailpat):
                x, _ = _hybrid_sublayer(cfg, x, params["tail"][f"sub{i}"],
                                        kind, positions)
        info["aux"] = aux0
    else:
        raise ValueError(cfg.family)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x, info
    if last_only:
        x = x[:, -1:]
    logits = logits_from(params["embed"], params.get("head"), cfg, x)
    logits = constrain(logits, ("batch", "seq", "vocab_out"))
    return logits, info


def chunked_ce_from_hidden(params: Params, cfg: ModelConfig, x, labels,
                           mask=None, chunk: int = 512):
    """Cross-entropy computed seq-chunk by seq-chunk straight from the
    hidden states: the (B, S, V) f32 logits tensor is never materialised
    (memory §Perf iteration — with 256k vocabs it dominates temp memory)."""
    B, S, D = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask if mask is not None
                       else jnp.ones((B, S), jnp.float32),
                       ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    nc = (S + pad) // chunk
    xs = jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0)

    def step(carry, inp):
        tot, denom = carry
        xc, lc, mc = inp
        logits = logits_from(params["embed"], params.get("head"), cfg, xc)
        from repro.sharding import constrain as _c
        logits = _c(logits, ("batch", "seq", "vocab_out"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - ll) * mc
        return (tot + nll.sum(), denom + mc.sum()), None

    (tot, denom), _ = lax.scan(step, (jnp.zeros(()), jnp.zeros(())),
                               (xs, ls, ms), unroll=cfg.probe_unroll)
    return tot / jnp.maximum(denom, 1.0)


def loss_fn(params: Params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, Dict]:
    labels = batch["labels"]
    mask = batch.get("mask")
    S = batch["tokens"].shape[1]
    if S * cfg.vocab > 2 ** 26:
        # big-vocab / long-seq path: loss from hidden states, chunked
        x, info = forward(params, cfg, batch["tokens"],
                          embeds=batch.get("embeds"), return_hidden=True)
        if cfg.family == "vlm" and batch.get("embeds") is not None:
            x = x[:, batch["embeds"].shape[1]:]
        loss = chunked_ce_from_hidden(
            params, cfg, x[:, :-1], labels[:, 1:],
            mask[:, 1:] if mask is not None else None)
    else:
        logits, info = forward(params, cfg, batch["tokens"],
                               embeds=batch.get("embeds"))
        if cfg.family == "vlm" and batch.get("embeds") is not None:
            logits = logits[:, batch["embeds"].shape[1]:]
        loss = cross_entropy(logits[:, :-1], labels[:, 1:],
                             mask[:, 1:] if mask is not None else None)
    total = loss + info.get("aux", 0.0)
    return total, {"loss": loss, **{k: v for k, v in info.items()}}


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dtype = cfg.activation_dtype()
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        if cfg.mla is not None:
            one = lambda: init_mla_cache(cfg, batch, max_len, dtype)
        else:
            one = lambda: init_attention_cache(cfg, batch, max_len, dtype)

        def stack(n):
            return jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[one() for _ in range(n)])

        n_dense = n_dense_layers(cfg)
        state = {"layers": stack(cfg.n_layers - n_dense)}
        if n_dense:
            state["dense"] = stack(n_dense)
        if cfg.moe is not None:
            # the routes each expert received in the call that produced
            # this state, per expert layer
            state["expert_counts"] = jnp.zeros(
                (cfg.n_layers - n_dense, cfg.moe.n_experts), jnp.int32)
        return state
    if cfg.family == "ssm":
        dh = cfg.recurrent.head_dim
        H = cfg.d_model // dh
        one = {
            "S": jnp.zeros((batch, H, dh, dh), jnp.float32),
            "last_tm": jnp.zeros((batch, cfg.d_model), dtype),
            "last_cm": jnp.zeros((batch, cfg.d_model), dtype),
        }
        return {"layers": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one)}
    if cfg.family == "hybrid":
        n_blocks, tailpat = hybrid_pattern(cfg)
        pat = cfg.recurrent.block_pattern
        w = cfg.recurrent.lru_width or cfg.d_model
        cw = cfg.recurrent.conv_width

        def sub_state(kind):
            if kind == "rec":
                return {"conv": jnp.zeros((batch, cw - 1, w), dtype),
                        "h": jnp.zeros((batch, w), jnp.float32)}
            return init_attention_cache(cfg, batch, max_len, dtype)

        block = {f"sub{i}": sub_state(k) for i, k in enumerate(pat)}
        state = {"blocks": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_blocks,) + x.shape), block)}
        if tailpat:
            state["tail"] = {f"sub{i}": sub_state(k)
                             for i, k in enumerate(tailpat)}
        return state
    raise ValueError(cfg.family)


def decode_step(params: Params, cfg: ModelConfig, state: Params,
                tokens, pos) -> Tuple[jnp.ndarray, Params]:
    """One decode step.  tokens (B, 1); pos scalar int32 (current position).
    Returns (logits (B,1,V), new_state)."""
    x = embed(params["embed"], cfg, tokens)
    positions = pos[None] if pos.ndim == 0 else pos
    x = constrain(x, ("batch", "seq", "act_embed"))
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        def attend(xx, lp, cache):
            h = rms_norm(xx, lp["ln1"], cfg.norm_eps)
            if cfg.mla is not None:
                h, new_cache = mla_attention(lp["attn"], cfg, h, positions,
                                             cache=cache)
            else:
                h, new_cache = attention(lp["attn"], cfg, h, positions,
                                         cache=cache)
            xx = xx + h
            return xx, rms_norm(xx, lp["ln2"], cfg.norm_eps), new_cache

        def block(carry, inp):
            xx, aux = carry
            lp, cache = inp
            xx, h, new_cache = attend(xx, lp, cache)
            h = mlp(lp["mlp"], h, cfg.activation)
            return (xx + h, aux), new_cache

        carry = (x, jnp.zeros((), jnp.float32))
        new_state = {}
        if "dense" in params:
            carry, new_state["dense"] = lax.scan(
                block, carry, (params["dense"], state["dense"]),
                unroll=cfg.probe_unroll)
        if cfg.moe is None:
            carry, new_state["layers"] = lax.scan(
                block, carry, (params["layers"], state["layers"]),
                unroll=cfg.probe_unroll)
        else:
            # The expert weights stay out of the scanned slices: each
            # layer reads its experts from the stack by layer index.
            moe_p = dict(params["layers"]["moe"])
            experts = {k: moe_p.pop(k) for k in moe_mod.EXPERT_KEYS}
            rest = {**params["layers"], "moe": moe_p}

            def moe_layer(carry, inp):
                xx, aux = carry
                layer, lp, cache = inp
                xx, h, new_cache = attend(xx, lp, cache)
                h, a, counts = moe_mod.moe_block({**lp["moe"], **experts},
                                                 cfg, h, layer=layer)
                return (xx + h, aux + a), (new_cache, counts)

            n_moe = cfg.n_layers - n_dense_layers(cfg)
            carry, (new_state["layers"], new_state["expert_counts"]) = \
                lax.scan(moe_layer, carry,
                         (jnp.arange(n_moe), rest, state["layers"]),
                         unroll=cfg.probe_unroll)
        x = carry[0]
    elif cfg.family == "ssm":
        def block(xx, inp):
            lp, st = inp
            return _rwkv_block(cfg, xx, lp, state=st)

        x, new_layers = lax.scan(block, x,
                                 (params["layers"], state["layers"]),
                                 unroll=cfg.probe_unroll)
        new_state = {"layers": new_layers}
    elif cfg.family == "hybrid":
        pat = cfg.recurrent.block_pattern

        def blockfn(xx, inp):
            bp, bst = inp
            new_bst = {}
            for i, kind in enumerate(pat):
                xx, new_bst[f"sub{i}"] = _hybrid_sublayer(
                    cfg, xx, bp[f"sub{i}"], kind, positions,
                    state=bst[f"sub{i}"])
            return xx, new_bst

        x, new_blocks = lax.scan(blockfn, x,
                                 (params["blocks"], state["blocks"]),
                                 unroll=cfg.probe_unroll)
        new_state = {"blocks": new_blocks}
        if "tail" in params:
            _, tailpat = hybrid_pattern(cfg)
            new_state["tail"] = {}
            for i, kind in enumerate(tailpat):
                x, new_state["tail"][f"sub{i}"] = _hybrid_sublayer(
                    cfg, x, params["tail"][f"sub{i}"], kind, positions,
                    state=state["tail"][f"sub{i}"])
    else:
        raise ValueError(cfg.family)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from(params["embed"], params.get("head"), cfg, x)
    return logits, new_state

"""DeepSeek-V2-Lite at one chip's expert share: its weights, drawn from the
seed, and its plain float32 reference forward pass, importing nothing of
the program.

Weights: every leaf is ``std * N(0, 1)`` drawn with the key
``fold_in(fold_in(key(seed), leaf), layer)`` and stored in bfloat16, the
type it is served in (``bench.reference.dense_lm`` draws the same way).
:func:`init_params` makes them all on the device in one jitted call, in
the layout the program's transformer takes: the leading dense layers
stacked under ``dense``, the expert layers under ``layers``, each expert
layer holding the configuration's block of routed experts.
:func:`layer_weights` draws one layer's again, bit for bit, so the
reference never holds more than one layer.

Reference (DeepSeek-V2, arXiv:2405.04434; the layer equations of the
published ``modeling_deepseek.py``): token embedding; per layer, RMSNorm
with weight ``1 + w``, then multi-head latent attention: the query
(no compression) split into a 128-dim part and a 64-dim rotary part; the
hidden state projected to a 512-dim latent, normalised (kv_a_layernorm),
and a 64-dim rotary key shared by the heads; keys and values decompressed
from the latent; YaRN rotary frequencies (the interpolated and original
ones blended between the beta_fast and beta_slow correction dims) and a
softmax scale of ``192 ** -0.5 * mscale ** 2``; causal softmax attention.
Then RMSNorm and, in the leading dense layer, a SwiGLU MLP; in the
others the router's softmax over all ``n_routed_experts_published``
experts, each token's top-k gates (renormalised only with
``norm_topk_prob``, times ``routed_scaling_factor``), the part of the
held experts (each a SwiGLU, every token through every held expert,
weighted by its gate, zero where not routed), and the shared experts.
Final RMSNorm and an untied head.  No cache, no batching, no kernels:
float32 throughout, matrix products at ``Precision.HIGHEST``.

Departures from the published model, each the program's as well: the
rotary halves are rotated as (x[:32], x[32:]) where DeepSeek pairs
interleaved columns (a fixed permutation of the rotary columns of ``wq``
and ``wkv_a``, which random weights cannot tell apart); only the held
block of experts is computed (the other chips' part is left out, as the
deployment's chip leaves it out before its all-to-all).

``quant="int8"`` or ``"fp8"`` is the control, as in ``dense_lm``: every
matrix product with weights and activations in the lower precision.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from .dense_lm import HIGHEST, _draw, _key, _mm


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    return {
        "L": c["num_hidden_layers"], "d": c["hidden_size"],
        "H": c["num_attention_heads"], "r": c["kv_lora_rank"],
        "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
        "dv": c["v_head_dim"], "ff": c["intermediate_size"],
        "fe": c["moe_intermediate_size"], "E": c["n_routed_experts_published"],
        "held": c["n_routed_experts"], "first": c["first_held_expert"],
        "k": c["num_experts_per_tok"], "ns": c["n_shared_experts"],
        "dense": c["first_k_dense_replace"], "V": c["vocab_size"],
    }


def _attn_leaves(g, n):
    d, H, r, dr = n["d"], n["H"], n["r"], n["dr"]
    qd = n["dn"] + dr
    return [
        (f"{g}/ln1", (d,), 0.1), (f"{g}/ln2", (d,), 0.1),
        (f"{g}/attn/wq", (d, H, qd), d ** -0.5),
        (f"{g}/attn/wkv_a", (d, r + dr), d ** -0.5),
        (f"{g}/attn/kv_norm", (r,), 0.1),
        (f"{g}/attn/wkv_b", (r, H, n["dn"] + n["dv"]), r ** -0.5),
        (f"{g}/attn/wo", (H, n["dv"], d), (H * n["dv"]) ** -0.5),
    ]


def leaves(c: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], float, str]]:
    """(path, per-layer shape, std, stack) of every weight; stack is
    ``dense``, ``layers`` or ``""`` (not stacked)."""
    n = dims(c)
    d, ff, fe, held, sh = n["d"], n["ff"], n["fe"], n["held"], \
        n["fe"] * n["ns"]
    dense = [(p, s, sd, "dense") for p, s, sd in _attn_leaves("dense", n) + [
        ("dense/mlp/wi", (d, ff), d ** -0.5),
        ("dense/mlp/wg", (d, ff), d ** -0.5),
        ("dense/mlp/wo", (ff, d), ff ** -0.5)]]
    moe = [(p, s, sd, "layers") for p, s, sd in _attn_leaves("layers", n) + [
        ("layers/moe/router", (d, n["E"]), d ** -0.5),
        ("layers/moe/wi", (held, d, fe), d ** -0.5),
        ("layers/moe/wg", (held, d, fe), d ** -0.5),
        ("layers/moe/wo", (held, fe, d), fe ** -0.5),
        ("layers/moe/shared_wi", (d, sh), d ** -0.5),
        ("layers/moe/shared_wg", (d, sh), d ** -0.5),
        ("layers/moe/shared_wo", (sh, d), sh ** -0.5)]]
    return ([("embed/tokens", (n["V"], d), 1.0, "")] + dense + moe
            + [("final_norm", (d,), 0.1, ""),
               ("head", (d, n["V"]), d ** -0.5, "")])


def _stack_sizes(c):
    n = dims(c)
    return {"dense": n["dense"], "layers": n["L"] - n["dense"]}


def init_params(c: Dict[str, Any], seed: int):
    """All weights, bfloat16, on the device, in one jitted call (one layer
    drawn at a time, so no float32 copy of a whole stack is ever held)."""
    import jax
    import jax.numpy as jnp

    sizes = _stack_sizes(c)
    spec = leaves(c)

    def make(key):
        tree: Dict[str, Any] = {}
        for i, (path, shape, std, stack) in enumerate(spec):
            if stack:
                v = jax.lax.map(lambda layer, i=i, s=shape, sd=std:
                                _draw(key, i, layer, s, sd),
                                jnp.arange(sizes[stack]))
            else:
                v = _draw(key, i, 0, shape, std)
            node = tree
            *head, last = path.split("/")
            for p in head:
                node = node.setdefault(p, {})
            node[last] = v
        return tree

    return jax.jit(make)(_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(spec, stack):
    import jax
    import jax.numpy as jnp

    def fn(key, layer):
        return {path.split("/", 1)[1]: _draw(key, i, layer, shape, std)
                .astype(jnp.float32)
                for i, (path, shape, std, s) in enumerate(spec)
                if s == stack}
    return jax.jit(fn)


def layer_weights(c: Dict[str, Any], seed: int, layer: int):
    """Layer ``layer``'s weights (0-based over the whole model) as served
    (bfloat16), in float32."""
    import jax.numpy as jnp
    dense = dims(c)["dense"]
    stack, i = ("dense", layer) if layer < dense else ("layers",
                                                       layer - dense)
    return _layer_fn(tuple(leaves(c)), stack)(_key(seed), jnp.int32(i))


def other_weights(c: Dict[str, Any], seed: int):
    """The embedding, final norm and head as served, in float32."""
    import jax
    import jax.numpy as jnp
    key = _key(seed)
    return {path: jax.jit(lambda k, i=i, s=shape, sd=std:
                          _draw(k, i, 0, s, sd).astype(jnp.float32))(key)
            for i, (path, shape, std, stack) in enumerate(leaves(c))
            if not stack}


# -- the layer equations --------------------------------------------------------
def yarn_inv_freq(c: Dict[str, Any]) -> np.ndarray:
    """The rotary frequencies with YaRN (DeepSeekV2YarnRotaryEmbedding)."""
    y, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]

    def corr_dim(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]
    return inter * ramp + extra * (1.0 - ramp)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(c: Dict[str, Any]) -> float:
    y = c["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, c):
    """Rotate halves of the last dim by the YaRN angles of ``pos``."""
    import jax.numpy as jnp
    y = c["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale"]) \
        / yarn_mscale(y["factor"], y["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(c), jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, c, quant="none"):
    """Multi-head latent attention over the whole sequence x (S, d)."""
    import jax
    import jax.numpy as jnp
    n = dims(c)
    S, d, H, r, dn, dr, dv = (x.shape[0], n["d"], n["H"], n["r"], n["dn"],
                              n["dr"], n["dv"])
    pos = jnp.arange(S)
    q = _mm("sd,dk->sk", x, w["attn/wq"].reshape(d, -1),
            quant).reshape(S, H, dn + dr)
    kv_a = _mm("sd,dk->sk", x, w["attn/wkv_a"], quant)
    latent = _rms(kv_a[:, :r], w["attn/kv_norm"], c["rms_norm_eps"])
    kv = _mm("sr,rk->sk", latent, w["attn/wkv_b"].reshape(r, -1),
             quant).reshape(S, H, dn + dv)
    q_rot = _rope(q[..., dn:], pos, c)
    k_rot = _rope(kv_a[:, None, r:], pos, c)[:, 0]
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn],
                         precision=HIGHEST)
              + jnp.einsum("qhd,kd->hqk", q_rot, k_rot, precision=HIGHEST))
    scores = scores * softmax_scale(c)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, kv[..., dn:], precision=HIGHEST)
    return _mm("sk,kd->sd", o.reshape(S, H * dv),
               w["attn/wo"].reshape(H * dv, d), quant)


def swiglu(x, wg, wi, wo, quant="none"):
    import jax
    a = jax.nn.silu(_mm("sd,df->sf", x, wg, quant)) \
        * _mm("sd,df->sf", x, wi, quant)
    return _mm("sf,fd->sd", a, wo, quant)


def routing(x, w, c, quant="none"):
    """(gates (S, E), zero where a token does not route) over all the
    published experts."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(_mm("sd,de->se", x, w["moe/router"], quant),
                           axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def experts(x, w, c, quant="none"):
    """The held experts' part for every token of x (S, d), plus the shared
    experts'."""
    n = dims(c)
    gates = routing(x, w, c, quant)
    out = swiglu(x, w["moe/shared_wg"], w["moe/shared_wi"],
                 w["moe/shared_wo"], quant)
    for e in range(n["held"]):
        y = swiglu(x, w["moe/wg"][e], w["moe/wi"][e], w["moe/wo"][e], quant)
        out = out + gates[:, n["first"] + e][:, None] * y
    return out


@functools.lru_cache(maxsize=None)
def _block_fn(cfg_key, dense: bool, quant: str):
    import jax
    c = _unkey(cfg_key)
    eps = c["rms_norm_eps"]

    def block(x, w):
        x = x + attention(_rms(x, w["ln1"], eps), w, c, quant)
        h = _rms(x, w["ln2"], eps)
        if dense:
            return x + swiglu(h, w["mlp/wg"], w["mlp/wi"], w["mlp/wo"],
                              quant)
        return x + experts(h, w, c, quant)

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_key, quant: str):
    import jax
    import jax.numpy as jnp
    eps = _unkey(cfg_key)["rms_norm_eps"]

    def head(x, norm, w, tokens, valid):
        """Per position: the reference's best logit less the logit of the
        token that follows (the served one), and the index of the best."""
        logits = _mm("sd,dv->sv", _rms(x, norm, eps), w, quant)
        best = jnp.max(logits, axis=-1)
        nxt = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        return jnp.where(valid, best - nxt, 0.0), jnp.argmax(logits, -1)

    return jax.jit(head)


def _cfg_key(c):
    return json.dumps(c, sort_keys=True)


def _unkey(cfg_key):
    return json.loads(cfg_key)


def hidden(c: Dict[str, Any], seed: int, sequences: Sequence[np.ndarray],
           quants: Sequence[str] = ("none",)):
    """The final hidden states (before the last norm) of each sequence,
    per precision: ``{q: [(S, d) per sequence]}``.  Layer by layer, one
    sequence at a time."""
    import jax.numpy as jnp
    n, ck = dims(c), _cfg_key(c)
    emb = other_weights(c, seed)["embed/tokens"]
    xs = {q: [emb[jnp.asarray(t)] for t in sequences] for q in quants}
    for layer in range(n["L"]):
        w = layer_weights(c, seed, layer)
        for q in quants:
            blk = _block_fn(ck, layer < n["dense"], q)
            xs[q] = [blk(x, w) for x in xs[q]]
        del w
    return xs


def routed_counts(c: Dict[str, Any], seed: int, tokens: np.ndarray):
    """(expert layers, published experts): the routes each expert received
    over one sequence, layer by layer."""
    import jax.numpy as jnp
    n, ck, eps = dims(c), _cfg_key(c), c["rms_norm_eps"]
    x = other_weights(c, seed)["embed/tokens"][jnp.asarray(tokens)]
    out = []
    for layer in range(n["L"]):
        w = layer_weights(c, seed, layer)
        if layer >= n["dense"]:
            h = x + attention(_rms(x, w["ln1"], eps), w, c)
            out.append((routing(_rms(h, w["ln2"], eps), w, c) > 0).sum(0))
        x = _block_fn(ck, layer < n["dense"], "none")(x, w)
    return np.asarray(jnp.stack(out))


def logits(c: Dict[str, Any], seed: int, tokens: np.ndarray):
    """The reference's logits (S, V) of one sequence."""
    import jax.numpy as jnp
    other = other_weights(c, seed)
    x, = hidden(c, seed, [tokens])["none"]
    return _mm("sd,dv->sv", _rms(x, other["final_norm"], c["rms_norm_eps"]),
               other["head"], "none")


def forward_gaps(c: Dict[str, Any], seed: int,
                 sequences: Sequence[np.ndarray], scored: Sequence[range],
                 length: int, quants: Sequence[str] = ()):
    """Per sequence, the float32 reference's best logit less its logit of
    the token that follows, at each position in ``scored`` (zero
    elsewhere); and for each lower precision in ``quants``, the same gap of
    the token that precision puts first (``dense_lm.forward_gaps``'s
    contract).  Sequences are padded to ``length`` at the end; the causal
    mask keeps padding out of every scored position."""
    import jax.numpy as jnp
    ck = _cfg_key(c)
    other = other_weights(c, seed)
    toks = [np.pad(s, (0, length - len(s))) for s in sequences]
    xs = hidden(c, seed, toks, ("none",) + tuple(quants))
    valid = []
    for rg in scored:
        v = np.zeros(length, bool)
        v[list(rg)] = True
        valid.append(jnp.asarray(v))
    head = _head_fn(ck, "none")
    out = {"none": [np.asarray(head(x, other["final_norm"], other["head"],
                                    jnp.asarray(np.roll(t, -1)), v)[0])
                    for x, t, v in zip(xs["none"], toks, valid)]}
    for q in quants:
        qhead = _head_fn(ck, q)
        out[q] = []
        for x, xq, v in zip(xs["none"], xs[q], valid):
            _, first = qhead(xq, other["final_norm"], other["head"],
                             jnp.zeros(length, jnp.int32), v)
            out[q].append(np.asarray(head(x, other["final_norm"],
                                          other["head"], first, v)[0]))
    return out

